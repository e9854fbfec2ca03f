"""Share of its roofline that the paged flash-attention kernel reaches over
the traced steps: the least time the attention needs (larger of FLOPs over
peak and bytes over bandwidth, from each row's valid queries and KV span,
once per layer) over the kernel's device time in the trace."""

from bench.model import attention_call, least_time

KERNELS = {"flash": r"flash_attention|_flash_kernel"}


def reduce(run):
    if run.trace is None or run.window.trace_steps is None:
        return None
    conf = run.cell.config
    need = 0.0
    for i in run.window.trace_steps:
        s = run.window.steps[i]
        if s.q_lens is not None:
            need += conf["num_hidden_layers"] * least_time(
                *attention_call(conf, s.q_lens, s.cache_pos), run.peak)
    return 100.0 * need / run.trace.kernel_s["flash"]
