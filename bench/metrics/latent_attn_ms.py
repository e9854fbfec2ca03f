"""Kernels, MLA: device time of the latent attention kernel
(``flash_attention_paged_latent``, every layer's call) per traced forward.
It tells a change in the kernel from one in the MoE when the step time
moves.  A trace without the kernel (a program or cell without latent
attention) gives nothing to read."""

KERNELS = {"latent": r"flash_attention_paged_latent"}


def reduce(run):
    if run.trace is None or not run.window.trace_steps:
        return None
    if not run.trace.kernel_calls.get("latent"):
        return None
    return 1e3 * run.trace.kernel_s["latent"] / len(run.window.trace_steps)
