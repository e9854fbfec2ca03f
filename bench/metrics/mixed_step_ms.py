"""Stage executor + model, steps that carry a prefill chunk ([slots,
chunk]): mean host wall time of ``executor.forward``, which waits for the
device."""


def reduce(run):
    t = [s.fwd_s for s in run.window.steps if s.width > 1]
    return 1e3 * sum(t) / len(t) if t else None
