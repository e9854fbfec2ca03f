"""Engine host time per step: step wall time minus the time inside
``executor.forward``, averaged over the window's steps."""


def reduce(run):
    steps = [s for s in run.window.steps if s.width]
    if not steps:
        return None
    return 1e3 * sum(s.t1 - s.t0 - s.fwd_s for s in steps) / len(steps)
