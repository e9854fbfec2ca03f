"""Stage executor, device idle while the host waits on it: device idle in
the traced window whose innermost program span is ``executor.wait`` (the
host blocked on the stage's result), averaged over devices as busy time is,
per traced forward.  Idle here is a bubble inside the device's own work, or
events the trace lost, not host work.  A program without these spans gives
nothing to read."""

from bench import progspans


def reduce(run):
    s = progspans.of_run(run)
    if s is None or s.forwards == 0:
        return None
    return 1e3 * s.idle_s.get("executor.wait", 0.0) / s.forwards
