"""Stage executor, host side of each forward: time in the program's
``executor.put`` (the step's input transfers and the eager programs that
make its inputs) and ``executor.launch`` (dispatch of each stage program,
or a retrace and compile) spans in the traced window, per traced forward.
The device has nothing of this step to run while the host is here.  A
program without these spans gives nothing to read."""

from bench import progspans


def reduce(run):
    s = progspans.of_run(run)
    if s is None or s.forwards == 0:
        return None
    return 1e3 * s.launch_s / s.forwards
