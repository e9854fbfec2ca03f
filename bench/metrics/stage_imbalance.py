"""Stage executor and placement: the busiest chip's device busy time in the
traced window over the mean busy time of the cell's chips, minus 1 (a chip
that ran nothing counts as 0).  A one-chip run gives nothing."""


def reduce(run):
    if run.trace is None or run.chips < 2:
        return None
    busy = list(run.trace.busy_by_device.values())
    busy += [0.0] * (run.chips - len(busy))
    return 100.0 * (max(busy) * len(busy) / sum(busy) - 1.0)
