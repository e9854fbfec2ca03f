"""Engine admission: due time to the start of the step that admitted the
request into a slot, 80th percentile over every request due in the window
(one still queued at the end counts at end - due)."""

from bench.loop import percentile


def reduce(run):
    v = percentile(run.window.queue_wait(), 80)
    return None if v is None else v * 1e3
