"""Set-up: process start to the first request of the window (host clock).
Covers JAX start-up, weights, planning, engine build, compiles or cache
reads, and warm-up."""


def reduce(run):
    return run.setup_s
