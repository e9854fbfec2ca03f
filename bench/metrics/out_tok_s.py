"""Output tokens emitted inside the window over the window's length."""


def reduce(run):
    return run.window.tokens_in_window() / run.window.end
