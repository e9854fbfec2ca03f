"""Mean gap between consecutive output tokens, over all gaps of all
requests.  A gap is one engine step, either a decode-only step or a step
that carries a prefill chunk; their mean follows the share of each."""


def reduce(run):
    gaps = run.window.itl()
    return 1e3 * sum(gaps) / len(gaps) if gaps else None
