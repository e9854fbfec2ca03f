"""Model FLOPs of the window's valid tokens (2 per weight a token multiplies
through, MoE counting its top-k and shared experts, plus attention at each
token's context) over window x chips x peak bf16 rate."""

from bench.model import step_flops


def reduce(run):
    steps = [s for s in run.window.steps if s.q_lens is not None]
    if not steps:
        return None
    flops = sum(step_flops(run.cell.config, s.q_lens, s.cache_pos) for s in steps)
    return 100.0 * flops / (run.window.end * run.chips * run.peak["bf16_flops_per_s"])
