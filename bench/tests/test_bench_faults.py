"""A whole run on the CPU at a tiny size, past the look for a chip: sound, it
comes out correct; with the timed path broken underneath, not correct.

The faults a one-chip serving cell can have: a step that returns its state
(the KV cache) unchanged; half of the batch left out; a token altered where
it is produced.  (It has no exchange between chips.)"""

import json
import time

import jax
import jax.numpy as jnp
import pytest

from bench import run, spec

PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def wrap_forward(eng, change):
    inner = eng.executor.forward

    def forward(tokens, caches=None, cache_pos=None, **kw):
        return change(inner, tokens, caches, cache_pos, kw)

    eng.executor.forward = forward


def state_unchanged(eng):
    def change(inner, tokens, caches, cache_pos, kw):
        logits, _ = inner(tokens, caches, cache_pos, **kw)
        return logits, caches
    wrap_forward(eng, change)


def half_batch_left_out(eng):
    def change(inner, tokens, caches, cache_pos, kw):
        kw = dict(kw, q_lens=kw["q_lens"].at[::2].set(0))   # every other row
        return inner(tokens, caches, cache_pos, **kw)
    wrap_forward(eng, change)


def token_altered(eng):
    calls = [0]

    def change(inner, tokens, caches, cache_pos, kw):
        logits, new = inner(tokens, caches, cache_pos, **kw)
        calls[0] += 1
        if calls[0] % 3 == 0:             # every third step's tokens come out wrong
            logits = logits.at[..., 7].set(jnp.asarray(1e4, logits.dtype))
        return logits, new
    wrap_forward(eng, change)


@pytest.mark.parametrize("cell,fault,correct", [
    ("tiny-qwen3.tiny-open", None, True),
    ("tiny-moe.tiny-closed", None, True),
    ("tiny-qwen3.tiny-open", state_unchanged, False),
    ("tiny-qwen3.tiny-open", half_batch_left_out, False),
    ("tiny-moe.tiny-closed", token_altered, False),
])
def test_correct_only_when_the_timed_path_is_sound(tiny_root, cell, fault, correct,
                                                   capsys):
    c = spec.load_cell(tiny_root, cell)
    out = run.run_cell(c, 2**31 + 101, 4.0, False, jax.devices()[:1], PEAK,
                       t_start=time.perf_counter(), fault=fault)
    gap = out["check"]["max_logit_gap"]
    assert out["correct"] is correct, out["check"]
    assert out["check"]["tokens_compared"]["value"] > 0
    capsys.readouterr()
    run.print_result(out)
    printed = capsys.readouterr()
    line = json.loads(printed.out.splitlines()[-1])
    assert list(line) == ["correct", "attempted", "failed", "metrics", "device", "check"]
    assert printed.err.splitlines()[-3].startswith("check max_logit_gap")
    if correct:
        assert gap["value"] <= gap["limit"]
        assert out["failed"] == 0 and out["attempted"] > 0


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_float8_control_fails_the_limit(tiny_root, seed):
    """The reference computed in float8 in the program's place reads above
    the limit that the program's bf16 tokens meet, on the same positions,
    and the run's check calls it not correct."""
    c = spec.load_cell(tiny_root, "tiny-qwen3.tiny-open")
    out = run.run_cell(c, seed, 4.0, False, jax.devices()[:1], PEAK,
                       t_start=time.perf_counter(), control=True)
    limit = out["check"]["max_logit_gap"]["limit"]
    assert out["correct"]
    assert max(out["readings"]["control"]) > limit >= max(out["readings"]["served"])
    # the control's tokens, judged by the run's own check as if served
    assert out["control"]["correct"] is False
    assert out["control"]["check"]["max_logit_gap"]["value"] > limit
    assert out["control"]["check"]["tokens_compared"]["value"] == \
        out["check"]["tokens_compared"]["value"]
