"""The benchmark's weights layout, parameter counts and work counts against
the program's own parameter tree (``jax.eval_shape``: shapes only)."""

import json
import math
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import model

BENCH = Path(__file__).resolve().parents[1]
CONFIGS = ["qwen3-14b-10L", "qwen2-moe-a2.7b-6L"]


def conf_of(name):
    path = BENCH / "configs" / f"{name}.json"
    if not path.exists():
        path = BENCH / "tests" / "data" / f"{name}.json"
    return json.loads(path.read_text())


def program_tree(conf):
    from repro.models.model import build_model

    cfg = model.program_config(conf)
    return cfg, jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))


@pytest.mark.parametrize("name", CONFIGS + ["tiny-qwen3", "tiny-moe"])
def test_layout_is_the_program_tree(name):
    conf = conf_of(name)
    cfg, tree = program_tree(conf)
    held = cfg.n_experts_padded or cfg.n_experts
    ours = model.layout(conf, held)
    is_leaf = lambda x: isinstance(x, model.Leaf)
    assert jax.tree.structure(tree) == jax.tree.structure(ours, is_leaf=is_leaf)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(ours, is_leaf=is_leaf)):
        assert tuple(a.shape) == b.shape and str(a.dtype) == b.dtype
    n = sum(math.prod(x.shape) for x in jax.tree.leaves(tree))
    assert model.param_count(conf, held) == n


def test_published_sizes():
    q = conf_of("qwen3-14b-10L")
    assert model.param_count(q, 0) == 10 * 330_311_936 + 2 * 151936 * 5120 + 5120
    assert model.kv_bytes_per_token(q) == 40 * 1024
    m = conf_of("qwen2-moe-a2.7b-6L")
    assert model.kv_bytes_per_token(m) == 48 * 1024
    # active: attention 16.8M + router over 60 + top-4 experts + shared, per layer
    per = 4 * 2048 * 2048 + 2048 * 60 + 4 * 3 * 2048 * 1408 + 3 * 2048 * 5632
    assert model.active_matmul_params(m) == 6 * per + 2048 * 151936


def test_active_params_match_the_program_count_for_dense():
    from repro.models.model import active_param_count

    conf = conf_of("qwen3-14b-10L")
    cfg = model.program_config(conf)
    norms = 10 * (2 * 5120 + 2 * 128) + 5120
    embed = 151936 * 5120
    assert model.active_matmul_params(conf) == active_param_count(cfg) - norms - embed


def brute_attention(conf, q_lens, cache_pos):
    h, kv, hd = conf["num_attention_heads"], conf["num_key_value_heads"], model.head_dim(conf)
    flops = nbytes = 0
    for q, c in zip(q_lens.tolist(), cache_pos.tolist()):
        for i in range(q):
            flops += 4 * h * hd * (c + i + 1)
        if q:
            nbytes += 2 * (q * h * hd * 2 + (c + q) * kv * hd * 2)
    return flops, nbytes


def test_attention_work_against_a_loop():
    conf = conf_of("qwen3-14b-10L")
    q = np.array([64, 1, 0, 17, 1], np.int32)
    c = np.array([128, 900, 5, 0, 2047], np.int32)
    f, b = model.attention_call(conf, q, c)
    bf, bb = brute_attention(conf, q, c)
    assert f == pytest.approx(bf) and b == pytest.approx(bb)
    total = model.step_flops(conf, q, c)
    assert total == pytest.approx(
        2 * model.active_matmul_params(conf) * int(q.sum()) + conf["num_hidden_layers"] * bf)
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert model.least_time(f, b, peak) == max(f / 197e12, b / 819e9)


def test_seeds_make_distinct_weights_reproducibly():
    conf = conf_of("tiny-qwen3")
    cfg = model.program_config(conf)
    dev = jax.devices()[0]
    a = model.make_params(conf, 0, 2**33 + 5, lambda p: dev)
    b = model.make_params(conf, 0, 2**33 + 5, lambda p: dev)
    c = model.make_params(conf, 0, 2**33 + 6, lambda p: dev)
    assert cfg.n_layers == 2
    np.testing.assert_array_equal(a["layers"][1]["mlp"]["w_up"], b["layers"][1]["mlp"]["w_up"])
    assert not np.array_equal(a["layers"][1]["mlp"]["w_up"], c["layers"][1]["mlp"]["w_up"])
    assert a["layers"][0]["attn"]["wq"].dtype == np.dtype("bfloat16") or \
        str(a["layers"][0]["attn"]["wq"].dtype) == "bfloat16"
