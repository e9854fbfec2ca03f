"""Multi-head latent attention (MLA) and DeepSeek-V3 routing in the program.

The absorbed latent kernel against plain attention over the latent rows
(K width ≠ V width, 16 query heads on one latent head, ragged rows, a page
shared by two slots); the sigmoid bias-corrected, group-limited router
against a numpy version; the latent cache's layout; a leading dense layer;
and the MLA model's prefill-then-decode and paged fused steps against its
own teacher-forced forward."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.model import build_model
from repro.models.moe import sigmoid_topk

# --------------------------------------------------------------- the kernel
H, LAT, ROPE, W = 16, 128, 64, 256      # latent 128 + rope 64, padded to 256
P, PAGES = 16, 4
SLOTS = 3


def _latent_case(sq, seed):
    """Three slots at their own depths (one idle), their latent rows in a
    shuffled pool, slot 2 sharing slot 0's first page (a common prefix),
    unmapped pages past each row's span clamped to a finite trash page."""
    r = np.random.default_rng(seed)
    L = P * PAGES
    offs = np.array([P, 0, 2 * P + 3], np.int32)
    lens = np.array([sq, 0, max(1, sq // 2)], np.int32)
    q = r.standard_normal((SLOTS, sq, H, W)).astype(np.float32)
    q[..., LAT + ROPE:] = 0.0
    rows = r.standard_normal((SLOTS, L, W)).astype(np.float32)
    rows[..., LAT + ROPE:] = 0.0
    rows[2, :P] = rows[0, :P]                       # the shared prefix page
    perm = r.permutation(SLOTS * PAGES)
    table = perm.reshape(SLOTS, PAGES).astype(np.int32)
    table[2, 0] = table[0, 0]
    pool = np.zeros((1, SLOTS * PAGES + 1, P, W), np.float32)
    for b in range(SLOTS):
        last = offs[b] + max(lens[b], 1) - 1
        for j in range(PAGES):
            pool[0, table[b, j]] = rows[b, j * P:(j + 1) * P]
            if j * P > last:
                table[b, j] = -1
    pool[0, -1] = 1e4
    return offs, lens, q, rows, table, pool


def _plain(offs, lens, q, rows, scale):
    """Each head's softmax over the row's causal keys, values the latent."""
    out = np.zeros(q.shape[:3] + (LAT,), np.float32)
    for b in range(SLOTS):
        n = int(lens[b])
        for i in range(n):
            keys = rows[b, : offs[b] + i + 1]
            s = q[b, i] @ keys.T * scale                # [H, ctx]
            w = np.exp(s - s.max(-1, keepdims=True))
            w /= w.sum(-1, keepdims=True)
            out[b, i] = w @ keys[:, :LAT]
    return out


@pytest.mark.parametrize("sq", [1, 5, 24])
def test_latent_kernel_matches_plain_attention(sq):
    from repro.kernels.flash_attention.ops import flash_attention_paged

    offs, lens, q, rows, table, pool = _latent_case(sq, 11 + sq)
    scale = 1.0 / np.sqrt(192)
    q_pos = jnp.asarray(offs[:, None] + np.arange(sq)[None], jnp.int32)
    pool = jnp.asarray(pool)
    out = flash_attention_paged(
        jnp.asarray(q), pool, pool, jnp.asarray(table), q_pos,
        jnp.asarray(lens), scale=scale, v_dim=LAT, interpret=True,
    )
    out = np.asarray(out, np.float32)
    assert out.shape == (SLOTS, sq, H, LAT)
    np.testing.assert_allclose(out, _plain(offs, lens, q, rows, scale),
                               rtol=2e-4, atol=2e-4)
    for b, n in enumerate(lens):                    # padding rows: exact zeros
        assert not np.any(out[b, n:])


# -------------------------------------------------------------- the router
def _route_numpy(x, w_r, bias, k, scale, norm):
    s = 1.0 / (1.0 + np.exp(-(x.astype(np.float64) @ w_r.astype(np.float64))))
    experts = np.argsort(-(s + bias), axis=-1)[:, :k]
    w = np.take_along_axis(s, experts, axis=-1)
    return (w / w.sum(-1, keepdims=True) if norm else w) * scale, experts


def _by_expert(w, e):
    order = np.argsort(e, axis=-1)
    return np.take_along_axis(w, order, -1), np.take_along_axis(e, order, -1)


@pytest.mark.parametrize("norm", [True, False])
def test_sigmoid_routing_matches_numpy(norm):
    cfg = dataclasses.replace(get_config("moonlight-16b-a3b"), router_norm_topk=norm)
    r = np.random.default_rng(int(norm))
    x = r.standard_normal((96, 64)).astype(np.float32)
    w_r = (r.standard_normal((64, cfg.n_experts)) * 0.1).astype(np.float32)
    bias = (r.standard_normal(cfg.n_experts) * 0.1).astype(np.float32)
    w, e, _ = sigmoid_topk(jnp.asarray(w_r), jnp.asarray(bias), jnp.asarray(x), cfg)
    want_w, want_e = _route_numpy(x, w_r, bias, cfg.top_k, cfg.routed_scaling, norm)
    got_w, got_e = _by_expert(np.asarray(w), np.asarray(e))
    want_w, want_e = _by_expert(want_w, want_e)
    np.testing.assert_array_equal(got_e, want_e)
    np.testing.assert_allclose(got_w, want_w, rtol=1e-5)
    if norm:
        np.testing.assert_allclose(got_w.sum(-1), cfg.routed_scaling, rtol=1e-5)


def test_the_correction_bias_changes_some_selections():
    cfg = get_config("moonlight-16b-a3b")
    r = np.random.default_rng(5)
    x = jnp.asarray(r.standard_normal((256, 2048)), jnp.float32)
    w_r = jnp.asarray(r.standard_normal((2048, 64)) * 0.02, jnp.float32)
    bias = jnp.asarray(r.standard_normal(64) * 0.1, jnp.float32)
    _, with_bias, _ = sigmoid_topk(w_r, bias, x, cfg)
    _, without, _ = sigmoid_topk(w_r, jnp.zeros(64), x, cfg)
    same = [set(a) == set(b) for a, b in zip(np.asarray(with_bias), np.asarray(without))]
    assert 0 < sum(same) < len(same)


# ------------------------------------------------------- model and caches
def _mla(impl=None):
    cfg = get_config("moonlight-16b-a3b").smoke()
    if impl is not None:
        cfg = dataclasses.replace(cfg, attention_impl=impl)
    return cfg, build_model(cfg)


def test_layer_kinds_and_latent_params():
    cfg, model = _mla()
    params = model.init(jax.random.PRNGKey(0))
    dense, routed = params["layers"]
    assert "mlp" in dense and "moe" not in dense
    assert {"moe", "shared_mlp"} <= set(routed) and "mlp" not in routed
    assert routed["moe"]["e_score_correction_bias"].shape == (cfg.n_experts,)
    assert set(dense["attn"]) == {"wq", "wkv_a", "kv_norm", "wkv_b", "wo"}


def test_the_cache_holds_one_latent_row_a_token():
    cfg, model = _mla()
    pools = model.init_paged_cache(10, 8)["layers"]
    assert list(pools) == ["kv"]
    assert pools["kv"].shape == (cfg.n_layers, 1, 11, 8, cfg.latent_cache_width)
    assert cfg.latent_cache_width == 128 >= cfg.kv_lora_rank + cfg.qk_rope_head_dim
    assert get_config("moonlight-16b-a3b").latent_cache_width == 640


@pytest.mark.parametrize("impl", ["naive", "chunked", "pallas"])
def test_paged_fused_steps_match_teacher_forcing(impl):
    """A prompt in two chunks into the paged latent pool, then one decode
    token, give the logits of the whole-sequence forward; the idle row
    writes nothing."""
    cfg, model = _mla(impl)
    params = model.init(jax.random.PRNGKey(0))
    b, s, page, pps = 3, 21, 8, 4
    toks = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0, cfg.vocab_size)
    full, _ = model.train_forward(params, {"tokens": toks})
    caches = model.init_paged_cache(b * pps, page)
    table = jnp.arange(b * pps, dtype=jnp.int32).reshape(b, pps)
    q_lens = jnp.asarray([12, 12, 0], jnp.int32)
    got = []
    for start, n in ((0, 12), (12, 8)):
        chunk = jnp.zeros((b, 12), jnp.int32).at[:, :n].set(toks[:, start:start + n])
        logits, caches = model.fused_step(
            params, {"tokens": chunk}, caches, jnp.full((b,), start, jnp.int32),
            q_lens.at[:2].set(n), page_table=table)
        got.append(logits[:2, :n])
    step = jnp.zeros((b, 12), jnp.int32).at[:, 0].set(toks[:, 20])
    logits, caches = model.fused_step(
        params, {"tokens": step}, caches, jnp.full((b,), 20, jnp.int32),
        jnp.asarray([1, 1, 0], jnp.int32), page_table=table)
    got = jnp.concatenate(got + [logits[:2, :1]], axis=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(full[:2]),
                               rtol=1e-4, atol=1e-4)
    assert not np.any(np.asarray(caches["layers"]["kv"][:, :, 2 * pps:3 * pps]))
