"""Per-kernel shape/dtype sweeps vs the pure-jnp ref.py oracles
(interpret=True executes the Pallas kernel bodies on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

rng = np.random.default_rng(42)


# ------------------------------------------------------------------ rmsnorm
@pytest.mark.parametrize("shape", [(4, 128), (2, 7, 256), (1, 300, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_kernel(shape, dtype):
    from repro.kernels.rmsnorm.kernel import rmsnorm_pallas
    from repro.models.layers import rmsnorm as ref

    x = jnp.asarray(rng.standard_normal(shape), dtype)
    w = jnp.asarray(rng.standard_normal(shape[-1]) * 0.1, jnp.float32)
    out = rmsnorm_pallas(x, w, interpret=True)
    expect = ref(x, w)
    tol = 1e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32),
        rtol=tol, atol=tol,
    )


# --------------------------------------------------------------- flash attn
CASES = [
    # b, sq, sk, h, kv, d, window, cap
    (2, 128, 128, 4, 2, 64, 0, 0.0),
    (1, 256, 256, 8, 8, 128, 64, 50.0),   # window + softcap (gemma2)
    (2, 96, 96, 4, 1, 80, 0, 0.0),        # MQA + unaligned dims
    (1, 128, 384, 2, 2, 64, 0, 0.0),      # long KV (q_offset)
]


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_kernel(case, dtype):
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import attention_ref

    b, sq, sk, h, kv, d, window, cap = case
    q = jnp.asarray(rng.standard_normal((b, sq, h, d)), dtype)
    k = jnp.asarray(rng.standard_normal((b, sk, kv, d)), dtype)
    v = jnp.asarray(rng.standard_normal((b, sk, kv, d)), dtype)
    scale = 1.0 / np.sqrt(d)
    qo = sk - sq
    out = flash_attention(
        q, k, v, scale=scale, causal=True, window=window or None,
        softcap=cap or None, q_offset=qo, interpret=True,
    )
    rep = h // kv
    qk = q.reshape(b, sq, kv, rep, d).transpose(0, 2, 3, 1, 4).reshape(-1, sq, d)
    kk = jnp.broadcast_to(k.transpose(0, 2, 1, 3)[:, :, None], (b, kv, rep, sk, d)).reshape(-1, sk, d)
    vk = jnp.broadcast_to(v.transpose(0, 2, 1, 3)[:, :, None], (b, kv, rep, sk, d)).reshape(-1, sk, d)
    ref = attention_ref(qk, kk, vk, scale=scale, causal=True, window=window,
                        softcap=cap, q_offset=qo)
    ref = ref.reshape(b, kv, rep, sq, d).transpose(0, 3, 1, 2, 4).reshape(b, sq, h, d)
    tol = 2e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), rtol=tol, atol=tol
    )


# ------------------------------------------- flash attn, GQA-stacked rows
# ragged serving rows: slot 0 a chunk at a page boundary, slot 1 idle
# (q_len 0), slot 2 a shorter chunk at the next boundary
GB, GKV, GD, GPAGE, GPAGES = 3, 2, 32, 16, 8


def _grouped_case(rep, sq, seed):
    """A ragged fused batch: per-slot offsets at page boundaries, q_lens with
    an idle slot, logical K/V of ``GPAGES`` pages, and the page pool it
    lives in (pages shuffled; pages beyond a slot's span unmapped, i.e.
    read from a trash page of large finite garbage)."""
    r = np.random.default_rng(seed)
    h, L = GKV * rep, GPAGE * GPAGES
    offs = np.array([GPAGE, 0, 3 * GPAGE], np.int32)
    lens = np.array([sq, 0, max(1, sq // 2)], np.int32)
    q = r.standard_normal((GB, sq, h, GD)).astype(np.float32)
    k = r.standard_normal((GB, L, GKV, GD)).astype(np.float32)
    v = r.standard_normal((GB, L, GKV, GD)).astype(np.float32)
    perm = r.permutation(GB * GPAGES)
    table = perm.reshape(GB, GPAGES).astype(np.int32)
    pool_k = np.zeros((GKV, GB * GPAGES + 1, GPAGE, GD), np.float32)
    pool_v = np.zeros_like(pool_k)
    for b in range(GB):
        last = offs[b] + max(lens[b], 1) - 1        # last visible position
        for j in range(GPAGES):
            sl = slice(j * GPAGE, (j + 1) * GPAGE)
            pool_k[:, table[b, j]] = k[b, sl].transpose(1, 0, 2)
            pool_v[:, table[b, j]] = v[b, sl].transpose(1, 0, 2)
            if j * GPAGE > last:
                table[b, j] = -1                    # unmapped → trash page
    pool_k[:, -1] = 1e4                             # finite trash
    pool_v[:, -1] = -1e4
    return offs, lens, q, k, v, table, pool_k, pool_v


def _grouped_ref(offs, lens, q, k, v, *, window, cap):
    from repro.kernels.flash_attention.ref import attention_ref

    b, sq, h, d = q.shape
    kv = k.shape[2]
    rep = h // kv
    out = np.zeros((b, sq, h, d), np.float32)
    for s in range(b):
        n = int(lens[s])
        for hh in range(h if n else 0):
            g = hh // rep
            o = attention_ref(
                jnp.asarray(q[s, :n, hh])[None], jnp.asarray(k[s, :, g])[None],
                jnp.asarray(v[s, :, g])[None], scale=1.0 / np.sqrt(d),
                causal=True, window=window, softcap=cap, q_offset=int(offs[s]),
            )
            out[s, :n, hh] = np.asarray(o[0])
    return out


def _check_grouped(out, ref, lens):
    out = np.asarray(out, np.float32)
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)
    for s, n in enumerate(lens):                    # padding rows: exact zeros
        assert not np.any(out[s, n:]), (s, n)


@pytest.mark.parametrize("sq", [1, 3, 64])
@pytest.mark.parametrize("rep", [1, 2, 5, 8])
@pytest.mark.parametrize("paged", [False, True])
def test_grouped_flash_kernel_ragged(paged, rep, sq):
    """The GQA group's ``rep`` query heads stacked in one grid row give the
    reference's per-head attention on a ragged fused batch: an idle slot,
    offsets at page boundaries, a trash-clamped page table."""
    from repro.kernels.flash_attention.ops import (
        flash_attention, flash_attention_paged,
    )

    offs, lens, q, k, v, table, pk, pv = _grouped_case(rep, sq, 7 * rep + sq)
    q_pos = jnp.asarray(offs[:, None] + np.arange(sq)[None], jnp.int32)
    scale = 1.0 / np.sqrt(GD)
    if paged:
        out = flash_attention_paged(
            jnp.asarray(q), jnp.asarray(pk), jnp.asarray(pv),
            jnp.asarray(table), q_pos, jnp.asarray(lens),
            scale=scale, interpret=True,
        )
    else:
        out = flash_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), q_pos, None,
            jnp.asarray(lens), scale=scale, interpret=True,
        )
    _check_grouped(out, _grouped_ref(offs, lens, q, k, v, window=0, cap=0.0),
                   lens)


@pytest.mark.parametrize("window", [24, 1 << 30])
@pytest.mark.parametrize("rep", [1, 2, 5, 8])
@pytest.mark.parametrize("paged", [False, True])
def test_grouped_flash_kernel_window_softcap(paged, rep, window):
    """Window and softcap masks stay per position under the stacked rows:
    gemma2's local layers (a 24-token window across page boundaries) and
    its global ones (the model's ``1 << 30`` window, whose scaled form
    would overflow int32)."""
    from repro.kernels.flash_attention.ops import (
        flash_attention, flash_attention_paged,
    )

    sq, cap = 40, 5.0
    offs, lens, q, k, v, table, pk, pv = _grouped_case(rep, sq, 100 + rep)
    q_pos = jnp.asarray(offs[:, None] + np.arange(sq)[None], jnp.int32)
    kw = dict(scale=1.0 / np.sqrt(GD), window=window, softcap=cap,
              interpret=True)
    if paged:
        out = flash_attention_paged(
            jnp.asarray(q), jnp.asarray(pk), jnp.asarray(pv),
            jnp.asarray(table), q_pos, jnp.asarray(lens), **kw,
        )
    else:
        out = flash_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), q_pos, None,
            jnp.asarray(lens), **kw,
        )
    _check_grouped(
        out, _grouped_ref(offs, lens, q, k, v, window=window, cap=cap), lens,
    )


# ------------------------------------------------------------- grouped gemm
@pytest.mark.parametrize(
    "m,k,n,e,sizes",
    [
        (256, 256, 128, 4, [64, 0, 128, 64]),
        (384, 128, 256, 6, None),
        (96, 128, 128, 3, [0, 96, 0]),
    ],
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_grouped_gemm_kernel(m, k, n, e, sizes, dtype):
    from repro.kernels.grouped_gemm.ops import grouped_gemm
    from repro.kernels.grouped_gemm.ref import grouped_gemm_ref

    if sizes is None:
        cuts = np.sort(rng.integers(0, m, e - 1))
        sizes = np.diff(np.concatenate([[0], cuts, [m]]))
    gs = jnp.asarray(sizes, jnp.int32)
    x = jnp.asarray(rng.standard_normal((m, k)), dtype)
    w = jnp.asarray(rng.standard_normal((e, k, n)) * 0.1, dtype)
    out = grouped_gemm(x, w, gs, interpret=True)
    ref = grouped_gemm_ref(x, w, gs)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), rtol=tol, atol=tol
    )


# ----------------------------------------------------------------- ssd scan
@pytest.mark.parametrize(
    "B,S,H,P,G,N,chunk",
    [
        (2, 64, 4, 16, 1, 32, 16),
        (1, 128, 8, 64, 2, 64, 32),
        (2, 50, 4, 16, 4, 32, 16),     # padding path
    ],
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan_kernel(B, S, H, P, G, N, chunk, dtype):
    from repro.kernels.ssd_scan.ops import ssd_scan
    from repro.kernels.ssd_scan.ref import ssd_ref

    x = jnp.asarray(rng.standard_normal((B, S, H, P)), dtype)
    dt = jnp.asarray(np.abs(rng.standard_normal((B, S, H))) * 0.1 + 0.01, jnp.float32)
    a = -jnp.asarray(np.abs(rng.standard_normal(H)) + 0.5, jnp.float32)
    b = jnp.asarray(rng.standard_normal((B, S, G, N)), dtype)
    c = jnp.asarray(rng.standard_normal((B, S, G, N)), dtype)
    y, st = ssd_scan(x, dt, a, b, c, chunk=chunk, interpret=True)
    yr, str_ = ssd_ref(x, dt, a, b, c, chunk)
    scale = float(jnp.max(jnp.abs(yr.astype(jnp.float32)))) + 1e-9
    tol = 1e-5 if dtype == jnp.float32 else 4e-2
    assert float(jnp.max(jnp.abs(y.astype(jnp.float32) - yr.astype(jnp.float32)))) / scale < tol
    s_scale = float(jnp.max(jnp.abs(str_))) + 1e-9
    assert float(jnp.max(jnp.abs(st - str_))) / s_scale < tol


def test_flash_attention_matches_model_attention():
    """Kernel path == the model's chunked/naive path on a real config."""
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.models.layers import _naive_attention

    b, s, h, kv, d = 2, 128, 4, 2, 64
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, kv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, kv, d)), jnp.float32)
    scale = 1.0 / np.sqrt(d)
    out_kernel = flash_attention(q, k, v, scale=scale, causal=True, interpret=True)
    qg = q.reshape(b, s, kv, h // kv, d)
    out_model = _naive_attention(
        qg, k, v, jnp.arange(s), jnp.arange(s),
        causal=True, window=None, cap=None, scale=scale,
    ).reshape(b, s, h, d)
    np.testing.assert_allclose(
        np.asarray(out_kernel), np.asarray(out_model), rtol=2e-4, atol=2e-4
    )
