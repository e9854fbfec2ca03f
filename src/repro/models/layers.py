"""Shared neural-net layers for the model zoo (pure JAX, no flax).

Parameters are nested dicts of jnp arrays.  Every layer takes the param
sub-tree as its first argument.  Attention supports GQA, causal and
sliding-window masking, soft-capping (gemma2), qk-norm (qwen3), RoPE and
M-RoPE (qwen2-vl), and three implementations:

* ``naive``   — materializes the [S, S] score matrix (oracle / small tests),
* ``chunked`` — lax.scan over KV blocks with online softmax (flash-attention
  algorithm in pure jnp; memory-safe at 32k+ and what the dry-run lowers),
* ``pallas``  — the TPU kernel in repro.kernels (interpret mode off the TPU);
  it specializes on the layer window, which must be a static int.

All three accept **ragged decode batches**: a ``(B,)`` ``cache_pos`` vector
gives every batch row its own KV write index and causal mask over its own
valid length, so serving slots at different depths decode in one batch.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.parallel.context import shard_hint

Params = Dict[str, Any]


# --------------------------------------------------------------------------
# init helpers
# --------------------------------------------------------------------------


def dense_init(key, d_in: int, d_out: int, dtype) -> jax.Array:
    scale = 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), dtype=jnp.float32) * scale).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype) -> jax.Array:
    return (jax.random.normal(key, (vocab, d), dtype=jnp.float32) * 0.02).astype(dtype)


# --------------------------------------------------------------------------
# norms
# --------------------------------------------------------------------------


def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * (1.0 + w.astype(jnp.float32))).astype(dt)


def rmsnorm_init(d: int) -> jax.Array:
    return jnp.zeros((d,), dtype=jnp.float32)  # stored as (1 + w) offset form


# --------------------------------------------------------------------------
# RoPE / M-RoPE
# --------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [B, S, H, D]; positions: [B, S] (int)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                                  # [D/2]
    angles = positions[..., None].astype(jnp.float32) * freqs     # [B, S, D/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(
    x: jax.Array,
    positions: jax.Array,        # [3, B, S] — (t, h, w) position streams
    theta: float,
    sections: Tuple[int, int, int],
) -> jax.Array:
    """Qwen2-VL multimodal RoPE: the D/2 frequency slots are split into
    (t, h, w) sections, each rotated by its own position stream."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                                  # [D/2]
    assert sum(sections) == d // 2, (sections, d)
    # build per-slot positions: [B, S, D/2]
    parts = []
    start = 0
    for i, sec in enumerate(sections):
        pos_i = positions[i][..., None].astype(jnp.float32)       # [B, S, 1]
        parts.append(jnp.broadcast_to(pos_i, pos_i.shape[:-1] + (sec,)))
        start += sec
    pos_slots = jnp.concatenate(parts, axis=-1)                   # [B, S, D/2]
    angles = pos_slots * freqs
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def softcap(x: jax.Array, cap: Optional[float]) -> jax.Array:
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)


# --------------------------------------------------------------------------
# attention
# --------------------------------------------------------------------------


def attention_params(key, cfg: ModelConfig, dtype) -> Params:
    if cfg.is_mla:
        return latent_attention_params(key, cfg, dtype)
    hd = cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], cfg.d_model, cfg.n_heads * hd, dtype),
        "wk": dense_init(ks[1], cfg.d_model, cfg.n_kv_heads * hd, dtype),
        "wv": dense_init(ks[2], cfg.d_model, cfg.n_kv_heads * hd, dtype),
        "wo": dense_init(ks[3], cfg.n_heads * hd, cfg.d_model, dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = rmsnorm_init(hd)
        p["k_norm"] = rmsnorm_init(hd)
    return p


def _repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.repeat(k, n_rep, axis=2)


def _attn_mask(
    q_pos: jax.Array,          # [Sq] or [B, Sq] absolute positions of queries
    k_pos: jax.Array,          # [Sk]
    causal: bool,
    window: Optional[int],
) -> jax.Array:
    """True where attention is allowed: [Sq, Sk], or [B, Sq, Sk] when
    ``q_pos`` carries per-row positions (ragged batches — each serving slot
    sits at its own decode depth)."""
    qp = q_pos[..., :, None]
    m = jnp.ones(qp.shape[:-1] + (k_pos.shape[0],), dtype=bool)
    if causal:
        m &= k_pos <= qp
    if window is not None:
        m &= k_pos > (qp - window)
    return m


def _bcast_mask(mask: jax.Array) -> jax.Array:
    """[Sq,Sk] or [B,Sq,Sk] mask → broadcastable over [B,G,R,Sq,Sk] scores."""
    if mask.ndim == 2:
        return mask[None, None, None]
    return mask[:, None, None]


def _naive_attention(q, k, v, q_pos, k_pos, *, causal, window, cap, scale):
    """q: [B,Sq,G,R,D] (GQA-grouped), k/v: [B,Sk,G,D] — no KV repeat is ever
    materialized (2× memory at 32k-decode otherwise)."""
    scores = jnp.einsum(
        "bqgrd,bkgd->bgrqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    scores = softcap(scores, cap)
    mask = _attn_mask(q_pos, k_pos, causal, window)
    scores = jnp.where(_bcast_mask(mask), scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bgrqk,bkgd->bqgrd", w.astype(v.dtype), v)
    return out


def _chunked_attention(q, k, v, q_pos, k_pos, *, causal, window, cap, scale, chunk):
    """Online-softmax attention, scanning over KV chunks (flash algorithm).

    q: [B,Sq,G,R,D] (GQA-grouped), k/v: [B,Sk,G,D]."""
    b, sq, g, r, d = q.shape
    sk = k.shape[1]
    nchunks = -(-sk // chunk)
    pad = nchunks * chunk - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, (0, pad), constant_values=jnp.iinfo(jnp.int32).max)
    kc = k.reshape(b, nchunks, chunk, g, d).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, nchunks, chunk, g, d).transpose(1, 0, 2, 3, 4)
    pc = k_pos.reshape(nchunks, chunk)

    qf = q.astype(jnp.float32)

    def body(carry, xs):
        m_prev, l_prev, acc = carry
        kb, vb, pb = xs                                           # [B,C,G,D], [C]
        s = jnp.einsum("bqgrd,bkgd->bgrqk", qf, kb.astype(jnp.float32)) * scale
        s = softcap(s, cap)
        mask = _attn_mask(q_pos, pb, causal, window)
        s = jnp.where(_bcast_mask(mask), s, -1e30)
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        pexp = jnp.exp(s - m_new[..., None])
        l_new = l_prev * alpha + pexp.sum(axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bgrqk,bkgd->bgrqd", pexp, vb.astype(jnp.float32)
        )
        return (m_new, l_new, acc), None

    m0 = jnp.full((b, g, r, sq), -jnp.inf, dtype=jnp.float32)
    l0 = jnp.zeros((b, g, r, sq), dtype=jnp.float32)
    a0 = jnp.zeros((b, g, r, sq, v.shape[-1]), dtype=jnp.float32)
    (m_f, l_f, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kc, vc, pc))
    out = acc / jnp.maximum(l_f[..., None], 1e-30)
    # [B,G,R,Sq,D] -> [B,Sq,G,R,D]
    return out.transpose(0, 3, 1, 2, 4).astype(q.dtype)


def _paged_update(pools, table, news, cache_pos, q_lens):
    """Scatter this call's new cache rows into their page pools through
    ``table``; returns the updated pools.

    Each pool is head-major, ``[heads, num_pages + 1, P, width]``, and
    ``news`` holds its ``[B, Sq, heads, width]`` rows (GQA: K and V; MLA:
    one latent row).  Flat-pool indexing: token ``i`` of row ``b`` lands
    at physical position ``table[b, pos // P] * P + pos % P`` of every
    head's pool, with ``pos = cache_pos[b] + i``.
    Rows/tokens outside their valid span (``i ≥ q_lens[b]``), and any
    unmapped table entry, are redirected to the reserved TRASH page (the
    pool's last page) — the scatter can therefore never corrupt a real
    page, which is what lets idle rows of a fused mixed batch ride along.
    """
    b, sq = news[0].shape[0], news[0].shape[1]
    _, np1, page_tokens, _ = pools[0].shape
    pages_per_slot = table.shape[1]
    trash = np1 - 1
    cp = jnp.broadcast_to(jnp.asarray(cache_pos, jnp.int32), (b,))
    ql = (
        jnp.full((b,), sq, jnp.int32)
        if q_lens is None
        else q_lens.astype(jnp.int32)
    )
    ii = jnp.arange(sq, dtype=jnp.int32)[None, :]
    pos = cp[:, None] + ii                                     # [B, Sq]
    valid = (ii < ql[:, None]) & (pos < pages_per_slot * page_tokens)
    pidx = jnp.clip(pos // page_tokens, 0, pages_per_slot - 1)
    page = jnp.take_along_axis(table, pidx, axis=1)            # [B, Sq]
    page = jnp.where(valid & (page >= 0), page, trash)
    dest = (page * page_tokens + pos % page_tokens).reshape(-1)

    def put(pool, new):
        # [B, Sq, heads, w] → [heads, B·Sq, w], scattered along the token axis
        heads, _, _, w = pool.shape
        item = new.astype(pool.dtype).reshape(b * sq, heads, w).transpose(1, 0, 2)
        flat = (heads, np1 * page_tokens, w)
        return pool.reshape(flat).at[:, dest].set(item).reshape(pool.shape)

    return tuple(put(pool, new) for pool, new in zip(pools, news))


def _dense_cache_write(cache, new, cache_pos, q_lens, ragged):
    """Write this call's rows ``new[name]`` ``[B, Sq, heads, w]`` into the
    dense cache rows ``cache[name]`` ``[B, Smax, heads, w]`` at
    ``cache_pos``; returns the updated dict."""
    sq = next(iter(new.values())).shape[1]
    if ragged and q_lens is not None:
        # fused mixed batch: row b writes new[b, :q_lens[b]] at its own
        # depth and NOTHING else.  dynamic_update_slice cannot express
        # this — it clamps start indices, so a short row near max_len
        # would slide backwards and corrupt valid KV — so scatter via a
        # masked gather-from-source instead: cache slot t of row b takes
        # chunk token (t - cache_pos[b]) iff that lands in [0, q_lens[b]).
        smax = next(iter(cache.values())).shape[1]
        src = jnp.arange(smax, dtype=jnp.int32)[None, :] - cache_pos[:, None]
        valid = (src >= 0) & (src < q_lens[:, None])           # [B, Smax]
        idx = jnp.clip(src, 0, sq - 1)[:, :, None, None]
        w4 = valid[:, :, None, None]

        def put(c, n):
            return jnp.where(w4, jnp.take_along_axis(n.astype(c.dtype), idx, axis=1), c)
    elif ragged:
        # each row writes at its own depth (per-slot KV write index)
        upd = lambda c, n, pos: jax.lax.dynamic_update_slice_in_dim(c, n, pos, axis=0)

        def put(c, n):
            return jax.vmap(upd)(c, n.astype(c.dtype), cache_pos)
    else:
        def put(c, n):
            return jax.lax.dynamic_update_slice_in_dim(
                c, n.astype(c.dtype), cache_pos, axis=1
            )
    return {name: put(cache[name], n) for name, n in new.items()}


def _paged_view(pool, table):
    """Gather the logical ``[B, max_len, KV, D]`` cache view out of the page
    pool (the naive/chunked reference read path; the pallas kernel reads
    through the table directly and never materializes this).  Unmapped
    entries resolve to the trash page — garbage, but always causally masked
    (they sit beyond every row's written span)."""
    kv, np1, page_tokens, hd = pool.shape
    trash = np1 - 1
    pages_per_slot = table.shape[1]
    t = jnp.arange(pages_per_slot * page_tokens, dtype=jnp.int32)
    pages = jnp.where(table >= 0, table, trash)
    src = pages[:, t // page_tokens] * page_tokens + (t % page_tokens)[None, :]
    flat = pool.reshape((kv, np1 * page_tokens, hd))
    return jnp.take(flat, src, axis=1).transpose(1, 2, 0, 3)   # [B, L, KV, D]


def multihead_attention(
    p: Params,
    x: jax.Array,                     # [B, Sq, D_model]
    cfg: ModelConfig,
    *,
    positions: jax.Array,             # [B, Sq] (or [3, B, Sq] for M-RoPE)
    kv_cache: Optional[Dict[str, jax.Array]] = None,   # {"k","v": [B,Smax,KV,hd]}
    cache_pos: Optional[jax.Array] = None,             # scalar, or [B] per-row
                                                       # (#valid cache entries)
    layer_window: Optional[int] = None,
    cross_kv: Optional[Tuple[jax.Array, jax.Array]] = None,  # enc-dec cross attn
    causal: Optional[bool] = None,    # None → causal for self, full for cross
    q_lens: Optional[jax.Array] = None,  # [B] valid query rows per batch row
                                         # (fused mixed batch: decode rows 1,
                                         # prefill chunks n, idle rows 0)
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    hd = cfg.resolved_head_dim
    b, sq, _ = x.shape
    # ragged decode: a [B] cache_pos vector means every batch row sits at its
    # own depth — per-row KV write index and per-row causal mask below
    ragged = cache_pos is not None and jnp.ndim(cache_pos) > 0
    q = (x @ p["wq"]).reshape(b, sq, cfg.n_heads, hd)

    if cross_kv is None:
        k = (x @ p["wk"]).reshape(b, sq, cfg.n_kv_heads, hd)
        v = (x @ p["wv"]).reshape(b, sq, cfg.n_kv_heads, hd)
    else:
        k, v = cross_kv

    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        if cross_kv is None:
            k = rmsnorm(k, p["k_norm"], cfg.norm_eps)

    # RoPE (self-attention only; seamless cross-attn has no rope on kv)
    # q_pos: [Sq] shared across rows, or [B, Sq] per-row (ragged decode —
    # positions already carry the per-row cache_pos offset)
    if cross_kv is None:
        if cfg.mrope_sections is not None:
            q = apply_mrope(q, positions, cfg.rope_theta, cfg.mrope_sections)
            k = apply_mrope(k, positions, cfg.rope_theta, cfg.mrope_sections)
            q_pos = positions[0] if ragged else positions[0][0]  # temporal stream
        else:
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
            q_pos = positions if ragged else positions[0]
    else:
        q_pos = positions[0] if positions.ndim == 2 else positions[0][0]

    q = shard_hint(q, "batch", None, "heads", None)

    new_cache = None
    # paged cache: {"k","v": [KV, num_pages+1, P, hd] pool, "table": [B, pps]}
    paged = kv_cache is not None and cross_kv is None and "table" in kv_cache
    if paged:
        pool_k, pool_v, table = kv_cache["k"], kv_cache["v"], kv_cache["table"]
        cp = cache_pos if cache_pos is not None else 0
        pool_k, pool_v = _paged_update((pool_k, pool_v), table, (k, v), cp, q_lens)
        new_cache = {"k": pool_k, "v": pool_v, "table": table}
        # logical cache depth = pages_per_slot · page_tokens; reads beyond a
        # row's written span hit stale/trash data that the causal-vs-q_pos
        # mask already zeroes, same as unwritten dense rows
        k_pos1d = jnp.arange(table.shape[1] * pool_k.shape[2])
        causal = True
    elif kv_cache is not None and cross_kv is None:
        # decode / incremental prefill: write new kv at cache_pos
        new_cache = _dense_cache_write(
            kv_cache, {"k": k, "v": v}, cache_pos, q_lens, ragged
        )
        k, v = new_cache["k"], new_cache["v"]
        k_pos1d = jnp.arange(k.shape[1])
        # the causal test against q_pos also masks unwritten cache slots
        # (per ROW in the ragged case: row b sees only its own ≤ cache_pos[b])
        causal = True
    else:
        k_pos1d = (
            q_pos
            if cross_kv is None and q_pos.ndim == 1
            else jnp.arange(k.shape[1])
        )
        if causal is None:
            causal = cross_kv is None

    g = pool_k.shape[0] if paged else k.shape[2]
    n_rep = cfg.n_heads // g
    qg = q.reshape(b, sq, g, n_rep, hd)   # GQA grouping — KV is never repeated

    scale = cfg.attn_logit_scale if cfg.attn_logit_scale is not None else 1.0 / math.sqrt(hd)
    window = layer_window
    impl = cfg.attention_impl
    if impl == "pallas":
        # the kernel specializes on the window at trace time, so it must be a
        # static int: a traced window (a layer-scan xs) is a caller error
        if isinstance(window, jax.core.Tracer):
            raise TypeError(
                "attention_impl='pallas' needs a static per-layer window; "
                "got a traced value (serve per-layer params, "
                "scan_layers=False)"
            )
        static_window = None if window is None else int(window)
        if static_window is not None and static_window <= 0:
            static_window = None
    if paged and impl != "pallas":
        # naive/chunked reference read path: gather the logical [B, L, KV, hd]
        # view out of the pool once, then reuse the dense mask logic unchanged
        k = _paged_view(pool_k, table)
        v = _paged_view(pool_v, table)
    if impl == "pallas":
        from repro.kernels.flash_attention import ops as fa_ops

        if paged:
            out = fa_ops.flash_attention_paged(
                q, pool_k, pool_v, table, q_pos, q_lens, causal=causal,
                window=static_window, softcap=cfg.attn_softcap, scale=scale,
            )
        else:
            out = fa_ops.flash_attention(
                q, k, v, q_pos, k_pos1d, q_lens, causal=causal,
                window=static_window, softcap=cfg.attn_softcap, scale=scale,
            )
    elif impl == "chunked" and k.shape[1] > cfg.attn_chunk and sq > 1:
        out = _chunked_attention(
            qg, k, v, q_pos, k_pos1d,
            causal=causal, window=window, cap=cfg.attn_softcap, scale=scale,
            chunk=cfg.attn_chunk,
        ).reshape(b, sq, cfg.n_heads, hd)
    else:
        out = _naive_attention(
            qg, k, v, q_pos, k_pos1d,
            causal=causal, window=window, cap=cfg.attn_softcap, scale=scale,
        ).astype(x.dtype).reshape(b, sq, cfg.n_heads, hd)

    out = out.reshape(b, sq, cfg.n_heads * hd)
    if q_lens is not None:
        # fused-batch padding contract: query rows beyond a row's q_len emit
        # exact zeros from EVERY impl (the pallas kernel zeroes them via its
        # all-masked denominator; naive/chunked would leak a uniform softmax)
        out = jnp.where(
            jnp.arange(sq, dtype=jnp.int32)[None, :, None] < q_lens[:, None, None],
            out, jnp.zeros_like(out),
        )
    out = out @ p["wo"]
    out = shard_hint(out, "batch", None, "embed")
    return out, new_cache


# --------------------------------------------------------------------------
# multi-head latent attention (deepseek-v3 MLA), absorbed form
# --------------------------------------------------------------------------


def latent_attention_params(key, cfg: ModelConfig, dtype) -> Params:
    """MLA without a query low-rank: ``wq`` → per head [nope, rope];
    ``wkv_a`` → [latent, rope] a token (the rope part shared by every
    head); ``kv_norm`` on the latent; ``wkv_b`` → per head [k_nope, v]."""
    h, r = cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], cfg.d_model, h * (dn + dr), dtype),
        "wkv_a": dense_init(ks[1], cfg.d_model, r + dr, dtype),
        "kv_norm": rmsnorm_init(r),
        "wkv_b": dense_init(ks[2], r, h * (dn + dv), dtype),
        "wo": dense_init(ks[3], h * dv, cfg.d_model, dtype),
    }


def _latent_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """RoPE on MLA's rope dims, which rotate in interleaved pairs (2i,
    2i+1) (deepseek-v3's ``rope_interleave``): the dims are gathered to
    [evens, odds], as the published model does, so rotate-half turns the
    same pairs; q and k are permuted alike, so scores are unchanged."""
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    return apply_rope(x, positions, theta)


def latent_attention(
    p: Params,
    x: jax.Array,                     # [B, Sq, D_model]
    cfg: ModelConfig,
    *,
    positions: jax.Array,             # [B, Sq]
    kv_cache: Optional[Dict[str, jax.Array]] = None,   # {"kv": latent rows}
    cache_pos: Optional[jax.Array] = None,             # scalar, or [B] per-row
    layer_window: Optional[int] = None,
    q_lens: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """MLA in the absorbed form.  Each token caches one row ``[c, k_rope]``
    (``c`` the RMS-normed latent, ``k_rope`` its roped shared key part,
    zero-padded to ``cfg.latent_cache_width``).  A head's query is folded
    through its key up-projection, ``q_lat = q_nope · W_uk_h``, so scores
    are ``[q_lat, q_rope] · [c, k_rope]`` (scaled by the published
    1/sqrt(nope + rope)) and the context ``p · c`` is lifted by ``W_uv_h``
    afterwards: per-head K and V are never built for any token."""
    del layer_window                  # MLA models use full attention
    b, sq, _ = x.shape
    h, r = cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    width = cfg.latent_cache_width
    ragged = cache_pos is not None and jnp.ndim(cache_pos) > 0
    q_pos = positions if ragged else positions[0]

    q = (x @ p["wq"]).reshape(b, sq, h, dn + dr)
    kva = x @ p["wkv_a"]                                       # [B, Sq, r + dr]
    c = rmsnorm(kva[..., :r], p["kv_norm"], cfg.norm_eps)
    k_rope = _latent_rope(kva[..., None, r:], positions, cfg.rope_theta)
    wkv_b = p["wkv_b"].reshape(r, h, dn + dv)
    q_lat = jnp.einsum("bshn,rhn->bshr", q[..., :dn], wkv_b[..., :dn])
    pad = width - r - dr
    qc = jnp.concatenate(
        [q_lat, _latent_rope(q[..., dn:], positions, cfg.rope_theta),
         jnp.zeros((b, sq, h, pad), q.dtype)], axis=-1)        # [B, Sq, H, W]
    row = jnp.concatenate(
        [c[:, :, None, :], k_rope.astype(c.dtype),
         jnp.zeros((b, sq, 1, pad), c.dtype)], axis=-1)        # [B, Sq, 1, W]
    qc = shard_hint(qc, "batch", None, "heads", None)
    scale = 1.0 / math.sqrt(dn + dr)

    paged = kv_cache is not None and "table" in kv_cache
    if paged:
        table = kv_cache["table"]
        cp = cache_pos if cache_pos is not None else 0
        (pool,) = _paged_update((kv_cache["kv"],), table, (row,), cp, q_lens)
        new_cache = {"kv": pool, "table": table}
    elif kv_cache is not None:
        new_cache = _dense_cache_write(kv_cache, {"kv": row}, cache_pos, q_lens, ragged)
        lat = new_cache["kv"]
    else:
        new_cache, lat = None, row

    if paged and cfg.attention_impl == "pallas":
        from repro.kernels.flash_attention import ops as fa_ops

        out = fa_ops.flash_attention_paged(
            qc, pool, pool, table, q_pos, q_lens, scale=scale, v_dim=r,
        )
    else:
        if paged:                     # reference read path: the logical view
            lat = _paged_view(pool, table)
        k_pos = q_pos if kv_cache is None and q_pos.ndim == 1 else jnp.arange(lat.shape[1])
        qg = qc.reshape(b, sq, 1, h, width)
        if cfg.attention_impl == "chunked" and lat.shape[1] > cfg.attn_chunk and sq > 1:
            out = _chunked_attention(
                qg, lat, lat[..., :r], q_pos, k_pos, causal=True, window=None,
                cap=None, scale=scale, chunk=cfg.attn_chunk,
            )
        else:
            out = _naive_attention(
                qg, lat, lat[..., :r], q_pos, k_pos, causal=True, window=None,
                cap=None, scale=scale,
            ).astype(x.dtype)
        out = out.reshape(b, sq, h, r)

    out = jnp.einsum("bshr,rhv->bshv", out, wkv_b[..., dn:]).reshape(b, sq, h * dv)
    if q_lens is not None:
        # fused-batch padding contract: rows beyond a row's q_len are zeros
        out = jnp.where(
            jnp.arange(sq, dtype=jnp.int32)[None, :, None] < q_lens[:, None, None],
            out, jnp.zeros_like(out),
        )
    out = shard_hint(out @ p["wo"], "batch", None, "embed")
    return out, new_cache


# --------------------------------------------------------------------------
# MLP
# --------------------------------------------------------------------------


def mlp_params(key, d_model: int, d_ff: int, activation: str, dtype) -> Params:
    ks = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(ks[0], d_model, d_ff, dtype),
        "w_up": dense_init(ks[1], d_model, d_ff, dtype),
        "w_down": dense_init(ks[2], d_ff, d_model, dtype),
    }


def mlp(p: Params, x: jax.Array, activation: str) -> jax.Array:
    gate = x @ p["w_gate"]
    up = x @ p["w_up"]
    gate = shard_hint(gate, "batch", None, "ff")
    up = shard_hint(up, "batch", None, "ff")
    if activation == "silu":
        h = jax.nn.silu(gate) * up
    elif activation == "geglu":
        h = jax.nn.gelu(gate, approximate=True) * up
    elif activation == "gelu":
        h = jax.nn.gelu(gate, approximate=True) * up
    else:
        raise ValueError(activation)
    out = h @ p["w_down"]
    return shard_hint(out, "batch", None, "embed")
