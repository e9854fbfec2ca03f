"""jit'd wrapper: model-layout [B,S,H,D] GQA attention on the flash kernel.

Handles GQA head grouping and query padding.  The ``rep = H/KV`` query heads
of one KV head's group are stacked into one kernel row:
``[B, Sq, KV, rep, D] → [B, KV, Sq, rep, D] → [B·KV, Sq·rep, D]``, so
folded row ``i`` is query position ``i // rep`` of head ``i % rep``, and
each K/V block is read once per group (K/V keep their ``KV`` heads; nothing
is repeated).  The folded rows are padded to a multiple of 8 and of the
query block, which holds up to ``DEFAULT_BQ`` positions of every head of the
group (at most ``MAX_BQ`` rows); keys are padded to the KV block.  Blocks
span the whole head_dim, which the TPU accepts for any head_dim.  On CPU
the kernel runs in interpret mode (correctness path); on TPU it compiles
to the real blockwise kernel (see ``repro.kernels.resolve_interpret``).

Ragged decode batches are supported through ``q_pos``: a per-row position
operand ([B] or [B, Sq]) makes every batch row mask against its own cache
depth (the per-slot ``cache_pos`` vector of the serving engine), streamed
into the kernel as a scalar-prefetch operand.  A 1-D ``q_pos`` ([Sq]) or
the static ``q_offset`` keep the classic shared-offset behavior.

Fused mixed prefill/decode batches additionally carry ``q_lens`` ([B]): the
number of VALID query rows per batch row (decode rows 1, prefill chunks
``chunk``, idle rows 0).  Queries beyond a row's ``q_lens`` are fully
masked inside the kernel and output exact zeros.

The kernels compute only the (query block, KV block) steps that a live
query of the row can see; :func:`paged_live_blocks` counts them for one
paged call on the host, by the kernel's own rule.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import resolve_interpret

from .kernel import (
    DEFAULT_BK,
    DEFAULT_BQ,
    MAX_BQ,
    _live_span,
    flash_attention_pallas,
    flash_attention_pallas_paged,
)


def _block_q(rows: int, rep: int) -> int:
    """Folded rows a query block holds: ``DEFAULT_BQ`` positions of each of
    the ``rep`` heads, at most ``MAX_BQ``, a multiple of 8."""
    return min(DEFAULT_BQ * rep, MAX_BQ, -(-rows // 8) * 8)


def _fold_queries(q: jax.Array, kv: int) -> tuple[jax.Array, int]:
    """``[B, Sq, H, D]`` → ``([B·KV, rows, D], block_q)``: each KV head's
    ``rep`` query heads stacked position-major in one row, padded to a
    multiple of ``block_q`` (itself a multiple of 8)."""
    b, sq, h, d = q.shape
    rep = h // kv
    rows = sq * rep
    bq = _block_q(rows, rep)
    qk = q.reshape(b, sq, kv, rep, d).transpose(0, 2, 1, 3, 4).reshape(
        b * kv, rows, d
    )
    pad = (-rows) % bq
    if pad:
        qk = jnp.pad(qk, ((0, 0), (0, pad), (0, 0)))
    return qk, bq


def _unfold(out: jax.Array, b: int, sq: int, h: int) -> jax.Array:
    """Inverse of :func:`_fold_queries` on the kernel's output."""
    bk, _, d = out.shape
    kv = bk // b
    rep = h // kv
    return out[:, : sq * rep].reshape(b, kv, sq, rep, d).transpose(
        0, 2, 1, 3, 4
    ).reshape(b, sq, h, d)


def _row_offsets(q_pos: Optional[jax.Array], b: int, default: int) -> jax.Array:
    """Position of query position 0 per batch row ([B] int32)."""
    if q_pos is None:
        return jnp.full((b,), int(default), jnp.int32)
    if q_pos.ndim == 2:                      # [B, Sq] — ragged rows
        return q_pos[:, 0].astype(jnp.int32)
    return jnp.full((b,), q_pos[0].astype(jnp.int32))   # [Sq] shared


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "window", "softcap", "scale", "q_offset", "interpret",
    ),
)
def flash_attention(
    q: jax.Array,            # [B, Sq, H, D]
    k: jax.Array,            # [B, Sk, KV, D]
    v: jax.Array,            # [B, Sk, KV, D]
    q_pos: Optional[jax.Array] = None,   # [Sq] or [B, Sq] query positions
    k_pos: Optional[jax.Array] = None,   # [Sk] — must be arange(Sk) (affine);
                                         # kept for signature parity with the
                                         # naive/chunked impls
    q_lens: Optional[jax.Array] = None,  # [B] valid query rows per batch row
                                         # (fused mixed batch; None → all Sq)
    *,
    scale: float,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    q_offset: int = 0,
    interpret: Optional[bool] = None,
) -> jax.Array:
    interpret = resolve_interpret(interpret)
    del k_pos  # affine by construction (cache rows 0..Sk-1); masking uses q_pos
    b, sq, h, d = q.shape
    sk, kv = k.shape[1], k.shape[2]

    # per-(batch, KV head) rows: one offset and length per row
    offs = jnp.repeat(_row_offsets(q_pos, b, q_offset), kv)
    lens = None if q_lens is None else jnp.repeat(q_lens.astype(jnp.int32), kv)
    qk, bq = _fold_queries(q, kv)
    # K/V keep their KV heads: [B·KV, Sk, D], read once per group
    kk = k.transpose(0, 2, 1, 3).reshape(b * kv, sk, d)
    vk = v.transpose(0, 2, 1, 3).reshape(b * kv, sk, d)

    # pad keys to the KV block; every block spans the full head_dim (legal on
    # the TPU for any head_dim), so head_dim is never padded
    bk = min(DEFAULT_BK, max(8, sk))
    skp = (-sk) % bk
    if skp:
        kk = jnp.pad(kk, ((0, 0), (0, skp), (0, 0)))
        vk = jnp.pad(vk, ((0, 0), (0, skp), (0, 0)))

    out = flash_attention_pallas(
        qk, kk, vk,
        scale=scale,
        causal=causal,
        window=int(window or 0),
        softcap=float(softcap or 0.0),
        q_offsets=offs,
        q_lens=lens,
        k_len=sk,
        rep=h // kv,
        block_q=bq,
        block_k=bk,
        interpret=interpret,
    )
    return _unfold(out, b, sq, h)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "scale", "v_dim", "interpret"),
)
def flash_attention_paged(
    q: jax.Array,            # [B, Sq, H, D]
    pool_k: jax.Array,       # [KV, num_pages + 1, P, D] — last page = trash
    pool_v: jax.Array,       # [KV, num_pages + 1, P, D]
    table: jax.Array,        # [B, pages_per_slot] int32 (−1 = unmapped)
    q_pos: Optional[jax.Array] = None,   # [Sq] or [B, Sq] query positions
    q_lens: Optional[jax.Array] = None,  # [B] valid query rows per batch row
    *,
    scale: float,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    v_dim: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Model-layout wrapper for the PAGED flash kernel: K/V live in a
    physical page pool and the per-slot page table rides into the kernel as
    a scalar-prefetch operand — the kernel's K/V index maps dereference it
    per (slot·KV head, page) grid step, so no ``[B, max_len]`` logical view
    is ever gathered, and each page is fetched once per GQA group (the
    kv-head axis is indexed straight out of the pool).

    A (query block, page) step computes only when a live query of the row
    can see a key of that page (causal, window and ``q_lens``); every other
    step skips its work, and the K/V index maps clamp the page to the live
    span, so it fetches nothing.  Pages past a row's written depth, mapped
    or not, are therefore never read.

    Unmapped table entries (−1) are clamped to the reserved trash page
    ``num_pages``; it sits at logical positions beyond every row's written
    span, outside the live span, so whatever it holds, NaN included, gets
    exactly zero attention weight.

    ``v_dim`` below the pool's width reads V as each page's leading
    ``v_dim`` columns: MLA's absorbed form passes its one latent pool
    ``[1, num_pages + 1, P, W]`` (rows ``[c, k_rope]``) as both K and V, so
    per-head K and V are never built, and its 16 query heads stack on the
    one latent "KV head".  That call's kernel is named
    ``flash_attention_paged_latent``.  Returns ``[B, Sq, H, v_dim or D]``."""
    interpret = resolve_interpret(interpret)
    b, sq, h, d = q.shape
    kv = pool_k.shape[0]
    num_pages = pool_k.shape[1] - 1
    tbl = jnp.where(table >= 0, table, num_pages).astype(jnp.int32)

    offs = jnp.repeat(_row_offsets(q_pos, b, 0), kv)
    lens = None if q_lens is None else jnp.repeat(q_lens.astype(jnp.int32), kv)
    qk, bq = _fold_queries(q, kv)
    latent = v_dim not in (None, d)

    out = flash_attention_pallas_paged(
        qk, pool_k, pool_v, tbl,
        scale=scale,
        causal=causal,
        window=int(window or 0),
        softcap=float(softcap or 0.0),
        q_offsets=offs,
        q_lens=lens,
        kv_heads=kv,
        rep=h // kv,
        block_q=bq,
        interpret=interpret,
        v_dim=v_dim,
        name="flash_attention_paged_latent" if latent else "flash_attention_paged",
    )
    return _unfold(out, b, sq, h)


def paged_live_blocks(
    q_lens, cache_pos, sq: int, *, rep: int, kv_heads: int,
    pages_per_slot: int, page_tokens: int, window: Optional[int] = None,
) -> Tuple[int, int]:
    """``(live_blocks, grid_blocks)`` of one causal
    :func:`flash_attention_paged` call on a ``[slots, sq]`` batch with
    per-slot ``q_lens`` and ``cache_pos`` (host arrays): the grid's
    (slot·KV head, query block, page) steps that compute, by the kernel's
    own rule, and all of them."""
    rows = sq * rep
    bq = _block_q(rows, rep)
    n_q = -(-rows // bq)
    rows_live, lo, hi = _live_span(
        np.asarray(cache_pos, np.int64)[:, None],
        np.asarray(q_lens, np.int64)[:, None],
        np.arange(n_q)[None], causal=True, window=int(window or 0),
        k_len=pages_per_slot * page_tokens, block_q=bq, block_k=page_tokens,
        rep=rep, xp=np,
    )
    live = np.where(rows_live, np.maximum(hi - lo + 1, 0), 0).sum()
    slots = np.shape(q_lens)[0]
    return int(live) * kv_heads, slots * kv_heads * n_q * pages_per_slot
