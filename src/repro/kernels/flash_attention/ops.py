"""jit'd wrapper: model-layout [B,S,H,D] GQA attention on the flash kernel.

Handles GQA head grouping (queries of one KV head's group are folded into
the batch·kv_head axis) and sequence padding to block multiples; blocks
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
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret

from .kernel import (
    DEFAULT_BK,
    DEFAULT_BQ,
    flash_attention_pallas,
    flash_attention_pallas_paged,
)


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
    rep = h // kv

    # resolve per-(batch·head) query offsets: position of query row 0 per row
    if q_pos is None:
        offs = jnp.full((b,), int(q_offset), jnp.int32)
    elif q_pos.ndim == 2:                    # [B, Sq] — ragged rows
        offs = q_pos[:, 0].astype(jnp.int32)
    else:                                    # [Sq] shared across rows
        offs = jnp.full((b,), q_pos[0].astype(jnp.int32))
    offs_bh = jnp.repeat(offs, kv * rep)     # row-major (b, kv, rep) fold below
    lens_bh = (
        None if q_lens is None else jnp.repeat(q_lens.astype(jnp.int32), kv * rep)
    )

    # fold GQA groups into the kernel's batch axis: [B·KV·rep, S, D]
    qk = q.reshape(b, sq, kv, rep, d).transpose(0, 2, 3, 1, 4).reshape(b * kv * rep, sq, d)
    kk = jnp.broadcast_to(
        k.transpose(0, 2, 1, 3)[:, :, None], (b, kv, rep, sk, d)
    ).reshape(b * kv * rep, sk, d)
    vk = jnp.broadcast_to(
        v.transpose(0, 2, 1, 3)[:, :, None], (b, kv, rep, sk, d)
    ).reshape(b * kv * rep, sk, d)

    # pad sequences to block multiples; every block spans the full head_dim
    # (legal on the TPU for any head_dim), so head_dim is never padded
    bq = min(DEFAULT_BQ, max(8, sq))
    bk = min(DEFAULT_BK, max(8, sk))
    sqp = (-sq) % bq
    skp = (-sk) % bk
    if sqp:
        qk = jnp.pad(qk, ((0, 0), (0, sqp), (0, 0)))
    if skp:
        kk = jnp.pad(kk, ((0, 0), (0, skp), (0, 0)))
        vk = jnp.pad(vk, ((0, 0), (0, skp), (0, 0)))

    out = flash_attention_pallas(
        qk, kk, vk,
        scale=scale,
        causal=causal,
        window=int(window or 0),
        softcap=float(softcap or 0.0),
        q_offsets=offs_bh,
        q_lens=lens_bh,
        k_len=sk,
        block_q=bq,
        block_k=bk,
        interpret=interpret,
    )
    out = out[:, :sq]
    return out.reshape(b, kv, rep, sq, d).transpose(0, 3, 1, 2, 4).reshape(b, sq, h, d)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "scale", "interpret"),
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
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Model-layout wrapper for the PAGED flash kernel: K/V live in a
    physical page pool and the per-slot page table rides into the kernel as
    a scalar-prefetch operand — the kernel's K/V index maps dereference it
    per (row, page) grid step, so no ``[B, max_len]`` logical view is ever
    gathered (the dense wrapper's KV broadcast across GQA groups is gone
    too: the kv-head axis is indexed straight out of the pool).

    Unmapped table entries (−1) are clamped to the reserved trash page
    ``num_pages``; whatever garbage it holds sits at logical positions
    beyond every row's written span, where the causal mask already
    guarantees exact zero attention weight."""
    interpret = resolve_interpret(interpret)
    b, sq, h, d = q.shape
    kv = pool_k.shape[0]
    rep = h // kv
    num_pages = pool_k.shape[1] - 1
    tbl = jnp.where(table >= 0, table, num_pages).astype(jnp.int32)

    if q_pos is None:
        offs = jnp.zeros((b,), jnp.int32)
    elif q_pos.ndim == 2:                    # [B, Sq] — ragged rows
        offs = q_pos[:, 0].astype(jnp.int32)
    else:                                    # [Sq] shared across rows
        offs = jnp.full((b,), q_pos[0].astype(jnp.int32))
    offs_bh = jnp.repeat(offs, kv * rep)
    lens_bh = (
        None if q_lens is None else jnp.repeat(q_lens.astype(jnp.int32), kv * rep)
    )

    # fold GQA groups into the kernel's batch axis: [B·KV·rep, Sq, D]
    qk = q.reshape(b, sq, kv, rep, d).transpose(0, 2, 3, 1, 4).reshape(
        b * kv * rep, sq, d
    )

    # pad queries to a block multiple; head_dim is never padded — every
    # block spans the full head_dim, so the pools are read in place
    bq = min(DEFAULT_BQ, max(8, sq))
    sqp = (-sq) % bq
    if sqp:
        qk = jnp.pad(qk, ((0, 0), (0, sqp), (0, 0)))

    out = flash_attention_pallas_paged(
        qk, pool_k, pool_v, tbl,
        scale=scale,
        causal=causal,
        window=int(window or 0),
        softcap=float(softcap or 0.0),
        q_offsets=offs_bh,
        q_lens=lens_bh,
        kv_heads=kv,
        rep=rep,
        block_q=bq,
        interpret=interpret,
    )
    out = out[:, :sq]
    return out.reshape(b, kv, rep, sq, d).transpose(0, 3, 1, 2, 4).reshape(
        b, sq, h, d
    )
