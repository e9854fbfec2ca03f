"""Flash-attention Pallas TPU kernel (online softmax, blockwise VMEM tiling).

Grid: (batch·heads, Sq/BQ, Sk/BK).  On TPU the last grid axis runs
sequentially per core, so the running max / denominator / accumulator live in
VMEM scratch across the KV sweep — the classic flash recurrence:

  m'   = max(m, rowmax(S))
  l'   = l·e^{m−m'} + rowsum(e^{S−m'})
  acc' = acc·e^{m−m'} + e^{S−m'}·V

Features: causal masking, sliding window (gemma2 local layers), score
soft-capping, GQA handled by the ops.py wrapper (KV streamed per group,
never repeated in memory).  Query/key positions are affine in the block
indices (pos = block_idx·B + iota + offset); each row's ragged shape rides
in as **per-row scalar-prefetch operands** ``(q_offsets[bh], q_lens[bh])``:
``q_offsets`` is the absolute position of query row 0 (the row's cache
depth), ``q_lens`` the number of VALID query rows.  Mixed fused batches —
decode rows at ``q_len=1``, prefill chunks at ``q_len=chunk``, idle rows at
``q_len=0``, every serving slot at its own cache depth — run in ONE kernel
launch with per-row causal masks; queries beyond a row's ``q_len`` are
fully masked and produce exact zeros (the fused-batch padding contract).
BQ=128 query rows per block; KV blocks are BK=64 tokens, one page of a
64-token paged pool, so the dense and paged kernels walk KV in the same
tiles and, on the same K/V, return bit-identical attention at that page
size.  Every block spans the whole head_dim.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BQ = 128
DEFAULT_BK = 64
NEG_INF = -1e30


def _flash_kernel(
    offs_ref,   # scalar-prefetch [BH] — absolute position of query row 0,
                # per batch·head row (ragged decode: one depth per slot)
    lens_ref,   # scalar-prefetch [BH] — valid query rows per batch·head row
                # (fused mixed batch: 1 = decode, chunk = prefill, 0 = idle)
    q_ref,      # [BQ, D]
    k_ref,      # [BK, D]
    v_ref,      # [BK, D]
    o_ref,      # [BQ, D]
    m_scr,      # VMEM [BQ, 1]    running max
    l_scr,      # VMEM [BQ, 1]    running denominator
    acc_scr,    # VMEM [BQ, D]    running numerator
    *,
    scale: float,
    causal: bool,
    window: int,          # 0 = none
    softcap: float,       # 0 = none
    k_len: int,           # valid key count (padding beyond is masked)
    n_kv_blocks: int,
    block_q: int,
    block_k: int,
):
    bh = pl.program_id(0)
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    q_offset = offs_ref[bh]
    q_len = lens_ref[bh]

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[...].astype(jnp.float32)
    k = k_ref[...].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale                                             # [BQ, BK]
    if softcap > 0.0:
        s = softcap * jnp.tanh(s / softcap)

    qrow = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )                                        # local query row index
    qp = qrow + q_offset
    kp = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = kp < k_len                        # sequence padding is never visible
    # per-row ragged length: query rows beyond this row's q_len are fully
    # masked — their denominator stays 0 and _finalize emits exact zeros,
    # the deterministic padding output of a fused mixed batch
    mask &= qrow < q_len
    if causal:
        mask &= kp <= qp
    if window > 0:
        mask &= kp > qp - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...][:, 0]
    l_prev = l_scr[...][:, 0]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)
    l_new = l_prev * alpha + jnp.sum(p, axis=-1)

    v = v_ref[...].astype(jnp.float32)
    pv = jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    acc_scr[...] = acc_scr[...] * alpha[:, None] + pv
    m_scr[...] = m_new[:, None]
    l_scr[...] = l_new[:, None]

    @pl.when(kj == n_kv_blocks - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[...] = (acc_scr[...] / denom).astype(o_ref.dtype)


def _flash_kernel_paged(
    offs_ref,   # scalar-prefetch [BH] — per-row query offsets (cache depth)
    lens_ref,   # scalar-prefetch [BH] — valid query rows per batch·head row
    tbl_ref,    # scalar-prefetch [B, pages_per_slot] — page table: logical
                # KV block j of batch row b lives in physical page
                # tbl[b, j] (invalid entries pre-clamped to the trash page)
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    **kw,
):
    """Paged flash kernel body: identical math to :func:`_flash_kernel`.

    The page table is consumed by the K/V BlockSpec *index maps* (physical
    page selection happens at DMA-schedule time, before the body runs); the
    body itself still sees logical positions — ``kp = kj·block_k + iota`` is
    the logical KV position because grid axis 2 walks logical pages — so
    causal/window masking is unchanged and needs no gather."""
    del tbl_ref  # consumed by the BlockSpec index maps, not the body
    _flash_kernel(
        offs_ref, lens_ref, q_ref, k_ref, v_ref, o_ref,
        m_scr, l_scr, acc_scr, **kw,
    )


def flash_attention_pallas_paged(
    q: jax.Array,            # [BH, Sq, D] (GQA-folded, row-major (b, kv, rep))
    pool_k: jax.Array,       # [KV, num_pages + 1, P, D] — last page = trash
    pool_v: jax.Array,       # [KV, num_pages + 1, P, D]
    table: jax.Array,        # [B, pages_per_slot] int32, trash-clamped (≥ 0)
    *,
    scale: float,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    q_offsets: Optional[jax.Array] = None,   # [BH] per-row query offsets
    q_lens: Optional[jax.Array] = None,      # [BH] valid query rows
    kv_heads: int = 1,
    rep: int = 1,
    block_q: int = DEFAULT_BQ,
    interpret: bool = False,
) -> jax.Array:
    """Flash attention reading K/V *through a page table* — no logical-row
    gather is ever materialized.  ``block_k`` is pinned to the page size so
    each KV grid step maps 1:1 onto one physical page: the K/V index maps
    read ``table[b, j]`` from the scalar-prefetch operand and point the DMA
    at that page of the pool (kv-head axis indexed per folded row).

    The pool is kv-head-major, so one (head, page) block is a contiguous
    ``[P, D]`` tile: its last two dims are the pool's own, which is what the
    TPU's (8, 128) block rule accepts for any page size and head_dim."""
    bh, sq, d = q.shape
    page_tokens = pool_k.shape[2]
    pages_per_slot = table.shape[1]
    assert sq % block_q == 0, (sq, block_q)
    assert bh == table.shape[0] * kv_heads * rep, (bh, table.shape, kv_heads, rep)
    n_q = sq // block_q
    k_len = pages_per_slot * page_tokens
    if q_offsets is None:
        q_offsets = jnp.zeros((bh,), jnp.int32)
    if q_lens is None:
        q_lens = jnp.full((bh,), sq, jnp.int32)

    kernel = functools.partial(
        _flash_kernel_paged,
        scale=scale,
        causal=causal,
        window=int(window or 0),
        softcap=float(softcap or 0.0),
        k_len=k_len,
        n_kv_blocks=pages_per_slot,
        block_q=block_q,
        block_k=page_tokens,
    )

    def _kv_spec():
        # block (1, 1, P, D): index maps pick (kv head, physical page, 0, 0)
        # — tbl is the third scalar-prefetch ref, available at
        # DMA-schedule time exactly like the (offs, lens) rows
        return pl.BlockSpec(
            (None, None, page_tokens, d),
            lambda b, i, j, offs, lens, tbl: (
                (b // rep) % kv_heads, tbl[b // (kv_heads * rep), j], 0, 0
            ),
        )

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(bh, n_q, pages_per_slot),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda b, i, j, *_: (b, i, 0)),
            _kv_spec(),
            _kv_spec(),
        ],
        out_specs=pl.BlockSpec((None, block_q, d), lambda b, i, j, *_: (b, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        interpret=interpret,
        name="flash_attention_paged",
    )(
        q_offsets.astype(jnp.int32),
        q_lens.astype(jnp.int32),
        table.astype(jnp.int32),
        q, pool_k, pool_v,
    )


def flash_attention_pallas(
    q: jax.Array,            # [BH, Sq, D]
    k: jax.Array,            # [BH, Sk, D]
    v: jax.Array,            # [BH, Sk, D]
    *,
    scale: float,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    q_offset: int = 0,
    q_offsets: Optional[jax.Array] = None,   # [BH] per-row query offsets
                                             # (overrides scalar q_offset)
    q_lens: Optional[jax.Array] = None,      # [BH] valid query rows per row
                                             # (None → all sq rows valid)
    k_len: int = 0,          # 0 → all keys valid
    block_q: int = DEFAULT_BQ,
    block_k: int = DEFAULT_BK,
    interpret: bool = False,
) -> jax.Array:
    bh, sq, d = q.shape
    sk = k.shape[1]
    assert sq % block_q == 0 and sk % block_k == 0, (sq, sk, block_q, block_k)
    n_q = sq // block_q
    n_k = sk // block_k
    if q_offsets is None:
        q_offsets = jnp.full((bh,), int(q_offset), jnp.int32)
    else:
        assert q_offsets.shape == (bh,), (q_offsets.shape, bh)
        q_offsets = q_offsets.astype(jnp.int32)
    if q_lens is None:
        q_lens = jnp.full((bh,), sq, jnp.int32)
    else:
        assert q_lens.shape == (bh,), (q_lens.shape, bh)
        q_lens = q_lens.astype(jnp.int32)

    kernel = functools.partial(
        _flash_kernel,
        scale=scale,
        causal=causal,
        window=int(window or 0),
        softcap=float(softcap or 0.0),
        k_len=int(k_len) if k_len else sk,
        n_kv_blocks=n_k,
        block_q=block_q,
        block_k=block_k,
    )
    # per-row (offset, len) ride in as scalar-prefetch operands (SMEM):
    # available before the body runs, so masks stay affine in block indices
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bh, n_q, n_k),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda b, i, j, *_: (b, i, 0)),
            pl.BlockSpec((None, block_k, d), lambda b, i, j, *_: (b, j, 0)),
            pl.BlockSpec((None, block_k, d), lambda b, i, j, *_: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, d), lambda b, i, j, *_: (b, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        interpret=interpret,
        name="flash_attention",
    )(q_offsets, q_lens, q, k, v)
