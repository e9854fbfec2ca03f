"""Flash-attention Pallas TPU kernel (online softmax, blockwise VMEM tiling).

Grid: (batch·kv_heads, rows/BQ, Sk/BK).  On TPU the last grid axis runs
sequentially per core, so the running max / denominator / accumulator live in
VMEM scratch across the KV sweep — the classic flash recurrence:

  m'   = max(m, rowmax(S))
  l'   = l·e^{m−m'} + rowsum(e^{S−m'})
  acc' = acc·e^{m−m'} + e^{S−m'}·V

Features: causal masking, sliding window (gemma2 local layers), score
soft-capping, and GQA by stacking: one grid row per (batch, KV head), whose
query block holds the ``rep = H/KV`` query heads of that head's group —
folded row ``r`` is query position ``r // rep`` of head ``r % rep`` — so each
K/V block is DMA'd once per group and KV is never repeated in memory.  Each
row's ragged shape rides in as **per-row scalar-prefetch operands**
``(q_offsets[b], q_lens[b])``: ``q_offsets`` is the absolute position of
query position 0 (the row's cache depth), ``q_lens`` the number of VALID
query positions.  Mixed fused batches — decode rows at ``q_len=1``, prefill
chunks at ``q_len=chunk``, idle rows at ``q_len=0``, every serving slot at
its own cache depth — run in ONE kernel launch with per-row causal masks;
queries beyond a row's ``q_len`` are fully masked and produce exact zeros
(the fused-batch padding contract).  The masks compare positions scaled by
``rep`` (``k ≤ r // rep`` ⇔ ``k·rep ≤ r`` for integer ``k``), so the body
needs no vector division; at ``rep = 1`` it is the ungrouped kernel, op for
op.  KV blocks are BK=64 tokens, one page of a 64-token paged pool, so the
dense and paged kernels walk KV in the same tiles and, on the same K/V,
return bit-identical attention at that page size.  Every block spans the
whole head_dim.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BQ = 128     # query positions a block holds per query head
MAX_BQ = 512         # VMEM cap on a block's folded rows (positions × rep)
DEFAULT_BK = 64
NEG_INF = -1e30


def _flash_kernel(
    offs_ref,   # scalar-prefetch [B·KV] — absolute position of query
                # position 0, per grid row (ragged decode: one depth a slot)
    lens_ref,   # scalar-prefetch [B·KV] — valid query positions per grid
                # row (fused mixed batch: 1 = decode, chunk = prefill,
                # 0 = idle)
    q_ref,      # [BQ, D] folded rows: position r // rep of head r % rep
    k_ref,      # [BK, D]
    v_ref,      # [BK, Dv] (Dv = D but for MLA's latent)
    o_ref,      # [BQ, Dv]
    m_scr,      # VMEM [BQ, 1]    running max
    l_scr,      # VMEM [BQ, 1]    running denominator
    acc_scr,    # VMEM [BQ, Dv]   running numerator
    *,
    scale: float,
    causal: bool,
    window: int,          # 0 = none
    softcap: float,       # 0 = none
    k_len: int,           # valid key count (padding beyond is masked)
    n_kv_blocks: int,
    block_q: int,
    block_k: int,
    rep: int = 1,         # query heads stacked per grid row (GQA group)
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
    )                                        # local folded row index
    # folded row r is query position r // rep; positions are compared scaled
    # by rep (for integer k, k <= r // rep <=> k·rep <= r): no vector division
    qp = qrow + (q_offset * rep if rep > 1 else q_offset)
    kp = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    kps = kp * rep if rep > 1 else kp
    mask = kp < k_len                        # sequence padding is never visible
    # per-row ragged length: query positions beyond this row's q_len are
    # fully masked — their denominator stays 0 and _finalize emits exact
    # zeros, the deterministic padding output of a fused mixed batch
    mask &= qrow < (q_len * rep if rep > 1 else q_len)
    if causal:
        mask &= kps <= qp
    if window > 0:
        # a scaled window past int32 masks nothing (qp - kps < 2**31 - 1 for
        # every int32 scaled position), so saturating it is exact
        mask &= kps > qp - (min(window * rep, 2**31 - 1) if rep > 1 else window)
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
    offs_ref,   # scalar-prefetch [B·KV] — per-row query offsets (cache depth)
    lens_ref,   # scalar-prefetch [B·KV] — valid query positions per grid row
    tbl_ref,    # scalar-prefetch [B, pages_per_slot] — page table: logical
                # KV block j of slot s lives in physical page tbl[s, j]
                # (invalid entries pre-clamped to the trash page)
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
    q: jax.Array,            # [B·KV, rows, D] (row-major (slot, kv); rows
                             # folded (position, rep), see module docstring)
    pool_k: jax.Array,       # [KV, num_pages + 1, P, D] — last page = trash
    pool_v: jax.Array,       # [KV, num_pages + 1, P, D]
    table: jax.Array,        # [B, pages_per_slot] int32, trash-clamped (≥ 0)
    *,
    scale: float,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    q_offsets: Optional[jax.Array] = None,   # [B·KV] per-row query offsets
    q_lens: Optional[jax.Array] = None,      # [B·KV] valid query positions
    kv_heads: int = 1,
    rep: int = 1,
    block_q: int = DEFAULT_BQ,
    v_dim: Optional[int] = None,             # V's width (None: V's own)
    name: str = "flash_attention_paged",
    interpret: bool = False,
) -> jax.Array:
    """Flash attention reading K/V *through a page table* — no logical-row
    gather is ever materialized.  ``block_k`` is pinned to the page size so
    each KV grid step maps 1:1 onto one physical page: the K/V index maps
    read ``table[b // kv_heads, j]`` from the scalar-prefetch operand and
    point the DMA at that page of the pool, for KV head ``b % kv_heads``.
    Grid row ``b`` carries the ``rep`` query heads of that KV head, so each
    page is fetched once per group.

    The pool is kv-head-major, so one (head, page) block is a contiguous
    ``[P, D]`` tile: its last two dims are the pool's own, which is what the
    TPU's (8, 128) block rule accepts for any page size and head_dim.

    ``v_dim`` narrower than ``pool_v``'s width reads V as the leading
    ``v_dim`` columns of each page (a multiple of 128): MLA passes its one
    latent pool as both K (latent + rope) and V (latent), so a page is
    fetched from one buffer for both."""
    bh, rows, d = q.shape
    dv = pool_v.shape[-1] if v_dim is None else v_dim
    page_tokens = pool_k.shape[2]
    pages_per_slot = table.shape[1]
    assert rows % block_q == 0, (rows, block_q)
    assert bh == table.shape[0] * kv_heads, (bh, table.shape, kv_heads)
    n_q = rows // block_q
    k_len = pages_per_slot * page_tokens
    if q_offsets is None:
        q_offsets = jnp.zeros((bh,), jnp.int32)
    if q_lens is None:
        q_lens = jnp.full((bh,), -(-rows // rep), jnp.int32)

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
        rep=rep,
    )

    def _kv_spec(width):
        # block (1, 1, P, width): index maps pick (kv head, physical page,
        # 0, 0) — tbl is the third scalar-prefetch ref, available at
        # DMA-schedule time exactly like the (offs, lens) rows
        return pl.BlockSpec(
            (None, None, page_tokens, width),
            lambda b, i, j, offs, lens, tbl: (
                b % kv_heads, tbl[b // kv_heads, j], 0, 0
            ),
        )

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(bh, n_q, pages_per_slot),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda b, i, j, *_: (b, i, 0)),
            _kv_spec(d),
            _kv_spec(dv),
        ],
        out_specs=pl.BlockSpec((None, block_q, dv), lambda b, i, j, *_: (b, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, dv), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bh, rows, dv), q.dtype),
        interpret=interpret,
        name=name,
    )(
        q_offsets.astype(jnp.int32),
        q_lens.astype(jnp.int32),
        table.astype(jnp.int32),
        q, pool_k, pool_v,
    )


def flash_attention_pallas(
    q: jax.Array,            # [B·KV, rows, D] rows folded (position, rep)
    k: jax.Array,            # [B·KV, Sk, D]
    v: jax.Array,            # [B·KV, Sk, D]
    *,
    scale: float,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    q_offset: int = 0,
    q_offsets: Optional[jax.Array] = None,   # [B·KV] per-row query offsets
                                             # (overrides scalar q_offset)
    q_lens: Optional[jax.Array] = None,      # [B·KV] valid query positions
                                             # (None → every position valid)
    k_len: int = 0,          # 0 → all keys valid
    rep: int = 1,            # query heads stacked per row (GQA group)
    block_q: int = DEFAULT_BQ,
    block_k: int = DEFAULT_BK,
    interpret: bool = False,
) -> jax.Array:
    bh, rows, d = q.shape
    sk = k.shape[1]
    assert rows % block_q == 0 and sk % block_k == 0, (rows, sk, block_q, block_k)
    n_q = rows // block_q
    n_k = sk // block_k
    if q_offsets is None:
        q_offsets = jnp.full((bh,), int(q_offset), jnp.int32)
    else:
        assert q_offsets.shape == (bh,), (q_offsets.shape, bh)
        q_offsets = q_offsets.astype(jnp.int32)
    if q_lens is None:
        q_lens = jnp.full((bh,), -(-rows // rep), jnp.int32)
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
        rep=rep,
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
        out_shape=jax.ShapeDtypeStruct((bh, rows, d), q.dtype),
        interpret=interpret,
        name="flash_attention",
    )(q_offsets, q_lens, q, k, v)
