"""jit'd wrapper: ragged_dot-compatible interface over the Pallas kernel.

Takes (x sorted by group, w [E,K,N], group_sizes [E]) like ragged_dot.
Rows are re-packed so each expert's rows occupy whole BLOCK_M row-blocks
(megablocks padding); the block→expert map is scalar-prefetched so the
kernel only fetches the weight tiles it needs.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret

from .kernel import BLOCK_M, grouped_gemm_pallas


@functools.partial(jax.jit, static_argnames=("interpret", "block_m"))
def grouped_gemm(
    x: jax.Array,              # [M, K] rows sorted by group
    w: jax.Array,              # [E, K, N]
    group_sizes: jax.Array,    # [E] int32, sums to M
    *,
    block_m: int = BLOCK_M,
    interpret: Optional[bool] = None,
) -> jax.Array:
    interpret = resolve_interpret(interpret)
    m, k = x.shape
    e, _, n = w.shape

    # --- megablocks packing: pad each group to a BLOCK_M multiple ----------
    gs = group_sizes.astype(jnp.int32)
    padded = ((gs + block_m - 1) // block_m) * block_m       # [E]
    src_start = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(gs)[:-1]])
    dst_start = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(padded)[:-1]])
    mp = m + e * (block_m - 1)                               # static upper bound
    mp = ((mp + block_m - 1) // block_m) * block_m

    row = jnp.arange(m, dtype=jnp.int32)
    grp = jnp.searchsorted(jnp.cumsum(gs), row, side="right").astype(jnp.int32)
    dst_row = dst_start[grp] + (row - src_start[grp])
    xp = jnp.zeros((mp, k), x.dtype).at[dst_row].set(x)

    n_blocks = mp // block_m
    blk = jnp.arange(n_blocks, dtype=jnp.int32)
    # expert of a block: the group whose padded range contains block start
    pad_ends = jnp.cumsum(padded)                            # [E]
    block_expert = jnp.searchsorted(pad_ends, blk * block_m, side="right").astype(
        jnp.int32
    )
    block_expert = jnp.minimum(block_expert, e - 1)

    out_p = grouped_gemm_pallas(
        xp, w, block_expert, block_m=block_m, interpret=interpret
    )
    return out_p[dst_row]
