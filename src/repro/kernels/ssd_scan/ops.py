"""jit'd wrapper: model layout [B,S,H,P] → kernel layout, pad, call, restore."""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret

from .kernel import ssd_scan_pallas


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(
    x: jax.Array,     # [B, S, H, P]
    dt: jax.Array,    # [B, S, H]   (post-softplus)
    a: jax.Array,     # [H]         (negative)
    b: jax.Array,     # [B, S, G, N]
    c: jax.Array,     # [B, S, G, N]
    *,
    chunk: int = 128,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    interpret = resolve_interpret(interpret)
    bsz, s, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))    # dt=0 ⇒ no contribution
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0), (0, 0)))

    xk = x.transpose(0, 2, 1, 3)                       # [B, H, S, P]
    dtk = dt.transpose(0, 2, 1)[..., None]             # [B, H, S, 1]
    ak = a[:, None].astype(jnp.float32)                # [H, 1]
    bk = b.transpose(0, 2, 1, 3)                       # [B, G, S, N]
    ck = c.transpose(0, 2, 1, 3)

    y, st = ssd_scan_pallas(xk, dtk, ak, bk, ck, chunk=chunk, interpret=interpret)
    y = y.transpose(0, 2, 1, 3)[:, :s]                 # [B, S, H, P]
    return y, st
