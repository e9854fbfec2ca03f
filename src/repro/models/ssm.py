"""Mamba2 (SSD — state-space duality) block, pure JAX.

Implements the chunked SSD algorithm of arXiv:2405.21060 (matmul-dominant,
MXU-friendly — this is the TPU adaptation of the paper-pool arch; the
per-chunk einsums are exactly what kernels/ssd_scan tiles in Pallas):

  within chunk:  Y_diag = (C Bᵀ ⊙ L) · (dt·x)        L = exp(segsum(dt·A))
  chunk states:  S_c    = Σ_j exp(cum_L − cum_j) (dt_j x_j) ⊗ B_j
  across chunks: S_c⁺   = S_{c-1} e^{Σ dt·A} + S_c    (lax.scan recurrence)
  offset:        Y_off  = C_i · S_{c-1} · e^{cum_i}

Decode keeps the recurrent form: state ← state·e^{dt·A} + dt·x⊗B, y = C·state.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from .layers import dense_init, rmsnorm, rmsnorm_init

Params = Dict[str, Any]


def mamba2_dims(cfg: ModelConfig) -> Dict[str, int]:
    d_inner = cfg.ssm_expand * cfg.d_model
    nheads = d_inner // cfg.ssm_headdim
    conv_ch = d_inner + 2 * cfg.ngroups * cfg.ssm_state
    return dict(
        d_inner=d_inner,
        nheads=nheads,
        conv_ch=conv_ch,
        d_in_proj=2 * d_inner + 2 * cfg.ngroups * cfg.ssm_state + nheads,
    )


def mamba2_params(key, cfg: ModelConfig, dtype) -> Params:
    """Projections are SEPARATE matrices (not one fused in_proj) so each is
    cleanly shardable over the TP axis — the §Perf zamba2 iteration: a merged
    [D, 2·d_inner+2GN+H] matrix mixes segment widths that don't divide the
    mesh, forcing full trunk replication (16× redundant compute)."""
    dims = mamba2_dims(cfg)
    gn = cfg.ngroups * cfg.ssm_state
    ks = jax.random.split(key, 8)
    return {
        "w_z": dense_init(ks[0], cfg.d_model, dims["d_inner"], dtype),
        "w_x": dense_init(ks[1], cfg.d_model, dims["d_inner"], dtype),
        "w_B": dense_init(ks[2], cfg.d_model, gn, dtype),
        "w_C": dense_init(ks[3], cfg.d_model, gn, dtype),
        "w_dt": dense_init(ks[4], cfg.d_model, dims["nheads"], dtype),
        "conv_x": (jax.random.normal(ks[5], (cfg.ssm_conv, dims["d_inner"]), jnp.float32) * 0.1).astype(dtype),
        "conv_B": (jax.random.normal(ks[6], (cfg.ssm_conv, gn), jnp.float32) * 0.1).astype(dtype),
        "conv_C": (jax.random.normal(ks[7], (cfg.ssm_conv, gn), jnp.float32) * 0.1).astype(dtype),
        "b_x": jnp.zeros((dims["d_inner"],), dtype),
        "b_B": jnp.zeros((gn,), dtype),
        "b_C": jnp.zeros((gn,), dtype),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, dims["nheads"], dtype=jnp.float32)),
        "D": jnp.ones((dims["nheads"],), jnp.float32),
        "dt_bias": jnp.log(jnp.expm1(jnp.full((dims["nheads"],), 0.01, jnp.float32))),
        "norm_w": rmsnorm_init(dims["d_inner"]),
        "out_proj": dense_init(ks[4], dims["d_inner"], cfg.d_model, dtype),
    }


def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv: x [B,S,C], w [W,C] → [B,S,C]."""
    width = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    # sum of shifted slices — avoids conv_general_dilated channel plumbing and
    # lowers to W fused multiply-adds
    out = jnp.zeros_like(x)
    s = x.shape[1]
    for i in range(width):
        out = out + xp[:, i : i + s, :] * w[i]
    return out + b


def _segsum(x: jax.Array) -> jax.Array:
    """segsum(x)[..., i, j] = Σ_{j<t≤i} x_t  (−inf for j>i): [.., L] → [.., L, L]."""
    L = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((L, L), bool), k=0)
    return jnp.where(mask, diff, -jnp.inf)


def ssd_chunked(
    x: jax.Array,      # [B, S, H, P]   (already dt-scaled inputs NOT applied)
    dt: jax.Array,     # [B, S, H]      (post-softplus)
    A: jax.Array,      # [H]            (negative)
    B_: jax.Array,     # [B, S, G, N]
    C_: jax.Array,     # [B, S, G, N]
    chunk: int,
    init_state: Optional[jax.Array] = None,   # [B, H, P, N]
) -> Tuple[jax.Array, jax.Array]:
    """Returns (y [B,S,H,P], final_state [B,H,P,N])."""
    b, s, h, p = x.shape
    g, n = B_.shape[2], B_.shape[3]
    rep = h // g
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B_ = jnp.pad(B_, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C_ = jnp.pad(C_, ((0, 0), (0, pad), (0, 0), (0, 0)))
    sp = s + pad
    nc = sp // chunk

    xc = x.reshape(b, nc, chunk, h, p)
    dtc = dt.reshape(b, nc, chunk, h).astype(jnp.float32)
    Bc = B_.reshape(b, nc, chunk, g, n).astype(jnp.float32)
    Cc = C_.reshape(b, nc, chunk, g, n).astype(jnp.float32)
    dA = dtc * A  # [B,nc,L,H]  (log-decay increments, ≤ 0)

    # head-expanded B,C: [B,nc,L,H,N]
    Bh = jnp.repeat(Bc, rep, axis=3)
    Ch = jnp.repeat(Cc, rep, axis=3)
    xw = xc.astype(jnp.float32) * dtc[..., None]  # dt-weighted inputs

    # ---- intra-chunk (the "quadratic branch" of SSD) ----------------------
    Lmat = jnp.exp(_segsum(dA.transpose(0, 1, 3, 2)))          # [B,nc,H,L,L]
    scores = jnp.einsum("bclhn,bcshn->bchls", Ch, Bh) * Lmat
    y_diag = jnp.einsum("bchls,bcshp->bclhp", scores, xw)

    # ---- chunk states ------------------------------------------------------
    cum = jnp.cumsum(dA, axis=2)                                # [B,nc,L,H]
    decay_to_end = jnp.exp(cum[:, :, -1:, :] - cum)            # [B,nc,L,H]
    states = jnp.einsum("bclhn,bclhp->bchpn", Bh * decay_to_end[..., None], xw)

    # ---- inter-chunk recurrence -------------------------------------------
    chunk_decay = jnp.exp(cum[:, :, -1, :])                    # [B,nc,H]

    def scan_fn(carry, inp):
        st, dec = inp                                          # [B,H,P,N], [B,H]
        new = carry * dec[..., None, None] + st
        return new, carry                                      # emit state BEFORE chunk

    s0 = (
        init_state.astype(jnp.float32)
        if init_state is not None
        else jnp.zeros((b, h, p, n), jnp.float32)
    )
    final, prev_states = jax.lax.scan(
        scan_fn,
        s0,
        (states.transpose(1, 0, 2, 3, 4), chunk_decay.transpose(1, 0, 2)),
    )
    prev_states = prev_states.transpose(1, 0, 2, 3, 4)          # [B,nc,H,P,N]

    # ---- inter-chunk contribution -----------------------------------------
    y_off = jnp.einsum(
        "bclhn,bchpn->bclhp", Ch * jnp.exp(cum)[..., None], prev_states
    )

    y = (y_diag + y_off).reshape(b, sp, h, p)[:, :s]
    return y.astype(x.dtype), final


def mamba2_block(
    p: Params,
    xin: jax.Array,                # [B, S, D]
    cfg: ModelConfig,
    *,
    state: Optional[Dict[str, jax.Array]] = None,   # decode: {"conv","ssm"}
    seq_lens: Optional[jax.Array] = None,  # [B] valid tokens per row (fused
                                           # mixed batch; requires state)
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    dims = mamba2_dims(cfg)
    b, s, _ = xin.shape
    h, pdim, n, g = dims["nheads"], cfg.ssm_headdim, cfg.ssm_state, cfg.ngroups
    A = -jnp.exp(p["A_log"])

    z = xin @ p["w_z"]
    xr = xin @ p["w_x"]
    Br = xin @ p["w_B"]
    Cr = xin @ p["w_C"]
    dt = jax.nn.softplus((xin @ p["w_dt"]).astype(jnp.float32) + p["dt_bias"])
    if seq_lens is not None and state is not None:
        # ragged rows: tokens beyond a row's seq_len are EXACT state no-ops
        # in the SSD recurrence — dt=0 means decay exp(0·A)=1 and a zero
        # dt-weighted input — so masking dt is sufficient to freeze the ssm
        # state through padding (conv tails are gathered per-row below)
        dt = dt * (
            jnp.arange(s, dtype=jnp.int32)[None, :, None] < seq_lens[:, None, None]
        )

    new_state = None
    if state is None:
        width = p["conv_x"].shape[0]
        # conv tails for the prefill→decode hand-off: last W-1 pre-conv inputs
        def tail_of(v):
            return jnp.pad(v, ((0, 0), (max(width - 1 - s, 0), 0), (0, 0)))[:, -(width - 1):]

        tails = {"x": tail_of(xr), "B": tail_of(Br), "C": tail_of(Cr)}
        xc = jax.nn.silu(_causal_conv(xr, p["conv_x"], p["b_x"]))
        Bc = jax.nn.silu(_causal_conv(Br, p["conv_B"], p["b_B"]))
        Cc = jax.nn.silu(_causal_conv(Cr, p["conv_C"], p["b_C"]))
        xs = xc.reshape(b, s, h, pdim)
        B_ = Bc.reshape(b, s, g, n)
        C_ = Cc.reshape(b, s, g, n)
        y, final = ssd_chunked(xs, dt, A, B_, C_, cfg.ssm_chunk)
        new_state = {"ssm": final, "conv_x": tails["x"], "conv_B": tails["B"], "conv_C": tails["C"]}
    elif s > 1:
        # chunked-prefill continuation: the recurrent state carries across
        # chunk boundaries — the causal conv's left context is the previous
        # chunk's last W-1 pre-activation inputs (the stored tails), and the
        # SSD scan seeds from the carried ssm state.  With zero state this
        # is bit-for-bit the fresh-prefill path above.
        width = p["conv_x"].shape[0]

        def conv_cont(v_new, st, w, bias):
            full = jnp.concatenate([st, v_new], axis=1)     # [B, W-1+s, ch]
            out = jnp.zeros_like(v_new)
            for i in range(width):
                out = out + full[:, i : i + s, :] * w[i]
            if seq_lens is None:
                tail = full[:, full.shape[1] - (width - 1):]
            else:
                # per-row tail: the last W-1 inputs BEFORE padding.  In
                # ``full`` (old tail ++ chunk) those sit at seq_len + m for
                # m = 0..W-2 — uniformly correct whether they fall in the
                # old-tail region (seq_len < W-1) or the chunk region, and
                # an idle row (seq_len = 0) keeps its old tail verbatim.
                idx = seq_lens[:, None] + jnp.arange(width - 1, dtype=jnp.int32)[None, :]
                tail = jnp.take_along_axis(full, idx[:, :, None], axis=1)
            return jax.nn.silu(out + bias), tail

        xc, new_cx = conv_cont(xr, state["conv_x"], p["conv_x"], p["b_x"])
        Bc, new_cB = conv_cont(Br, state["conv_B"], p["conv_B"], p["b_B"])
        Cc, new_cC = conv_cont(Cr, state["conv_C"], p["conv_C"], p["b_C"])
        xs = xc.reshape(b, s, h, pdim)
        B_ = Bc.reshape(b, s, g, n)
        C_ = Cc.reshape(b, s, g, n)
        y, final = ssd_chunked(
            xs, dt, A, B_, C_, cfg.ssm_chunk, init_state=state["ssm"]
        )
        new_state = {"ssm": final, "conv_x": new_cx, "conv_B": new_cB, "conv_C": new_cC}
    else:
        # single-token recurrent step: s == 1
        width = p["conv_x"].shape[0]

        def conv_step(v_new, st, w, bias):
            full = jnp.concatenate([st, v_new], axis=1)            # [B, W, ch]
            out = (full * w[None]).sum(axis=1, keepdims=True) + bias
            tail = full[:, 1:]
            if seq_lens is not None:
                # idle rows (seq_len = 0) must not shift their conv tail
                tail = jnp.where((seq_lens > 0)[:, None, None], tail, st)
            return jax.nn.silu(out), tail

        xc, new_cx = conv_step(xr, state["conv_x"], p["conv_x"], p["b_x"])
        Bc, new_cB = conv_step(Br, state["conv_B"], p["conv_B"], p["b_B"])
        Cc, new_cC = conv_step(Cr, state["conv_C"], p["conv_C"], p["b_C"])
        xs = xc.reshape(b, 1, h, pdim)
        B_ = Bc.reshape(b, 1, g, n)
        C_ = Cc.reshape(b, 1, g, n)
        rep = h // g
        Bh = jnp.repeat(B_[:, 0], rep, axis=1)            # [B,H,N]
        Ch = jnp.repeat(C_[:, 0], rep, axis=1)
        dt1 = dt[:, 0]                                    # [B,H]
        dec = jnp.exp(dt1 * A)                            # [B,H]
        ssm = state["ssm"].astype(jnp.float32)
        xw = xs[:, 0].astype(jnp.float32) * dt1[..., None]          # [B,H,P]
        ssm = ssm * dec[..., None, None] + jnp.einsum("bhp,bhn->bhpn", xw, Bh.astype(jnp.float32))
        y = jnp.einsum("bhpn,bhn->bhp", ssm, Ch.astype(jnp.float32))[:, None]
        y = y.astype(xin.dtype)
        new_state = {"ssm": ssm, "conv_x": new_cx, "conv_B": new_cB, "conv_C": new_cC}

    y = y + xs.astype(y.dtype) * p["D"][None, None, :, None].astype(y.dtype)
    y = y.reshape(b, s, dims["d_inner"])
    # gated RMSNorm (mamba2's norm-before-out_proj, gated by z)
    y = rmsnorm(y * jax.nn.silu(z), p["norm_w"], cfg.norm_eps)
    out = y @ p["out_proj"]
    return out, new_state


def mamba2_init_state(cfg: ModelConfig, batch: int, dtype) -> Dict[str, jax.Array]:
    dims = mamba2_dims(cfg)
    gn = cfg.ngroups * cfg.ssm_state
    w1 = cfg.ssm_conv - 1
    return {
        "conv_x": jnp.zeros((batch, w1, dims["d_inner"]), dtype),
        "conv_B": jnp.zeros((batch, w1, gn), dtype),
        "conv_C": jnp.zeros((batch, w1, gn), dtype),
        "ssm": jnp.zeros(
            (batch, dims["nheads"], cfg.ssm_headdim, cfg.ssm_state), jnp.float32
        ),
    }
