"""Expert-parallel MoE via shard_map: capacity-bounded all-to-all dispatch.

Layout (matches parallel/sharding.py):
  tokens  — sharded over the DP axes (pod, data)
  experts — sharded over "data" (EP groups are intra-pod: the all-to-all
            stays on ICI; experts replicate across pods)
  expert FFN hidden — sharded over "model" (TP inside each expert, partial
            sums reduced with a psum over "model")

Algorithm per device (GShard-style dropping, capacity factor cf):
  1. route local tokens (top-k), flatten (token, choice) pairs
  2. bucket pairs by owner EP peer; slot = rank within bucket; drop ≥ cap
  3. all_to_all token payloads + local-expert ids to the owners
  4. sort received tokens by local expert, grouped GEMM (ragged_dot —
     kernels/grouped_gemm is the Pallas version of exactly this contraction)
  5. all_to_all results back (slot-aligned), combine with router weights

The pure-reference oracle is models/moe.moe_reference; equivalence is tested
in tests/test_moe_parallel.py under a forced 8-device CPU mesh.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.moe import router_topk
from repro.parallel.context import ParallelContext

def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def moe_ep(
    p: Dict[str, jax.Array],
    x: jax.Array,                    # [B, S, D] (global)
    cfg: ModelConfig,
    ctx: ParallelContext,
) -> Tuple[jax.Array, jax.Array]:
    mesh = ctx.mesh
    ep_axes = ctx.ep_axes            # ("data",)
    dp = ctx.dp_axes                 # ("pod", "data") or ("data",)
    tp = ctx.tp_axis                 # "model"
    ep = ctx.axis_size(ep_axes)
    e_pad = cfg.n_experts_padded or cfg.n_experts
    e_loc = e_pad // ep

    x_spec = P(dp, None, None)
    w_spec = P(ep_axes if len(ep_axes) > 1 else ep_axes[0], None, tp)
    w2_spec = P(ep_axes if len(ep_axes) > 1 else ep_axes[0], tp, None)
    # pure-DP×EP mode (tp None): experts hold full FFN width — no psum

    # §Perf (arctic): weight-gathered mode — every TP rank otherwise runs an
    # IDENTICAL all-to-all on the full local token set (16× redundant ICI
    # traffic).  Instead: slice tokens over the TP axis (1/16 each),
    # all-gather the expert weight slices (small vs token payload at 32k
    # prefill), dispatch only the slice, and all-gather results at the end.
    # The switch is COST-BASED at trace time (shapes are static): gathering
    # loses when the (micro)batch is small — arctic train_4k regressed 27%
    # before this guard (EXPERIMENTS.md §Perf C2).
    gather = bool(getattr(cfg, "moe_gather_weights", False)) and tp is not None
    if gather:
        b_, s_, d_ = x.shape
        dp_size = ctx.axis_size(dp) if dp else 1
        t_loc = max(b_ * s_ // max(dp_size, 1), 1)
        tok_bytes = 2.0 * t_loc * cfg.top_k * d_ * 2  # a2a there+back, bf16
        tpsize0 = ctx.axis_size(tp)
        w_bytes = (
            3.0 * e_loc * d_ * cfg.moe_d_ff * 2 * (tpsize0 - 1) / tpsize0
        )
        gather = tok_bytes > w_bytes and t_loc % tpsize0 == 0

    def body(router_w, w_gate, w_up, w_down, xl):
        b_loc, s, d = xl.shape
        t = b_loc * s
        xt = xl.reshape(t, d)
        if gather:
            tpsize = ctx.axis_size(tp)
            m = jax.lax.axis_index(tp)
            t_slice = t // tpsize
            xt = jax.lax.dynamic_slice_in_dim(xt, m * t_slice, t_slice)
            t = t_slice
            w_gate = jax.lax.all_gather(w_gate, tp, axis=2, tiled=True)
            w_up = jax.lax.all_gather(w_up, tp, axis=2, tiled=True)
            w_down = jax.lax.all_gather(w_down, tp, axis=1, tiled=True)
        weights, experts, aux = router_topk(router_w, xt, cfg)   # [t,k]
        k = cfg.top_k

        flat_tok = jnp.repeat(jnp.arange(t), k)                  # [t*k]
        flat_exp = experts.reshape(-1)                           # global expert id
        flat_w = weights.reshape(-1)
        dest = flat_exp // e_loc                                 # owner peer
        local_exp = flat_exp % e_loc

        cap = _round_up(
            max(int(math.ceil(t * k / ep * cfg.capacity_factor)), 1), 8
        )

        # bucket by dest peer; slot = rank within bucket (stable sort keeps
        # token order so drops hit the latest tokens)
        order = jnp.argsort(dest, stable=True)
        dest_s = dest[order]
        # rank within each bucket: position - first position of that bucket
        pos = jnp.arange(t * k)
        first_of_bucket = jnp.searchsorted(dest_s, jnp.arange(ep), side="left")
        slot = pos - first_of_bucket[dest_s]
        keep = slot < cap

        send_tok = jnp.zeros((ep, cap, d), xl.dtype)
        send_exp = jnp.zeros((ep, cap), jnp.int32)
        send_valid = jnp.zeros((ep, cap), jnp.bool_)
        src_flat = jnp.full((ep, cap), -1, jnp.int32)            # return map

        tok_idx_s = flat_tok[order]
        lexp_s = local_exp[order]
        slot_c = jnp.where(keep, slot, cap - 1)                  # clamp; masked below
        # .add (not .set): dropped entries contribute zeros and must not
        # clobber a legitimate token occupying slot cap-1
        send_tok = send_tok.at[dest_s, slot_c].add(
            jnp.where(keep[:, None], xt[tok_idx_s], 0.0).astype(xl.dtype)
        )
        send_exp = send_exp.at[dest_s, slot_c].max(
            jnp.where(keep, lexp_s, 0).astype(jnp.int32)
        )
        send_valid = send_valid.at[dest_s, slot_c].max(keep)
        src_flat = src_flat.at[dest_s, slot_c].max(
            jnp.where(keep, order, -1).astype(jnp.int32)
        )

        # ---- exchange to expert owners --------------------------------
        recv_tok = jax.lax.all_to_all(send_tok, ep_axes, 0, 0, tiled=False)
        recv_exp = jax.lax.all_to_all(send_exp, ep_axes, 0, 0, tiled=False)
        recv_valid = jax.lax.all_to_all(send_valid, ep_axes, 0, 0, tiled=False)

        rt = recv_tok.reshape(ep * cap, d)
        re = recv_exp.reshape(ep * cap)
        rv = recv_valid.reshape(ep * cap)
        re = jnp.where(rv, re, e_loc - 1)                        # park invalid

        # ---- grouped GEMM over local experts ---------------------------
        sort_idx = jnp.argsort(re, stable=True)
        rt_s = rt[sort_idx]
        group_sizes = jnp.bincount(re, length=e_loc)
        gate = jax.lax.ragged_dot(rt_s, w_gate, group_sizes)
        up = jax.lax.ragged_dot(rt_s, w_up, group_sizes)
        h = (jax.nn.silu(gate.astype(jnp.float32)) * up.astype(jnp.float32)).astype(rt_s.dtype)
        y_s = jax.lax.ragged_dot(h, w_down, group_sizes)         # partial over tp
        # unsort
        y = jnp.zeros_like(y_s).at[sort_idx].set(y_s)
        y = jnp.where(rv[:, None], y, 0.0)
        y = y.reshape(ep, cap, d)

        # ---- return (+ reduce TP partial sums in the sliced-FFN mode) ----
        back = jax.lax.all_to_all(y, ep_axes, 0, 0, tiled=False)
        if tp is not None and not gather:
            back = jax.lax.psum(back, tp)

        # ---- combine at the original sender ------------------------------
        w_s = jnp.where(keep, flat_w[order], 0.0)
        contrib = back[dest_s, slot_c] * w_s[:, None].astype(back.dtype)
        y_tok = jnp.zeros((t, d), jnp.float32).at[tok_idx_s].add(
            contrib.astype(jnp.float32)
        )
        if gather:
            # token slices are disjoint across TP ranks: restore the full set
            y_tok = jax.lax.all_gather(y_tok, tp, axis=0, tiled=True)
        aux = jax.lax.pmean(aux, dp)
        return y_tok.reshape(b_loc, s, d).astype(xl.dtype), aux

    y, aux = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(), w_spec, w_spec, w2_spec, x_spec),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(p["router"], p["w_gate"], p["w_up"], p["w_down"], x)
    return y, aux
