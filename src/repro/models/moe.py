"""Mixture-of-Experts layer: router + experts, two execution paths.

* ``reference`` — dense one-hot dispatch (computes every expert on every
  token).  Exact, simple, used for smoke tests and as the oracle for the EP
  path and the grouped-GEMM Pallas kernel.
* ``ep`` — production path: shard_map over the mesh with expert parallelism
  (experts sharded over the data axes, expert FFN dim over the model axis),
  capacity-bounded all-to-all dispatch/return (GShard-style dropping with a
  configurable capacity factor).  Lives in repro.parallel.moe_parallel; the
  layer picks it automatically when a ParallelContext with ep_axes is active
  and the (padded) expert count divides the EP degree.

Config notes: qwen2-moe's 60 routed experts are padded to 64 (router logits
of padding experts are −inf, so they are never selected and contribute
nothing); arctic's dense residual FFN (``dense_parallel_ff``) and qwen2-moe's
shared experts (merged into one FFN of ``n_shared_experts·moe_d_ff``) are
handled by the caller in transformer.py.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.parallel.context import current_context
from .layers import dense_init

Params = Dict[str, Any]


def moe_params(key, cfg: ModelConfig, dtype) -> Params:
    e = cfg.n_experts_padded or cfg.n_experts
    d, f = cfg.d_model, cfg.moe_d_ff
    ks = jax.random.split(key, 4)
    scale_in = 1.0 / jnp.sqrt(d)
    scale_out = 1.0 / jnp.sqrt(f)
    p = {
        "router": dense_init(ks[0], d, e, jnp.float32),
        "w_gate": (jax.random.normal(ks[1], (e, d, f), jnp.float32) * scale_in).astype(dtype),
        "w_up": (jax.random.normal(ks[2], (e, d, f), jnp.float32) * scale_in).astype(dtype),
        "w_down": (jax.random.normal(ks[3], (e, f, d), jnp.float32) * scale_out).astype(dtype),
    }
    if cfg.router_scoring == "sigmoid":
        p["e_score_correction_bias"] = jnp.zeros((e,), jnp.float32)
    return p


def sigmoid_topk(
    p_router: jax.Array, bias: jax.Array, x: jax.Array, cfg: ModelConfig
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """DeepSeek-V3 routing (``noaux_tc``): scores ``s = sigmoid(x·W_r)`` in
    f32; experts are chosen on ``s + bias`` in one group (DeepSeek-V3's
    group limit is not served), weighted by their unbiased ``s``,
    renormalised over the chosen (``norm_topk``), times ``routed_scaling``.
    Returns (weights [T,k], experts [T,k], 0)."""
    # the published gate runs in float32; at default precision a TPU would
    # round the f32 operands to bf16 and flip near-tied selections
    s = jax.nn.sigmoid(jnp.matmul(x.astype(jnp.float32), p_router,
                                  precision=jax.lax.Precision.HIGHEST))  # [T, E]
    _, experts = jax.lax.top_k(s + bias.astype(jnp.float32), cfg.top_k)  # [T, k]
    weights = jnp.take_along_axis(s, experts, axis=-1)
    if cfg.router_norm_topk:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    return weights * cfg.routed_scaling, experts, jnp.zeros((), jnp.float32)


def router_topk(
    p_router: jax.Array, x: jax.Array, cfg: ModelConfig
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (weights [T,k], experts [T,k], aux_loss scalar) for flat tokens."""
    e_real, e_pad = cfg.n_experts, cfg.n_experts_padded or cfg.n_experts
    logits = (x.astype(jnp.float32) @ p_router)            # [T, Epad]
    if e_pad > e_real:
        neg = jnp.full((x.shape[0], e_pad - e_real), -1e30, dtype=logits.dtype)
        logits = jnp.concatenate([logits[:, :e_real], neg], axis=-1)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, experts = jax.lax.top_k(probs, cfg.top_k)     # [T, k]
    if cfg.router_norm_topk:
        weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
    # load-balancing auxiliary loss (Switch-style): E · Σ_e f_e · P_e
    me = probs.mean(axis=0)                                # mean prob per expert
    ce = jnp.zeros_like(me).at[experts.reshape(-1)].add(
        jnp.ones_like(experts.reshape(-1), dtype=me.dtype)
    ) / (experts.size)
    aux = e_real * jnp.sum(me * ce)
    return weights, experts, aux


def moe_reference(p: Params, x: jax.Array, cfg: ModelConfig) -> Tuple[jax.Array, jax.Array]:
    """Dense dispatch oracle.  x: [B, S, D] → (y, aux_loss)."""
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    if cfg.router_scoring == "sigmoid":
        weights, experts, aux = sigmoid_topk(
            p["router"], p["e_score_correction_bias"], xt, cfg)
    else:
        weights, experts, aux = router_topk(p["router"], xt, cfg)
    e = cfg.n_experts_padded or cfg.n_experts
    onehot = jax.nn.one_hot(experts, e, dtype=x.dtype)     # [T, k, E]
    comb = (onehot * weights[..., None].astype(x.dtype)).sum(1)  # [T, E]
    # every expert on every token (E× flops — smoke scale only)
    gate = jnp.einsum("td,edf->tef", xt, p["w_gate"])
    up = jnp.einsum("td,edf->tef", xt, p["w_up"])
    h = jax.nn.silu(gate) * up
    y_e = jnp.einsum("tef,efd->ted", h, p["w_down"])       # [T, E, D]
    y = jnp.einsum("ted,te->td", y_e, comb)
    return y.reshape(b, s, d), aux


def moe_apply(p: Params, x: jax.Array, cfg: ModelConfig) -> Tuple[jax.Array, jax.Array]:
    """Dispatch to the EP path when a parallel context is active, else reference."""
    ctx = current_context()
    e_pad = cfg.n_experts_padded or cfg.n_experts
    # the EP path routes with softmax only; sigmoid routing takes the
    # reference dispatch
    if (ctx is not None and ctx.ep_axes and ctx.mesh is not None
            and cfg.router_scoring == "softmax"):
        ep = ctx.axis_size(ctx.ep_axes)
        if e_pad % ep == 0:
            from repro.parallel.moe_parallel import moe_ep

            return moe_ep(p, x, cfg, ctx)
    return moe_reference(p, x, cfg)
