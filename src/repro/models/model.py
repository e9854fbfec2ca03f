"""Model façade: family dispatch + loss + parameter accounting.

``build_model(cfg)`` returns a ``Model`` with uniform entry points so the
launcher, trainer, serving engine and dry-run never branch on family.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from . import encdec, transformer


@dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    init: Callable[..., Any]
    train_forward: Callable[..., Tuple[jax.Array, jax.Array]]
    prefill: Callable[..., Tuple[jax.Array, Any]]
    # chunked prefill (prompt consumed in fixed-token pieces, state carried
    # across boundaries) — greedy-token-identical to `prefill`; the unit the
    # serving engine interleaves with ragged decode steps
    prefill_chunked: Callable[..., Tuple[jax.Array, Any]]
    decode_step: Callable[..., Tuple[jax.Array, Any]]
    # fused mixed prefill/decode step: tokens [B,S] with per-row
    # (cache_pos, q_lens) — decode rows q_len=1, prefill chunks q_len=n,
    # idle rows q_len=0.  Returns (full logits [B,S,V], new_caches); one
    # compiled program serves the whole serving step
    fused_step: Callable[..., Tuple[jax.Array, Any]]
    init_cache: Callable[..., Any]
    # paged KV: (num_pages, page_tokens, batch) → per-layer page pools (last
    # page reserved as trash).  decode_step/fused_step/prefill_chunked accept
    # a page_table=[B, pages_per_slot] kwarg that switches reads/writes to
    # the pools; the table itself is host-owned (serving.kv_pool.KVPool)
    init_paged_cache: Optional[Callable[..., Any]] = None


def build_model(cfg: ModelConfig) -> Model:
    if cfg.family == "encdec":
        return Model(
            cfg=cfg,
            init=lambda key: encdec.init_params(key, cfg),
            train_forward=lambda p, b: encdec.train_forward(p, b, cfg),
            prefill=lambda p, b, max_len=None: encdec.prefill(p, b, cfg, max_len),
            prefill_chunked=lambda p, b, max_len=None, chunk=64, **kw: encdec.prefill_chunked(
                p, b, cfg, max_len, chunk=chunk, **kw
            ),
            decode_step=lambda p, t, c, pos, page_table=None: encdec.decode_step(
                p, t, c, pos, cfg, page_table=page_table
            ),
            fused_step=lambda p, t, c, pos, qlens, page_table=None: encdec.fused_step(
                p, t, c, pos, qlens, cfg, page_table=page_table
            ),
            # cross cache length = encoder frame count (same seq grid here)
            init_cache=lambda b, s: {
                "self": encdec.init_self_cache(cfg, b, s),
                "cross": encdec.init_self_cache(cfg, b, s),
            },
            init_paged_cache=lambda num_pages, page_tokens, b=1: {
                "self": encdec.init_paged_self_cache(cfg, num_pages, page_tokens),
                "cross": None,  # computed at prefill from the encoder memory
            },
        )
    return Model(
        cfg=cfg,
        init=lambda key: transformer.init_params(key, cfg),
        train_forward=lambda p, b: transformer.train_forward(p, b, cfg),
        prefill=lambda p, b, max_len=None: transformer.prefill(p, b, cfg, max_len),
        prefill_chunked=lambda p, b, max_len=None, chunk=64, **kw: transformer.prefill_chunked(
            p, b, cfg, max_len, chunk=chunk, **kw
        ),
        decode_step=lambda p, t, c, pos, page_table=None: transformer.decode_step(
            p, t, c, pos, cfg, page_table=page_table
        ),
        fused_step=lambda p, t, c, pos, qlens, page_table=None: transformer.fused_step(
            p, t, c, pos, qlens, cfg, page_table=page_table
        ),
        init_cache=lambda b, s: transformer.init_cache(cfg, b, s),
        init_paged_cache=lambda num_pages, page_tokens, b=1: transformer.init_paged_cache(
            cfg, b, num_pages, page_tokens
        ),
    )


# --------------------------------------------------------------------------
# loss
# --------------------------------------------------------------------------


def cross_entropy_loss(
    logits: jax.Array,         # [B, S, V]
    labels: jax.Array,         # [B, S] int32; −1 = ignore
    *,
    aux_loss: jax.Array | float = 0.0,
    aux_weight: float = 0.01,
    z_loss: float = 1e-4,
) -> jax.Array:
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(
        logits, jnp.maximum(labels, 0)[..., None], axis=-1
    )[..., 0]
    nll = lse - gold
    mask = (labels >= 0).astype(jnp.float32)
    loss = (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    # z-loss stabilizes the softmax normalizer at scale (PaLM-style)
    loss = loss + z_loss * ((lse * mask) ** 2).sum() / jnp.maximum(mask.sum(), 1.0)
    return loss + aux_weight * aux_loss


# --------------------------------------------------------------------------
# parameter accounting (used by configs' self-checks and the roofline)
# --------------------------------------------------------------------------


def param_count(params) -> int:
    return int(
        sum(x.size for x in jax.tree.leaves(params) if hasattr(x, "size"))
    )


def param_count_shape(cfg: ModelConfig) -> int:
    """Parameter count from shapes only (eval_shape — no allocation)."""
    import math as _math

    model = build_model(cfg)
    tree = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    return sum(_math.prod(x.shape) for x in jax.tree.leaves(tree))


def active_param_count(cfg: ModelConfig) -> int:
    """MoE: parameters touched per token (for MODEL_FLOPS = 6·N_active·D)."""
    total = param_count_shape(cfg)
    if not cfg.n_experts:
        return total
    e_pad = cfg.n_experts_padded or cfg.n_experts
    per_expert = 3 * cfg.d_model * cfg.moe_d_ff
    moe_layers = sum(cfg.is_moe_layer(i) for i in range(cfg.n_layers))
    routed_total = e_pad * per_expert * moe_layers
    routed_active = cfg.top_k * per_expert * moe_layers
    return total - routed_total + routed_active
