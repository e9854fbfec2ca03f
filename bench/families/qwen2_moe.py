"""Qwen2-MoE (``model_type`` "qwen2_moe"): Qwen's GQA attention (no norm on
q and k) with a sparse feed-forward part: a softmax router over
``num_experts`` SwiGLU experts, the top ``num_experts_per_tok`` of them
weighted by their gates, plus an always-on shared expert.

The program holds ``n_experts_padded`` experts, the ones beyond the
published count masked out of routing; the reference routes over the
published ones.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench.families import qwen3 as dense
from bench.model import Leaf
from bench.reference import HI

QK_NORM = False

head_dim = dense.head_dim
attention_call = dense.attention_call
kv_bytes_per_token = dense.kv_bytes_per_token


def program_config(conf: Dict):
    return dense.program(
        conf, QK_NORM,
        n_experts=conf["num_experts"],
        top_k=conf["num_experts_per_tok"],
        moe_d_ff=conf["moe_intermediate_size"],
        d_ff=conf["moe_intermediate_size"],
        shared_d_ff=conf["shared_expert_intermediate_size"],
        n_shared_experts=(conf["shared_expert_intermediate_size"]
                          // conf["moe_intermediate_size"]),
        router_norm_topk=bool(conf["norm_topk_prob"]),
    )


def experts_held(conf: Dict, cfg) -> int:
    """The program's padded expert count (the router's width)."""
    return cfg.n_experts_padded or cfg.n_experts


def layout(conf: Dict, held: int) -> Dict:
    d, f = conf["hidden_size"], conf["moe_intermediate_size"]

    def ffn(mat):
        return {"moe": {"router": Leaf((d, held), "float32", "matrix"),
                        "w_gate": mat(held, d, f), "w_up": mat(held, d, f),
                        "w_down": mat(held, f, d)},
                "shared_mlp": dense.mlp_leaves(
                    mat, d, conf["shared_expert_intermediate_size"])}

    return dense.tree(conf, QK_NORM, ffn)


def active_matmul_params(conf: Dict) -> int:
    """Weights one token multiplies through: the projections, the router
    over the published experts, its top-k experts, the shared expert and
    the head."""
    d = conf["hidden_size"]
    per = dense.attention_matmul_params(conf) + d * conf["num_experts"]
    per += conf["num_experts_per_tok"] * 3 * d * conf["moe_intermediate_size"]
    per += 3 * d * conf["shared_expert_intermediate_size"]
    return conf["num_hidden_layers"] * per + d * conf["vocab_size"]


def step_flops(conf: Dict, q_lens: np.ndarray, cache_pos: np.ndarray) -> float:
    return dense.flops_with(active_matmul_params(conf), conf, q_lens, cache_pos)


def moe(p, x, conf: Dict, quant: bool):
    """Softmax router over the published experts, top-k weights (not
    renormalised unless the config says so), every expert's SwiGLU weighted
    by its gate, plus the shared expert."""
    e, k = conf["num_experts"], conf["num_experts_per_tok"]
    logits = jnp.matmul(x, p["moe"]["router"][:, :e].astype(jnp.float32), precision=HI)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = jax.lax.top_k(probs, k)
    if conf["norm_topk_prob"]:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    gates = jnp.zeros_like(probs).at[jnp.arange(x.shape[0])[:, None], top_i].set(top_w)

    def expert(acc, j):
        w = {n: p["moe"][n][j] for n in ("w_gate", "w_up", "w_down")}
        return acc + gates[:, j][:, None] * dense.mlp(w, x, quant), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(e))
    return y + dense.mlp(p["shared_mlp"], x, quant)


def layer(p, x, conf: Dict, quant: bool):
    return dense.frame(p, x, conf, quant, QK_NORM, lambda p, h: moe(p, h, conf, quant))
