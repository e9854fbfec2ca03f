"""DeepSeek-V3 (``model_type`` "deepseek_v3"): pre-norm decoder layers of
multi-head latent attention (MLA) and a feed-forward part that is a dense
SwiGLU MLP in the first ``first_k_dense_replace`` layers and a routed MoE
after them; an untied head.

MLA, as published (no query low-rank, ``q_lora_rank`` null): per head a
query of ``qk_nope_head_dim`` + ``qk_rope_head_dim``; per token one latent
``c = RMSNorm(x · W_kva[:, :kv_lora_rank])`` and one rope key part
``k_rope = rope(x · W_kva[:, kv_lora_rank:])`` shared by every head; per
head ``k = [c · W_uk_h, k_rope]`` and ``v = c · W_uv_h``; scores scaled by
1/sqrt(nope + rope).  The reference builds K and V so (the published form);
the program serves the absorbed form over a cache of ``[c, k_rope]``.

Routing (``scoring_func`` sigmoid, ``topk_method`` noaux_tc): scores
``s = sigmoid(x · W_r)``; the top-k experts are chosen on
``s + e_score_correction_bias`` in one group (``program_config`` refuses
``n_group`` above 1), weighted by their unbiased ``s``, renormalised
(``norm_topk_prob``) and times ``routed_scaling_factor``; plus
``n_shared_experts`` shared experts as one SwiGLU of
``n_shared_experts * moe_intermediate_size``.

Rope on the rope dims uses interleaved pairs (HF's ``rope_interleave``,
true by default and absent from the file): the dims are gathered to
[evens, odds], then rotated half against half.
"""

from __future__ import annotations

import dataclasses
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench.families import qwen3 as dense
from bench.model import Leaf, bytes_per_param, ctx_sum
from bench.reference import HI, Q_BLOCK, linear, rmsnorm, rope


def head_dim(conf: Dict) -> int:
    """A head's score width, nope + rope."""
    return conf["qk_nope_head_dim"] + conf["qk_rope_head_dim"]


def _widths(conf: Dict):
    return (conf["num_attention_heads"], conf["kv_lora_rank"],
            conf["qk_nope_head_dim"], conf["qk_rope_head_dim"], conf["v_head_dim"])


def _is_moe(conf: Dict, i: int) -> bool:
    return i >= conf["first_k_dense_replace"] and i % conf["moe_layer_freq"] == 0


# --------------------------------------------------------------------------
# the program's configuration
# --------------------------------------------------------------------------


def program_config(conf: Dict):
    """The program's registry entry ``conf["arch"]`` with every size and
    routing setting taken from the file, then the serving variant."""
    from repro.configs import get_config

    wanted = {"hidden_act": "silu", "q_lora_rank": None, "scoring_func": "sigmoid",
              "topk_method": "noaux_tc", "moe_layer_freq": 1, "attention_bias": False,
              "rope_interleave": True, "n_group": 1, "topk_group": 1}
    read = {"rope_interleave": True, **conf}      # HF deepseek_v3's default
    off = {k: read.get(k) for k, v in wanted.items() if read.get(k) != v}
    if off or conf.get("rope_scaling"):
        raise ValueError(f"the program serves deepseek_v3 as {wanted} with no "
                         f"rope scaling; the file has {off or conf['rope_scaling']}")
    h, r, dn, dr, dv = _widths(conf)
    return dataclasses.replace(
        get_config(conf["arch"]),
        n_layers=conf["num_hidden_layers"],
        d_model=conf["hidden_size"],
        n_heads=h,
        n_kv_heads=conf["num_key_value_heads"],
        head_dim=dv,
        d_ff=conf["intermediate_size"],
        vocab_size=conf["vocab_size"],
        rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["rms_norm_eps"]),
        kv_lora_rank=r,
        qk_nope_head_dim=dn,
        qk_rope_head_dim=dr,
        v_head_dim=dv,
        n_experts=conf["n_routed_experts"],
        n_experts_padded=0,
        top_k=conf["num_experts_per_tok"],
        moe_d_ff=conf["moe_intermediate_size"],
        n_shared_experts=conf["n_shared_experts"],
        shared_d_ff=conf["n_shared_experts"] * conf["moe_intermediate_size"],
        router_norm_topk=bool(conf["norm_topk_prob"]),
        router_scoring="sigmoid",
        routed_scaling=float(conf["routed_scaling_factor"]),
        first_k_dense=conf["first_k_dense_replace"],
        tie_embeddings=bool(conf["tie_word_embeddings"]),
        dtype=conf["torch_dtype"],
    ).serving()


def experts_held(conf: Dict, cfg) -> int:
    return cfg.n_experts


# --------------------------------------------------------------------------
# the parameter tree
# --------------------------------------------------------------------------


def layout(conf: Dict, held: int) -> Dict:
    """The program's tree.  Norm scales are offsets from 1.  The routing
    bias is made as norm leaves are, N(0, 0.1): about half the spread of
    the sigmoid scores, so that it changes some selections."""
    d, v = conf["hidden_size"], conf["vocab_size"]
    h, r, dn, dr, dv = _widths(conf)
    f = conf["moe_intermediate_size"]
    dt = conf["torch_dtype"]
    mat = lambda *s: Leaf(tuple(s), dt, "matrix")
    norm = lambda n: Leaf((n,), "float32", "norm")

    def layer(i):
        p = {"ln_attn": norm(d), "ln_mlp": norm(d),
             "attn": {"wq": mat(d, h * (dn + dr)), "wkv_a": mat(d, r + dr),
                      "kv_norm": norm(r), "wkv_b": mat(r, h * (dn + dv)),
                      "wo": mat(h * dv, d)}}
        if not _is_moe(conf, i):
            p["mlp"] = dense.mlp_leaves(mat, d, conf["intermediate_size"])
            return p
        p["moe"] = {"router": Leaf((d, held), "float32", "matrix"),
                    "e_score_correction_bias": norm(held),
                    "w_gate": mat(held, d, f), "w_up": mat(held, d, f),
                    "w_down": mat(held, f, d)}
        p["shared_mlp"] = dense.mlp_leaves(mat, d, conf["n_shared_experts"] * f)
        return p

    out = {"embed": mat(v, d), "ln_final": norm(d),
           "layers": [layer(i) for i in range(conf["num_hidden_layers"])]}
    if not conf["tie_word_embeddings"]:
        out["lm_head"] = mat(d, v)
    return out


# --------------------------------------------------------------------------
# work of a step
# --------------------------------------------------------------------------


def attention_matmul_params(conf: Dict) -> int:
    """Projection weights a token multiplies through.  The absorbed form
    multiplies through as many: W_uk and W_uv, folded into the query and
    the context, are W_kvb's two halves."""
    d = conf["hidden_size"]
    h, r, dn, dr, dv = _widths(conf)
    return d * h * (dn + dr) + d * (r + dr) + r * h * (dn + dv) + h * dv * d


def active_matmul_params(conf: Dict) -> int:
    """Weights one token multiplies through: the projections; the dense
    MLP in a leading layer; else the router over every expert, its top-k
    experts and the shared expert; and the head."""
    d, f = conf["hidden_size"], conf["moe_intermediate_size"]
    total = d * conf["vocab_size"]
    for i in range(conf["num_hidden_layers"]):
        total += attention_matmul_params(conf)
        if _is_moe(conf, i):
            total += d * conf["n_routed_experts"]
            total += (conf["num_experts_per_tok"] + conf["n_shared_experts"]) * 3 * d * f
        else:
            total += 3 * d * conf["intermediate_size"]
    return total


def step_flops(conf: Dict, q_lens: np.ndarray, cache_pos: np.ndarray) -> float:
    """Model FLOPs of the valid tokens: 2 per weight multiplied, plus the
    published attention at each token's context, QK^T over nope + rope and
    PV over ``v_head_dim`` (what the model needs, whichever form serves)."""
    h, _, dn, dr, dv = _widths(conf)
    tokens = float(np.sum(q_lens))
    return (2.0 * active_matmul_params(conf) * tokens
            + 2.0 * conf["num_hidden_layers"] * h * (dn + dr + dv) * ctx_sum(q_lens, cache_pos))


def attention_call(conf: Dict, q_lens: np.ndarray, cache_pos: np.ndarray,
                   dtype_bytes: int = 2) -> tuple:
    """(FLOPs, bytes) of one layer's absorbed latent kernel in one step, at
    its least: every head scores each valid query against its causal keys
    over the latent + rope width and sums the latent; the queries
    ``[q_lat, q_rope]``, each row's latent span and the latent outputs read
    or written once."""
    h, r, _, dr, _ = _widths(conf)
    flops = 2.0 * h * (r + dr + r) * ctx_sum(q_lens, cache_pos)
    live = q_lens > 0
    q = q_lens[live].astype(np.float64)
    span = cache_pos[live].astype(np.float64) + q
    nbytes = dtype_bytes * float(np.sum(q * h * (r + dr + r) + span * (r + dr)))
    return flops, nbytes


def kv_bytes_per_token(conf: Dict) -> int:
    """The latent and the rope key part, a layer."""
    return (conf["num_hidden_layers"] * (conf["kv_lora_rank"] + conf["qk_rope_head_dim"])
            * bytes_per_param(conf))


# --------------------------------------------------------------------------
# the reference layer
# --------------------------------------------------------------------------


def rope_dims(x, conf: Dict):
    """Rope on the rope dims (x [L, heads, dr]) in the published
    convention."""
    if conf.get("rope_interleave", True):
        x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    return rope(x, conf["rope_theta"])


def attention(p, x, conf: Dict, quant: bool):
    """Causal MLA over one sequence x [L, d], in the published form: each
    head's K and V built from the latent."""
    l = x.shape[0]
    h, r, dn, dr, dv = _widths(conf)
    q = linear(x, p["wq"], quant).reshape(l, h, dn + dr)
    kva = linear(x, p["wkv_a"], quant)
    c = rmsnorm(kva[:, :r], p["kv_norm"], conf["rms_norm_eps"])
    k_rope = rope_dims(kva[:, None, r:], conf)                  # [L, 1, dr]
    kv = linear(c, p["wkv_b"], quant).reshape(l, h, dn + dv)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_rope, (l, h, dr))], -1)
    v = kv[..., dn:]
    q = jnp.concatenate([q[..., :dn], rope_dims(q[..., dn:], conf)], -1)
    q = q.reshape(l // Q_BLOCK, Q_BLOCK, h, dn + dr)
    kpos = jnp.arange(l)

    def block(args):
        qb, i = args                                     # [Q, h, dn + dr]
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) / np.sqrt(dn + dr)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", w, v, precision=HI)

    o = jax.lax.map(block, (q, jnp.arange(l // Q_BLOCK)))
    return linear(o.reshape(l, h * dv), p["wo"], quant)


def route(p, x, conf: Dict):
    """Gates [L, experts]: sigmoid scores, top-k chosen on the biased
    scores, weighted by the unbiased ones."""
    e, k = conf["n_routed_experts"], conf["num_experts_per_tok"]
    s = jax.nn.sigmoid(jnp.matmul(x, p["router"][:, :e].astype(jnp.float32),
                                  precision=HI))
    _, top_i = jax.lax.top_k(s + p["e_score_correction_bias"][:e], k)
    top_w = jnp.take_along_axis(s, top_i, axis=-1)
    if conf["norm_topk_prob"]:
        top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + 1e-20)
    top_w = top_w * conf["routed_scaling_factor"]
    return jnp.zeros_like(s).at[jnp.arange(x.shape[0])[:, None], top_i].set(top_w)


def moe(p, x, conf: Dict, quant: bool):
    """Every routed expert's SwiGLU weighted by its gate, plus the shared
    expert."""
    gates = route(p["moe"], x, conf)

    def expert(acc, j):
        w = {n: p["moe"][n][j] for n in ("w_gate", "w_up", "w_down")}
        return acc + gates[:, j][:, None] * dense.mlp(w, x, quant), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(conf["n_routed_experts"]))
    return y + dense.mlp(p["shared_mlp"], x, quant)


def layer(p, x, conf: Dict, quant: bool):
    """One pre-norm layer; dense or MoE by its parameters (the reference
    passes no layer index)."""
    eps = conf["rms_norm_eps"]
    x = x + attention(p["attn"], rmsnorm(x, p["ln_attn"], eps), conf, quant)
    h = rmsnorm(x, p["ln_mlp"], eps)
    return x + (moe(p, h, conf, quant) if "moe" in p else dense.mlp(p["mlp"], h, quant))
