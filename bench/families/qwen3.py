"""Qwen3 (``model_type`` "qwen3"): pre-norm decoder layers of GQA attention,
with an RMSNorm over each head of q and k, rotate-half RoPE, and a SwiGLU
MLP; an untied head.

The attention, the MLP and the frame of a layer are written once here and
shared with ``qwen2_moe``, which passes its own feed-forward part.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench.model import Leaf, bytes_per_param, ctx_sum
from bench.reference import HI, Q_BLOCK, linear, rmsnorm, rope

PROGRAM_EPS = 1e-6      # the program's RMSNorm epsilon, fixed in its code
QK_NORM = True


def head_dim(conf: Dict) -> int:
    return int(conf.get("head_dim") or conf["hidden_size"] // conf["num_attention_heads"])


# --------------------------------------------------------------------------
# the program's configuration
# --------------------------------------------------------------------------


def program(conf: Dict, qk_norm: bool, **ffn):
    """The program's registry entry ``conf["arch"]`` with every size taken
    from the file, attention's here and the feed-forward part's ``ffn``,
    then the serving variant."""
    from repro.configs import get_config

    if conf["rms_norm_eps"] != PROGRAM_EPS or conf["hidden_act"] != "silu":
        raise ValueError("the program serves RMSNorm eps 1e-6 and SiLU only")
    return dataclasses.replace(
        get_config(conf["arch"]),
        n_layers=conf["num_hidden_layers"],
        d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"],
        head_dim=head_dim(conf),
        vocab_size=conf["vocab_size"],
        rope_theta=float(conf["rope_theta"]),
        tie_embeddings=bool(conf["tie_word_embeddings"]),
        qk_norm=qk_norm,
        dtype=conf["torch_dtype"],
        **ffn,
    ).serving()


def program_config(conf: Dict):
    return program(conf, QK_NORM, d_ff=conf["intermediate_size"])


def experts_held(conf: Dict, cfg) -> int:
    return 0


# --------------------------------------------------------------------------
# the parameter tree
# --------------------------------------------------------------------------


def tree(conf: Dict, qk_norm: bool, ffn: Callable[[Callable], Dict]) -> Dict:
    """The parameter tree, with ``ffn(mat)`` giving each layer's
    feed-forward leaves.  Norm scales are stored as offsets from 1."""
    d, v, hd = conf["hidden_size"], conf["vocab_size"], head_dim(conf)
    h, kv = conf["num_attention_heads"], conf["num_key_value_heads"]
    dt = conf["torch_dtype"]
    mat = lambda *s: Leaf(tuple(s), dt, "matrix")
    norm = lambda n: Leaf((n,), "float32", "norm")

    def layer():
        attn = {"wq": mat(d, h * hd), "wk": mat(d, kv * hd),
                "wv": mat(d, kv * hd), "wo": mat(h * hd, d)}
        if qk_norm:
            attn.update(q_norm=norm(hd), k_norm=norm(hd))
        return {"ln_attn": norm(d), "attn": attn, "ln_mlp": norm(d), **ffn(mat)}

    out = {"embed": mat(v, d), "ln_final": norm(d),
           "layers": [layer() for _ in range(conf["num_hidden_layers"])]}
    if not conf["tie_word_embeddings"]:
        out["lm_head"] = mat(d, v)
    return out


def mlp_leaves(mat: Callable, d: int, ff: int) -> Dict:
    return {"w_gate": mat(d, ff), "w_up": mat(d, ff), "w_down": mat(ff, d)}


def layout(conf: Dict, held: int) -> Dict:
    return tree(conf, QK_NORM, lambda mat: {
        "mlp": mlp_leaves(mat, conf["hidden_size"], conf["intermediate_size"])})


# --------------------------------------------------------------------------
# work of a step
# --------------------------------------------------------------------------


def attention_matmul_params(conf: Dict) -> int:
    d, hd = conf["hidden_size"], head_dim(conf)
    return d * conf["num_attention_heads"] * hd * 2 + d * conf["num_key_value_heads"] * hd * 2


def active_matmul_params(conf: Dict) -> int:
    """Weights one token multiplies through: the projections, the MLP and
    the head.  The embedding is a gather, not a matmul."""
    d = conf["hidden_size"]
    per = attention_matmul_params(conf) + 3 * d * conf["intermediate_size"]
    return conf["num_hidden_layers"] * per + d * conf["vocab_size"]


def flops_with(active: int, conf: Dict, q_lens: np.ndarray, cache_pos: np.ndarray) -> float:
    """2 per weight a valid token multiplies through (``active``), plus
    QK^T and PV at each token's context in every layer."""
    h, hd, n = conf["num_attention_heads"], head_dim(conf), conf["num_hidden_layers"]
    tokens = float(np.sum(q_lens))
    return 2.0 * active * tokens + 4.0 * n * h * hd * ctx_sum(q_lens, cache_pos)


def step_flops(conf: Dict, q_lens: np.ndarray, cache_pos: np.ndarray) -> float:
    return flops_with(active_matmul_params(conf), conf, q_lens, cache_pos)


def attention_call(conf: Dict, q_lens: np.ndarray, cache_pos: np.ndarray,
                   dtype_bytes: int = 2) -> tuple:
    """(FLOPs, bytes) the attention of one layer needs in one step: QK^T and
    PV over each row's valid queries and causal keys; Q, the valid K and V
    span, and O read or written once."""
    h, kv, hd = conf["num_attention_heads"], conf["num_key_value_heads"], head_dim(conf)
    flops = 4.0 * h * hd * ctx_sum(q_lens, cache_pos)
    live = q_lens > 0
    q = q_lens[live].astype(np.float64)
    span = cache_pos[live].astype(np.float64) + q
    nbytes = dtype_bytes * float(np.sum(2 * q * h * hd + 2 * span * kv * hd))
    return flops, nbytes


def kv_bytes_per_token(conf: Dict) -> int:
    return (2 * conf["num_hidden_layers"] * conf["num_key_value_heads"]
            * head_dim(conf) * bytes_per_param(conf))


# --------------------------------------------------------------------------
# the reference layer
# --------------------------------------------------------------------------


def attention(p, x, conf: Dict, quant: bool, qk_norm: bool):
    """Causal GQA self-attention over one sequence x [L, d]."""
    l = x.shape[0]
    h, kv, hd = conf["num_attention_heads"], conf["num_key_value_heads"], head_dim(conf)
    eps = conf["rms_norm_eps"]
    q = linear(x, p["wq"], quant).reshape(l, h, hd)
    k = linear(x, p["wk"], quant).reshape(l, kv, hd)
    v = linear(x, p["wv"], quant).reshape(l, kv, hd)
    if qk_norm:                                # per-head RMSNorm on q and k
        q = rmsnorm(q, p["q_norm"], eps)
        k = rmsnorm(k, p["k_norm"], eps)
    q = rope(q, conf["rope_theta"]).reshape(l // Q_BLOCK, Q_BLOCK, kv, h // kv, hd)
    k = rope(k, conf["rope_theta"])
    kpos = jnp.arange(l)

    def block(args):
        qb, i = args                                     # [Q, kv, rep, hd]
        s = jnp.einsum("qgrd,kgd->grqk", qb, k, precision=HI) / np.sqrt(hd)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("grqk,kgd->qgrd", w, v, precision=HI)

    o = jax.lax.map(block, (q, jnp.arange(l // Q_BLOCK)))
    return linear(o.reshape(l, h * hd), p["wo"], quant)


def mlp(p, x, quant: bool):
    g = linear(x, p["w_gate"], quant)
    u = linear(x, p["w_up"], quant)
    return linear(jax.nn.silu(g) * u, p["w_down"], quant)


def frame(p, x, conf: Dict, quant: bool, qk_norm: bool, ffn: Callable):
    """One pre-norm layer: attention, then ``ffn(p, h)`` on the normed
    residual."""
    eps = conf["rms_norm_eps"]
    x = x + attention(p["attn"], rmsnorm(x, p["ln_attn"], eps), conf, quant, qk_norm)
    return x + ffn(p, rmsnorm(x, p["ln_mlp"], eps))


def layer(p, x, conf: Dict, quant: bool):
    return frame(p, x, conf, quant, QK_NORM, lambda p, h: mlp(p["mlp"], h, quant))
