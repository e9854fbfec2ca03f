"""A configuration file as the program runs it, the weights the benchmark
makes for it, and the operations and bytes its work needs.

The configuration files hold the model's published ``config.json`` keys
(Qwen style).  This module maps them onto the program's ``ModelConfig``
(``program_config``), lays out the parameter tree the program serves
(``layout``: the names and shapes are the interface to the system under
test, the values are the benchmark's own) and counts the work of a step
from shapes alone.
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Any, Callable, Dict, List

import numpy as np

PROGRAM_EPS = 1e-6      # the program's RMSNorm epsilon, fixed in its code


def is_moe(conf: Dict) -> bool:
    return "num_experts" in conf


def head_dim(conf: Dict) -> int:
    return int(conf.get("head_dim") or conf["hidden_size"] // conf["num_attention_heads"])


def program_config(conf: Dict):
    """The program's ``ModelConfig`` for this file: its registry entry with
    every size taken from the file, then the serving variant."""
    from repro.configs import get_config

    if conf["rms_norm_eps"] != PROGRAM_EPS or conf["hidden_act"] != "silu":
        raise ValueError("the program serves RMSNorm eps 1e-6 and SiLU only")
    f = dict(
        n_layers=conf["num_hidden_layers"],
        d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"],
        head_dim=head_dim(conf),
        vocab_size=conf["vocab_size"],
        rope_theta=float(conf["rope_theta"]),
        tie_embeddings=bool(conf["tie_word_embeddings"]),
        qk_norm=conf["model_type"] == "qwen3",
        dtype=conf["torch_dtype"],
    )
    if is_moe(conf):
        f.update(
            n_experts=conf["num_experts"],
            top_k=conf["num_experts_per_tok"],
            moe_d_ff=conf["moe_intermediate_size"],
            d_ff=conf["moe_intermediate_size"],
            shared_d_ff=conf["shared_expert_intermediate_size"],
            n_shared_experts=(conf["shared_expert_intermediate_size"]
                              // conf["moe_intermediate_size"]),
            router_norm_topk=bool(conf["norm_topk_prob"]),
        )
    else:
        f["d_ff"] = conf["intermediate_size"]
    return dataclasses.replace(get_config(conf["arch"]), **f).serving()


# --------------------------------------------------------------------------
# weights
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class Leaf:
    shape: tuple
    dtype: str
    kind: str          # "matrix" | "norm"


def layout(conf: Dict, experts_held: int) -> Dict[str, Any]:
    """The parameter tree the program serves, as ``Leaf`` specs.  Norm
    scales are stored as offsets from 1; ``experts_held`` is the program's
    padded expert count (the router's width)."""
    d, v, hd = conf["hidden_size"], conf["vocab_size"], head_dim(conf)
    h, kv = conf["num_attention_heads"], conf["num_key_value_heads"]
    dt = conf["torch_dtype"]
    mat = lambda *s: Leaf(tuple(s), dt, "matrix")
    norm = lambda n: Leaf((n,), "float32", "norm")

    def mlp(ff):
        return {"w_gate": mat(d, ff), "w_up": mat(d, ff), "w_down": mat(ff, d)}

    def layer():
        attn = {"wq": mat(d, h * hd), "wk": mat(d, kv * hd),
                "wv": mat(d, kv * hd), "wo": mat(h * hd, d)}
        if conf["model_type"] == "qwen3":
            attn.update(q_norm=norm(hd), k_norm=norm(hd))
        p = {"ln_attn": norm(d), "attn": attn, "ln_mlp": norm(d)}
        if is_moe(conf):
            e, f = experts_held, conf["moe_intermediate_size"]
            p["moe"] = {"router": Leaf((d, e), "float32", "matrix"),
                        "w_gate": mat(e, d, f), "w_up": mat(e, d, f),
                        "w_down": mat(e, f, d)}
            p["shared_mlp"] = mlp(conf["shared_expert_intermediate_size"])
        else:
            p["mlp"] = mlp(conf["intermediate_size"])
        return p

    tree = {"embed": mat(v, d), "ln_final": norm(d),
            "layers": [layer() for _ in range(conf["num_hidden_layers"])]}
    if not conf["tie_word_embeddings"]:
        tree["lm_head"] = mat(d, v)
    return tree


def seed_key(seed: int):
    """A JAX key from any non-negative seed (more than 32 bits included)."""
    import jax
    import jax.numpy as jnp

    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words), impl="threefry2x32")


def make_params(conf: Dict, experts_held: int, seed: int,
                device_of: Callable[[tuple], Any]):
    """The weights, made on the device from ``seed``: one jitted call per
    device, each leaf on ``device_of(path)``.  A leaf's values depend only
    on the seed and its path, not on where it is placed."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    tree = layout(conf, experts_held)
    paths, leaves = zip(*jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, Leaf))[0])
    std = float(conf["initializer_range"])
    by_dev: Dict[Any, List[int]] = {}
    for i, p in enumerate(paths):
        by_dev.setdefault(device_of(p), []).append(i)
    key = seed_key(seed)
    out: List[Any] = [None] * len(leaves)
    for dev, idx in by_dev.items():
        def make(key, idx=tuple(idx)):
            vals = []
            for i in idx:
                lf = leaves[i]
                z = jax.random.normal(jax.random.fold_in(key, i), lf.shape, jnp.float32)
                z = z * (0.1 if lf.kind == "norm" else std)
                vals.append(z.astype(lf.dtype))
            return vals

        made = jax.jit(make, out_shardings=SingleDeviceSharding(dev))(
            jax.device_put(key, dev))
        for i, a in zip(idx, made):
            out[i] = a
    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(tree, is_leaf=lambda x: isinstance(x, Leaf)),
        out)


def param_count(conf: Dict, experts_held: int) -> int:
    """Parameters the program holds, from the sizes alone."""
    d, v, hd = conf["hidden_size"], conf["vocab_size"], head_dim(conf)
    h, kv, n = conf["num_attention_heads"], conf["num_key_value_heads"], conf["num_hidden_layers"]
    per = d * h * hd * 2 + d * kv * hd * 2 + 2 * d
    if conf["model_type"] == "qwen3":
        per += 2 * hd
    if is_moe(conf):
        per += d * experts_held + experts_held * 3 * d * conf["moe_intermediate_size"]
        per += 3 * d * conf["shared_expert_intermediate_size"]
    else:
        per += 3 * d * conf["intermediate_size"]
    top = v * d + d + (0 if conf["tie_word_embeddings"] else d * v)
    return n * per + top


# --------------------------------------------------------------------------
# work of a step
# --------------------------------------------------------------------------


def active_matmul_params(conf: Dict) -> int:
    """Weights one token multiplies through: the projections, the MLP (for
    MoE the router over the published experts, top-k experts and the
    shared expert) and the head.  The embedding is a gather, not a matmul."""
    d, v, hd = conf["hidden_size"], conf["vocab_size"], head_dim(conf)
    h, kv = conf["num_attention_heads"], conf["num_key_value_heads"]
    per = d * h * hd * 2 + d * kv * hd * 2
    if is_moe(conf):
        per += d * conf["num_experts"]
        per += conf["num_experts_per_tok"] * 3 * d * conf["moe_intermediate_size"]
        per += 3 * d * conf["shared_expert_intermediate_size"]
    else:
        per += 3 * d * conf["intermediate_size"]
    return conf["num_hidden_layers"] * per + d * v


def _ctx_sum(q_lens: np.ndarray, cache_pos: np.ndarray) -> float:
    """Keys seen, summed over every valid query: query i of a row at depth
    c attends c + i + 1 keys."""
    q = q_lens.astype(np.float64)
    return float(np.sum(q * cache_pos + q * (q + 1) / 2))


def step_flops(conf: Dict, q_lens: np.ndarray, cache_pos: np.ndarray) -> float:
    """Model FLOPs of the valid tokens of one step: 2 per weight a token
    multiplies through, plus QK^T and PV at each token's context."""
    h, hd, n = conf["num_attention_heads"], head_dim(conf), conf["num_hidden_layers"]
    tokens = float(np.sum(q_lens))
    return 2.0 * active_matmul_params(conf) * tokens + 4.0 * n * h * hd * _ctx_sum(q_lens, cache_pos)


def attention_call(conf: Dict, q_lens: np.ndarray, cache_pos: np.ndarray,
                   dtype_bytes: int = 2) -> tuple:
    """(FLOPs, bytes) the attention of one layer needs in one step: QK^T and
    PV over each row's valid queries and causal keys; Q, the valid K and V
    span, and O read or written once."""
    h, kv, hd = conf["num_attention_heads"], conf["num_key_value_heads"], head_dim(conf)
    flops = 4.0 * h * hd * _ctx_sum(q_lens, cache_pos)
    live = q_lens > 0
    q = q_lens[live].astype(np.float64)
    span = cache_pos[live].astype(np.float64) + q
    nbytes = dtype_bytes * float(np.sum(2 * q * h * hd + 2 * span * kv * hd))
    return flops, nbytes


def least_time(flops: float, nbytes: float, peak: Dict) -> float:
    """Roofline time: the larger of operations over peak rate and bytes
    over peak bandwidth."""
    return max(flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])


def bytes_per_param(conf: Dict) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[conf["torch_dtype"]]


def kv_bytes_per_token(conf: Dict) -> int:
    return (2 * conf["num_hidden_layers"] * conf["num_key_value_heads"]
            * head_dim(conf) * bytes_per_param(conf))


def describe(conf: Dict, experts_held: int) -> str:
    n = param_count(conf, experts_held)
    return (f"{n / 1e9:.3f} B params ({n * bytes_per_param(conf) / 1e9:.2f} GB), "
            f"{active_matmul_params(conf) / 1e9:.3f} B active matmul params, "
            f"KV {kv_bytes_per_token(conf) / 1024:.0f} KiB/token")


def ceil_to(x: int, m: int) -> int:
    return int(math.ceil(x / m) * m)
