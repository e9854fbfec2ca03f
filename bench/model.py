"""A configuration file as the program runs it, the weights the benchmark
makes for it, and the operations and bytes its work needs.

The configuration files hold the model's published ``config.json`` keys.
Everything that depends on the architecture lives in the family module
that the file's ``model_type`` names (``bench/families/<model_type>.py``,
see ``bench/families/__init__.py``); the functions here that take a
configuration hand it to that module.  What is the same for every family
is here: the leaf specs and the weights made from them (the names and
shapes are the interface to the system under test, the values are the
benchmark's own), the parameter count, and the roofline arithmetic.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from types import ModuleType
from typing import Any, Callable, Dict, List

import numpy as np

from bench import spec


def family(conf: Dict) -> ModuleType:
    """The family module of ``conf["model_type"]``."""
    return spec.family(conf["model_type"])


def program_config(conf: Dict):
    """The program's ``ModelConfig`` for this file."""
    return family(conf).program_config(conf)


def experts_held(conf: Dict, cfg) -> int:
    """Routed experts the program ``cfg`` holds on a chip (0: dense)."""
    return family(conf).experts_held(conf, cfg)


def head_dim(conf: Dict) -> int:
    return family(conf).head_dim(conf)


# --------------------------------------------------------------------------
# weights
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class Leaf:
    shape: tuple
    dtype: str
    kind: str          # "matrix" | "norm"


def layout(conf: Dict, held: int) -> Dict[str, Any]:
    """The parameter tree the program serves, as ``Leaf`` specs; ``held``
    is ``experts_held``."""
    return family(conf).layout(conf, held)


def seed_key(seed: int):
    """A JAX key from any non-negative seed (more than 32 bits included)."""
    import jax
    import jax.numpy as jnp

    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words), impl="threefry2x32")


def make_params(conf: Dict, held: int, seed: int,
                device_of: Callable[[tuple], Any]):
    """The weights, made on the device from ``seed``: one jitted call per
    device, each leaf on ``device_of(path)``.  A leaf's values depend only
    on the seed and its path, not on where it is placed."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    tree = layout(conf, held)
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


def param_count(conf: Dict, held: int) -> int:
    """Parameters the program holds: the sum over the layout's leaves."""
    import jax

    return sum(math.prod(lf.shape) for lf in jax.tree.leaves(layout(conf, held)))


# --------------------------------------------------------------------------
# work of a step
# --------------------------------------------------------------------------


def active_matmul_params(conf: Dict) -> int:
    """Weights one token multiplies through (MoE: its top-k experts)."""
    return family(conf).active_matmul_params(conf)


def ctx_sum(q_lens: np.ndarray, cache_pos: np.ndarray) -> float:
    """Keys seen, summed over every valid query: query i of a row at depth
    c attends c + i + 1 keys."""
    q = q_lens.astype(np.float64)
    return float(np.sum(q * cache_pos + q * (q + 1) / 2))


def step_flops(conf: Dict, q_lens: np.ndarray, cache_pos: np.ndarray) -> float:
    """Model FLOPs of the valid tokens of one step."""
    return family(conf).step_flops(conf, q_lens, cache_pos)


def attention_call(conf: Dict, q_lens: np.ndarray, cache_pos: np.ndarray,
                   dtype_bytes: int = 2) -> tuple:
    """(FLOPs, bytes) the attention kernel of one layer needs in one step."""
    return family(conf).attention_call(conf, q_lens, cache_pos, dtype_bytes)


def least_time(flops: float, nbytes: float, peak: Dict) -> float:
    """Roofline time: the larger of operations over peak rate and bytes
    over peak bandwidth."""
    return max(flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])


def bytes_per_param(conf: Dict) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[conf["torch_dtype"]]


def kv_bytes_per_token(conf: Dict) -> int:
    return family(conf).kv_bytes_per_token(conf)


def describe(conf: Dict, held: int) -> str:
    n = param_count(conf, held)
    return (f"{n / 1e9:.3f} B params ({n * bytes_per_param(conf) / 1e9:.2f} GB), "
            f"{active_matmul_params(conf) / 1e9:.3f} B active matmul params, "
            f"KV {kv_bytes_per_token(conf) / 1024:.0f} KiB/token")


def ceil_to(x: int, m: int) -> int:
    return int(math.ceil(x / m) * m)
