"""Profile-calibrate the batch-aware cost model's invariant fractions.

The batched decode roofline (:meth:`CostModel._roofline`) splits an op's
HBM traffic into a batch-invariant share (weights — streamed once per
batched step) and a per-request share (activations / KV), using the
per-op-class fractions in :data:`DEFAULT_BATCH_INVARIANT_FRAC`.  Those
fractions are a *traffic model*; this script measures them, per op class,
from the XLA compiler's own ``cost_analysis()`` byte counts:

1. for each op class, compile a representative decode-shaped computation
   at several batch widths and record ``bytes accessed``;
2. least-squares fit ``bytes(B) = invariant + B * per_request`` per class
   (:func:`repro.core.costmodel.calibrate_invariant_frac`);
3. report ``invariant / bytes(1)`` — the exact quantity the roofline
   consumes — next to the shipped default.

Run::

    PYTHONPATH=src python -m repro.launch.calibrate_invariant \
        --batches 1,2,4,8 --out calib_invariant.json

The representative computations mirror where each class shows up in the
serving decode step: ``matmul`` is a weight-resident GEMV, ``conv`` the
mamba short causal conv (weights small, state per-request), ``einsum`` the
attention score/value contractions against a per-request KV stream,
``ssd`` the mamba2 chunked state update (shared A/dt vectors, per-request
state), ``scan`` an associative state scan, ``softmax`` pure activation
traffic.  Classes with no invariant operand calibrate to ~0 by
construction — measuring that (instead of guessing 0.3–0.6) is the point.
"""

from __future__ import annotations

import argparse
import json
from functools import partial
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.costmodel import (
    DEFAULT_BATCH_INVARIANT_FRAC,
    calibrate_invariant_frac,
)

# decode-step working sizes: big enough that byte counts dominate compiler
# bookkeeping noise, small enough to compile instantly on CPU
D_MODEL = 512
D_HEAD = 64
N_HEADS = 8
SEQ = 256          # resident KV / state length a decode step streams
CONV_K = 4
SSD_CHUNK = 64


def _op_matmul(B: int) -> Tuple[Callable, tuple]:
    # decode GEMV: per-request activation row against resident weights
    w = jnp.zeros((D_MODEL, 4 * D_MODEL), jnp.float32)
    x = jnp.zeros((B, D_MODEL), jnp.float32)
    return (lambda x, w: x @ w), (x, w)

def _op_conv(B: int) -> Tuple[Callable, tuple]:
    # mamba-style depthwise causal conv over the short conv window
    w = jnp.zeros((D_MODEL, 1, CONV_K), jnp.float32)
    x = jnp.zeros((B, D_MODEL, CONV_K), jnp.float32)
    fn = partial(
        jax.lax.conv_general_dilated,
        window_strides=(1,), padding="VALID", feature_group_count=D_MODEL,
    )
    return fn, (x, w)

def _op_einsum(B: int) -> Tuple[Callable, tuple]:
    # decode attention: q row against the per-request KV stream (scores +
    # weighted values) — no resident-weight operand at all
    q = jnp.zeros((B, N_HEADS, 1, D_HEAD), jnp.float32)
    k = jnp.zeros((B, N_HEADS, SEQ, D_HEAD), jnp.float32)
    v = jnp.zeros((B, N_HEADS, SEQ, D_HEAD), jnp.float32)

    def fn(q, k, v):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k)
        return jnp.einsum("bhqk,bhkd->bhqd", s, v)

    return fn, (q, k, v)

def _op_ssd(B: int) -> Tuple[Callable, tuple]:
    # mamba2 chunked state update: per-request hidden state vs shared
    # per-head decay/step vectors (the only invariant operands)
    a = jnp.zeros((N_HEADS,), jnp.float32)
    dt = jnp.zeros((N_HEADS,), jnp.float32)
    state = jnp.zeros((B, N_HEADS, D_HEAD, D_HEAD), jnp.float32)
    xbc = jnp.zeros((B, SSD_CHUNK, N_HEADS, D_HEAD), jnp.float32)

    def fn(state, xbc, a, dt):
        decay = jnp.exp(a * dt)[None, :, None, None]
        upd = jnp.einsum("blhd,blhe->bhde", xbc, xbc)
        return state * decay + upd

    return fn, (state, xbc, a, dt)

def _op_scan(B: int) -> Tuple[Callable, tuple]:
    # associative state scan over per-request sequences
    x = jnp.zeros((B, SEQ, D_MODEL), jnp.float32)
    return (lambda x: jax.lax.associative_scan(jnp.add, x, axis=1)), (x,)

def _op_softmax(B: int) -> Tuple[Callable, tuple]:
    x = jnp.zeros((B, N_HEADS, SEQ), jnp.float32)
    return (lambda x: jax.nn.softmax(x, axis=-1)), (x,)


OPS: Dict[str, Callable[[int], Tuple[Callable, tuple]]] = {
    "matmul": _op_matmul,
    "conv": _op_conv,
    "einsum": _op_einsum,
    "ssd": _op_ssd,
    "scan": _op_scan,
    "softmax": _op_softmax,
}


def measured_bytes(cls: str, batch: int) -> float:
    fn, args = OPS[cls](batch)
    compiled = jax.jit(fn).lower(*args).compile()
    ca = compiled.cost_analysis()
    return float(ca.get("bytes accessed", 0.0))


def collect(batches) -> Dict[str, Dict[int, float]]:
    out: Dict[str, Dict[int, float]] = {}
    for cls in OPS:
        out[cls] = {b: measured_bytes(cls, b) for b in batches}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--batches", default="1,2,4,8",
                    help="comma-separated batch widths (need >= 2)")
    ap.add_argument("--out", default=None,
                    help="write raw bytes + fitted fractions to this JSON")
    args = ap.parse_args(argv)
    batches = sorted({int(b) for b in args.batches.split(",")})
    if len(batches) < 2:
        ap.error("need at least two batch widths to fit the traffic model")

    bytes_by_batch = collect(batches)
    fracs = calibrate_invariant_frac(bytes_by_batch)

    print(f"{'class':<10} {'bytes(B=1)':>12} {'bytes(B=max)':>13} "
          f"{'fitted':>8} {'shipped':>8}")
    for cls in OPS:
        pts = bytes_by_batch[cls]
        print(f"{cls:<10} {pts[batches[0]]:>12.0f} {pts[batches[-1]]:>13.0f} "
              f"{fracs[cls]:>8.3f} {DEFAULT_BATCH_INVARIANT_FRAC[cls]:>8.2f}")

    if args.out:
        payload = {
            "batches": batches,
            "bytes_by_batch": {c: {str(b): v for b, v in p.items()}
                               for c, p in bytes_by_batch.items()},
            "fractions": fracs,
            "shipped_defaults": dict(DEFAULT_BATCH_INVARIANT_FRAC),
        }
        with open(args.out, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"# wrote {args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
