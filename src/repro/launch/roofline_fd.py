# Placeholder-device mesh MUST be configured before any jax import.
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""Loop-aware roofline reconstruction by finite differences over compiles.

``compiled.cost_analysis()`` counts a lax.while/scan body ONCE regardless of
trip count (verified: a 10-trip scan of matmuls reports 1 matmul of FLOPs) —
so the full-size dry-run's raw numbers undercount by ~L×accum.  Instead of
guessing correction factors, we reconstruct the true per-device cost from
compiled artifacts only:

  train:   c(A, L) = c_opt + A · (c_micro + L · c_layer)
           → compile the optimizer update alone (c_opt), and the fwd+bwd at
             (A=1, L=L1) and (A=1, L=L2): the difference isolates c_layer
             *as XLA actually fused it*, then scale to the full config.
  serve:   c(L) = c_base + L · c_layer   → two compiles (L1, L2).

The same reconstruction applies to FLOPs, bytes accessed, and HLO-parsed
collective bytes (a collective inside the loop body appears once in the
body's computation text; the L-difference isolates the per-layer set).

Output: benchmarks/artifacts/roofline/<arch>__<shape>.json
"""

import argparse
import json
import sys
import time
from dataclasses import replace
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, SHAPES, get_config, get_shape, shape_applicable
from repro.launch.dryrun import (
    _sharded_struct_tree,
    input_specs,
    make_context,
)
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import (
    collective_bytes_from_hlo,
    roofline_terms,
)
from repro.models.model import active_param_count, build_model, param_count_shape
from repro.parallel.context import parallel_context
from repro.parallel.sharding import (
    batch_pspecs,
    cache_pspec_tree,
    dp_axes,
    param_pspec_tree,
)
from repro.train.optimizer import AdamWConfig, adamw_update, init_opt_state, zero1_shardings
from repro.train.step import make_decode_step, make_loss_fn, make_prefill_step

ARTIFACTS = Path(__file__).resolve().parents[3] / "benchmarks" / "artifacts" / "roofline"


def _measure(lowered):
    compiled = lowered.compile()
    cost = compiled.cost_analysis()
    coll = collective_bytes_from_hlo(compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll": float(coll["total"]),
        "coll_by_kind": coll["by_kind"],
    }


def _sub(a, b):
    return {k: a[k] - b[k] for k in ("flops", "bytes", "coll")}


def _layer_counts(cfg):
    """(L1, L2, unit) — unit respects layer-pattern periodicity."""
    if cfg.family == "hybrid":
        u = cfg.shared_attn_every
        return u, 2 * u, u
    if cfg.local_global_pattern:
        u = len(cfg.local_global_pattern)
        return u, 2 * u, u
    return 1, 2, 1


def _with_layers(cfg, n):
    # FD compiles must be loop-free where it matters: unrolled layers and
    # naive (non-scanned) attention, else the L-difference measures nothing.
    kw = {
        "n_layers": n,
        "scan_layers": False,
        "attention_impl": "naive",
        "attn_chunk": 1 << 30,
    }
    if cfg.family == "encdec":
        kw["n_encoder_layers"] = n
    return replace(cfg, **kw)


def _grad_fn(cfg, model):
    loss_fn = make_loss_fn(model)

    def fwd_bwd(params, batch):
        return jax.value_and_grad(loss_fn)(params, batch)

    return fwd_bwd


def _lower_cell(cfg, shape, mesh, kind):
    """Lower one program variant; returns the lowered object."""
    ctx = make_context(mesh, cfg, shape.global_batch)
    model = build_model(cfg)
    with mesh, parallel_context(ctx):
        params_shape = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        p_specs = param_pspec_tree(cfg, mesh, params_shape,
                                   pure_dp=(ctx.tp_axis is None))
        p_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), p_specs,
                            is_leaf=lambda x: isinstance(x, P))
        params_in = _sharded_struct_tree(params_shape, p_sh)
        batch_in = input_specs(cfg, shape, mesh)
        if kind == "train_fwdbwd":
            return jax.jit(_grad_fn(cfg, model)).lower(params_in, batch_in)
        if kind == "opt":
            quant8 = param_count_shape(cfg) > 100e9
            opt_shape = jax.eval_shape(
                partial(init_opt_state, quant8=quant8), params_shape
            )
            o_sh = zero1_shardings(mesh, opt_shape)
            opt_in = _sharded_struct_tree(opt_shape, o_sh)
            grads_in = params_in
            upd = partial(adamw_update, AdamWConfig())
            return jax.jit(upd, donate_argnums=(2,)).lower(
                params_in, grads_in, opt_in
            )
        if kind == "prefill":
            return jax.jit(make_prefill_step(model)).lower(params_in, batch_in)
        # decode
        step = make_decode_step(model)
        cache_shape = jax.eval_shape(
            lambda: model.init_cache(shape.global_batch, shape.seq_len)
        )
        c_specs = cache_pspec_tree(cfg, shape, mesh, cache_shape)
        c_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), c_specs,
                            is_leaf=lambda x: isinstance(x, P))
        cache_in = _sharded_struct_tree(cache_shape, c_sh)
        pos_in = jax.ShapeDtypeStruct((), jnp.int32, sharding=NamedSharding(mesh, P()))
        return jax.jit(step, donate_argnums=(2,)).lower(
            params_in, batch_in, cache_in, pos_in
        )


def run_cell(arch: str, shape_name: str):
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    ok, reason = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "status": "skipped", "reason": reason}
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=False)
    L1, L2, unit = _layer_counts(cfg)
    L_full = cfg.n_layers

    kind = {"train": "train_fwdbwd", "prefill": "prefill", "decode": "decode"}[shape.kind]
    meas_shape = shape
    accum = 1
    if shape.kind == "train":
        from repro.launch.dryrun import _accum_steps

        accum = _accum_steps(cfg, shape, mesh)
        # FD measures ONE microbatch's fwd+bwd; total = opt + accum · micro
        meas_shape = replace(shape, global_batch=shape.global_batch // accum)
    c1 = _measure(_lower_cell(_with_layers(cfg, L1), meas_shape, mesh, kind))
    c2 = _measure(_lower_cell(_with_layers(cfg, L2), meas_shape, mesh, kind))
    per_unit = _sub(c2, c1)                                  # one unit of layers
    n_units = L_full // unit
    base = {k: c1[k] - per_unit[k] * (L1 // unit) for k in per_unit}
    micro = {k: base[k] + per_unit[k] * n_units for k in per_unit}

    result = {
        "arch": arch,
        "shape": shape_name,
        "status": "ok",
        "n_devices": mesh.size,
        "elapsed_s": round(time.time() - t0, 1),
        "params": param_count_shape(cfg),
        "active_params": active_param_count(cfg),
        "per_layer_unit": per_unit,
        "base": base,
    }

    if shape.kind == "train":
        copt = _measure(_lower_cell(cfg, shape, mesh, "opt"))
        total = {
            k: copt[k] + accum * micro[k] for k in ("flops", "bytes", "coll")
        }
        result["accum_steps"] = accum
        result["opt"] = {k: copt[k] for k in ("flops", "bytes", "coll")}
    else:
        total = micro

    result["flops_per_device"] = total["flops"]
    result["bytes_per_device"] = total["bytes"]
    result["collective_bytes_per_device"] = total["coll"]
    result.update(roofline_terms(result, cfg, shape))
    print(
        f"[roofline] {arch} × {shape_name}: compute={result['t_compute_s']:.4f}s "
        f"memory={result['t_memory_s']:.4f}s coll={result['t_collective_s']:.4f}s "
        f"dominant={result['dominant']} useful={result['useful_flops_ratio']:.2f} "
        f"({result['elapsed_s']}s)"
    )
    return result


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--out", default=str(ARTIFACTS))
    args = ap.parse_args(argv)
    archs = ARCHS if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    outdir = Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)
    failures = 0
    for arch in archs:
        for shape in shapes:
            fp = outdir / f"{arch}__{shape}.json"
            try:
                res = run_cell(arch, shape)
            except Exception as e:  # noqa: BLE001
                import traceback

                traceback.print_exc()
                res = {"arch": arch, "shape": shape, "status": "error",
                       "error": f"{type(e).__name__}: {e}"}
                failures += 1
            fp.write_text(json.dumps(res, indent=2, default=str))
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
