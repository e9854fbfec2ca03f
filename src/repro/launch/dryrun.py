# The dry-run (and ONLY the dry-run) builds the production mesh out of 512
# host-platform placeholder devices; jax locks the device count on first
# init, so this MUST precede every other import.  (setdefault: tests that
# import helpers from this module under their own forced device count keep
# their setting; a direct launch gets the 512-device mesh.)
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""Multi-pod dry-run: .lower().compile() every (arch × shape × mesh) cell.

For each cell this produces (no real allocation — ShapeDtypeStruct stand-ins):
  * proof the program partitions over the production mesh (compile succeeds),
  * per-device memory_analysis (proves it fits 16 GB/chip),
  * cost_analysis FLOPs/bytes + collective bytes parsed from the partitioned
    HLO — the three roofline terms of EXPERIMENTS.md §Roofline.

Usage:
  python -m repro.launch.dryrun --arch llama3.2-1b --shape train_4k
  python -m repro.launch.dryrun --arch all --shape all --multi-pod both
"""

import argparse
import json
import sys
import time
import traceback
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, SHAPES, get_config, get_shape, shape_applicable
from repro.configs.base import ModelConfig, ShapeConfig
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import (
    collective_bytes_from_hlo,
    roofline_terms,
)
from repro.models.model import active_param_count, build_model, param_count_shape
from repro.parallel.context import ParallelContext, parallel_context
from repro.parallel.sharding import (
    batch_pspecs,
    cache_pspec_tree,
    dp_axes,
    logical_rules,
    param_pspec_tree,
)
from repro.train.optimizer import AdamWConfig, init_opt_state, zero1_shardings
from repro.train.step import make_decode_step, make_prefill_step, make_train_step

ARTIFACTS = Path(__file__).resolve().parents[3] / "benchmarks" / "artifacts" / "dryrun"


# --------------------------------------------------------------------------
# input specs (ShapeDtypeStruct stand-ins; weak-type-correct, shardable)
# --------------------------------------------------------------------------


def input_specs(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh):
    """Batch ShapeDtypeStructs with NamedShardings for one cell."""
    b, s = shape.global_batch, shape.seq_len
    specs = batch_pspecs(cfg, shape, mesh)
    sds = {}

    def mk(shp, dtype, spec):
        return jax.ShapeDtypeStruct(shp, dtype, sharding=NamedSharding(mesh, spec))

    if shape.kind == "decode":
        # one new token against a cache of length s
        if cfg.frontend == "patch_stub":
            sds["embeds"] = mk((b, 1, cfg.d_model), jnp.bfloat16, specs["embeds"])
            sds["positions"] = mk((3, b, 1), jnp.int32, specs["positions"])
        else:
            tok_spec = specs["tokens"]
            sds["tokens"] = mk((b, 1), jnp.int32, tok_spec)
        return sds

    if cfg.frontend == "patch_stub":
        sds["embeds"] = mk((b, s, cfg.d_model), jnp.bfloat16, specs["embeds"])
        sds["positions"] = mk((3, b, s), jnp.int32, specs["positions"])
    elif cfg.frontend == "frame_stub":
        sds["frames"] = mk((b, s, cfg.d_model), jnp.bfloat16, specs["frames"])
        sds["tokens"] = mk((b, s), jnp.int32, specs["tokens"])
    else:
        sds["tokens"] = mk((b, s), jnp.int32, specs["tokens"])
    if shape.kind == "train":
        sds["labels"] = mk((b, s), jnp.int32, specs["labels"])
    return sds


def _sharded_struct_tree(shape_tree, sharding_tree):
    return jax.tree.map(
        lambda sds, sh: jax.ShapeDtypeStruct(sds.shape, sds.dtype, sharding=sh),
        shape_tree,
        sharding_tree,
    )


def _accum_steps(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh) -> int:
    """Pick gradient-accumulation steps from an activation-memory budget.

    §Perf iteration (qwen2-moe train_4k): per-microbatch collectives (TP
    psums, MoE all-to-alls, per-micro grad psum) scale linearly with accum;
    the old fixed micro=2 policy left a 13.5× t_coll/t_comp ratio.  Choose
    the LARGEST microbatch whose rematted layer-boundary activations
    (L · B_micro · S · D · 2B) fit ~4 GB instead."""
    from repro.parallel.sharding import dp_axes_for

    dp = int(np.prod([mesh.shape[a] for a in dp_axes_for(cfg, mesh, shape.global_batch)]))
    b_loc = max(shape.global_batch // max(dp, 1), 1)
    budget = 4e9
    layers = cfg.n_layers + cfg.n_encoder_layers
    per_seq = max(layers * shape.seq_len * cfg.d_model * 2.0, 1.0)
    micro_target = max(int(budget // per_seq), 1)
    # largest power-of-two divisor of b_loc that fits the budget
    micro = 1
    while micro * 2 <= micro_target and b_loc % (micro * 2) == 0:
        micro *= 2
    return max(1, b_loc // micro)


def make_context(mesh: Mesh, cfg: ModelConfig = None, global_batch: int = 0) -> ParallelContext:
    from repro.parallel.sharding import dp_axes_for, pure_dp_active

    pure_dp = cfg is not None and pure_dp_active(cfg, mesh, global_batch)
    rules = logical_rules(mesh)
    if pure_dp:
        dp = dp_axes_for(cfg, mesh, global_batch)
        rules = dict(rules)
        rules.update({"batch": dp, "heads": None, "kv_heads": None,
                      "ff": None, "vocab": None, "experts": "data",
                      "expert_ff": None})
        return ParallelContext(mesh, rules, ep_axes=("data",), dp_axes=dp,
                               tp_axis=None)
    return ParallelContext(
        mesh,
        rules,
        ep_axes=("data",),
        dp_axes=dp_axes(mesh),
        tp_axis="model",
    )


# --------------------------------------------------------------------------
# one cell
# --------------------------------------------------------------------------


def run_cell(arch: str, shape_name: str, multi_pod: bool, *, verbose: bool = True):
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    ok, reason = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
                "status": "skipped", "reason": reason}

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    ctx = make_context(mesh, cfg, shape.global_batch)
    model = build_model(cfg)
    quant8 = param_count_shape(cfg) > 100e9

    with mesh, parallel_context(ctx):
        params_shape = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        p_specs = param_pspec_tree(cfg, mesh, params_shape,
                                   pure_dp=(ctx.tp_axis is None))
        p_shardings = jax.tree.map(
            lambda s: NamedSharding(mesh, s), p_specs,
            is_leaf=lambda x: isinstance(x, P),
        )
        params_in = _sharded_struct_tree(params_shape, p_shardings)
        batch_in = input_specs(cfg, shape, mesh)

        if shape.kind == "train":
            accum = _accum_steps(cfg, shape, mesh)
            step = make_train_step(model, AdamWConfig(), accum_steps=accum)
            opt_shape = jax.eval_shape(partial(init_opt_state, quant8=quant8), params_shape)
            o_shardings = zero1_shardings(mesh, opt_shape)
            opt_in = _sharded_struct_tree(opt_shape, o_shardings)
            jitted = jax.jit(
                step,
                donate_argnums=(0, 1),
                out_shardings=(p_shardings, o_shardings, None),
            )
            lowered = jitted.lower(params_in, opt_in, batch_in)
        elif shape.kind == "prefill":
            step = make_prefill_step(model)
            jitted = jax.jit(step)
            lowered = jitted.lower(params_in, batch_in)
        else:  # decode
            step = make_decode_step(model)
            cache_shape = jax.eval_shape(
                lambda: model.init_cache(shape.global_batch, shape.seq_len)
            )
            c_specs = cache_pspec_tree(cfg, shape, mesh, cache_shape)
            c_shardings = jax.tree.map(
                lambda s: NamedSharding(mesh, s), c_specs,
                is_leaf=lambda x: isinstance(x, P),
            )
            cache_in = _sharded_struct_tree(cache_shape, c_shardings)
            pos_in = jax.ShapeDtypeStruct((), jnp.int32,
                                          sharding=NamedSharding(mesh, P()))
            jitted = jax.jit(step, donate_argnums=(2,),
                             out_shardings=(None, c_shardings))
            lowered = jitted.lower(params_in, batch_in, cache_in, pos_in)

        compiled = lowered.compile()

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = collective_bytes_from_hlo(hlo)
    n_dev = mesh.size

    flops = float(cost.get("flops", 0.0))
    bytes_acc = float(cost.get("bytes accessed", 0.0))
    result = {
        "arch": arch,
        "shape": shape_name,
        "multi_pod": multi_pod,
        "status": "ok",
        "n_devices": n_dev,
        "compile_s": round(time.time() - t0, 1),
        "flops_per_device": flops,
        "bytes_per_device": bytes_acc,
        "collective_bytes_per_device": coll["total"],
        "collectives": coll["by_kind"],
        "params": param_count_shape(cfg),
        "active_params": active_param_count(cfg),
        "quant8_opt": quant8,
        "memory": {
            k: getattr(mem, k)
            for k in (
                "argument_size_in_bytes",
                "output_size_in_bytes",
                "temp_size_in_bytes",
                "generated_code_size_in_bytes",
            )
            if mem is not None and hasattr(mem, k)
        },
    }
    # TPU-fit estimate: args/outputs are exact (shapes×shardings are fixed);
    # the CPU buffer assigner's temp_size materializes elementwise chains that
    # TPU fusion streams (e.g. the fp32 optimizer-update chain), so we bound
    # the fused working set instead: donated outputs alias arguments, plus a
    # small multiple of the largest single temp-producing tensor.
    args_b = result["memory"].get("argument_size_in_bytes", 0)
    out_b = result["memory"].get("output_size_in_bytes", 0)
    temp_b = result["memory"].get("temp_size_in_bytes", 0)
    working = min(temp_b, max(4e9, 0.25 * temp_b))
    result["tpu_fit_estimate_gb"] = round((max(args_b, out_b) + working) / 1e9, 2)
    result["fits_16gb"] = bool(result["tpu_fit_estimate_gb"] <= 16.0)
    result.update(roofline_terms(result, cfg, shape))
    if verbose:
        m = result["memory"]
        peak = (m.get("argument_size_in_bytes", 0) + m.get("temp_size_in_bytes", 0)) / 1e9
        print(
            f"[dryrun] {arch} × {shape_name} × {'2pod' if multi_pod else '1pod'}: OK "
            f"compile={result['compile_s']}s flops/dev={flops:.3e} "
            f"bytes/dev={bytes_acc:.3e} coll/dev={coll['total']:.3e} "
            f"mem≈{peak:.2f}GB dominant={result['dominant']}"
        )
    return result


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", choices=["off", "on", "both"], default="off")
    ap.add_argument("--out", default=str(ARTIFACTS))
    args = ap.parse_args(argv)

    archs = ARCHS if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    pods = {"off": [False], "on": [True], "both": [False, True]}[args.multi_pod]

    outdir = Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)
    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in pods:
                tag = f"{arch}__{shape}__{'2pod' if mp else '1pod'}"
                fp = outdir / f"{tag}.json"
                try:
                    res = run_cell(arch, shape, mp)
                except Exception as e:  # noqa: BLE001 — record and continue
                    traceback.print_exc()
                    res = {
                        "arch": arch, "shape": shape, "multi_pod": mp,
                        "status": "error", "error": f"{type(e).__name__}: {e}",
                    }
                    failures += 1
                fp.write_text(json.dumps(res, indent=2, default=str))
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
