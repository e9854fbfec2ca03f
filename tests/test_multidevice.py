"""Multi-device integration tests via subprocess (the forced host-device
count must be set before jax initializes, so these run out-of-process —
the main test process keeps its single CPU device)."""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

# each test spawns a fresh interpreter and compiles on a forced multi-device
# mesh — minutes of wall clock; the fast tier runs with -m "not slow"
pytestmark = pytest.mark.slow

SRC = str(Path(__file__).resolve().parents[1] / "src")


def run_with_devices(n_devices: int, body: str, timeout: int = 600) -> str:
    code = textwrap.dedent(body)
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-c", code],
        env=env, capture_output=True, text=True, timeout=timeout,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_moe_ep_matches_reference():
    """EP all-to-all MoE == dense-dispatch oracle on an 8-device mesh."""
    run_with_devices(8, """
        import jax, jax.numpy as jnp, numpy as np
        from dataclasses import replace
        from repro.configs import get_config
        from repro.models.moe import moe_params, moe_reference
        from repro.parallel.moe_parallel import moe_ep
        from repro.parallel.context import ParallelContext, default_rules

        cfg = replace(
            get_config("qwen2-moe-a2.7b").smoke(),
            n_experts=8, n_experts_padded=8, top_k=2, moe_d_ff=64, d_model=128,
            capacity_factor=8.0,   # no drops → exact match with the oracle
        )
        from jax.sharding import AxisType
        mesh = jax.make_mesh((4, 2), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
        ctx = ParallelContext(mesh, default_rules(False), ep_axes=("data",),
                              dp_axes=("data",), tp_axis="model")
        key = jax.random.PRNGKey(0)
        p = moe_params(key, cfg, jnp.float32)
        x = jax.random.normal(key, (8, 16, cfg.d_model), jnp.float32)
        y_ref, aux_ref = moe_reference(p, x, cfg)
        with mesh:
            y_ep, aux_ep = jax.jit(lambda p, x: moe_ep(p, x, cfg, ctx))(p, x)
        err = float(jnp.max(jnp.abs(y_ep - y_ref)))
        scale = float(jnp.max(jnp.abs(y_ref))) + 1e-9
        assert err / scale < 2e-5, (err, scale)
        print("EP-vs-ref OK", err / scale)
    """)


def test_stage_executor_spreads_across_devices():
    run_with_devices(4, """
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.core.modelgraph import transformer_graph
        from repro.models.model import build_model
        from repro.serving.stage_executor import StageExecutor, stages_from_placement

        cfg = get_config("llama3.2-1b").smoke()
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        graph = transformer_graph(cfg, seq_len=32, granularity="block")
        order = graph.topo_order()
        # split layers across all 4 devices
        placement = {nid: min(i * 4 // len(order), 3) for i, nid in enumerate(order)}
        stages = stages_from_placement(graph, placement, jax.devices(), cfg.n_layers)
        assert len(stages) == 4, len(stages)
        ex = StageExecutor(cfg, params, stages)
        toks = jnp.asarray([[1,2,3,4]], jnp.int32)
        logits_ref, _ = model.prefill(params, {"tokens": toks}, 32)
        caches = ex.init_caches(1, 32)
        logits_ex, _ = ex.forward(toks, caches, cache_pos=0)
        np.testing.assert_allclose(np.asarray(logits_ref, np.float32),
                                   np.asarray(logits_ex[:, -1], np.float32),
                                   rtol=3e-3, atol=3e-3)
        devs = {st.device for st in stages}
        assert len(devs) == 4
        print("multi-device stages OK")
    """)


def test_engine_replan_on_device_failure():
    run_with_devices(4, """
        import jax, jax.numpy as jnp
        from repro.configs import get_config
        from repro.core.devices import tpu_slice_cluster
        from repro.core.placement import PlanConfig
        from repro.models.model import build_model
        from repro.serving.engine import ServingEngine, Request

        cfg = get_config("llama3.2-1b").smoke()
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        cluster = tpu_slice_cluster(n_slices=4, heterogeneous=True)
        eng = ServingEngine(cfg, params, cluster, slots=1, max_len=32,
                            plan_cfg=PlanConfig(method="msct"), eos_id=-1)
        r1 = Request(rid=0, prompt=[1,2,3], max_new_tokens=3)
        eng.submit(r1); eng.run_until_drained()
        assert r1.done
        # kill device 0 → replan on survivors → same answers
        eng.on_device_failure(0)
        assert len(eng.devices) == 3
        r2 = Request(rid=1, prompt=[1,2,3], max_new_tokens=3)
        eng.submit(r2); eng.run_until_drained()
        assert r2.done and r2.out_tokens == r1.out_tokens
        print("replan-on-failure OK")
    """)


def test_sharded_train_step_runs_on_debug_mesh():
    """A real (executed, not just compiled) DP+TP train step on 8 devices."""
    run_with_devices(8, """
        import numpy as np, jax, jax.numpy as jnp
        from functools import partial
        from jax.sharding import NamedSharding, PartitionSpec as P
        from dataclasses import replace
        from repro.configs import get_config
        from repro.models.model import build_model
        from repro.parallel.context import ParallelContext, parallel_context, default_rules
        from repro.parallel.sharding import param_pspec_tree
        from repro.train.optimizer import AdamWConfig, init_opt_state
        from repro.train.step import make_train_step

        cfg = replace(get_config("llama3.2-1b").smoke(), d_model=128, n_heads=4,
                      n_kv_heads=4, head_dim=32, d_ff=256, vocab_size=512)
        from jax.sharding import AxisType
        mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
        ctx = ParallelContext(mesh, default_rules(False), ep_axes=("data",),
                              dp_axes=("data",), tp_axis="model")
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        pspecs = param_pspec_tree(cfg, mesh, jax.eval_shape(model.init, jax.random.PRNGKey(0)))
        params = jax.tree.map(lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
                              params, pspecs, is_leaf=lambda x: hasattr(x, "dtype"))
        opt = init_opt_state(params)
        step = make_train_step(model, AdamWConfig(lr=1e-3, warmup_steps=1))
        batch = {
            "tokens": jnp.zeros((4, 16), jnp.int32),
            "labels": jnp.zeros((4, 16), jnp.int32),
        }
        batch = jax.tree.map(lambda x: jax.device_put(x, NamedSharding(mesh, P("data", None))), batch)
        with mesh, parallel_context(ctx):
            p2, o2, m = jax.jit(step)(params, opt, batch)
        assert np.isfinite(float(m["loss"]))
        print("sharded train step OK, loss", float(m["loss"]))
    """)


def test_pure_dp_moe_train_step_runs():
    """§Perf layout (qwen2-moe): pure DP×EP — executed end-to-end on a
    (2 data × 4 model) debug mesh with batch covering all 8 devices."""
    run_with_devices(8, """
        import numpy as np, jax, jax.numpy as jnp
        from dataclasses import replace
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config
        from repro.models.model import build_model
        from repro.launch.dryrun import make_context
        from repro.parallel.context import parallel_context
        from repro.parallel.sharding import param_pspec_tree, pure_dp_active
        from repro.train.optimizer import AdamWConfig, init_opt_state
        from repro.train.step import make_train_step

        cfg = replace(get_config("qwen2-moe-a2.7b").smoke(),
                      n_experts=8, n_experts_padded=8, capacity_factor=8.0)
        assert cfg.prefer_pure_dp
        from jax.sharding import AxisType
        mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
        B = 8
        assert pure_dp_active(cfg, mesh, B)
        ctx = make_context(mesh, cfg, B)
        assert ctx.tp_axis is None
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        pspecs = param_pspec_tree(cfg, mesh, jax.eval_shape(model.init, jax.random.PRNGKey(0)),
                                  pure_dp=True)
        params = jax.tree.map(lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
                              params, pspecs, is_leaf=lambda x: hasattr(x, "dtype"))
        opt = init_opt_state(params)
        step = make_train_step(model, AdamWConfig(lr=1e-3, warmup_steps=1))
        batch = {"tokens": jnp.zeros((B, 16), jnp.int32),
                 "labels": jnp.zeros((B, 16), jnp.int32)}
        bspec = NamedSharding(mesh, P(("data", "model"), None))
        batch = jax.tree.map(lambda x: jax.device_put(x, bspec), batch)
        with mesh, parallel_context(ctx):
            p2, o2, m = jax.jit(step)(params, opt, batch)
        assert np.isfinite(float(m["loss"])), m
        print("pure-DP MoE train step OK, loss", float(m["loss"]))
    """)


def test_elastic_resume_across_mesh_sizes(tmp_path):
    """Save a checkpoint under an 8-device mesh, resume under 4 devices —
    elasticity via layout-free checkpoints + mesh-driven shardings."""
    ckpt = str(tmp_path / "elastic_ckpt")
    common = """
        import numpy as np, jax, jax.numpy as jnp
        from dataclasses import replace
        from repro.configs import get_config
        from repro.models.model import build_model
        cfg = replace(get_config("llama3.2-1b").smoke(), d_model=128, n_heads=4,
                      n_kv_heads=4, head_dim=32, d_ff=256, vocab_size=512)
    """
    run_with_devices(8, common + f"""
        from repro.train.checkpoint import save_checkpoint
        from repro.train.optimizer import init_opt_state
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(7))
        opt = init_opt_state(params)
        save_checkpoint({ckpt!r}, 42, {{"params": params, "opt": opt}})
        print("saved at 8 devices")
    """)
    run_with_devices(4, common + f"""
        from repro.train.elastic import elastic_resume
        from jax.sharding import AxisType
        mesh = jax.make_mesh((2, 2), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
        step, params, opt = elastic_resume(cfg, mesh, {ckpt!r})
        assert step == 42
        # state is usable: run a forward pass under the new mesh
        logits, _ = model_fwd = build_model(cfg).train_forward(
            params, {{"tokens": jnp.zeros((2, 8), jnp.int32)}}
        )
        assert np.isfinite(np.asarray(logits, np.float32)).all()
        print("resumed at 4 devices, step", step)
    """)


def test_dryrun_cell_end_to_end():
    """The launch path: one full dry-run cell on the 256-device production
    mesh (llama decode — the fastest-compiling cell)."""
    out = run_with_devices(256, """
        import os
        os.environ.setdefault("XLA_FLAGS", "")
        from repro.launch.dryrun import run_cell
        res = run_cell("llama3.2-1b", "decode_32k", False, verbose=False)
        assert res["status"] == "ok", res
        assert res["n_devices"] == 256
        assert res["flops_per_device"] > 0
        assert res["fits_16gb"], res.get("tpu_fit_estimate_gb")
        print("dryrun cell OK", res["tpu_fit_estimate_gb"], "GB")
    """, timeout=900)
    assert "dryrun cell OK" in out
