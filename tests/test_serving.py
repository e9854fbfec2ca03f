"""Serving engine + Moirai stage executor integration (1-device CPU;
multi-device splits are exercised via the forced-host-device subprocess in
test_multidevice.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.devices import tpu_slice_cluster
from repro.core.modelgraph import transformer_graph
from repro.core.placement import PlanConfig, plan
from repro.models import transformer
from repro.models.model import build_model
from repro.serving.engine import Request, ServingEngine
from repro.serving.stage_executor import StageExecutor, stages_from_placement


@pytest.fixture(scope="module")
def small_model():
    cfg = get_config("llama3.2-1b").smoke()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def test_executor_matches_model_path(small_model):
    cfg, model, params = small_model
    graph = transformer_graph(cfg, seq_len=64, granularity="block")
    placement = {nid: 0 for nid in graph.nodes}
    stages = stages_from_placement(graph, placement, jax.devices(), cfg.n_layers)
    ex = StageExecutor(cfg, params, stages)

    toks = jnp.asarray([[1, 2, 3, 4, 5]], jnp.int32)
    logits_ref, _ = model.prefill(params, {"tokens": toks}, 64)
    caches = ex.init_caches(1, 64)
    logits_ex, caches = ex.forward(toks, caches, cache_pos=0)
    np.testing.assert_allclose(
        np.asarray(logits_ref, np.float32),
        np.asarray(logits_ex[:, -1], np.float32),
        rtol=2e-3, atol=2e-3,
    )
    # decode continuation matches too
    nxt = jnp.argmax(logits_ex[:, -1], -1).astype(jnp.int32)[:, None]
    _, caches_ref = model.prefill(params, {"tokens": toks}, 64)
    ld_ref, _ = model.decode_step(params, {"tokens": nxt}, caches_ref,
                                  jnp.asarray(5, jnp.int32))
    ld_ex, _ = ex.forward(nxt, caches, cache_pos=5)
    np.testing.assert_allclose(
        np.asarray(ld_ref, np.float32), np.asarray(ld_ex[:, -1], np.float32),
        rtol=2e-3, atol=2e-3,
    )


@pytest.mark.parametrize("paged", [False, True])
def test_stage_program_runs_the_pallas_kernel(small_model, paged):
    """The serving config's stage program holds the Pallas kernel (dense
    and paged KV): nothing swaps it for a pure-jnp path behind the caller."""
    cfg, _, params = small_model
    cfg = cfg.serving()
    graph = transformer_graph(cfg, seq_len=32, granularity="block")
    stages = stages_from_placement(
        graph, {nid: 0 for nid in graph.nodes}, jax.devices(), cfg.n_layers
    )
    ex = StageExecutor(cfg, params, stages)
    table = None
    if paged:
        caches = ex.init_paged_caches(num_pages=8, page_tokens=8)
        table = jnp.asarray([[0, 1, -1, -1], [2, -1, -1, -1]], jnp.int32)
    else:
        caches = ex.init_caches(2, 32)
    traced = ex.trace_stage(
        0, jnp.zeros((2, 4), jnp.int32), caches,
        cache_pos=jnp.asarray([0, 3], jnp.int32),
        q_lens=jnp.asarray([4, 1], jnp.int32), page_table=table,
    )
    assert "pallas_call" in str(traced.jaxpr)


def test_traced_window_under_pallas_raises(small_model):
    """The kernel specializes on the window: a traced one is an error, not
    a reason to fall back to another attention implementation."""
    cfg, _, params = small_model
    cfg = cfg.serving()
    x = jnp.zeros((1, 4, cfg.d_model), jnp.float32)
    pos = jnp.arange(4, dtype=jnp.int32)[None]
    step = jax.jit(lambda w: transformer.block_apply(
        params["layers"][0], x, cfg, positions=pos, window=w)[0])
    with pytest.raises(TypeError, match="static per-layer window"):
        step(jnp.int32(1 << 30))


def test_engine_serves_batched_requests(small_model):
    cfg, model, params = small_model
    cluster = tpu_slice_cluster(n_slices=1)
    eng = ServingEngine(cfg, params, cluster, slots=2, max_len=64,
                        plan_cfg=PlanConfig(method="etf"), eos_id=-1)
    # a caller-supplied plan config still gets the engine's real concurrency
    # (Eq. 5 charges one KV-cache copy per slot), for plan AND future replans
    assert eng.plan_cfg.serving_slots == 2
    reqs = [Request(rid=i, prompt=[1, 2, 3 + i], max_new_tokens=4) for i in range(5)]
    for r in reqs:
        eng.submit(r)
    eng.run_until_drained()
    for r in reqs:
        assert r.done
        assert len(r.out_tokens) == 4
    # greedy decode is deterministic: identical prompts → identical outputs
    assert reqs[0].prompt != reqs[1].prompt
    r_again = Request(rid=99, prompt=[1, 2, 3], max_new_tokens=4)
    eng.submit(r_again)
    eng.run_until_drained()
    assert r_again.out_tokens == reqs[0].out_tokens


def test_engine_continuous_batching_slot_reuse(small_model):
    cfg, model, params = small_model
    cluster = tpu_slice_cluster(n_slices=1)
    eng = ServingEngine(cfg, params, cluster, slots=1, max_len=64,
                        plan_cfg=PlanConfig(method="etf"), eos_id=-1)
    a = Request(rid=0, prompt=[5, 6], max_new_tokens=2)
    b = Request(rid=1, prompt=[7, 8, 9], max_new_tokens=2)
    eng.submit(a)
    eng.submit(b)
    eng.run_until_drained()
    assert a.done and b.done


def test_submit_refuses_an_empty_prompt(small_model):
    """A request with no prompt and no generated tokens has nothing to
    predict from: submit raises and queues nothing, and the engine serves
    on."""
    cfg, model, params = small_model
    cluster = tpu_slice_cluster(n_slices=1)
    eng = ServingEngine(cfg, params, cluster, slots=1, max_len=64,
                        plan_cfg=PlanConfig(method="etf"), eos_id=-1)
    with pytest.raises(ValueError, match="empty prompt"):
        eng.submit(Request(rid=0, prompt=[], max_new_tokens=3))
    assert eng.queue == []
    r = Request(rid=1, prompt=[1, 2], max_new_tokens=3)
    eng.submit(r)
    eng.run_until_drained()
    assert r.done and len(r.out_tokens) == 3


def test_straggler_report_shape(small_model):
    cfg, model, params = small_model
    cluster = tpu_slice_cluster(n_slices=1)
    eng = ServingEngine(cfg, params, cluster, slots=1, max_len=64,
                        plan_cfg=PlanConfig(method="etf"), eos_id=-1)
    eng.submit(Request(rid=0, prompt=[1, 2], max_new_tokens=3))
    eng.run_until_drained()
    rep = eng.straggler_report()
    assert "stages" in rep and isinstance(rep["stragglers"], list)


def test_engine_objective_defaults_follow_slots(small_model):
    """slots>1 serves a pipeline → plan for throughput; slots=1 → latency."""
    cfg, model, params = small_model
    cluster = tpu_slice_cluster(n_slices=2, heterogeneous=True)
    eng = ServingEngine(cfg, params, cluster, slots=4, max_len=64, eos_id=-1)
    assert eng.plan_cfg.objective == "throughput"
    assert eng.placement_result.extra["objective"] == "throughput"
    eng1 = ServingEngine(cfg, params, cluster, slots=1, max_len=64, eos_id=-1)
    assert eng1.plan_cfg.objective == "latency"
    # the throughput-planned engine still serves correctly
    req = Request(rid=0, prompt=[1, 2, 3], max_new_tokens=3)
    eng.submit(req)
    eng.run_until_drained()
    assert req.done and len(req.out_tokens) == 3


def test_engine_failure_replans_with_throughput_objective(small_model):
    cfg, model, params = small_model
    cluster = tpu_slice_cluster(n_slices=3, heterogeneous=True)
    eng = ServingEngine(cfg, params, cluster, slots=2, max_len=64, eos_id=-1)
    assert eng.plan_cfg.objective == "throughput"
    eng.on_device_failure(1)
    assert eng.failed_devices == [1]
    assert 1 not in set(eng.placement_result.placement.values())
    assert eng.placement_result.extra["objective"] == "throughput"
    # predictions were rebuilt for the new stage split
    assert len(eng._pred_stage_s) == len(eng.executor.stages)
    req = Request(rid=0, prompt=[4, 5], max_new_tokens=2)
    eng.submit(req)
    eng.run_until_drained()
    assert req.done
    # a SECOND failure must exclude BOTH failed devices (original indices)
    eng.on_device_failure(2)
    assert eng.failed_devices == [1, 2]
    assert set(eng.placement_result.placement.values()) == {0}
    with pytest.raises(ValueError):
        eng.on_device_failure(2)  # already failed
    req2 = Request(rid=1, prompt=[6], max_new_tokens=2)
    eng.submit(req2)
    eng.run_until_drained()
    assert req2.done


def test_straggler_report_compares_against_predictions(small_model):
    """Deterministic: inject observed stage latencies and predictions."""
    cfg, model, params = small_model
    cluster = tpu_slice_cluster(n_slices=1)
    eng = ServingEngine(cfg, params, cluster, slots=1, max_len=64,
                        plan_cfg=PlanConfig(method="etf"), eos_id=-1,
                        straggler_factor=4.0)
    # pretend the placement split into 3 stages with known predicted costs
    eng._pred_stage_s = [1e-3, 1e-3, 2e-3]
    observed = [[1.0e-3] * 5, [9.0e-3] * 5, [2.0e-3] * 5]
    rep = eng.straggler_report(observed=observed)
    # ratios = [1, 9, 1] → median 1 → only stage 1 exceeds 4× expectation
    assert rep["stragglers"] == [1]
    assert rep["median_ratio"] == pytest.approx(1.0)
    assert rep["stages"][1]["obs_over_pred"] == pytest.approx(9.0)
    assert rep["stages"][2]["predicted_s"] == pytest.approx(2e-3)
    # proportionally slow stages are NOT stragglers: a stage predicted 2×
    # slower may run 2× slower without being flagged
    rep2 = eng.straggler_report(
        observed=[[2.0e-3] * 5, [2.0e-3] * 5, [4.0e-3] * 5]
    )
    assert rep2["stragglers"] == []
    # under-sampled stages (n <= 3) are never flagged
    rep3 = eng.straggler_report(observed=[[1e-3] * 5, [99.0] * 2, [2e-3] * 5])
    assert rep3["stragglers"] == []
    # more observed stages than predictions (stale monitor after a replan
    # shrank the stage count): extra stages get nan ratios, never flagged
    eng._pred_stage_s = [1e-3]
    rep4 = eng.straggler_report(observed=[[1e-3] * 5, [99.0] * 5])
    assert rep4["stragglers"] == []
    assert np.isnan(rep4["stages"][1]["obs_over_pred"])
    # 2-stage pipelines CAN flag (leave-one-out baseline — a plain median
    # would include the straggler's own ratio and never trigger)
    eng._pred_stage_s = [1e-3, 1e-3]
    rep5 = eng.straggler_report(observed=[[1e-3] * 5, [1e-2] * 5])
    assert rep5["stragglers"] == [1]
    # report shape is stable even with zero traffic
    rep6 = eng.straggler_report(observed=[[], []])
    assert rep6["stragglers"] == []
    assert np.isnan(rep6["median_ratio"]) and np.isnan(rep6["median_p95"])


def test_serving_placement_simulated_latency_ranks_methods():
    """Moirai's simulated serving makespan ≤ round-robin's on a hetero cluster."""
    from repro.core.costmodel import CostModel
    from repro.core.simulate import evaluate

    cfg = get_config("llama3.2-1b")
    graph = transformer_graph(cfg, seq_len=2048, granularity="block")
    cluster = tpu_slice_cluster(n_slices=4, heterogeneous=True)
    cm = CostModel(cluster)
    res_m = plan(graph, cluster, method="moirai", time_limit=20, mip_rel_gap=0.05)
    res_rr = plan(graph, cluster, method="round_robin")
    mk_m = evaluate(graph, res_m.placement, cm)
    mk_rr = evaluate(graph, res_rr.placement, cm)
    assert mk_m <= mk_rr * 1.01


def test_serve_cli_exits_nonzero_on_unserved_request():
    """Without a fault schedule an unserved request fails the run: a
    request that cannot fit max_len is shed, and serve exits non-zero."""
    from repro.launch import serve

    with pytest.raises(SystemExit, match="1 request"):
        serve.main([
            "--arch", "llama3.2-1b", "--smoke", "--requests", "1",
            "--max-len", "16", "--max-new-tokens", "20",
            "--oversize", "reject", "--method", "etf", "--adapt-every", "0",
        ])
