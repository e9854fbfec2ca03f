"""Ragged continuous batching (ISSUE 4): per-slot cache positions end-to-end.

Covers the tentpole and its satellites:

* property test — ragged mixed-depth batches produce token-for-token
  identical greedy outputs to a sequential single-request reference;
* admission invariants — retire-and-refill mid-flight without KV row
  corruption, and KV-aware admission still enforced per slot;
* ragged attention at the kernel level (naive vs pallas, per-row masks);
* the batch-aware cost model (roofline bending, simulator wiring);
* the throughput MILP's per-channel big-M horizon tightening;
* `DeratePolicy` persistence (round trip + engine restart resume).
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.costmodel import CostModel
from repro.core.devices import inter_server_cluster, tpu_slice_cluster
from repro.core.graph import chain_graph, random_dag
from repro.core.heuristics import bottleneck_balance
from repro.core.milp import solve_placement
from repro.core.placement import PlanConfig
from repro.core.simulate import bottleneck_time, simulate_pipeline
from repro.models.model import build_model
from repro.serving.adaptation import AdaptationConfig, DeratePolicy
from repro.serving.engine import Request, ServingEngine


@pytest.fixture(scope="module")
def small_model():
    cfg = get_config("llama3.2-1b").smoke()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _mk_engine(cfg, params, slots, **kw):
    cluster = tpu_slice_cluster(n_slices=1)
    kw.setdefault("plan_cfg", PlanConfig(method="etf"))
    kw.setdefault("eos_id", -1)
    return ServingEngine(cfg, params, cluster, slots=slots, max_len=64, **kw)


# ----------------------------------------------------------------------
# tentpole: ragged == sequential reference (greedy token identity)
# ----------------------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 558, 999])
def test_ragged_mixed_depth_matches_sequential_reference(small_model, seed):
    """Any mix of prompt/output lengths decoded raggedly (slots=4) must
    emit exactly the tokens each request gets when served alone."""
    cfg, model, params = small_model
    rng = np.random.default_rng(seed)
    reqs_spec = [
        (
            [int(t) for t in rng.integers(1, 200, size=int(rng.integers(1, 10)))],
            int(rng.integers(2, 9)),
        )
        for _ in range(7)
    ]
    ref_eng = _mk_engine(cfg, params, slots=1)
    refs = []
    for i, (prompt, m) in enumerate(reqs_spec):
        r = Request(rid=i, prompt=list(prompt), max_new_tokens=m)
        ref_eng.submit(r)
        ref_eng.run_until_drained()
        refs.append(r.out_tokens)

    eng = _mk_engine(cfg, params, slots=4)
    reqs = [
        Request(rid=i, prompt=list(p), max_new_tokens=m)
        for i, (p, m) in enumerate(reqs_spec)
    ]
    for r in reqs:
        eng.submit(r)
    eng.run_until_drained()
    for r, ref in zip(reqs, refs):
        assert r.done
        assert r.out_tokens == ref, (r.rid, r.out_tokens, ref)


def test_retire_and_refill_mid_flight_no_kv_corruption(small_model):
    """A slot freed mid-flight is refilled IMMEDIATELY (no wave drain) into
    its dirty cache row, while the co-resident long request keeps decoding —
    and everyone's tokens still match their solo runs."""
    cfg, model, params = small_model
    spec = [([1, 2, 3, 4], 12), ([7, 8], 3), ([9, 10, 11], 3)]
    solo = []
    for i, (p, m) in enumerate(spec):
        e = _mk_engine(cfg, params, slots=1)
        r = Request(rid=i, prompt=list(p), max_new_tokens=m)
        e.submit(r)
        e.run_until_drained()
        solo.append(r.out_tokens)

    eng = _mk_engine(cfg, params, slots=2)
    long_r = Request(rid=0, prompt=[1, 2, 3, 4], max_new_tokens=12)
    short_r = Request(rid=1, prompt=[7, 8], max_new_tokens=3)
    refill_r = Request(rid=2, prompt=[9, 10, 11], max_new_tokens=3)
    eng.submit(long_r)
    eng.submit(short_r)
    overlapped = False
    refill_submitted = False
    for _ in range(40):
        if short_r.done and not refill_submitted:
            # short retired; hand the engine its replacement NOW
            eng.submit(refill_r)
            refill_submitted = True
        eng.step()
        if refill_r in eng.active and long_r in eng.active:
            overlapped = True  # refill joined mid-flight at a DIFFERENT depth
        if long_r.done and short_r.done and refill_r.done:
            break
    assert long_r.done and short_r.done and refill_r.done
    assert overlapped, "refill request never decoded alongside the long one"
    assert [long_r.out_tokens, short_r.out_tokens, refill_r.out_tokens] == solo


def test_kv_admission_still_enforced_per_slot(small_model):
    """The runtime Eq. 5 cap survives the ragged refactor: in-flight count
    never exceeds the resolved KV-feasible width, queued requests wait."""
    cfg, model, params = small_model
    eng = _mk_engine(cfg, params, slots=4)
    eng._max_in_flight = 2  # pretend only 2 concurrent KV copies fit
    reqs = [Request(rid=i, prompt=[1 + i, 2], max_new_tokens=3) for i in range(5)]
    for r in reqs:
        eng.submit(r)
    peak = 0
    for _ in range(100):
        eng.step()
        peak = max(peak, sum(r is not None for r in eng.active))
        if all(r.done for r in reqs):
            break
    assert all(r.done for r in reqs)
    assert peak == 2, f"admission admitted {peak} > KV-feasible width 2"

    # reject mode: over-cap fresh requests are turned away, not queued
    eng2 = _mk_engine(cfg, params, slots=4, admission="reject")
    eng2._max_in_flight = 1
    reqs2 = [Request(rid=i, prompt=[3, 4 + i], max_new_tokens=3) for i in range(3)]
    for r in reqs2:
        eng2.submit(r)
    eng2.run_until_drained()
    assert sum(r.rejected for r in reqs2) >= 1
    assert all(r.done for r in reqs2)


def test_ragged_attention_pallas_matches_naive(small_model):
    """Per-row cache positions through the flash kernel: pallas ragged
    decode logits == naive ragged decode logits (per-row masks agree)."""
    import dataclasses

    cfg, model, params = small_model
    B, max_len = 3, 32
    pos = jnp.asarray([5, 2, 9], jnp.int32)
    tok = jnp.asarray([[11], [12], [13]], jnp.int32)
    outs = {}
    for impl in ("naive", "pallas"):
        icfg = dataclasses.replace(cfg, attention_impl=impl)
        m = build_model(icfg)
        caches = m.init_cache(B, max_len)
        # seed the caches with distinct prefixes per row
        rng = np.random.default_rng(0)
        for b, plen in enumerate((5, 2, 9)):
            toks = jnp.asarray([rng.integers(1, 100, size=plen).tolist()], jnp.int32)
            _, c1 = m.prefill(params, {"tokens": toks}, max_len)
            caches = jax.tree.map(lambda f, o: f.at[:, b].set(o[:, 0]), caches, c1)
        logits, _ = m.decode_step(params, {"tokens": tok}, caches, pos)
        outs[impl] = np.asarray(logits, np.float32)
    np.testing.assert_allclose(outs["pallas"], outs["naive"], rtol=2e-3, atol=2e-3)


def test_encdec_ragged_decode_positions():
    """Enc-dec path accepts a per-row cache_pos vector (shapes + mask)."""
    cfg = get_config("seamless-m4t-large-v2").smoke()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    B, s_enc, max_len = 2, 4, 16
    frames = jnp.zeros((B, s_enc, cfg.d_model), jnp.float32)
    toks = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)
    logits, caches = model.prefill(
        params, {"frames": frames, "tokens": toks}, max_len
    )
    # ragged continuation: row 0 at depth 3, row 1 pretend-depth 5
    nxt = jnp.asarray([[7], [8]], jnp.int32)
    pos = jnp.asarray([3, 5], jnp.int32)
    l2, _ = model.decode_step(params, {"tokens": nxt}, caches, pos)
    assert l2.shape == (B, cfg.vocab_size)
    assert np.isfinite(np.asarray(l2, np.float32)).all()


# ----------------------------------------------------------------------
# batch-aware cost model
# ----------------------------------------------------------------------


def test_batch_aware_roofline_bends_memory_bound_ops():
    cm = CostModel(inter_server_cluster())
    g = chain_graph(["matmul"] * 2, flops=1e6, output_bytes=1e4)
    node = g.nodes[0]
    node.bytes_accessed = 1e9      # memory-bound: decode GEMV shape
    node.param_bytes = 9e8         # weights dominate the traffic
    t1 = cm.compute_time(node, 0)
    t4 = cm.compute_time(node, 0, batch=4)
    t16 = cm.compute_time(node, 0, batch=16)
    # amortizing the weight stream shrinks the per-request cost, monotonically
    assert t4 < t1 * 0.75
    assert t16 <= t4 + 1e-15
    # flops-bound op: batching cannot help (roofline already at compute roof)
    node.bytes_accessed = 1.0
    node.param_bytes = 0.0
    node.flops = 1e12
    tf1 = cm.compute_time(node, 0)
    tf8 = cm.compute_time(node, 0, batch=8)
    # only the (amortized) dispatch overhead may shrink — the roofline term
    # itself is pinned at the compute roof
    assert tf8 <= tf1
    assert tf1 - tf8 <= cm.dispatch_overhead_s

    # class-table fallback (no param split): still monotone non-increasing
    node2 = g.nodes[1]
    node2.bytes_accessed = 1e9
    node2.param_bytes = 0.0
    node2.flops = 1e6
    assert cm.compute_time(node2, 0, batch=8) < cm.compute_time(node2, 0)


def test_batch_aware_default_is_bit_identical_to_legacy():
    """batch=1 must reproduce the pre-refactor roofline exactly — planner
    objectives and MILP costs may not drift."""
    cm = CostModel(tpu_slice_cluster(n_slices=4, heterogeneous=True))
    g = random_dag(12, seed=3)
    for nid, node in g.nodes.items():
        for k in range(cm.cluster.k):
            dev = cm.cluster.devices[k]
            eff = cm._eff(node.op_type)
            t_f = node.flops / (dev.peak_flops * eff) if node.flops else 0.0
            t_b = node.bytes_accessed / dev.hbm_bw if node.bytes_accessed else 0.0
            legacy = (max(t_f, t_b) + cm.dispatch_overhead_s) * float(
                cm.device_scale[k]
            )
            assert cm.compute_time(node, k) == legacy


def test_simulator_decode_batch_raises_throughput():
    g = chain_graph(["matmul"] * 4, flops=1e7, output_bytes=1e4)
    for node in g.nodes.values():
        node.bytes_accessed = 5e8
        node.param_bytes = 4.5e8
    cl = inter_server_cluster()
    cm = CostModel(cl)
    pl = {nid: nid % cl.k for nid in g.nodes}
    base = simulate_pipeline(g, pl, cm, 16, max_in_flight=4)
    batched = simulate_pipeline(g, pl, cm, 16, max_in_flight=4, decode_batch=4)
    assert batched.steady_throughput > base.steady_throughput * 1.5
    assert bottleneck_time(g, pl, cm, decode_batch=4) < bottleneck_time(g, pl, cm)


# ----------------------------------------------------------------------
# MILP: per-channel big-M horizon tightening
# ----------------------------------------------------------------------


def test_milp_throughput_horizon_tightening():
    g = random_dag(10, seed=1)
    cl = inter_server_cluster()
    cm = CostModel(cl)
    ub = bottleneck_time(g, bottleneck_balance(g, cm).placement, cm)
    loose = solve_placement(
        g, cm, objective="throughput", upper_bound=ub,
        tighten_horizon=False, time_limit=20, mip_rel_gap=1e-3,
    )
    tight = solve_placement(
        g, cm, objective="throughput", upper_bound=ub,
        tighten_horizon=True, time_limit=20, mip_rel_gap=1e-3,
    )
    assert tight.extra["horizon_s"] <= loose.extra["horizon_s"] * 1.001
    # tightening is optimality-preserving: same objective (both solved)
    if loose.status == "optimal" and tight.status == "optimal":
        assert tight.objective == pytest.approx(loose.objective, rel=5e-3)
    # the returned schedule/objective relation still holds
    assert tight.objective == pytest.approx(
        bottleneck_time(g, tight.placement, cm), rel=1e-6
    )


# ----------------------------------------------------------------------
# DeratePolicy persistence
# ----------------------------------------------------------------------


def test_derate_policy_json_round_trip(tmp_path):
    pol = DeratePolicy(AdaptationConfig(confirm_windows=1, smoothing=1.0))
    pol.observe({0: 2.0, 1: 1.0})          # derates device 0 to ~0.5
    pol.observe({0: 1.4, 1: 1.0})          # builds EMA/streak state
    payload = pol.to_json()
    clone = DeratePolicy.from_json(payload, pol.config)
    assert clone.factors == pol.factors
    assert clone._ema == pol._ema
    assert clone._hi == pol._hi and clone._lo == pol._lo
    assert clone.windows == pol.windows
    assert clone.derate_map() == pol.derate_map()
    # file round trip (atomic save)
    path = str(tmp_path / "derate.json")
    pol.save(path)
    loaded = DeratePolicy.load(path, pol.config)
    assert loaded.to_json() == pol.to_json()
    # versioning: unknown payloads refuse loudly
    with pytest.raises(ValueError):
        DeratePolicy.from_json(json.dumps({"version": 99}))


def test_engine_resumes_persisted_derate(small_model, tmp_path):
    cfg, model, params = small_model
    cluster = tpu_slice_cluster(n_slices=2, heterogeneous=True)
    path = str(tmp_path / "state.json")
    adapt = AdaptationConfig(
        confirm_windows=1, smoothing=1.0, min_samples=1, state_path=path
    )
    eng = ServingEngine(
        cfg, params, cluster, slots=2, max_len=64,
        plan_cfg=PlanConfig(method="etf"), eos_id=-1, adapt=adapt,
    )
    assert eng.derate == {}
    # a committed derate (policy state as the loop would have left it —
    # single-CPU runs fold every stage onto one jax device, so the organic
    # evidence path is exercised by test_adaptation's policy tests instead)
    eng.policy.factors = {1: 0.5}
    eng.policy.windows = 7
    eng.policy._hi = {1: 0}
    eng.policy._ema = {0: 0.05}
    eng._persist_policy()
    assert os.path.exists(path), "state_path must be written on persist"
    # a RESTARTED engine resumes the learned derate and plans on it
    eng2 = ServingEngine(
        cfg, params, cluster, slots=2, max_len=64,
        plan_cfg=PlanConfig(method="etf"), eos_id=-1, adapt=adapt,
    )
    assert eng2.derate == {1: 0.5}
    assert eng2.policy.factors == {1: 0.5}
    assert eng2.policy.windows == 7
    assert eng2.policy._ema == {0: 0.05}
    assert eng2.cluster_effective.devices != cluster.devices
    assert eng2.placement_result.extra.get("derate") == {1: 0.5}
    r = Request(rid=0, prompt=[1, 2], max_new_tokens=2)
    eng2.submit(r)
    eng2.run_until_drained()
    assert r.done
