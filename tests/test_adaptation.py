"""Adaptive serving: derate API, policy convergence/stability, and the
engine's closed observe → derate → replan loop (1-device CPU; the planner
and cost model see the synthetic heterogeneous clusters)."""

import math

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.costmodel import CostModel, DerateCalibrator
from repro.core.devices import DeviceSpec, ClusterSpec, tpu_slice_cluster
from repro.core.modelgraph import transformer_graph
from repro.core.placement import PlanConfig, plan, replan
from repro.core.simulate import bottleneck_time
from repro.models.model import build_model
from repro.serving.adaptation import AdaptationConfig, DeratePolicy
from repro.serving.engine import Request, ServingEngine


@pytest.fixture(scope="module")
def small_model():
    cfg = get_config("llama3.2-1b").smoke()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


# ---------------------------------------------------------------------------
# immutable derate API (core.devices)
# ---------------------------------------------------------------------------


def test_with_derate_scales_speed_not_memory():
    cluster = tpu_slice_cluster(n_slices=3)
    d0 = cluster.devices[1]
    derated = cluster.with_derate({1: 0.5})
    # clone: original untouched, same indices, speed halved, memory kept
    assert cluster.devices[1] is d0
    assert derated.devices[1].peak_flops == pytest.approx(d0.peak_flops * 0.5)
    assert derated.devices[1].hbm_bw == pytest.approx(d0.hbm_bw * 0.5)
    assert derated.devices[1].mem_bytes == d0.mem_bytes
    assert derated.devices[0].peak_flops == cluster.devices[0].peak_flops
    assert derated.k == cluster.k
    np.testing.assert_array_equal(derated.link_bw, cluster.link_bw)
    # a flops-bound op takes 2x as long on the half-speed device
    from repro.core.graph import OpNode

    node = OpNode(id=0, op_type="matmul", flops=1e12, bytes_accessed=1e9)
    t_nom = CostModel(cluster).compute_time(node, 1)
    t_der = CostModel(derated).compute_time(node, 1)
    assert t_der == pytest.approx(t_nom * 2, rel=0.01)
    # identity and validation
    assert cluster.with_derate({}) is cluster
    assert cluster.devices[0].derated(1.0) is cluster.devices[0]
    with pytest.raises(ValueError):
        cluster.with_derate({7: 0.5})
    with pytest.raises(ValueError):
        cluster.devices[0].derated(0.0)
    with pytest.raises(ValueError):
        cluster.devices[0].derated(float("nan"))


def test_replan_with_derate_shifts_load_off_slow_device():
    """A derate-aware replan must beat the stale plan on the TRUE cluster."""
    cfg = get_config("llama3.2-1b")
    graph = transformer_graph(cfg, seq_len=1024, granularity="block")
    cluster = tpu_slice_cluster(n_slices=4, heterogeneous=True)
    pc = PlanConfig(method="bottleneck_balance", objective="throughput")
    nominal = plan(graph, cluster, pc)
    # device 0 (a fast slice) is secretly running at quarter speed
    truth_cm = CostModel(cluster.with_derate({0: 0.25}))
    adapted = replan(graph, cluster, (), pc, derate={0: 0.25})
    assert adapted.extra["derate"] == {0: 0.25}
    assert adapted.extra["failed_devices"] == []
    assert set(adapted.placement) == set(nominal.placement)
    b_stale = bottleneck_time(graph, nominal.placement, truth_cm)
    b_adapt = bottleneck_time(graph, adapted.placement, truth_cm)
    assert b_adapt < b_stale
    # derates for failed devices are dropped; survivors keep original indices
    both = replan(graph, cluster, [1], pc, derate={0: 0.5, 1: 0.5})
    assert 1 not in set(both.placement.values())
    assert both.extra["derate"] == {0: 0.5}


# ---------------------------------------------------------------------------
# DerateCalibrator (core.costmodel)
# ---------------------------------------------------------------------------


def test_calibrator_attributes_ratios_per_op_class():
    cal = DerateCalibrator()
    cal.add_stage_sample(0, 2.0, {"matmul": 1.0})
    cal.add_stage_sample(0, 8.0, {"softmax": 1.0})
    cal.add_stage_sample(1, 1.0, {"matmul": 3.0, "softmax": 1.0})
    assert cal.op_class_ratios(0) == {
        "matmul": pytest.approx(2.0), "softmax": pytest.approx(8.0)
    }
    # device ratio = weighted log-space mean = sqrt(2*8) = 4
    assert cal.device_ratios()[0] == pytest.approx(4.0)
    assert cal.device_ratios()[1] == pytest.approx(1.0)
    # garbage in, nothing out
    cal.add_stage_sample(2, float("nan"), {"matmul": 1.0})
    cal.add_stage_sample(2, -1.0, {"matmul": 1.0})
    assert 2 not in cal.device_ratios()
    # zero/empty weights fall back to a default bucket, not a crash
    cal.add_stage_sample(3, 2.0, {})
    assert cal.device_ratios()[3] == pytest.approx(2.0)


# ---------------------------------------------------------------------------
# DeratePolicy: convergence, stability, recovery
# ---------------------------------------------------------------------------


def _closed_loop(policy, truth, device=0, windows=30):
    """Emulate the engine loop: after each committed derate the cost model
    is rebuilt, so the observed ratio is current_factor / truth_factor."""
    replans = 0
    for _ in range(windows):
        ratio = policy.factor(device) / truth
        if policy.observe({device: ratio}) is not None:
            replans += 1
    return replans


def test_policy_converges_on_synthetic_2x_straggler():
    policy = DeratePolicy(AdaptationConfig(confirm_windows=2, smoothing=0.7))
    replans = _closed_loop(policy, truth=0.5)
    # converged to the true speed in one committed derate, then silent
    assert policy.factor(0) == pytest.approx(0.5, rel=1e-6)
    assert replans == 1
    assert policy.derate_map() == {0: pytest.approx(0.5, rel=1e-6)}
    actions = [e.action for e in policy.events]
    assert actions.count("derate") == 1 and actions.count("replan") == 1


def test_policy_converges_under_noise_without_oscillating():
    rng = np.random.default_rng(0)
    policy = DeratePolicy(AdaptationConfig(confirm_windows=2, smoothing=0.5))
    replans = 0
    for _ in range(60):
        noise = float(rng.uniform(0.9, 1.1))
        ratio = policy.factor(0) / 0.5 * noise
        if policy.observe({0: ratio}) is not None:
            replans += 1
    # lands near the true factor and stops replanning (hysteresis deadband)
    assert 0.4 < policy.factor(0) < 0.62
    assert replans <= 3


def test_policy_ignores_in_band_noise():
    """Ratios oscillating inside the trigger band never cause any action."""
    rng = np.random.default_rng(1)
    policy = DeratePolicy(AdaptationConfig())
    for _ in range(50):
        assert policy.observe({0: float(rng.uniform(0.85, 1.35)),
                               1: float(rng.uniform(0.85, 1.35))}) is None
    assert policy.factors == {}
    assert policy.events == []


def test_policy_transient_spikes_reset_streak():
    """A spike must persist confirm_windows consecutive windows to act."""
    policy = DeratePolicy(AdaptationConfig(confirm_windows=3))
    for _ in range(10):  # spike, recover, spike, recover…
        assert policy.observe({0: 4.0}) is None
        assert policy.observe({0: 1.0}) is None
    assert policy.factors == {}


def test_policy_underates_on_recovery():
    policy = DeratePolicy(AdaptationConfig(confirm_windows=2, smoothing=1.0))
    _closed_loop(policy, truth=0.5, windows=5)
    assert policy.factor(0) == pytest.approx(0.5, rel=1e-6)
    # the device recovers to nominal speed: observed ratio halves
    out = None
    for _ in range(5):
        ratio = policy.factor(0) / 1.0
        out = policy.observe({0: ratio})
        if out is not None:
            break
    assert out == {}  # fully un-derated: no device below nominal
    assert policy.factor(0) == pytest.approx(1.0)
    assert any(e.action == "underate" for e in policy.events)
    # and it stays quiet at nominal
    assert _closed_loop(policy, truth=1.0, windows=10) == 0


def test_policy_hold_inside_hysteresis_deadband():
    policy = DeratePolicy(AdaptationConfig(
        trigger_ratio=1.3, hysteresis=0.6, confirm_windows=1, smoothing=1.0))
    assert policy.observe({0: 1.4}) is None
    assert policy.factors == {}
    assert [e.action for e in policy.events] == ["hold"]


def test_policy_respects_min_derate_floor():
    policy = DeratePolicy(AdaptationConfig(
        confirm_windows=1, smoothing=1.0, min_derate=0.2))
    policy.observe({0: 100.0})
    assert policy.factor(0) == pytest.approx(0.2)


def test_policy_recovery_never_lowers_the_factor():
    """A transient unconfirmed spike pollutes the EMA; a confirmed recovery
    right after must still move the factor UP (direction clamp)."""
    policy = DeratePolicy(AdaptationConfig(
        confirm_windows=2, recover_windows=2, smoothing=0.2))
    _closed_loop(policy, truth=0.5, windows=10)
    before = policy.factor(0)
    assert before == pytest.approx(0.5, rel=0.05)
    policy.observe({0: 40.0})      # one spike window — streak not confirmed
    policy.observe({0: 0.75})      # genuine recovery evidence…
    policy.observe({0: 0.75})      # …confirmed
    assert policy.factor(0) >= before
    for e in policy.events:
        if e.action == "underate":
            assert e.new_factor >= e.old_factor
        if e.action == "derate":
            assert e.new_factor <= e.old_factor


def test_adaptation_config_validation():
    with pytest.raises(ValueError):
        AdaptationConfig(trigger_ratio=0.9)
    with pytest.raises(ValueError):
        AdaptationConfig(recover_ratio=1.2)
    with pytest.raises(ValueError):
        AdaptationConfig(smoothing=0.0)
    with pytest.raises(ValueError):
        AdaptationConfig(confirm_windows=0)
    with pytest.raises(ValueError):
        AdaptationConfig(min_samples=0)
    # auto windows shorter than the evidence filter would silently never act
    with pytest.raises(ValueError):
        AdaptationConfig(window_steps=2, min_samples=4)
    AdaptationConfig(window_steps=4, min_samples=4)  # boundary is fine


# ---------------------------------------------------------------------------
# engine: the closed loop end to end (synthetic observations)
# ---------------------------------------------------------------------------


def _compute_bound_cluster(k=2):
    """Weak devices + fat links: stage time is roofline-dominated, so a
    peak_flops/hbm_bw derate scales observed stage time almost exactly (on
    the real TPU presets the smoke model's microsecond ops drown in
    dispatch overhead, which derating deliberately does NOT scale)."""
    devs = [
        DeviceSpec(f"d{i}", peak_flops=1e9, mem_bytes=64e9, hbm_bw=1e9)
        for i in range(k)
    ]
    bw = np.full((k, k), 1e12)
    np.fill_diagonal(bw, 0.0)
    return ClusterSpec(devs, bw, name="compute-bound")


def _window(preds, devs, slow_dev, factor, n=5):
    """Observed stage times: nominal predictions with one device slowed."""
    return [
        [preds[i] * (factor if devs[i] == slow_dev else 1.0)] * n
        for i in range(len(preds))
    ]


def test_engine_closes_derate_loop_and_recovers(small_model):
    cfg, model, params = small_model
    cluster = _compute_bound_cluster(2)
    # one physical CPU, but DISTINCT sharding objects per Moirai device so
    # the executor keeps the planner's stage splits (stage breaks compare
    # device identity)
    cpu = jax.devices()[0]
    fakes = [jax.sharding.SingleDeviceSharding(cpu) for _ in range(2)]
    eng = ServingEngine(
        cfg, params, cluster, slots=1, max_len=64, devices=fakes,
        plan_cfg=PlanConfig(method="round_robin", coarsen=False), eos_id=-1,
        adapt=AdaptationConfig(confirm_windows=2, smoothing=1.0),
    )
    devs = eng._stage_devices()
    assert set(devs) == {0, 1}  # round robin spreads stages over both slices
    pred0 = list(eng._pred_stage_s)

    # --- device 1 is secretly 3x slower than the nominal model -----------
    out1 = eng.observe_window(observed=_window(pred0, devs, 1, 3.0))
    assert not out1["replanned"] and eng.derate == {}
    out2 = eng.observe_window(observed=_window(pred0, devs, 1, 3.0))
    assert out2["replanned"]
    assert eng.derate[1] == pytest.approx(1 / 3.0, rel=0.02)
    assert eng.placement_result.extra["derate"] == eng.derate
    assert eng.replan_history[-1]["reason"] == "adaptive derate"
    # cost model now tracks the derate: slowed stages' predictions tripled,
    # so the SAME true behavior reads as on-model → converged, no churn
    assert eng._stage_devices() == devs  # round robin is deterministic
    for i, d in enumerate(devs):
        exp = pred0[i] * (3.0 if d == 1 else 1.0)
        assert eng._pred_stage_s[i] == pytest.approx(exp, rel=0.05)
    for _ in range(3):
        out = eng.observe_window(observed=_window(pred0, devs, 1, 3.0))
        assert not out["replanned"]
    assert len(eng.replan_history) == 1

    # --- device 1 recovers: observed back at nominal ---------------------
    replans = 0
    for _ in range(10):
        out = eng.observe_window(observed=_window(pred0, devs, 1, 1.0))
        replans += out["replanned"]
        if not eng.derate:
            break
    assert eng.derate == {} and replans >= 1
    assert eng._pred_stage_s == pytest.approx(pred0, rel=0.05)
    assert any(e.action == "underate" for e in eng.adaptation_events)
    # healthy device 0 was never spuriously derated by the recovery epoch
    assert all(e.device != 0 for e in eng.adaptation_events
               if e.action in ("derate", "underate"))

    # the engine still serves correctly after both hot-swaps
    req = Request(rid=0, prompt=[1, 2, 3], max_new_tokens=3)
    eng.submit(req)
    eng.run_until_drained()
    assert req.done and len(req.out_tokens) == 3


def test_engine_two_stage_recovery_does_not_ping_pong(small_model):
    """With exactly one observable stage per device, a recovering derated
    device must NOT become the healthy device's fleet baseline — that would
    derate the healthy device and ping-pong the derate map forever."""
    cfg, model, params = small_model
    cluster = _compute_bound_cluster(2)
    cpu = jax.devices()[0]
    fakes = [jax.sharding.SingleDeviceSharding(cpu) for _ in range(2)]
    eng = ServingEngine(
        cfg, params, cluster, slots=1, max_len=64, devices=fakes,
        plan_cfg=PlanConfig(method="round_robin", coarsen=False), eos_id=-1,
        adapt=AdaptationConfig(confirm_windows=2, smoothing=1.0),
    )
    devs = eng._stage_devices()
    pred0 = list(eng._pred_stage_s)

    def window(ratio_by_dev):
        # exactly ONE stage per device carries >= min_samples samples; the
        # rest are under-sampled and filtered — the 2-stage contiguous case
        out, seen = [], set()
        for i in range(len(pred0)):
            t = pred0[i] * ratio_by_dev.get(devs[i], 1.0)
            out.append([t] * (5 if devs[i] not in seen else 1))
            seen.add(devs[i])
        return out

    # dev1 slows 3x -> derated
    for _ in range(2):
        eng.observe_window(observed=window({1: 3.0}))
    assert eng.derate.get(1, 1.0) == pytest.approx(1 / 3.0, rel=0.02)
    # dev1 recovers; drive nominal-truth windows until fully un-derated
    for _ in range(6):
        eng.observe_window(observed=window({}))
        if not eng.derate:
            break
    assert eng.derate == {}
    # …and STAYS converged: no ping-pong replans, dev0 never touched
    replans_before = len(eng.replan_history)
    for _ in range(6):
        out = eng.observe_window(observed=window({}))
        assert not out["replanned"]
    assert len(eng.replan_history) == replans_before
    assert all(e.device != 0 for e in eng.adaptation_events
               if e.action in ("derate", "underate"))


def test_engine_derates_device_hosting_majority_of_stages(small_model):
    """Leave-DEVICE-out baseline: a slow device hosting most observable
    stages must not inflate its own fleet baseline and dodge the derate."""
    cfg, model, params = small_model
    cluster = _compute_bound_cluster(2)
    cpu = jax.devices()[0]
    fakes = [jax.sharding.SingleDeviceSharding(cpu) for _ in range(2)]
    eng = ServingEngine(
        cfg, params, cluster, slots=1, max_len=64, devices=fakes,
        plan_cfg=PlanConfig(method="round_robin", coarsen=False), eos_id=-1,
        adapt=AdaptationConfig(confirm_windows=2, smoothing=1.0),
    )
    devs = eng._stage_devices()
    pred0 = list(eng._pred_stage_s)
    # observable stages: both dev-0 stages (slow 2x) and ONE dev-1 stage —
    # the slow device owns the majority of the observable fleet
    dev1_seen = False

    def window():
        nonlocal dev1_seen
        dev1_seen = False
        out = []
        for i in range(len(pred0)):
            if devs[i] == 0:
                out.append([pred0[i] * 2.0] * 5)
            elif not dev1_seen:
                dev1_seen = True
                out.append([pred0[i]] * 5)
            else:
                out.append([pred0[i]])  # under-sampled → filtered
        return out

    for _ in range(2):
        eng.observe_window(observed=window())
    assert eng.derate.get(0, 1.0) == pytest.approx(0.5, rel=0.02)


def test_engine_hot_swap_resumes_in_flight_requests(small_model):
    """A mid-generation replan re-queues active requests; greedy decode
    resumes from prompt+generated and produces the identical output."""
    cfg, model, params = small_model
    cluster = tpu_slice_cluster(n_slices=1)
    mk = lambda: ServingEngine(cfg, params, cluster, slots=1, max_len=64,
                               plan_cfg=PlanConfig(method="round_robin"),
                               eos_id=-1)
    ref_eng = mk()
    ref = Request(rid=0, prompt=[1, 2, 3], max_new_tokens=6)
    ref_eng.submit(ref)
    ref_eng.run_until_drained()
    assert ref.done and len(ref.out_tokens) == 6

    eng = mk()
    req = Request(rid=0, prompt=[1, 2, 3], max_new_tokens=6)
    eng.submit(req)
    eng.step()
    eng.step()
    assert 0 < len(req.out_tokens) < 6
    eng.derate = {0: 0.5}
    eng._replan_and_rebuild(reason="test swap")  # hot-swap mid-flight
    assert all(r is None for r in eng.active) and eng.queue == [req]
    done = eng.run_until_drained()
    assert req.done and req.out_tokens == ref.out_tokens
    assert done == [req]  # drained requests are returned to the caller

    # swap landing ONE token short of budget: the re-prefill token itself
    # finishes the request — it must retire at exactly max_new_tokens
    eng2 = mk()
    req2 = Request(rid=1, prompt=[1, 2, 3], max_new_tokens=6)
    eng2.submit(req2)
    for _ in range(5):  # prefill (1 token) + 4 decode tokens = 5 of 6
        eng2.step()
    assert len(req2.out_tokens) == 5
    eng2.derate = {0: 0.5}
    eng2._replan_and_rebuild(reason="test swap")
    eng2.run_until_drained()
    assert req2.done and len(req2.out_tokens) == 6
    assert req2.out_tokens == ref.out_tokens


def test_engine_mixed_depth_ragged_admits(small_model):
    """Every slot carries its own cache position, so a request whose depth
    differs from the active batch is admitted IMMEDIATELY mid-flight, and
    both requests match their solo runs."""
    cfg, model, params = small_model
    cluster = tpu_slice_cluster(n_slices=1)
    mk = lambda slots: ServingEngine(
        cfg, params, cluster, slots=slots, max_len=64,
        plan_cfg=PlanConfig(method="etf"), eos_id=-1)
    solo = {}
    for rid, prompt in ((0, [1, 2, 3]), (1, [7, 8])):
        e = mk(1)
        r = Request(rid=rid, prompt=list(prompt), max_new_tokens=5)
        e.submit(r)
        e.run_until_drained()
        solo[rid] = r.out_tokens

    eng = mk(2)
    r0 = Request(rid=0, prompt=[1, 2, 3], max_new_tokens=5)
    r1 = Request(rid=1, prompt=[7, 8], max_new_tokens=5)
    eng.submit(r0)
    eng.step()                      # r0 admitted and decoding
    eng.submit(r1)                  # different depth — admitted anyway
    assert eng.step() == 2 and eng.active.count(None) == 0
    eng.run_until_drained()
    assert r0.out_tokens == solo[0]
    assert r1.out_tokens == solo[1]


def test_engine_auto_windows_and_drain(small_model):
    cfg, model, params = small_model
    cluster = tpu_slice_cluster(n_slices=1)
    eng = ServingEngine(
        cfg, params, cluster, slots=2, max_len=64,
        plan_cfg=PlanConfig(method="etf"), eos_id=-1,
        adapt=AdaptationConfig(window_steps=4),
    )
    for i in range(3):
        eng.submit(Request(rid=i, prompt=[1, 2], max_new_tokens=12))
    done = eng.run_until_drained()
    assert sorted(r.rid for r in done) == [0, 1, 2]
    # windows closed automatically during serving; nothing derated (the
    # single real device IS the fleet baseline — no relative evidence)
    assert eng.policy.windows >= 2
    assert eng.derate == {}
    # the whole-run report survives window draining: it must cover more
    # samples than the executor still holds since the last drain
    rep = eng.straggler_report()
    assert rep["stages"][0]["n"] > len(eng.executor.stage_times()[0])
    # stage_times returns copies: external mutation cannot corrupt windows
    snap = eng.executor.stage_times()
    n0 = len(snap[0])
    snap[0].clear()
    assert len(eng.executor.stage_times()[0]) == n0
    # window drain consumes samples exactly once
    w = eng._drain_window()
    assert eng._drain_window() == [[] for _ in w]


def test_engine_failure_keeps_derates_on_survivors(small_model):
    cfg, model, params = small_model
    cluster = tpu_slice_cluster(n_slices=3, heterogeneous=True)
    eng = ServingEngine(cfg, params, cluster, slots=1, max_len=64,
                        plan_cfg=PlanConfig(method="etf"), eos_id=-1)
    eng.derate = {0: 0.5, 1: 0.5}
    eng.policy.factors = {0: 0.5, 1: 0.5}
    eng._replan_and_rebuild(reason="test derate")
    eng.on_device_failure(1)
    # the dead device's derate is dropped — from the engine AND the policy,
    # so a later policy commit cannot resurrect it
    assert eng.derate == {0: 0.5}
    assert eng.policy.factors == {0: 0.5}
    assert eng.placement_result.extra["derate"] == {0: 0.5}
    assert 1 not in set(eng.placement_result.placement.values())
    req = Request(rid=0, prompt=[4, 5], max_new_tokens=2)
    eng.submit(req)
    eng.run_until_drained()
    assert req.done


# ---------------------------------------------------------------------------
# KV-aware admission
# ---------------------------------------------------------------------------


def _tight_cluster(cfg, max_len, kv_copies):
    """One device whose memory fits the weights plus ``kv_copies`` KV caches
    (fractional copies give headroom below the next integer)."""
    g = transformer_graph(cfg, seq_len=max_len, granularity="block")
    params = sum(n.param_bytes for n in g.nodes.values())
    kv = sum(n.kv_bytes for n in g.nodes.values())
    assert kv > 0
    dev = DeviceSpec("tight", peak_flops=1e12, mem_bytes=params + kv_copies * kv,
                     hbm_bw=1e11)
    return ClusterSpec([dev], link_bw=np.zeros((1, 1)))


def test_kv_admission_caps_concurrency(small_model):
    cfg, model, params = small_model
    cluster = _tight_cluster(cfg, 64, kv_copies=2.5)  # 2 slots fit, 3 don't
    eng = ServingEngine(cfg, params, cluster, slots=3, max_len=64,
                        plan_cfg=PlanConfig(method="round_robin"), eos_id=-1)
    reqs = [Request(rid=i, prompt=[1, 2], max_new_tokens=6) for i in range(4)]
    for r in reqs:
        eng.submit(r)
    max_active = 0
    for _ in range(200):
        n = eng.step()
        max_active = max(max_active, n)
        if n == 0 and not eng.queue:
            break
    assert max_active == 2  # queued, not admitted into the 3rd slot
    assert all(r.done and not r.rejected for r in reqs)
    assert all(len(r.out_tokens) == 6 for r in reqs)


def test_kv_admission_reject_mode(small_model):
    cfg, model, params = small_model
    cluster = _tight_cluster(cfg, 64, kv_copies=2.5)
    eng = ServingEngine(cfg, params, cluster, slots=3, max_len=64,
                        plan_cfg=PlanConfig(method="round_robin"), eos_id=-1,
                        admission="reject")
    reqs = [Request(rid=i, prompt=[1, 2], max_new_tokens=4) for i in range(3)]
    for r in reqs:
        eng.submit(r)
    eng.run_until_drained()
    assert [r.rejected for r in reqs] == [False, False, True]
    assert reqs[2].out_tokens == []
    assert all(r.done for r in reqs)


def test_kv_admission_reject_never_discards_resumed_requests(small_model):
    """A re-queued (already-admitted-once) request carries generated
    tokens; reject mode must queue it, not throw away half-served work."""
    cfg, model, params = small_model
    cluster = _tight_cluster(cfg, 64, kv_copies=1.5)  # only 1 sequence fits
    eng = ServingEngine(cfg, params, cluster, slots=2, max_len=64,
                        plan_cfg=PlanConfig(method="round_robin"), eos_id=-1,
                        admission="reject")
    r0 = Request(rid=0, prompt=[1, 2], max_new_tokens=6)
    resumed = Request(rid=1, prompt=[1, 2], out_tokens=[5], max_new_tokens=6)
    eng.submit(r0)
    eng.submit(resumed)
    eng.step()  # r0 admitted via zero-active bypass; capacity now exhausted
    assert not resumed.rejected and resumed in eng.queue
    done = eng.run_until_drained()
    assert resumed.done and not resumed.rejected
    assert len(resumed.out_tokens) == 6  # resumed from its 1 kept token
    assert {r.rid for r in done} | {r0.rid} == {0, 1}


def test_kv_admission_never_livelocks_single_request(small_model):
    """If even ONE sequence overflows the planned devices, serve it
    best-effort instead of holding it forever."""
    cfg, model, params = small_model
    cluster = _tight_cluster(cfg, 64, kv_copies=0.5)  # not even 1 copy fits
    eng = ServingEngine(cfg, params, cluster, slots=2, max_len=64,
                        plan_cfg=PlanConfig(method="round_robin"), eos_id=-1)
    req = Request(rid=0, prompt=[1, 2], max_new_tokens=3)
    eng.submit(req)
    eng.run_until_drained()
    assert req.done and len(req.out_tokens) == 3
