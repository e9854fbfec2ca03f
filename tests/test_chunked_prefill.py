"""Chunked prefill into the live ragged batch (ISSUE 5).

Covers the tentpole and its satellites:

* chunked-vs-whole prefill greedy token identity — at the model level
  across architectures (gemma2 sliding windows, mamba2, zamba2, enc-dec,
  pallas/chunked/naive attention impls) and at the engine level;
* the engine's chunked prefill — no-starvation (active slots decode in
  every step while a prompt streams in), a chunk size that is not a
  positive int refused, hot-swap re-queue through the chunked path;
* prefill visibility in the scoring stack — `simulate_pipeline(prompt_len,
  prefill_chunk)` with `validate_pipeline_schedule`'s prefill-task checks,
  `bottleneck_time`/MILP busy accumulators, `PlanConfig.prompt_len`;
* observation-window hygiene — prefill samples tagged and excluded from
  the derate calibrator, batch-aware stage predictions;
* oversized-prompt validation at enqueue (truncate-with-flag / reject);
* the ``BENCH_*.json`` schema check in ``benchmarks/common.py``.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.costmodel import CostModel
from repro.core.devices import inter_server_cluster, tpu_slice_cluster
from repro.core.graph import chain_graph
from repro.core.modelgraph import transformer_graph
from repro.core.placement import PlanConfig, plan
from repro.core.simulate import (
    bottleneck_time,
    prefill_chunk_sizes,
    scale_node_to_tokens,
    simulate_pipeline,
    validate_pipeline_schedule,
)
from repro.models.model import build_model
from repro.serving.adaptation import AdaptationConfig
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
    kw.setdefault("max_len", 64)
    return ServingEngine(cfg, params, cluster, slots=slots, **kw)


# ----------------------------------------------------------------------
# model level: chunked == whole prefill (greedy token identity)
# ----------------------------------------------------------------------


def _greedy(model, params, batch, max_len, steps, *, chunked, chunk):
    if chunked:
        logits, caches = model.prefill_chunked(params, batch, max_len, chunk=chunk)
    else:
        logits, caches = model.prefill(params, batch, max_len)
    toks = [int(jnp.argmax(logits[0]))]
    pos = batch["tokens"].shape[1]
    for _ in range(steps - 1):
        t = jnp.asarray([[toks[-1]]], jnp.int32)
        logits, caches = model.decode_step(
            params, {"tokens": t}, caches, jnp.asarray(pos, jnp.int32)
        )
        toks.append(int(jnp.argmax(logits[0])))
        pos += 1
    return toks


@pytest.mark.parametrize("chunk", [1, 3, 5, 16])
def test_chunked_prefill_token_identity_dense(small_model, chunk):
    """Any chunk size (1-token steps, uneven tails, chunk > prompt) yields
    the whole-prompt greedy tokens bit-for-bit."""
    cfg, model, params = small_model
    rng = np.random.default_rng(7)
    batch = {"tokens": jnp.asarray([rng.integers(1, 200, size=11).tolist()], jnp.int32)}
    whole = _greedy(model, params, batch, 32, 4, chunked=False, chunk=chunk)
    ch = _greedy(model, params, batch, 32, 4, chunked=True, chunk=chunk)
    assert ch == whole


@pytest.mark.slow
@pytest.mark.parametrize(
    "arch",
    ["gemma2-27b", "mamba2-130m", "zamba2-2.7b", "qwen3-14b"],
)
def test_chunked_prefill_token_identity_across_archs(arch):
    """Sliding-window (gemma2), pure-SSM (mamba2: recurrent state + conv
    tails across chunk boundaries), hybrid (zamba2), and qk-norm dense all
    match their whole-prompt prefill."""
    cfg = get_config(arch).smoke()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray([rng.integers(1, 100, size=11).tolist()], jnp.int32)}
    whole = _greedy(model, params, batch, 32, 4, chunked=False, chunk=4)
    ch = _greedy(model, params, batch, 32, 4, chunked=True, chunk=4)
    assert ch == whole, (arch, ch, whole)


@pytest.mark.slow
@pytest.mark.parametrize("impl", ["naive", "chunked", "pallas"])
def test_chunked_prefill_token_identity_attention_impls(small_model, impl):
    """All three attention implementations agree chunk-for-chunk (the pallas
    kernel takes the chunk's start offset through its q_pos operand)."""
    cfg, _, params = small_model
    icfg = dataclasses.replace(cfg, attention_impl=impl)
    model = build_model(icfg)
    rng = np.random.default_rng(3)
    batch = {"tokens": jnp.asarray([rng.integers(1, 200, size=9).tolist()], jnp.int32)}
    whole = _greedy(model, params, batch, 32, 4, chunked=False, chunk=4)
    ch = _greedy(model, params, batch, 32, 4, chunked=True, chunk=4)
    assert ch == whole


@pytest.mark.slow
def test_chunked_prefill_token_identity_encdec():
    """Enc-dec: encoder + cross-KV run once, decoder prompt chunked."""
    cfg = get_config("seamless-m4t-large-v2").smoke()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    rng = np.random.default_rng(1)
    frames = jnp.asarray(rng.normal(size=(1, 4, cfg.d_model)), jnp.float32)
    toks = jnp.asarray([rng.integers(1, 100, size=9).tolist()], jnp.int32)
    batch = {"frames": frames, "tokens": toks}
    whole = _greedy(model, params, batch, 32, 4, chunked=False, chunk=4)
    ch = _greedy(model, params, batch, 32, 4, chunked=True, chunk=4)
    assert ch == whole


# ----------------------------------------------------------------------
# engine: chunked prefill
# ----------------------------------------------------------------------


def test_engine_chunked_prefill_no_starvation(small_model):
    """Active slots decode in EVERY step: while a long prompt streams in,
    chunk by chunk, the co-resident request gains one token per step."""
    cfg, model, params = small_model
    eng = _mk_engine(cfg, params, slots=2, prefill_chunk=4)
    short = Request(rid=0, prompt=[1, 2], max_new_tokens=30)
    eng.submit(short)
    eng.step()                     # short admitted (single chunk) + decoding
    assert len(short.out_tokens) >= 1
    long_prompt = [int(t) for t in np.random.default_rng(0).integers(1, 200, 25)]
    long_r = Request(rid=1, prompt=long_prompt, max_new_tokens=4)
    eng.submit(long_r)
    chunks_needed = len(prefill_chunk_sizes(25, 4))
    saw_prefill_steps = 0
    for _ in range(chunks_needed):
        before = len(short.out_tokens)
        eng.step()
        if 1 in eng._prefill_toks or long_r.out_tokens == []:
            saw_prefill_steps += 1
        # the short request NEVER stalls while the long prompt prefills
        assert len(short.out_tokens) == before + 1
    assert saw_prefill_steps >= chunks_needed - 1
    eng.run_until_drained()
    assert long_r.done and len(long_r.out_tokens) == 4


@pytest.mark.parametrize("chunk", [None, 0, -8])
def test_engine_refuses_a_prefill_chunk_that_is_not_positive(small_model, chunk):
    """The one step streams prompts in chunks: there is no whole-prompt
    mode for ``None`` or ``0`` to select."""
    cfg, model, params = small_model
    with pytest.raises(ValueError, match="positive int"):
        _mk_engine(cfg, params, slots=2, prefill_chunk=chunk)
    with pytest.raises(ValueError, match="positive int"):
        _mk_engine(cfg, params, slots=2,
                   plan_cfg=PlanConfig(method="etf", prefill_chunk=chunk))


def test_engine_hot_swap_requeues_through_chunked_prefill(small_model):
    """A hot-swap mid-generation re-queues requests; they re-prefill
    prompt+generated through the CHUNKED path and resume exactly."""
    cfg, model, params = small_model
    ref_eng = _mk_engine(cfg, params, slots=1, prefill_chunk=4)
    ref = Request(rid=0, prompt=[1, 2, 3, 4, 5], max_new_tokens=6)
    ref_eng.submit(ref)
    ref_eng.run_until_drained()

    eng = _mk_engine(cfg, params, slots=1, prefill_chunk=4,
                     plan_cfg=PlanConfig(method="round_robin"))
    req = Request(rid=0, prompt=[1, 2, 3, 4, 5], max_new_tokens=6)
    eng.submit(req)
    for _ in range(4):
        eng.step()
    assert 0 < len(req.out_tokens) < 6
    eng._replan_and_rebuild(reason="test swap")
    assert eng._prefill_toks == {}          # mid-prefill state cannot survive
    eng.run_until_drained()
    assert req.done and req.out_tokens == ref.out_tokens


# ----------------------------------------------------------------------
# satellite: oversized-prompt validation at enqueue
# ----------------------------------------------------------------------


def test_oversized_prompt_truncate_with_flag(small_model):
    cfg, model, params = small_model
    eng = _mk_engine(cfg, params, slots=2)   # max_len=64
    big = [int(t) for t in np.random.default_rng(1).integers(1, 200, size=90)]
    r = Request(rid=0, prompt=list(big), max_new_tokens=8)
    ok = Request(rid=1, prompt=[5, 6, 7], max_new_tokens=4)
    eng.submit(r)
    eng.submit(ok)
    assert r.truncated and len(r.prompt) == 64 - 8
    assert r.prompt == big[-(64 - 8):]       # newest context kept
    assert not ok.truncated
    eng.run_until_drained()
    assert r.done and len(r.out_tokens) == 8
    assert ok.done and len(ok.out_tokens) == 4
    # ...and the truncated request is equivalent to submitting the tail
    solo = _mk_engine(cfg, params, slots=1)
    r2 = Request(rid=2, prompt=big[-(64 - 8):], max_new_tokens=8)
    solo.submit(r2)
    solo.run_until_drained()
    assert r2.out_tokens == r.out_tokens


def test_oversized_prompt_reject(small_model):
    cfg, model, params = small_model
    eng = _mk_engine(cfg, params, slots=1, oversize="reject")
    big = Request(rid=0, prompt=list(range(1, 80)), max_new_tokens=8)
    eng.submit(big)
    assert big.rejected and big.done and big.out_tokens == []
    assert eng.queue == []
    # a submit-time reject still surfaces in the next drain's return list
    # (same contract as admission-time rejects)
    assert big in eng.run_until_drained()
    # truncation cannot save a generation budget that alone overflows
    eng2 = _mk_engine(cfg, params, slots=1)  # oversize="truncate"
    hopeless = Request(rid=1, prompt=[1, 2], max_new_tokens=70)
    eng2.submit(hopeless)
    assert hopeless.rejected and hopeless.done
    with pytest.raises(ValueError):
        _mk_engine(cfg, params, slots=1, oversize="drop")


# ----------------------------------------------------------------------
# satellite: observation-window hygiene (prefill tagging, batch-aware preds)
# ----------------------------------------------------------------------


def test_prefill_samples_tagged_and_excluded_from_windows(small_model):
    """StageExecutor tags forwards; _drain_window feeds DECODE samples only
    to the calibrator; prefill shows up in the report's own section."""
    cfg, model, params = small_model
    eng = _mk_engine(cfg, params, slots=2, prefill_chunk=4)
    rng = np.random.default_rng(2)
    for i in range(3):
        eng.submit(Request(
            rid=i,
            prompt=[int(t) for t in rng.integers(1, 200, size=20)],
            max_new_tokens=3,
        ))
    eng.run_until_drained()
    pre = eng.executor.stage_times(kind="prefill")
    dec = eng.executor.stage_times(kind="decode")
    both = eng.executor.stage_times()
    assert sum(map(len, pre)) > 0 and sum(map(len, dec)) > 0
    assert [len(a) + len(b) for a, b in zip(pre, dec)] == [len(t) for t in both]
    # the window drain returns ONLY decode samples...
    drained = eng._drain_window()
    assert drained == dec
    # ...and clears everything: prefill samples cannot leak into the NEXT
    # window either (they were preserved in the prefill history)
    assert eng.executor.stage_times() == [[] for _ in both]
    rep = eng.straggler_report()
    assert rep["prefill"]["chunk"] == 4
    assert sum(s["n"] for s in rep["prefill"]["stages"]) == sum(map(len, pre))
    # decode section of the report saw no prefill samples
    assert sum(s["n"] for s in rep["stages"]) == sum(map(len, dec))


def test_long_prompt_burst_commits_no_derate(small_model):
    """Regression for the observation-window pollution bug: a burst of long
    prompts (with auto windows on) must not read as device drift."""
    cfg, model, params = small_model
    eng = _mk_engine(
        cfg, params, slots=2, prefill_chunk=4,
        adapt=AdaptationConfig(window_steps=4, min_samples=1,
                               confirm_windows=1, smoothing=1.0),
    )
    rng = np.random.default_rng(5)
    for i in range(4):
        eng.submit(Request(
            rid=i,
            prompt=[int(t) for t in rng.integers(1, 200, size=30)],
            max_new_tokens=6,
        ))
    eng.run_until_drained()
    assert eng.policy.windows >= 1
    assert eng.derate == {}
    assert all(e.action not in ("derate", "underate")
               for e in eng.adaptation_events)


def test_stage_predictions_use_live_decode_batch(small_model):
    """Satellite: _predict_stage_times / _stage_class_weights run at the
    engine's real decode batch (slots), whole-batch cost — not batch-1."""
    cfg, model, params = small_model
    eng = _mk_engine(cfg, params, slots=4)
    pl = eng.placement_result.placement
    for si, st in enumerate(eng.executor.stages):
        expected = sum(
            4 * eng._cost.compute_time(eng.graph.nodes[n], pl[n], batch=4)
            for n in st.node_ids
        )
        if si > 0:
            prev = eng.executor.stages[si - 1].node_ids[-1]
            expected += eng._cost.comm_time(
                eng.graph.nodes[prev].output_bytes * 4, pl[prev],
                pl[st.node_ids[0]],
            )
        assert eng._pred_stage_s[si] == pytest.approx(expected)
    # a batch-sensitive stage really differs from the batch-1 prediction
    batch1 = [
        sum(eng._cost.compute_time(eng.graph.nodes[n], pl[n])
            for n in st.node_ids)
        for st in eng.executor.stages
    ]
    assert any(
        p != pytest.approx(b) for p, b in zip(eng._pred_stage_s, batch1)
    )
    # slots=1 engines keep the original batch-1 predictions bit-for-bit
    eng1 = _mk_engine(cfg, params, slots=1)
    pl1 = eng1.placement_result.placement
    for si, st in enumerate(eng1.executor.stages):
        expected = sum(
            eng1._cost.compute_time(eng1.graph.nodes[n], pl1[n])
            for n in st.node_ids
        )
        if si > 0:
            prev = eng1.executor.stages[si - 1].node_ids[-1]
            expected += eng1._cost.comm_time(
                eng1.graph.nodes[prev].output_bytes, pl1[prev],
                pl1[st.node_ids[0]],
            )
        assert eng1._pred_stage_s[si] == pytest.approx(expected)


# ----------------------------------------------------------------------
# simulator + cost model: prefill-aware scoring
# ----------------------------------------------------------------------


def _block_graph(seq_len=256):
    cfg = get_config("llama3.2-1b")
    return transformer_graph(cfg, seq_len=seq_len, granularity="block")


def test_simulate_pipeline_prompt_len_zero_is_regression_free():
    """prompt_len=0 (and None) reproduce the decode-only simulation exactly
    — same makespan, completions, and schedule records."""
    g = _block_graph()
    cl = inter_server_cluster()
    cm = CostModel(cl)
    pl = {nid: i % cl.k for i, nid in enumerate(g.topo_order())}
    base = simulate_pipeline(g, pl, cm, 6, 1e-4, max_in_flight=2)
    for spec in (0, None, [0] * 6):
        r = simulate_pipeline(g, pl, cm, 6, 1e-4, max_in_flight=2, prompt_len=spec)
        assert r.makespan == base.makespan
        assert r.completions == base.completions
        assert set(r.schedule) == set(base.schedule)
        assert all(
            r.schedule[k].start == base.schedule[k].start
            and r.schedule[k].end == base.schedule[k].end
            for k in base.schedule
        )
        assert r.prompt_chunks == [[]] * 6


def test_simulate_pipeline_prefill_tasks_validated():
    """Chunked prefill rounds are real tasks on shared resources: the
    extended validate_pipeline_schedule accepts them (per-round precedence,
    strict chunk ordering, decode-after-prefill) and throughput drops under
    prompt load."""
    g = _block_graph()
    cl = inter_server_cluster()
    cm = CostModel(cl)
    pl = {nid: i % cl.k for i, nid in enumerate(g.topo_order())}
    res = simulate_pipeline(
        g, pl, cm, 5, max_in_flight=2,
        prompt_len=[0, 16, 100, 64, 130], prefill_chunk=64,
    )
    assert res.prompt_chunks == [[], [16], [64, 36], [64], [64, 64, 2]]
    validate_pipeline_schedule(g, pl, cm, res)
    kinds = {r.kind for r in res.schedule.values()}
    assert "prefill-op" in kinds and "op" in kinds
    base = simulate_pipeline(g, pl, cm, 5, max_in_flight=2)
    assert res.makespan > base.makespan
    assert res.steady_throughput < base.steady_throughput
    # uniform prompts over two slots compose with admit-on-retire
    even = simulate_pipeline(
        g, pl, cm, 5, max_in_flight=2, prompt_len=64, prefill_chunk=32,
    )
    validate_pipeline_schedule(g, pl, cm, even)

    with pytest.raises(ValueError):
        simulate_pipeline(g, pl, cm, 3, prompt_len=[1, 2])     # wrong arity
    with pytest.raises(ValueError):
        simulate_pipeline(g, pl, cm, 3, prompt_len=-1)
    with pytest.raises(ValueError):
        # graphs without a token axis cannot be prefill-scored
        gg = chain_graph(["matmul"] * 3, flops=1e9, output_bytes=1e4)
        ppl = {nid: 0 for nid in gg.nodes}
        simulate_pipeline(gg, ppl, cm, 2, prompt_len=8)


def test_prefill_chunk_sizes_and_node_scaling():
    assert prefill_chunk_sizes(0, 64) == []
    assert prefill_chunk_sizes(130, 64) == [64, 64, 2]
    assert prefill_chunk_sizes(50, None) == [50]
    with pytest.raises(ValueError):
        prefill_chunk_sizes(10, -1)
    g = _block_graph(seq_len=256)
    node = next(n for n in g.nodes.values() if n.op_type == "block")
    half = scale_node_to_tokens(node, 128, 256)
    # attention's quadratic share (meta["quad_flops"]) scales queries × keys
    # — (1/2)² for a standalone half-length pass — the rest linearly
    quad = node.meta["quad_flops"]
    assert quad > 0
    assert half.flops == pytest.approx((node.flops - quad) / 2 + quad / 4)
    assert half.param_bytes == node.param_bytes           # weights unchanged
    act = node.bytes_accessed - node.param_bytes
    assert half.bytes_accessed == pytest.approx(node.param_bytes + act / 2)
    assert half.output_bytes == pytest.approx(node.output_bytes / 2)
    # with the KV context pinned to the full span (a late chunk attending the
    # whole cache) the quadratic share scales (1/2)·(1) instead of (1/2)²
    late = scale_node_to_tokens(node, 128, 256, context_tokens=256)
    assert late.flops == pytest.approx((node.flops - quad) / 2 + quad / 2)


def test_bottleneck_time_sees_prefill_work():
    g = _block_graph()
    cl = inter_server_cluster()
    cm = CostModel(cl)
    pl = {nid: i % cl.k for i, nid in enumerate(g.topo_order())}
    b0 = bottleneck_time(g, pl, cm)
    b_whole = bottleneck_time(g, pl, cm, prompt_len=512, prefill_chunk=None)
    b_chunk = bottleneck_time(g, pl, cm, prompt_len=512, prefill_chunk=64)
    assert b_whole > b0
    assert b_chunk > b0
    # chunking re-streams the weights once per chunk but SAVES quadratic
    # attention work (chunk i attends only its causal prefix, vs one
    # whole-prompt pass paying the full span² score term): at 512 prompt
    # tokens on this model the quadratic savings win, so the two costings
    # differ and chunked lands below whole-prompt — the cost model sees
    # both sides of the tradeoff
    assert b_chunk < b_whole
    assert b_whole < 1.02 * b_chunk  # ...but only by the quad-vs-weights margin
    # longer prompts, more busy time (monotone)
    assert bottleneck_time(g, pl, cm, prompt_len=1024, prefill_chunk=64) > b_chunk


def test_plan_and_milp_score_prefill_work():
    """PlanConfig.prompt_len threads into candidate scoring and the MILP's
    busy accumulators: the reported throughput objective includes prefill.

    ``fused_prefill=False`` pins the PR-5 standalone per-chunk costing
    (each chunk pays its own weight stream); the fused-rate default is
    covered in test_fused_step.py."""
    cfg = get_config("llama3.2-1b").smoke()
    g = transformer_graph(cfg, seq_len=64, granularity="block")
    cl = tpu_slice_cluster(n_slices=2, heterogeneous=True)
    res0 = plan(g, cl, PlanConfig(
        method="moirai", objective="throughput", time_limit=10,
        mip_rel_gap=0.05, fused_prefill=False,
    ))
    res1 = plan(g, cl, PlanConfig(
        method="moirai", objective="throughput", time_limit=10,
        mip_rel_gap=0.05, prompt_len=2048, prefill_chunk=64,
        fused_prefill=False,
    ))
    assert res0.extra["prompt_len"] == 0
    assert res1.extra["prompt_len"] == 2048
    cm = CostModel(cl)
    # each result's objective equals the prefill-aware bottleneck of its own
    # placement under its own workload assumption
    b1 = bottleneck_time(
        g, res1.placement, cm, prompt_len=2048, prefill_chunk=64,
        graph_seq_len=64,
    )
    assert res1.objective == pytest.approx(b1, rel=1e-6)
    assert res1.objective > bottleneck_time(g, res1.placement, cm) * 1.5


# ----------------------------------------------------------------------
# satellite: BENCH_*.json schema check
# ----------------------------------------------------------------------


def test_write_bench_json_schema(tmp_path, monkeypatch):
    import json
    import sys

    sys.path.insert(0, "benchmarks")
    try:
        from common import validate_bench_payload, write_bench_json
    finally:
        sys.path.pop(0)
    monkeypatch.setenv("BENCH_JSON_DIR", str(tmp_path))
    path = write_bench_json("demo", {"speedup": 2.0}, bar=1.3, measured=2.0)
    payload = json.loads(open(path).read())
    assert payload["name"] == "demo"
    assert payload["bar"] == 1.3 and payload["measured"] == 2.0
    validate_bench_payload(payload)
    with pytest.raises(ValueError):
        validate_bench_payload({"name": "x", "bar": 1.0})        # missing key
    with pytest.raises(ValueError):
        validate_bench_payload({"name": "", "bar": 1.0, "measured": 1.0})
    with pytest.raises(ValueError):
        validate_bench_payload({"name": "x", "bar": "high", "measured": 1.0})
    with pytest.raises(ValueError):
        write_bench_json("bad", {}, bar=1.0, measured=float("nan"))
