"""Variable-advance speculative decoding (ISSUE 10).

Covers the tentpole and its satellites:

* acceptance-protocol units — ``greedy_accept`` longest-prefix semantics,
  ``rolled_back_draft_pos`` bookkeeping, ``expected_accepted_tokens``
  closed form;
* model-level token identity — ``spec_generate`` (draft proposes k tokens,
  ONE ragged target forward verifies, variable per-row advance) reproduces
  sequential greedy decode bit-for-bit across families (dense, gemma2
  windows, pure-SSM, hybrid — including the SSM two-pass verify/commit
  rewind), attention impls (naive/chunked/pallas) and paged vs dense KV,
  at full, partial, and zero acceptance, property-tested;
* kernel-level verify rows — q_len=k+1 rows (a decode-depth row feeding
  several tokens) mixed with prefill chunks, plain decode rows and idle
  rows match the naive oracle under the pallas scalar-prefetch masks, with
  exact-zero padding;
* engine-level identity — a ``ServingEngine`` with a draft attached emits
  exactly the tokens the plain engine emits (dense and paged), while
  tracking per-request-class acceptance rates;
* joint placement — ``merge_spec_graphs`` pass-rate annotation,
  ``plan_speculative`` placing the draft on otherwise-idle weak devices
  while the target holds the strong ones, and simulate↔MILP busy-time
  parity pinned for the two-graph plan.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import hypothesis, st
from test_fused_step import _model, _naive_ragged, _sequential

from repro.core.costmodel import CostModel, expected_accepted_tokens
from repro.core.devices import GB, ClusterSpec, DeviceSpec
from repro.core.modelgraph import transformer_graph
from repro.core.placement import PlanConfig
from repro.core.simulate import bottleneck_time, simulate_pipeline
from repro.core.spec_plan import merge_spec_graphs, plan_speculative
from repro.models.speculative import (
    greedy_accept,
    rolled_back_draft_pos,
    spec_generate,
)
from repro.serving.engine import Request, ServingEngine


# ----------------------------------------------------------------------
# protocol units
# ----------------------------------------------------------------------


def test_greedy_accept_prefix_semantics():
    # full acceptance: all k drafts match, bonus appended
    assert greedy_accept([5, 6, 7], [5, 6, 7, 8]) == (3, [5, 6, 7, 8])
    # partial: first mismatch truncates, target's token replaces it
    assert greedy_accept([5, 6, 7], [5, 9, 7, 8]) == (1, [5, 9])
    # zero acceptance still emits the target's own token
    assert greedy_accept([5, 6, 7], [1, 2, 3, 4]) == (0, [1])
    with pytest.raises(AssertionError):
        greedy_accept([5, 6], [5, 6])           # needs k+1 preds


def test_rolled_back_draft_pos():
    # the draft fed proposals d_1..d_{k-1} past the committed length L; it
    # keeps the accepted prefix of what it actually fed
    L, k = 10, 4
    assert rolled_back_draft_pos(L, 0, k) == L          # all rejected
    assert rolled_back_draft_pos(L, 2, k) == L + 2      # d1,d2 kept
    assert rolled_back_draft_pos(L, 4, k) == L + 3      # fed only k-1
    # and the post-round catch-up is always 1 or 2 tokens: committed grows
    # by accepted+1
    for j in range(k + 1):
        behind = (L + j + 1) - rolled_back_draft_pos(L, j, k)
        assert behind in (1, 2)


def test_expected_accepted_tokens_closed_form():
    assert expected_accepted_tokens(0.0, 4) == 1.0
    assert expected_accepted_tokens(1.0, 4) == 5.0
    a, k = 0.8, 3
    assert expected_accepted_tokens(a, k) == pytest.approx(
        sum(a**i for i in range(k + 1))
    )
    # monotone in both arguments
    assert expected_accepted_tokens(0.9, 4) > expected_accepted_tokens(0.5, 4)
    assert expected_accepted_tokens(0.5, 6) > expected_accepted_tokens(0.5, 2)


# ----------------------------------------------------------------------
# kernel: verify rows (q_len=k+1) in the fused mixed batch
# ----------------------------------------------------------------------

# a verify row IS a q_len>1 row at decode depth: pending token + k drafts
# at cache_pos=14 (k=3), a full prefill chunk, a deep plain decode row, a
# partial tail chunk, and an idle row — all in one batch
_VERIFY_ROWS = [(14, 4), (0, 8), (19, 1), (5, 3), (0, 0)]


@pytest.mark.parametrize("window,softcap", [(0, 0.0), (7, 30.0)])
def test_pallas_verify_rows_match_naive_ref(window, softcap):
    """The pallas kernel serves verify rows (q_len=k+1 at decode depth)
    mixed with prefill/decode/idle rows exactly like the naive oracle —
    plain causal and the gemma2 window+softcap configuration — and padding
    query rows stay EXACT zeros."""
    from repro.kernels.flash_attention.ops import flash_attention

    rng = np.random.default_rng(23)
    b, sq, sk, h, kv, d = len(_VERIFY_ROWS), 8, 24, 4, 2, 64
    q = jnp.asarray(rng.standard_normal((b, sq, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, sk, kv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, sk, kv, d)), jnp.float32)
    cache_pos = np.asarray([r[0] for r in _VERIFY_ROWS], np.int32)
    q_lens = np.asarray([r[1] for r in _VERIFY_ROWS], np.int32)
    scale = 1.0 / np.sqrt(d)
    q_pos = cache_pos[:, None] + np.arange(sq, dtype=np.int32)[None]
    out = flash_attention(
        q, k, v, jnp.asarray(q_pos), None, jnp.asarray(q_lens),
        scale=scale, causal=True, window=window or None,
        softcap=softcap or None, interpret=True,
    )
    ref = _naive_ragged(
        q, k, v, cache_pos, q_lens, scale=scale, window=window,
        softcap=softcap,
    )
    np.testing.assert_allclose(np.asarray(out, np.float64), ref, atol=2e-5)
    arr = np.asarray(out)
    for bi, (_, n) in enumerate(_VERIFY_ROWS):
        assert not arr[bi, n:].any(), f"row {bi} padding queries leaked"


@pytest.mark.slow
@hypothesis.settings(max_examples=10, deadline=None)
@hypothesis.given(seed=st.integers(0, 10**6), spec=st.integers(1, 6))
def test_pallas_verify_rows_property(seed, spec):
    """Random verify-span compositions (q_len=spec+1 at random decode
    depths) against the oracle."""
    from repro.kernels.flash_attention.ops import flash_attention

    rng = np.random.default_rng(seed)
    sq = spec + 1
    b, sk, h, kv, d = 3, 32, 2, 1, 64
    q = jnp.asarray(rng.standard_normal((b, sq, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, sk, kv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, sk, kv, d)), jnp.float32)
    # row 0: verify span; row 1: plain decode; row 2: idle
    q_lens = np.asarray([sq, 1, 0], np.int32)
    cache_pos = np.asarray(
        [rng.integers(0, sk - sq + 1), rng.integers(0, sk), 0], np.int32
    )
    scale = 1.0 / np.sqrt(d)
    q_pos = cache_pos[:, None] + np.arange(sq, dtype=np.int32)[None]
    out = flash_attention(
        q, k, v, jnp.asarray(q_pos), None, jnp.asarray(q_lens),
        scale=scale, causal=True, interpret=True,
    )
    ref = _naive_ragged(q, k, v, cache_pos, q_lens, scale=scale)
    np.testing.assert_allclose(np.asarray(out, np.float64), ref, atol=2e-5)
    assert not np.asarray(out)[2].any()


# ----------------------------------------------------------------------
# model level: spec_generate ≡ sequential greedy
# ----------------------------------------------------------------------


def _perturbed(params, scale, seed=1):
    """A noisy copy of ``params`` — a draft correlated with the target, so
    acceptance is partial (scale ~1e-3) down to ~zero (scale ~0.1)."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    out = []
    for leaf, key in zip(leaves, keys):
        if hasattr(leaf, "dtype") and jnp.issubdtype(leaf.dtype, jnp.floating):
            out.append(
                leaf + scale * jax.random.normal(key, leaf.shape, leaf.dtype)
            )
        else:
            out.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, out)


def _spec_prompts(seed, b, lo=1, hi=13):
    rng = np.random.default_rng(seed)
    prompts = [
        [int(t) for t in rng.integers(1, 180, size=int(rng.integers(lo, hi)))]
        for _ in range(b)
    ]
    max_news = [int(rng.integers(2, 9)) for _ in range(b)]
    return prompts, max_news


def _check_spec_identity(
    target_arch,
    draft_arch,
    *,
    seed=3,
    spec_tokens=3,
    chunk=4,
    impl=None,
    page_tokens=None,
    draft_noise=None,
    stats=None,
):
    tcfg, tmodel, tparams = _model(target_arch, impl)
    dcfg, dmodel, dparams = _model(draft_arch, impl)
    if draft_noise is not None:
        dparams = _perturbed(dparams, draft_noise)
    prompts, max_news = _spec_prompts(seed, b=3)
    out = spec_generate(
        tmodel, tparams, dmodel, dparams, prompts, max_news,
        spec_tokens=spec_tokens, chunk=chunk, max_len=64,
        page_tokens=page_tokens, stats=stats,
    )
    for i, (p, m) in enumerate(zip(prompts, max_news)):
        ref = _sequential(tmodel, tparams, p, m, chunk=chunk, max_len=64)
        assert out[i] == ref, (i, out[i], ref)


def test_spec_identity_self_draft_full_acceptance():
    """Draft == target: every proposal accepted, rows advance k+1 per
    round, output still identical (the bonus-token path)."""
    stats = {}
    _check_spec_identity("llama3.2-1b", "llama3.2-1b", stats=stats)
    assert stats["accepted"] == stats["proposed"] > 0


def test_spec_identity_noisy_draft_partial_acceptance():
    """A perturbed draft accepts some-but-not-all proposals — the
    variable-advance path with real mid-span rejections."""
    stats = {}
    _check_spec_identity(
        "llama3.2-1b", "llama3.2-1b", draft_noise=2e-3, stats=stats
    )
    assert 0 <= stats["accepted"] < stats["proposed"]


def test_spec_identity_wrong_draft_zero_acceptance():
    """A garbage draft rejects everything: pure rollback traffic, one
    (bonus) token per round, still identical."""
    stats = {}
    _check_spec_identity(
        "llama3.2-1b", "llama3.2-1b", draft_noise=0.5, seed=9, stats=stats
    )
    assert stats["accepted"] < stats["proposed"]


def test_spec_identity_paged_target():
    """The target serving from a paged KV pool (per-row page tables, spec
    headroom mapped up front) is still token-identical."""
    _check_spec_identity(
        "llama3.2-1b", "llama3.2-1b", draft_noise=2e-3, page_tokens=8
    )


@hypothesis.settings(max_examples=4, deadline=None)
@hypothesis.given(
    seed=st.integers(0, 10**6),
    spec=st.integers(1, 5),
    chunk=st.integers(1, 6),
)
def test_spec_identity_property(seed, spec, chunk):
    """Property: ANY composition of prompts, budgets, k and chunk size is
    greedy-token-identical (dense target, noisy draft, fast tier)."""
    _check_spec_identity(
        "llama3.2-1b", "llama3.2-1b",
        seed=seed, spec_tokens=spec, chunk=chunk, draft_noise=2e-3,
    )


_CROSS_PAIRS = [
    ("gemma2-27b", "gemma2-27b"),       # sliding windows + softcap
    ("llama3.2-1b", "mamba2-130m"),     # recurrent DRAFT (snapshot-restore)
    ("mamba2-130m", "llama3.2-1b"),     # recurrent TARGET (two-pass commit)
    ("zamba2-2.7b", "mamba2-130m"),     # hybrid target, SSM draft
]


@pytest.mark.slow
@pytest.mark.parametrize("target,draft", _CROSS_PAIRS)
def test_spec_identity_cross_family(target, draft):
    """Draft/target pairs across model families — attention-only rollback,
    recurrent-draft snapshot restore, and the SSM/hybrid verify-then-commit
    state rewind all preserve token identity."""
    _check_spec_identity(target, draft, seed=5)


@pytest.mark.slow
@pytest.mark.parametrize("impl", ["chunked", "pallas"])
def test_spec_identity_attention_impls(impl):
    """The verify rows (q_len=k+1 at decode depth) go through the chunked
    and pallas attention paths identically."""
    _check_spec_identity(
        "llama3.2-1b", "llama3.2-1b", impl=impl, draft_noise=2e-3
    )


@pytest.mark.slow
@pytest.mark.parametrize("target,draft", [("zamba2-2.7b", "mamba2-130m")])
def test_spec_identity_cross_family_paged(target, draft):
    """Paged target + recurrent state rewind together."""
    _check_spec_identity(target, draft, seed=5, page_tokens=8)


# ----------------------------------------------------------------------
# engine level: speculative ServingEngine ≡ plain ServingEngine
# ----------------------------------------------------------------------


def _spec_cluster():
    return ClusterSpec(
        devices=[
            DeviceSpec("strong0", peak_flops=100e12, mem_bytes=40 * GB, hbm_bw=1500e9),
            DeviceSpec("strong1", peak_flops=100e12, mem_bytes=40 * GB, hbm_bw=1500e9),
            DeviceSpec("weak0", peak_flops=8e12, mem_bytes=16 * GB, hbm_bw=250e9),
            DeviceSpec("weak1", peak_flops=8e12, mem_bytes=16 * GB, hbm_bw=250e9),
        ],
        link_bw=np.full((4, 4), 50e9) * (1 - np.eye(4)),
        name="spec-hetero",
    )


def _run_engine(cfg, params, *, draft_params=None, spec_tokens=0,
                page_tokens=None, reqs=None):
    plan_cfg = PlanConfig(
        method="etf", objective="throughput", serving_slots=3,
        prefill_chunk=4, spec_tokens=spec_tokens,
        kv_page_tokens=page_tokens,
    )
    kw = {}
    if draft_params is not None:
        kw = dict(draft_cfg=cfg, draft_params=draft_params)
    eng = ServingEngine(
        cfg, params, _spec_cluster(), slots=3, max_len=64,
        plan_cfg=plan_cfg, eos_id=-1, **kw,
    )
    for r in reqs:
        eng.submit(r)
    eng.run_until_drained()
    return eng


def _engine_requests(seed=7, n=6):
    rng = np.random.default_rng(seed)
    return [
        Request(
            rid=i,
            prompt=[int(t) for t in rng.integers(1, 180, size=int(rng.integers(1, 13)))],
            max_new_tokens=int(rng.integers(3, 9)),
            tier=i % 2,
        )
        for i in range(n)
    ]


@pytest.mark.parametrize("page_tokens", [None, 8])
def test_engine_spec_token_identity(page_tokens):
    """A draft-attached engine (dense and paged) emits EXACTLY the plain
    engine's tokens while advancing variable counts per step, and reports
    per-class acceptance through straggler_report()["speculation"]."""
    cfg, model, params = _model()
    base = _run_engine(cfg, params, page_tokens=page_tokens,
                       reqs=_engine_requests())
    expect = {r.rid: list(r.out_tokens) for r in base.finished}

    spec = _run_engine(
        cfg, params, draft_params=_perturbed(params, 2e-3),
        spec_tokens=3, page_tokens=page_tokens, reqs=_engine_requests(),
    )
    got = {r.rid: list(r.out_tokens) for r in spec.finished}
    assert got == expect

    rep = spec.straggler_report()["speculation"]
    assert rep["spec_tokens"] == 3
    assert set(rep["classes"]) == {"tier0", "tier1"}
    for row in rep["classes"].values():
        assert row["rounds"] > 0
        assert 0.0 <= row["acceptance_rate"] <= 1.0
        # variable advance really happened: 1 <= tokens/round <= k+1
        assert 1.0 <= row["tokens_per_round"] <= 4.0
    # paged serving also surfaces the pool counters (satellite)
    kv = spec.straggler_report()["kv"]
    if page_tokens:
        assert kv is not None and kv["alloc"] > 0
    else:
        assert kv is None


def test_engine_spec_self_draft_multi_advance():
    """With draft == target every round accepts all k proposals — slots
    must advance k+1 tokens per fused step (strictly fewer engine steps
    than tokens emitted) and still match the plain engine."""
    cfg, model, params = _model()
    base = _run_engine(cfg, params, reqs=_engine_requests(seed=11))
    expect = {r.rid: list(r.out_tokens) for r in base.finished}
    spec = _run_engine(
        cfg, params, draft_params=params, spec_tokens=3,
        reqs=_engine_requests(seed=11),
    )
    got = {r.rid: list(r.out_tokens) for r in spec.finished}
    assert got == expect
    rep = spec.straggler_report()["speculation"]
    for row in rep["classes"].values():
        assert row["acceptance_rate"] == 1.0
        assert row["tokens_per_round"] == 4.0


def test_engine_spec_refuses_drafts_it_cannot_serve():
    cfg, model, params = _model()
    with pytest.raises(ValueError, match="draft_params"):
        ServingEngine(
            cfg, params, _spec_cluster(), slots=2, max_len=64,
            plan_cfg=PlanConfig(method="etf", spec_tokens=3),
            draft_cfg=cfg,
        )
    # the stage executor serves attention-family blocks only — an SSM
    # draft must fail loudly at construction, not KeyError mid-forward
    from repro.configs import get_config

    ssm_cfg = get_config("mamba2-130m").smoke()
    with pytest.raises(ValueError, match="dense/moe draft"):
        ServingEngine(
            cfg, params, _spec_cluster(), slots=2, max_len=64,
            plan_cfg=PlanConfig(method="etf", spec_tokens=3),
            draft_cfg=ssm_cfg, draft_params=params,
        )


# ----------------------------------------------------------------------
# joint placement: merged pass-rate graph, weak-device draft, MILP parity
# ----------------------------------------------------------------------


def get_cfg(arch):
    from repro.configs import get_config

    return get_config(arch).smoke()


def test_merge_spec_graphs_pass_rates():
    tg = transformer_graph(
        get_cfg("llama3.2-1b"), seq_len=64, granularity="block"
    )
    dg = transformer_graph(
        get_cfg("mamba2-130m"), seq_len=64, granularity="block"
    )
    k, a = 4, 0.8
    merged, tmap, dmap = merge_spec_graphs(
        tg, dg, spec_tokens=k, acceptance_rate=a
    )
    merged.validate()
    assert len(merged.nodes) == len(tg.nodes) + len(dg.nodes)
    e = expected_accepted_tokens(a, k)
    for orig, mid in tmap.items():
        node = merged.nodes[mid]
        assert node.meta["pass_rate"] == pytest.approx(1.0 / e)
        assert node.meta["spec_role"] == "target"
        # byte counts copied UNSCALED: rates scale time, not residency
        assert node.param_bytes == tg.nodes[orig].param_bytes
        assert node.kv_bytes == tg.nodes[orig].kv_bytes
    for orig, mid in dmap.items():
        node = merged.nodes[mid]
        assert node.meta["pass_rate"] == pytest.approx(k / e)
        assert node.meta["spec_role"] == "draft"
    # the two subgraphs stay disjoint components (token-level coupling
    # only): no merged edge crosses the target/draft boundary
    tids, dids = set(tmap.values()), set(dmap.values())
    for nid, node in merged.nodes.items():
        side = tids if nid in tids else dids
        assert all(i in side for i in node.inputs)


def test_joint_plan_weak_device_draft_and_milp_parity():
    """The pinned acceptance criterion: on a 2-strong/2-weak cluster the
    joint MILP keeps the target's decode path on the strong devices and
    exploits otherwise-idle weak devices for draft work, and the merged
    two-graph plan's MILP objective equals ``bottleneck_time`` on the
    merged graph (simulate↔MILP busy parity)."""
    # full-size configs: with 16 llama blocks vs 24 mamba blocks and a
    # 12.5x compute gap between device tiers, the placement is actually
    # discriminative (smoke graphs are 4 nodes — anything fits anywhere)
    from repro.configs import get_config

    tg = transformer_graph(
        get_config("llama3.2-1b"), seq_len=64, granularity="block"
    )
    dg = transformer_graph(
        get_config("mamba2-130m"), seq_len=64, granularity="block"
    )
    cluster = _spec_cluster()
    cfg = PlanConfig(
        method="moirai", objective="throughput", serving_slots=4,
        prompt_len=64, time_limit=60,
        spec_tokens=4, acceptance_rate=0.8,
    )
    sp = plan_speculative(tg, dg, cluster, cfg)
    res = sp.result
    assert res.status == "optimal"
    assert sp.expected_tokens_per_round == pytest.approx(
        expected_accepted_tokens(0.8, 4)
    )
    assert res.extra["spec_tokens"] == 4

    strong, weak = {0, 1}, {2, 3}
    tgt_on_strong = sum(
        1 for d in sp.target_placement.values() if d in strong
    )
    dft_on_weak = sum(1 for d in sp.draft_placement.values() if d in weak)
    # the target's serving path concentrates on the strong devices...
    assert tgt_on_strong > len(sp.target_placement) / 2, sp.target_placement
    # ...while the joint plan pushes real draft work onto the weak devices
    # — capacity a target-only plan would leave idle (the pass-rate
    # discount makes per-round draft work cheap enough for them)
    assert dft_on_weak >= len(sp.draft_placement) / 3, sp.draft_placement

    # simulate↔MILP parity on the merged two-graph problem: the envelope's
    # objective IS bottleneck_time under the same workload knobs
    cost = CostModel(cluster)
    bneck = bottleneck_time(
        sp.merged, res.placement, cost,
        prompt_len=cfg.prompt_len, prefill_chunk=cfg.prefill_chunk,
        graph_seq_len=sp.merged.seq_len, fused_prefill=True,
    )
    assert res.objective == pytest.approx(bneck, rel=1e-6)

    # the merged two-graph plan pipelines: the simulator runs the disjoint
    # draft/target components concurrently and its throughput respects the
    # merged bottleneck bound (same invariant test_pipeline_sim pins for
    # single-graph plans)
    sim = simulate_pipeline(sp.merged, res.placement, cost, 4)
    bneck0 = bottleneck_time(sp.merged, res.placement, cost)
    assert np.isfinite(sim.makespan) and sim.makespan > 0
    assert sim.throughput <= 1.0 / bneck0 + 1e-9


def test_plan_speculative_requires_spec_tokens():
    tg = transformer_graph(
        get_cfg("llama3.2-1b"), seq_len=32, granularity="block"
    )
    with pytest.raises(ValueError, match="spec_tokens"):
        plan_speculative(tg, tg, _spec_cluster(), PlanConfig(method="etf"))
