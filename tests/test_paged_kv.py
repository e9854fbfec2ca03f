"""Paged KV cache: differential paged-vs-dense suite + pool invariants.

The tentpole contract: block-paged serving (fixed-size page pools + per-slot
page tables, optional hash-based prefix sharing) is a pure storage-layout
change — greedy decode through pages is TOKEN-IDENTICAL to the dense
``(slots, max_len)`` rows it replaces, across every attention impl
(naive/chunked/pallas) and model family (dense/gemma2/mamba2/zamba2/enc-dec),
for chunked prefill streams that straddle page boundaries, and through the
serving engine's fused ragged path with shared-prefix reuse + copy-on-write.

Accounting moves with the layout: the engine's admission guard, the
planner's Eq. 5 resident-memory term, and the MILP all charge pages actually
resident via ``paged_kv_factor`` — and collapse EXACTLY to the legacy
``slots × kv_bytes`` accounting at ``kv_page_tokens = max_len``.

Also pinned here: the comm-billing fix for s²-shaped score tensors crossing
a stage cut (``meta["quad_out_bytes"]`` bills them queries × keys, not
linearly in the chunk).
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import hypothesis, st
from repro.configs import get_config
from repro.core.costmodel import CostModel, paged_kv_factor
from repro.core.devices import tpu_slice_cluster
from repro.core.graph import augment
from repro.core.modelgraph import transformer_graph
from repro.core.placement import PlanConfig
from repro.core.simulate import (
    prefill_busy,
    prefill_chunk_sizes,
    scale_edge_bytes,
    scale_node_to_tokens,
)
from repro.models.model import build_model
from repro.serving.engine import Request, ServingEngine
from repro.serving.kv_pool import KVPool, pages_needed

# ----------------------------------------------------------------------
# shared fixtures (memoized: the hypothesis shim hides signatures)
# ----------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _model(arch="llama3.2-1b", impl=None):
    cfg = get_config(arch).smoke()
    if impl is not None:
        cfg = dataclasses.replace(cfg, attention_impl=impl)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _mk_batch(cfg, prompt):
    batch = {"tokens": jnp.asarray([prompt], jnp.int32)}
    if cfg.family == "encdec":
        rng = np.random.default_rng(1)
        batch["frames"] = jnp.asarray(
            rng.standard_normal((1, 6, cfg.d_model)), jnp.float32
        )
    return batch


def _greedy_dense(arch, impl, prompt, max_new, chunk, max_len):
    cfg, model, params = _model(arch, impl)
    logits, caches = model.prefill_chunked(
        params, _mk_batch(cfg, prompt), max_len, chunk=chunk
    )
    toks = [int(jnp.argmax(logits[0]))]
    pos = len(prompt)
    while len(toks) < max_new:
        t = jnp.asarray([[toks[-1]]], jnp.int32)
        logits, caches = model.decode_step(
            params, {"tokens": t}, caches, jnp.asarray(pos, jnp.int32)
        )
        toks.append(int(jnp.argmax(logits[0])))
        pos += 1
    return toks


def _greedy_paged(arch, impl, prompt, max_new, chunk, max_len, page_tokens):
    cfg, model, params = _model(arch, impl)
    pool = KVPool(1, max_len, page_tokens, prefix_sharing=False)
    reuse, copies = pool.alloc_sequence(
        0, list(prompt), min(len(prompt) + max_new, max_len)
    )
    assert reuse == 0 and not copies
    pool.check_invariants()
    caches = model.init_paged_cache(pool.num_pages, page_tokens, 1)
    table = jnp.asarray(pool.table_array())
    kw = (
        {"self_cache": caches["self"]}
        if cfg.family == "encdec"
        else {"caches": caches}
    )
    logits, caches = model.prefill_chunked(
        params, _mk_batch(cfg, prompt), max_len, chunk=chunk,
        page_table=table, **kw,
    )
    toks = [int(jnp.argmax(logits[0]))]
    pos = len(prompt)
    while len(toks) < max_new:
        t = jnp.asarray([[toks[-1]]], jnp.int32)
        logits, caches = model.decode_step(
            params, {"tokens": t}, caches, jnp.asarray(pos, jnp.int32),
            page_table=table,
        )
        toks.append(int(jnp.argmax(logits[0])))
        pos += 1
    pool.free_slot(0)
    pool.check_invariants()
    return toks


# ----------------------------------------------------------------------
# pool: fast-tier round trip + invariants (pure numpy, no jit)
# ----------------------------------------------------------------------


def test_pool_round_trip_smoke():
    """Fast-tier smoke: import the pool, allocate a sequence through pages,
    commit its prefix, free it, and round-trip the device-facing table."""
    pool = KVPool(2, 32, 8)
    assert pool.pages_per_slot == 4 and pool.num_pages == 8
    reuse, copies = pool.alloc_sequence(0, list(range(11)), 20)
    assert reuse == 0 and copies == []
    # 20 tokens → 3 pages mapped; unmapped tail clamps to the trash page
    assert pool.pages_in_use() == 3
    tbl = pool.table_array()
    assert tbl.shape == (2, 4) and tbl.dtype == np.int32
    assert (tbl[0, :3] < pool.num_pages).all()
    assert tbl[0, 3] == pool.num_pages and (tbl[1] == pool.num_pages).all()
    pool.commit_prefix(0, list(range(11)))       # one full page registered
    assert pool.stats()["registered"] == 1
    pool.check_invariants()
    pool.free_slot(0)
    pool.check_invariants()
    assert pool.pages_in_use() == 0
    assert pool.free_pages() + pool.evictable_pages() == pool.num_pages


def test_pool_prefix_sharing_cow_and_refcounts():
    """Shared full prefix pages are refcounted read-only; a partially
    matching page is copy-on-write at admission; freeing dereferences."""
    P = 4
    pool = KVPool(3, 16, P)
    a = [1, 2, 3, 4, 5, 6, 7, 8, 9]
    pool.alloc_sequence(0, a, 12)
    pool.commit_prefix(0, a)                     # registers pages [1-4], [5-8]
    # b shares two full pages then diverges inside page 3 → 2 pages reused
    b = [1, 2, 3, 4, 5, 6, 7, 8, 99]
    reuse, copies = pool.alloc_sequence(1, b, 12)
    assert reuse == 8 and copies == []
    shared = pool.table[0, :2]
    assert (pool.table[1, :2] == shared).all()
    assert (pool.refcount[shared] == 2).all()
    pool.check_invariants()
    # c diverges INSIDE the second registered page → that page is COW'd:
    # reuse covers the partial match, the copy carries the matched tokens
    c = [1, 2, 3, 4, 5, 6, 99]
    reuse_c, copies_c = pool.alloc_sequence(2, c, 12)
    assert reuse_c == 6 and len(copies_c) == 1
    src, dst = copies_c[0]
    assert src == pool.table[0, 1] and dst == pool.table[2, 1]
    assert dst != src and pool.refcount[dst] == 1
    assert pool.stats()["cow_copies"] == 1
    pool.check_invariants()
    # page 0 is held by all three slots, page 1 by slots 0+1 (slot 2 COW'd)
    assert pool.refcount[shared[0]] == 3 and pool.refcount[shared[1]] == 2
    pool.free_slot(1)
    assert pool.refcount[shared[0]] == 2 and pool.refcount[shared[1]] == 1
    pool.free_slot(2)
    pool.free_slot(0)
    pool.check_invariants()
    # registered pages at refcount 0 linger on the LRU ring, reusable
    assert pool.evictable_pages() == 2
    d = [1, 2, 3, 4, 42]
    reuse_d, _ = pool.alloc_sequence(0, d, 8)
    assert reuse_d == 4 and pool.stats()["reused_pages"] >= 3


def test_pool_eviction_under_pressure():
    """When the free list runs dry, refcount-0 registered pages are evicted
    LRU-first (their hashes unregistered) rather than failing allocation."""
    P = 4
    pool = KVPool(2, 16, P, num_pages=6)
    a = list(range(1, 13))                       # 3 pages
    pool.alloc_sequence(0, a, 12)
    pool.commit_prefix(0, a)
    pool.free_slot(0)                            # 3 evictable + 3 free
    assert pool.evictable_pages() == 3 and pool.free_pages() == 3
    b = list(range(100, 116))                    # 4 pages: must evict one
    pool.alloc_sequence(1, b, 16)
    pool.check_invariants()
    assert pool.stats()["evicted"] >= 1
    assert pool.pages_in_use() == 4
    # over-commit beyond free + evictable must refuse, not corrupt
    assert not pool.can_admit(list(range(200, 216)), 16)
    with pytest.raises(RuntimeError):
        pool.alloc_sequence(0, list(range(200, 216)), 16)
    pool.check_invariants()                      # rollback left it clean


@hypothesis.settings(max_examples=30, deadline=None)
@hypothesis.example(seed=996048)   # a reused prefix page parked in the LRU
@hypothesis.given(seed=st.integers(0, 10**6))
def test_pool_invariants_random_ops(seed):
    """Property: any interleaving of admit/commit/free on a small pool keeps
    the refcount/free-list/LRU partition exact and never corrupts the
    registry (checked after every op); ``can_admit`` says yes exactly when
    ``alloc_sequence`` succeeds."""
    rng = np.random.default_rng(seed)
    P = int(rng.choice([2, 3, 4]))
    pool = KVPool(3, 12, P, num_pages=int(rng.integers(6, 14)))
    live = {}
    for _ in range(40):
        op = rng.random()
        free_slots = [s for s in range(3) if s not in live]
        if op < 0.5 and free_slots:
            slot = int(rng.choice(free_slots))
            n = int(rng.integers(1, 11))
            toks = [int(t) for t in rng.integers(1, 5, size=n)]
            total = min(n + int(rng.integers(0, 4)), 12)
            if pool.can_admit(toks, total):
                pool.alloc_sequence(slot, toks, total)
                live[slot] = toks
            else:
                with pytest.raises(RuntimeError):
                    pool.alloc_sequence(slot, toks, total)
        elif op < 0.75 and live:
            slot = int(rng.choice(list(live)))
            pool.commit_prefix(slot, live[slot])
        elif live:
            slot = int(rng.choice(list(live)))
            pool.free_slot(slot)
            del live[slot]
        pool.check_invariants()


def test_pages_needed_and_can_admit_arithmetic():
    assert pages_needed(0, 8) == 0
    assert pages_needed(1, 8) == 1
    assert pages_needed(8, 8) == 1
    assert pages_needed(9, 8) == 2
    pool = KVPool(1, 16, 8)                      # 2 pages total
    assert pool.can_admit([1] * 9, 16)
    assert pool.can_admit([1] * 9, 17)           # total clamps to max_len
    pool.alloc_sequence(0, [1] * 9, 16)          # pool is now full
    assert not pool.can_admit([2] * 9, 16)
    pool.free_slot(0)
    # full-page prefix reuse shrinks the page bill
    pool2 = KVPool(2, 16, 8, num_pages=3)
    a = list(range(1, 17))
    pool2.alloc_sequence(0, a, 16)
    pool2.commit_prefix(0, a)
    # a second identical prompt needs 2 pages but reuses both full prompt
    # pages → only the last-token page is fresh… reuse is capped at len-1,
    # so exactly one page (holding the re-written final token) is needed
    assert pool2.can_admit(a, 16)
    reuse, _ = pool2.alloc_sequence(1, a, 16)
    assert reuse == 15                           # capped at len(tokens)-1


# ----------------------------------------------------------------------
# model-level differential: paged == dense, page-straddling chunks
# ----------------------------------------------------------------------


def test_paged_matches_dense_fast():
    """Deterministic fast-tier pin: chunked prefill in chunks of 5 through
    8-token pages (every chunk straddles a page boundary) + paged decode is
    token-identical to the dense rows it replaces."""
    rng = np.random.default_rng(0)
    cfg, _, _ = _model("llama3.2-1b", "naive")
    prompt = [int(t) for t in rng.integers(1, cfg.vocab_size, size=13)]
    d = _greedy_dense("llama3.2-1b", "naive", prompt, 8, 5, 48)
    p = _greedy_paged("llama3.2-1b", "naive", prompt, 8, 5, 48, 8)
    assert d == p


def test_paged_page_tokens_max_len_collapses():
    """kv_page_tokens = max_len is ONE page per slot — the paged layout
    degenerates to a dense row and must stay token-identical too."""
    rng = np.random.default_rng(1)
    cfg, _, _ = _model("llama3.2-1b", "naive")
    prompt = [int(t) for t in rng.integers(1, cfg.vocab_size, size=13)]
    d = _greedy_dense("llama3.2-1b", "naive", prompt, 6, 5, 48)
    p = _greedy_paged("llama3.2-1b", "naive", prompt, 6, 5, 48, 48)
    assert d == p


def test_pallas_paged_bit_identical_to_dense_at_64_token_pages():
    """The dense Pallas kernel walks KV in the same 64-token tiles as a
    64-token page pool, so the two layouts give bit-identical decode logits
    across page boundaries (not merely close ones): dense is an exact
    oracle for the paged kernel."""
    cfg = get_config("llama3.2-1b").smoke().serving()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    max_len, page = 192, 64
    prompt = [int(t) for t in np.random.default_rng(0).integers(1, 500, 60)]
    batch = {"tokens": jnp.asarray([prompt], jnp.int32)}
    ld, dense = model.prefill_chunked(params, batch, max_len, chunk=64)
    pool = KVPool(1, max_len, page, prefix_sharing=False)
    pool.alloc_sequence(0, prompt, max_len)
    table = jnp.asarray(pool.table_array())
    lp, paged = model.prefill_chunked(
        params, batch, max_len, chunk=64, page_table=table,
        caches=model.init_paged_cache(pool.num_pages, page, 1),
    )
    for pos in range(len(prompt), len(prompt) + 72):   # crosses 64 and 128
        np.testing.assert_array_equal(np.asarray(ld), np.asarray(lp))
        t = jnp.argmax(ld[0]).astype(jnp.int32).reshape(1, 1)
        p = jnp.asarray(pos, jnp.int32)
        ld, dense = model.decode_step(params, {"tokens": t}, dense, p)
        lp, paged = model.decode_step(
            params, {"tokens": t}, paged, p, page_table=table
        )
    np.testing.assert_array_equal(np.asarray(ld), np.asarray(lp))


@pytest.mark.slow
@pytest.mark.parametrize("impl", ["naive", "chunked", "pallas"])
@pytest.mark.parametrize(
    "arch",
    ["llama3.2-1b", "gemma2-27b", "mamba2-130m", "zamba2-2.7b",
     "seamless-m4t-large-v2"],
)
def test_paged_matches_dense_all_families(arch, impl):
    """The full differential sweep: every family (dense, gemma2 windows +
    softcap, pure-SSM, hybrid, enc-dec) × every attention impl (incl. the
    paged pallas kernel) decodes identically through pages."""
    rng = np.random.default_rng(hash((arch, impl)) % 2**31)
    cfg, _, _ = _model(arch, impl)
    prompt = [int(t) for t in rng.integers(1, cfg.vocab_size, size=13)]
    d = _greedy_dense(arch, impl, prompt, 8, 5, 48)
    p = _greedy_paged(arch, impl, prompt, 8, 5, 48, 8)
    assert d == p


@pytest.mark.slow
@hypothesis.settings(max_examples=4, deadline=None)
@hypothesis.given(
    seed=st.integers(0, 10**6),
    chunk=st.integers(1, 9),
    page_tokens=st.sampled_from([4, 5, 8, 16]),
)
def test_paged_matches_dense_drawn_geometry(seed, chunk, page_tokens):
    """Property: paged == dense for DRAWN chunk/page geometry — coprime
    chunk and page sizes make chunks straddle page boundaries arbitrarily."""
    rng = np.random.default_rng(seed)
    cfg, _, _ = _model("llama3.2-1b", "chunked")
    n = int(rng.integers(3, 21))
    prompt = [int(t) for t in rng.integers(1, cfg.vocab_size, size=n)]
    max_new = int(rng.integers(2, 9))
    d = _greedy_dense("llama3.2-1b", "chunked", prompt, max_new, chunk, 48)
    p = _greedy_paged(
        "llama3.2-1b", "chunked", prompt, max_new, chunk, 48, page_tokens
    )
    assert d == p


# ----------------------------------------------------------------------
# engine-level differential: fused ragged serving through pages
# ----------------------------------------------------------------------


def _run_engine(cfg, params, spec, **plan_kw):
    cluster = tpu_slice_cluster(n_slices=1)
    eng = ServingEngine(
        cfg, params, cluster, slots=3,
        plan_cfg=PlanConfig(method="etf", **plan_kw),
        eos_id=-1, max_len=64, prefill_chunk=8,
    )
    reqs = [Request(rid=i, prompt=list(p), max_new_tokens=m)
            for i, (p, m) in enumerate(spec)]
    for r in reqs:
        eng.submit(r)
    eng.run_until_drained()
    assert all(r.done for r in reqs)
    return eng, [r.out_tokens for r in reqs]


def _shared_prefix_spec(seed=7, n=5, prefix_len=11):
    rng = np.random.default_rng(seed)
    prefix = [int(t) for t in rng.integers(1, 200, size=prefix_len)]
    spec = []
    for i in range(n):
        sfx = [int(t) for t in rng.integers(1, 200,
                                            size=int(rng.integers(1, 9)))]
        spec.append((prefix + sfx if i % 2 == 0 else sfx,
                     int(rng.integers(2, 6))))
    return spec


@pytest.mark.slow
def test_engine_paged_matches_dense_with_prefix_sharing():
    """Paged fused ragged serving — WITH hash-based prefix sharing, reuse
    skipping prefill chunks, and COW on divergence — emits exactly the
    dense engine's tokens; the pool drains clean."""
    cfg, _, params = _model("llama3.2-1b")
    spec = _shared_prefix_spec()
    _, dense = _run_engine(cfg, params, spec)
    eng, paged = _run_engine(cfg, params, spec, kv_page_tokens=8)
    assert paged == dense
    pool = eng._kv_pool
    pool.check_invariants()
    st_ = pool.stats()
    # requests 0/2/4 share an 11-token prefix: after request 0 registers it,
    # at least one later admission reuses a full page and COWs the partial
    assert st_["reused_pages"] >= 1 and st_["cow_copies"] >= 1
    assert pool.pages_in_use() == 0              # everything retired


@pytest.mark.slow
def test_engine_paged_matches_dense_no_sharing_and_collapse():
    """prefix_sharing=False (private pages) and kv_page_tokens=max_len
    (single-page slots) both stay token-identical to dense."""
    cfg, _, params = _model("llama3.2-1b")
    spec = _shared_prefix_spec(seed=3)
    _, dense = _run_engine(cfg, params, spec)
    eng, p1 = _run_engine(cfg, params, spec,
                          kv_page_tokens=8, prefix_sharing=False)
    assert p1 == dense
    assert eng._kv_pool.stats()["reused_pages"] == 0
    _, p2 = _run_engine(cfg, params, spec, kv_page_tokens=64)
    assert p2 == dense


def test_engine_paged_refuses_a_page_size_that_is_not_positive():
    cfg, _, params = _model("llama3.2-1b")
    cluster = tpu_slice_cluster(n_slices=1)
    with pytest.raises(ValueError, match="positive"):
        ServingEngine(
            cfg, params, cluster, slots=2,
            plan_cfg=PlanConfig(method="etf", kv_page_tokens=-4),
            eos_id=-1, max_len=64, prefill_chunk=8,
        )


# ----------------------------------------------------------------------
# accounting: Eq. 5 page term — engine == planner == MILP, exact collapse
# ----------------------------------------------------------------------


def test_paged_kv_factor_pins():
    assert paged_kv_factor(None, 64) == 1.0
    assert paged_kv_factor(64, None) == 1.0
    assert paged_kv_factor(64, 64, 1.0) == 1.0   # P = S collapses EXACTLY
    assert paged_kv_factor(8, 48, 1.0) == 1.0    # P divides S, full residency
    assert paged_kv_factor(8, 50, 1.0) == pytest.approx(56 / 50)
    assert paged_kv_factor(16, 64, 0.5) == 0.5   # half-full sequences
    assert paged_kv_factor(8, 64, 0.0) == 8 / 64  # at least one page resident


def test_accounting_collapse_to_dense_exact():
    """kv_page_tokens = max_len (and prefix_sharing off) reproduces the
    legacy slots × kv_bytes accounting BIT-EXACTLY across every Eq. 5
    consumer: CostModel.kv_bytes/resident_bytes/memory_ok and the MILP's
    m_res coefficients."""
    cfg = get_config("llama3.2-1b").smoke()
    g = transformer_graph(cfg, seq_len=64, granularity="block")
    cl = tpu_slice_cluster(n_slices=2, heterogeneous=True)
    dense = CostModel(cl)
    paged = CostModel(cl, kv_page_tokens=64, kv_seq_tokens=64)
    for n in g.nodes.values():
        assert paged.kv_bytes(n) == dense.kv_bytes(n) == n.kv_bytes
        for s in (1, 4, 16):
            assert paged.resident_bytes(n, s) == dense.resident_bytes(n, s)
            assert (
                paged.resident_bytes(n, s)
                == n.param_bytes + s * n.kv_bytes
            )


def test_engine_admission_agrees_with_planner_accounting():
    """The engine's page-aware cost model (admission width `_max_in_flight`)
    is the SAME accounting plan()/the MILP apply: kv_bytes scaled by
    paged_kv_factor(P, max_len, residency) — scoring what the engine runs
    holds for memory too."""
    cfg, _, params = _model("llama3.2-1b")
    cluster = tpu_slice_cluster(n_slices=1)

    def eng(**kw):
        return ServingEngine(
            cfg, params, cluster, slots=2,
            plan_cfg=PlanConfig(method="etf", **kw),
            eos_id=-1, max_len=64, prefill_chunk=8,
        )

    e_dense = eng()
    e_collapse = eng(kv_page_tokens=64, prefix_sharing=False)
    e_half = eng(kv_page_tokens=16, kv_residency=0.5)
    f = paged_kv_factor(16, 64, 0.5)
    for n in e_dense.graph.nodes.values():
        assert e_collapse._cost.kv_bytes(n) == e_dense._cost.kv_bytes(n)
        assert e_half._cost.kv_bytes(n) == n.kv_bytes * f
        # MILP Eq. 5 coefficient parity: m_res is cost.resident_bytes
        assert (
            e_half._cost.resident_bytes(n, 2)
            == n.param_bytes + 2 * n.kv_bytes * f
        )
    # identical memory model ⇒ identical admission width at collapse
    assert e_collapse._max_in_flight == e_dense._max_in_flight


def test_plan_threads_paged_cost_into_milp():
    """plan() with kv_page_tokens rebuilds its CostModel page-aware (using
    the graph's own seq_len), so the MILP memory constraint and heuristic
    caps all charge resident pages."""
    from repro.core.placement import plan

    cfg = get_config("llama3.2-1b").smoke()
    g = transformer_graph(cfg, seq_len=64, granularity="block")
    cl = tpu_slice_cluster(n_slices=2, heterogeneous=True)
    pc = PlanConfig(method="etf", serving_slots=4,
                    kv_page_tokens=16, kv_residency=0.5)
    res = plan(g, cl, pc)
    assert res.placement  # planned fine with the paged memory term


# ----------------------------------------------------------------------
# comm billing: s²-shaped payloads crossing a stage cut (regression)
# ----------------------------------------------------------------------


def _fine_graph_and_scores():
    cfg = get_config("llama3.2-1b").smoke()
    g = transformer_graph(cfg, seq_len=64, granularity="fine")
    scores = [
        n for n in g.nodes.values()
        if (n.meta or {}).get("quad_out_bytes")
    ]
    assert scores, "fine graph must tag its s²-shaped outputs"
    return cfg, g, scores


def test_quadratic_output_payload_scales_queries_times_keys():
    """Regression: an s²-shaped score tensor's output payload (and hence
    the comm bill of a stage cut right after it) scales frac × cfrac, not
    linearly — the FIRST 16-token chunk of a 64-seq graph ships 16×16
    score elements, not 16/64 of the full 64×64 tensor (the old linear
    bill overcharged it 4×)."""
    cfg, g, scores = _fine_graph_and_scores()
    n = scores[0]
    s, t, ctx = 64, 16, 16
    frac, cfrac = t / s, ctx / s
    scaled = scale_node_to_tokens(n, t, s, context_tokens=ctx)
    # the score output is FULLY quadratic: q·kᵀ at (t queries × ctx keys)
    assert n.meta["quad_out_bytes"] == n.output_bytes
    assert scaled.output_bytes == pytest.approx(n.output_bytes * frac * cfrac)
    assert scaled.output_bytes == pytest.approx(n.output_bytes * frac / 4)
    # a linear-output node (e.g. probs·V context) still scales linearly
    lin = next(
        nn for nn in g.nodes.values()
        if nn.op_type == "matmul" and not (nn.meta or {}).get("quad_out_bytes")
        and (nn.meta or {}).get("quad_flops")
    )
    assert scale_edge_bytes(lin, lin.output_bytes, frac, cfrac) == (
        pytest.approx(lin.output_bytes * frac)
    )


def test_prefill_busy_bills_quadratic_comm():
    """prefill_busy's channel accumulators bill each crossing edge's
    quad_out_bytes share queries × keys — verified against a hand-summed
    expectation over the chunk schedule."""
    cfg, g, _ = _fine_graph_and_scores()
    # cut right through attention: the score/mask/softmax chain sits on
    # device 0, everything else on device 1 — so an s²-shaped payload
    # (softmax probs → context matmul) crosses the channel every chunk
    cl = tpu_slice_cluster(n_slices=2)
    placement = {
        nid: (0 if (n.meta or {}).get("quad_out_bytes") else 1)
        for nid, n in g.nodes.items()
    }
    cost = CostModel(cl)
    aug = augment(g)
    s, prompt, chunk = 64, 48, 16
    busy = prefill_busy(
        g, placement, cost, prompt_len=prompt, prefill_chunk=chunk,
        seq_len=s, aug=aug,
    )
    expect = {}
    run = 0
    for t in prefill_chunk_sizes(prompt, chunk):
        run += t
        frac, cfrac = t / s, run / s
        for c in aug.comm.values():
            ks, kd = placement[c.src], placement[c.dst]
            if ks != kd:
                payload = scale_edge_bytes(
                    g.nodes[c.src], c.bytes, frac, cfrac
                )
                key = ("chan", ks, kd)
                expect[key] = expect.get(key, 0.0) + cost.comm_time(
                    payload, ks, kd
                )
    assert set(busy) >= set(expect)
    for key, v in expect.items():
        assert busy[key] == pytest.approx(v)
    # and the quadratic share genuinely moves the bill: zeroing the meta
    # reproduces the old linear total, which differs
    g2 = transformer_graph(cfg, seq_len=64, granularity="fine")
    for n in g2.nodes.values():
        if n.meta and "quad_out_bytes" in n.meta:
            n.meta["quad_out_bytes"] = 0.0
    busy_lin = prefill_busy(
        g2, placement, cost, prompt_len=prompt, prefill_chunk=chunk,
        seq_len=s, aug=augment(g2),
    )
    chan = [k for k in expect if k[0] == "chan"]
    assert any(
        busy[k] != pytest.approx(busy_lin[k]) for k in chan
    ), "quadratic comm billing should change a cut through attention"


@pytest.mark.slow
def test_milp_prefill_comm_parity_with_simulate():
    """The MILP's prefill comm accumulators iterate the same (size, context)
    pairs with the same scale_edge_bytes payloads as prefill_busy — the
    objective-parity contract extends to the quadratic comm fix."""
    from repro.core.milp import solve_placement
    from repro.core.simulate import bottleneck_time

    cfg = get_config("llama3.2-1b").smoke()
    g = transformer_graph(cfg, seq_len=64, granularity="fine")
    cl = tpu_slice_cluster(n_slices=2, heterogeneous=True)
    cm = CostModel(cl)
    r = solve_placement(
        g, cm, objective="throughput", prompt_len=96, prefill_chunk=32,
        graph_seq_len=64, time_limit=15, mip_rel_gap=1e-3,
    )
    assert r.status in ("optimal", "feasible")
    assert r.objective == pytest.approx(
        bottleneck_time(
            g, r.placement, cm, prompt_len=96, prefill_chunk=32,
            graph_seq_len=64,
        ),
        rel=1e-6,
    )
