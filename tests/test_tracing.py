"""Program spans and operator counters of the serving path.

The spans are read back from a real profiler trace of a tiny engine on the
CPU: every name, its parent, and the stage metadata.  The counters are
checked after runs whose work is known in advance."""

import functools
import glob

import jax
import pytest

from repro.configs import get_config
from repro.core.devices import tpu_slice_cluster
from repro.core.placement import PlanConfig
from repro.models.model import build_model
from repro.serving.adaptation import AdaptationConfig
from repro.serving.engine import Request, ServingEngine
from repro.serving.tracing import compile_stats

CHUNK, PAGE = 8, 8


@functools.lru_cache(maxsize=None)
def _model():
    cfg = get_config("llama3.2-1b").smoke()
    return cfg, build_model(cfg).init(jax.random.PRNGKey(0))


def _engine(**kw):
    cfg, params = _model()
    kw.setdefault("plan_cfg", PlanConfig(method="etf"))
    return ServingEngine(cfg, params, tpu_slice_cluster(n_slices=1),
                         eos_id=-1, max_len=64, **kw)


def _drain(eng, reqs):
    for r in reqs:
        eng.submit(r)
    n = 0
    while not all(r.done for r in reqs):
        eng.step()
        n += 1
    return n


def _read_spans(log_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("engine.", "executor.")):
                    out.append((e.name, e.start_ns, e.end_ns, dict(e.stats)))
    return sorted(out, key=lambda s: (s[1], -s[2]))


def _parents(spans):
    """Each span with the name of the innermost span that encloses it."""
    out, stack = [], []
    for s in spans:
        while stack and stack[-1][2] <= s[1]:
            stack.pop()
        out.append((s, stack[-1][0] if stack else None))
        stack.append(s)
    return out


PARENT = {
    "engine.step": None,
    "engine.admit": "engine.step",
    "executor.copy_pages": "engine.admit",
    "engine.batch": "engine.step",
    "executor.forward": "engine.step",
    "executor.put": "executor.forward",
    "executor.launch": "executor.forward",
    "executor.wait": "executor.forward",
    "engine.sample": "engine.step",
    "engine.retire": "engine.step",
    "engine.observe": "engine.retire",
}


def test_spans_land_in_the_profiler_trace_nested(tmp_path):
    """A paged engine with prefix sharing for a few fused steps: a second
    prompt diverges inside the first one's registered page (a page copy at
    admission), and an observation window closes every other step."""
    eng = _engine(slots=2, prefill_chunk=CHUNK,
                  plan_cfg=PlanConfig(method="etf", kv_page_tokens=PAGE),
                  adapt=AdaptationConfig(window_steps=2, min_samples=1))
    prompt = list(range(1, 21))
    before = _drain(eng, [Request(rid=0, prompt=prompt, max_new_tokens=2)])
    with jax.profiler.trace(str(tmp_path)):
        n = _drain(eng, [Request(rid=1, prompt=prompt[:12] + [99, 98, 97],
                                 max_new_tokens=3)])
    spans = _read_spans(tmp_path)
    names = {s[0] for s in spans}
    assert names == set(PARENT)
    for (name, _, _, meta), parent in _parents(spans):
        assert parent == PARENT[name], (name, parent)
        if name in ("executor.put", "executor.launch", "executor.wait"):
            assert meta == {"stage": 0}        # one stage on one CPU device
    steps = [s for s in spans if s[0] == "engine.step"]
    assert len(steps) == n
    assert [s[3]["step"] for s in steps] == list(range(before + 1, before + 1 + n))
    fwd = [s for s in spans if s[0] == "executor.forward"]
    assert len(fwd) == n
    for stage_span in ("executor.put", "executor.launch", "executor.wait"):
        assert sum(1 for s in spans if s[0] == stage_span) == \
            n * len(eng.executor.stages)
    assert sum(1 for s in spans if s[0] == "executor.copy_pages") == 1


@pytest.mark.parametrize("spec_tokens", [0, 2])
def test_batch_span_counts_the_paged_kernels_live_blocks(tmp_path, spec_tokens):
    """On the paged Pallas kernel, each fused step's ``engine.batch`` span
    carries the grid blocks of its paged flash call that compute
    (``attn_live``) and all of them (``attn_blocks``), in plain and in
    speculative serving.  One 19-token prompt in 8-token chunks on 2 slots
    of 8 pages: without a draft the traced steps are the chunks at depths 8
    and 16, then decodes at 19 and 20, which see 2, 3, 3 and 3 pages of one
    slot; the idle slot sees none."""
    cfg = get_config("llama3.2-1b").smoke().serving()
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    draft = dict(draft_cfg=cfg, draft_params=params) if spec_tokens else {}
    eng = ServingEngine(cfg, params, tpu_slice_cluster(n_slices=1), eos_id=-1,
                        max_len=64, slots=2, prefill_chunk=CHUNK,
                        plan_cfg=PlanConfig(method="etf", kv_page_tokens=PAGE,
                                            spec_tokens=spec_tokens), **draft)
    eng.submit(Request(rid=0, prompt=list(range(1, 20)), max_new_tokens=3))
    eng.step()                                   # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        while eng.step():
            pass
    batch = [s[3] for s in _read_spans(tmp_path) if s[0] == "engine.batch"]
    kv = cfg.n_kv_heads
    assert {m["attn_blocks"] for m in batch} == {2 * kv * 8}   # one query block
    live = [m["attn_live"] for m in batch]
    if spec_tokens:
        assert live and all(2 * kv <= n <= 3 * kv for n in live)
    else:
        assert live == [2 * kv, 3 * kv, 3 * kv, 3 * kv]


def test_counters_after_a_known_run():
    """Three prompts of 5, 12 and 20 tokens in 8-token chunks, 4 tokens
    each, no shared prefix: 37 prompt tokens, 12 output tokens, and the
    longest prompt's 3 chunks in as many steps (every prompt advances a
    chunk every step)."""
    eng = _engine(slots=3, prefill_chunk=CHUNK)
    reqs = [Request(rid=i, prompt=[100 * (i + 1) + t for t in range(n)],
                    max_new_tokens=4) for i, n in enumerate((5, 12, 20))]
    steps = _drain(eng, reqs)
    c = eng.straggler_report()["counters"]
    assert c["steps"] == steps
    assert c["chunk_steps"] == 3
    assert c["prefill_tokens"] == 37
    assert c["decode_tokens"] == 12
    assert c["admitted"] == 3
    assert c["compiles"] >= 2 and c["compile_s"] > 0      # the two step shapes


def test_compiles_count_new_shapes_only():
    """Blocking whole-prompt prefill compiles once per prompt length: a
    second prompt of the same length compiles nothing, a new length does."""
    eng = _engine(slots=1, prefill_chunk=None)

    def serve(n, rid):
        _drain(eng, [Request(rid=rid, prompt=list(range(1, n + 1)),
                             max_new_tokens=3)])
        return eng.straggler_report()["counters"]["compiles"]

    first = serve(6, 0)
    assert first > 0
    assert serve(6, 1) == first
    assert serve(9, 2) > first
    c = eng.straggler_report()["counters"]
    assert c["prefill_tokens"] == 6 + 6 + 9 and c["decode_tokens"] == 9
    assert c["chunk_steps"] == 0 and c["admitted"] == 3


def test_compile_counter_is_process_wide():
    x = jax.numpy.arange(7.0)
    n0, s0 = compile_stats()
    jax.jit(lambda x: x * 3 + 1)(x).block_until_ready()
    n1, s1 = compile_stats()
    assert n1 == n0 + 1 and s1 > s0


def test_counters_start_at_zero():
    c = _engine(slots=2, prefill_chunk=CHUNK).straggler_report()["counters"]
    assert {k: v for k, v in c.items() if k != "compile_s"} == dict(
        steps=0, chunk_steps=0, decode_tokens=0, prefill_tokens=0,
        admitted=0, compiles=0)
