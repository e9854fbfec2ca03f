"""The program's spans in a trace: the idle split and the readers built on
it, on hand-made intervals, on two traces recorded on a TPU v5e (the chat
cell's, which predates the program's spans as a parent checkout's trace
does, and the longdoc cell's, which has them), and on a CPU trace of a tiny
engine.  Also pins what ``bench/devtrace.py`` reads from the chat trace,
which this reduction leaves as it was."""

import functools
import gzip
import importlib.util
from pathlib import Path

import pytest

from bench import devtrace, progspans, run as bench_run, spec
from bench.devtrace import FORWARD_SPAN, STEP_SPAN, Op, Trace
from bench.progspans import Span

HERE = Path(__file__).resolve().parent
RECORDED = HERE / "data" / "v5e_chat.xplane.pb.gz"
FLASH = {"flash": r"flash_attention|_flash_kernel"}


def reader(name):
    path = HERE.parent / "metrics" / f"{name}.py"
    s = importlib.util.spec_from_file_location(f"test_reader_{name}", path)
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod


def step_spans(t0, t1, put, launch, wait, sample):
    return [Span("engine.step", t0, t1, {"step": 1}),
            Span("executor.forward", put[0], wait[1]),
            Span("executor.put", *put, {"stage": 0}),
            Span("executor.launch", *launch, {"stage": 0}),
            Span("executor.wait", *wait, {"stage": 0}),
            Span("engine.sample", *sample)]


def synthetic():
    tr = Trace()
    tr.spans = [(STEP_SPAN, 0.0, 1.0), (FORWARD_SPAN, 0.1, 0.8),
                (STEP_SPAN, 1.2, 2.0), (FORWARD_SPAN, 1.22, 1.9)]
    tr.devices["/device:TPU:0"] = [
        Op("fusion.1", -0.1, 0.12, "fusion.1"),
        Op("custom-call.3", 0.3, 0.5, "custom-call.3 flash_attention_paged"),
        Op("copy.4", 0.7, 1.0, "copy.4"),
        Op("custom-call.3", 1.3, 1.9, "custom-call.3 flash_attention_paged"),
    ]
    spans = (step_spans(0.01, 0.99, (0.1, 0.15), (0.15, 0.25), (0.25, 0.8),
                        (0.8, 0.95))
             + step_spans(1.21, 1.92, (1.22, 1.24), (1.24, 1.28), (1.28, 1.9),
                          (1.9, 1.91)))
    spans.sort(key=lambda s: (s.start, -s.end))
    return tr, spans


def test_idle_goes_to_the_innermost_span_at_each_gap():
    tr, spans = synthetic()
    s = progspans.split(tr, spans)
    # gaps: 0.12-0.3 in executor.launch; 0.5-0.7 in executor.wait;
    # 1.0-1.3 between steps; 1.9-2.0 inside bench.step, after engine.step
    assert s.idle_s == pytest.approx({"executor.launch": 0.18,
                                      "executor.wait": 0.2, "none": 0.4})
    assert s.none_in_step_s == pytest.approx(0.1)
    assert s.window_s == pytest.approx(2.0)
    busy = devtrace.summarize(tr, FLASH).busy_s
    assert sum(s.idle_s.values()) == pytest.approx(s.window_s - busy, rel=1e-12)
    assert s.forwards == 2
    assert s.launch_s == pytest.approx(0.05 + 0.1 + 0.02 + 0.04)
    assert s.longest_step_s == pytest.approx(1.0)
    assert s.longest_step == pytest.approx({
        "executor.wait": 0.55, "engine.sample": 0.15, "engine.step": 0.13,
        "executor.launch": 0.1, "executor.put": 0.05, "none": 0.02})


def test_wait_vs_launch_idle_in_the_readers(monkeypatch):
    """The same device gaps read as launch time or as wait idle by the span
    the host was in."""
    tr, spans = synthetic()
    moved = [Span("executor.wait" if s.name == "executor.launch" else
                  "executor.launch" if s.name == "executor.wait" else s.name,
                  s.start, s.end, s.meta) for s in spans]
    run = spec.Run(cell=None, window=None, setup_s=0.0, peak={}, chips=1,
                   trace=devtrace.summarize(tr, FLASH))
    out = {}
    for key, sp in (("as built", spans), ("swapped", moved)):
        monkeypatch.setattr(progspans, "of_run",
                            functools.partial(lambda sp, _: progspans.split(tr, sp), sp))
        out[key] = (reader("launch_ms").reduce(run),
                    reader("wait_idle_ms").reduce(run))
    assert out["as built"] == pytest.approx((1e3 * 0.21 / 2, 1e3 * 0.2 / 2))
    # launch spans now long (0.55 + 0.62 s), the 0.18 s gap now under wait
    assert out["swapped"] == pytest.approx((1e3 * (0.05 + 0.55 + 0.02 + 0.62) / 2,
                                            1e3 * 0.18 / 2))


def test_innermost_pieces_tile_the_outer_spans():
    tr, spans = synthetic()
    pieces = progspans.innermost(spans)
    assert all(a < b for a, b, _ in pieces)
    assert all(p[1] <= q[0] for p, q in zip(pieces, pieces[1:]))
    assert sum(b - a for a, b, _ in pieces) == pytest.approx(0.98 + 0.71)


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    d = tmp_path_factory.mktemp("recorded")
    path = d / "t.xplane.pb"
    path.write_bytes(gzip.decompress(RECORDED.read_bytes()))
    return d, str(path)


def test_devtrace_reads_the_recorded_trace_as_before(recorded):
    """Every field of devtrace's summary of the recorded v5e trace."""
    s = devtrace.summarize(devtrace.read(recorded[1]), FLASH)
    assert s.window_s == pytest.approx(3.9935293819999997, rel=1e-12)
    assert s.busy_s == pytest.approx(3.8225542110000106, rel=1e-12)
    assert s.kernel_s == pytest.approx({"flash": 2.814779888999999}, rel=1e-12)
    assert s.kernel_calls == {"flash": 250}
    assert [n for n, _ in s.device_ops] == [
        "flash_attention_paged bf16[640,64,128]",
        "flash_attention_paged bf16[640,8,128]",
        "fusion bf16[16,64,17408]", "fusion (f32[16,64]",
        "fusion bf16[16,64,151936]", "copy bf16[8,41024,128]",
        "copy bf16[8,641,64,128]", "fusion (f32[16]", "fusion f32[16,64,5120]",
        "fusion bf16[16,17408]"]
    assert [t for _, t in s.device_ops] == pytest.approx([
        1.869100835999999, 0.9456790529999999, 0.2888352160000034,
        0.18577220100000252, 0.12552681500000037, 0.09416978099999443,
        0.09312054600000144, 0.0545338040000003, 0.04311427999999941,
        0.02369513199999973], rel=1e-12)
    assert [n for n, _ in s.idle_gaps] == [
        "host in executor.forward", "host in engine step, outside forward"]
    assert [t for _, t in s.idle_gaps] == pytest.approx(
        [0.09194311100000974, 0.07903205999999852], rel=1e-12)


def test_a_trace_without_program_spans_reads_nothing(recorded, monkeypatch,
                                                     capsys):
    """The recorded trace holds no program span, as a trace of a program
    without them: all idle is ``none``, it still sums to the window's, and
    both readers report nothing."""
    d, path = recorded
    s = progspans.split(devtrace.read(path), progspans.read_spans(path))
    summary = devtrace.summarize(devtrace.read(path), FLASH)
    assert list(s.idle_s) == ["none"] and s.forwards == 0 and s.launch_s == 0
    assert sum(s.idle_s.values()) == pytest.approx(
        summary.window_s - summary.busy_s, rel=1e-6)
    monkeypatch.setattr(progspans, "TRACE_DIR", d)
    run = spec.Run(cell=None, window=None, setup_s=0.0, peak={}, chips=1,
                   trace=summary)
    assert reader("launch_ms").reduce(run) is None
    assert reader("wait_idle_ms").reduce(run) is None
    lines = [x for x in capsys.readouterr().out.splitlines()
             if x.startswith("[bench] ")]
    assert len(lines) == 2          # logged once for both readers
    assert "program spans: device idle" in lines[0]
    assert lines[1].startswith("[bench] longest traced step")
    untraced = spec.Run(cell=None, window=None, setup_s=0.0, peak={}, chips=1)
    assert reader("launch_ms").reduce(untraced) is None


def test_the_trace_dir_is_the_one_run_writes():
    assert progspans.TRACE_DIR == bench_run.TRACE_DIR


def test_spans_of_a_tiny_engine_traced_on_the_cpu(tmp_path):
    """The program's spans as the profiler writes them: names, stage
    metadata, and innermost pieces that tile each engine step."""
    import jax

    from repro.configs import get_config
    from repro.core.devices import tpu_slice_cluster
    from repro.core.placement import PlanConfig
    from repro.models.model import build_model
    from repro.serving.engine import Request, ServingEngine

    cfg = get_config("llama3.2-1b").smoke()
    eng = ServingEngine(cfg, build_model(cfg).init(jax.random.PRNGKey(0)),
                        tpu_slice_cluster(n_slices=1), slots=2, max_len=64,
                        eos_id=-1, prefill_chunk=8,
                        plan_cfg=PlanConfig(method="etf", kv_page_tokens=8))
    eng.submit(Request(rid=0, prompt=list(range(1, 12)), max_new_tokens=2))
    eng.step()                                   # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        while eng.step():
            pass
    spans = progspans.read_spans(devtrace.find_xplane(str(tmp_path)))
    names = {s.name for s in spans}
    assert {"engine.step", "engine.admit", "engine.batch", "executor.forward",
            "executor.put", "executor.launch", "executor.wait", "engine.sample",
            "engine.retire"} <= names
    for s in spans:
        if s.name in ("executor.put", "executor.launch", "executor.wait"):
            assert s.meta == {"stage": 0}
        if s.name == "engine.step":
            assert set(s.meta) == {"step"}
    steps = [s for s in spans if s.name == "engine.step"]
    pieces = progspans.innermost(spans)
    assert sum(b - a for a, b, _ in pieces) == pytest.approx(
        sum(s.end - s.start for s in steps), rel=1e-9)


SPANS_TRACE = HERE / "data" / "v5e_longdoc_spans.xplane.pb.gz"
IN_FORWARD = ("executor.forward", "executor.put", "executor.launch",
              "executor.wait")


def test_recorded_trace_with_program_spans(tmp_path):
    """The last 4 s of a traced window of qwen2-moe-a2.7b-6L.longdoc-closed,
    with the program's spans: 52 steps of one stage, every span once a
    step; the idle split refines devtrace's three labels exactly."""
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(gzip.decompress(SPANS_TRACE.read_bytes()))
    tr, spans = devtrace.read(str(path)), progspans.read_spans(str(path))
    summary = devtrace.summarize(tr, FLASH)
    assert summary.kernel_calls == {"flash": 312}        # 52 steps x 6 layers
    names = [s.name for s in spans]
    assert {n: names.count(n) for n in set(names)} == dict.fromkeys(
        ["engine.step", "engine.admit", "engine.batch", "executor.forward",
         "executor.put", "executor.launch", "executor.wait", "engine.sample",
         "engine.retire"], 52)
    assert all(s.meta == {"stage": 0} for s in spans
               if s.name in ("executor.put", "executor.launch", "executor.wait"))
    s = progspans.split(tr, spans)
    assert sum(s.idle_s.values()) == pytest.approx(
        summary.window_s - summary.busy_s, rel=1e-6)
    assert s.none_in_step_s == 0.0
    gaps = dict(summary.idle_gaps)
    assert sum(v for k, v in s.idle_s.items() if k in IN_FORWARD) == \
        pytest.approx(gaps["host in executor.forward"], rel=1e-9)
    assert s.idle_s["none"] == pytest.approx(gaps["host between steps"], rel=1e-9)
    assert s.forwards == 52
    assert 1e3 * s.launch_s / s.forwards == pytest.approx(3.32716, rel=1e-5)
    assert 1e3 * s.idle_s["executor.wait"] / s.forwards == pytest.approx(
        1.45469, rel=1e-5)
    assert max(s.idle_s, key=s.idle_s.get) == "executor.put"
