"""Trace reduction: on hand-made intervals, and on a short trace recorded on
a TPU v5e during a traced run of the dense cell (committed beside this
file)."""

import gzip
from pathlib import Path

import pytest

from bench import devtrace
from bench.devtrace import FORWARD_SPAN, STEP_SPAN, Op, Trace

RECORDED = Path(__file__).resolve().parent / "data" / "v5e_chat.xplane.pb.gz"


def synthetic():
    tr = Trace()
    tr.spans = [(STEP_SPAN, 0.0, 1.0), (FORWARD_SPAN, 0.1, 0.9),
                (STEP_SPAN, 1.2, 2.0), (FORWARD_SPAN, 1.3, 1.8)]
    flash = "custom-call.3 flash_attention_paged"
    tr.devices["/device:TPU:0"] = [
        Op("fusion.1", -0.5, 0.2, "fusion.1"),        # clipped at the window
        Op("custom-call.3", 0.2, 0.5, flash),
        Op("fusion.1", 0.4, 0.6, "fusion.1"),          # overlaps: one union
        Op("copy.4", 0.95, 1.0, "copy.4"),
        Op("custom-call.3", 1.25, 1.4, flash),
        Op("copy.5", 1.85, 1.9, "copy.5"),
        Op("copy.2", 2.5, 2.9, "copy.2"),              # after the window
    ]
    return tr


def test_busy_is_the_union_inside_the_window():
    s = devtrace.summarize(synthetic(), {"flash": "flash_attention"})
    assert s.window_s == pytest.approx(2.0)
    assert s.busy_s == pytest.approx(0.85)
    assert s.kernel_s["flash"] == pytest.approx(0.45)
    assert s.kernel_calls["flash"] == 2
    assert s.device_ops[0] == ["custom-call.3", pytest.approx(0.45)]
    assert s.device_ops[1] == ["fusion.1", pytest.approx(0.4)]
    gaps = dict(s.idle_gaps)
    assert gaps["host in executor.forward"] == pytest.approx(0.35 + 0.45)
    assert gaps["host between steps"] == pytest.approx(0.25)
    assert gaps["host in engine step, outside forward"] == pytest.approx(0.1)
    assert sum(gaps.values()) == pytest.approx(s.window_s - s.busy_s)


def test_a_missing_kernel_is_an_error_not_zero():
    with pytest.raises(ValueError, match="not found"):
        devtrace.summarize(synthetic(), {"moe": "grouped_gemm"})


def test_no_step_or_no_device_op_is_an_error():
    tr = synthetic()
    tr.spans = []
    with pytest.raises(ValueError):
        devtrace.summarize(tr, {})
    tr = synthetic()
    tr.devices = {}
    with pytest.raises(ValueError):
        devtrace.summarize(tr, {})


def test_recorded_chip_trace(tmp_path):
    """The last 4 s of a traced window of qwen3-14b-10L.chat-poisson."""
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(gzip.decompress(RECORDED.read_bytes()))
    tr = devtrace.read(str(path))
    assert list(tr.devices) == ["/device:TPU:0"]
    s = devtrace.summarize(tr, {"flash": r"flash_attention|_flash_kernel"})
    assert 0 < s.busy_s <= s.window_s
    assert s.kernel_calls["flash"] > 0
    assert 0 < s.kernel_s["flash"] < s.busy_s
    assert sum(g for _, g in s.idle_gaps) == pytest.approx(s.window_s - s.busy_s, rel=1e-6)
    assert len(s.device_ops) == 10
    assert s.device_ops[0][0].startswith("flash_attention_paged bf16[")
    # 25 steps of 10 layers, one kernel call per layer
    assert s.kernel_calls["flash"] == 250
    assert s.busy_s / s.window_s == pytest.approx(3.8225542110000106 / 3.9935293819999997, rel=1e-3)
