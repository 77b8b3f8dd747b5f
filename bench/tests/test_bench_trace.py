"""Trace reduction: busy union, idle share, kernel time and labelled idle
gaps, on hand-made events and on a small trace recorded on a TPU v5e."""
from pathlib import Path

import pytest

from bench import trace_reduce as tr

RECORDED = Path(__file__).resolve().parent / "data" / "small.xplane.pb"


def _ev(name, start, dur):
    return tr.Event(name, float(start), float(dur))


def test_merge_and_clip():
    assert tr.merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr.clip([(0, 3), (5, 8)], 2, 6) == [(2, 3), (5, 6)]


def test_summarize_hand_made_trace():
    device = [
        _ev("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %x)", 10, 20),    # 10-30
        _ev("%convolution.2 = f32[8]{0} convolution(f32[8]{0} %y)", 25, 15),  # 25-40
        _ev('%masked_agg.3 = f32[8192]{0} custom-call(u32[3,8192]{1,0} %a), '
            'custom_call_target="tpu_custom_call"', 60, 10),     # 60-70
        _ev("%fusion.4 = f32[8]{0} fusion(f32[8]{0} %z)", 95, 20),    # 95-115, clipped
    ]
    spans = [
        _ev("fed.window", 0, 100),
        _ev("fed.round", 0, 100),
        _ev("fed.train", 0, 45),
        _ev("fed.aggregate", 45, 30),
    ]
    modules = [_ev("jit_run(123)", 5, 40), _ev("jit_masked_aggregate(9)", 58, 14)]
    s = tr.summarize(tr.Trace({"/device:TPU:0": device}, {"/device:TPU:0": modules}, spans))
    assert s.window_ns == 100
    assert s.busy_ns == {"/device:TPU:0": 30 + 10 + 5}  # 10-40, 60-70, 95-100
    assert s.busy_s == pytest.approx(45e-9)
    assert s.kernel_ns == {"masked_agg": 10} and s.kernel_runs == {"masked_agg": 1}
    assert s.op_ns["fusion.4"] == 5 and s.op_ns["convolution.2"] == 15
    assert s.module_ns == {"jit_run": 40, "jit_masked_aggregate": 14}
    # gaps: 0-10 (train), 40-60 (mid 50: aggregate), 70-95 (mid 82.5: round)
    assert s.idle_ns_by_span == {"train": 10, "aggregate": 20, "round": 25}
    assert s.gaps[0] == ("round", 25)


def test_no_window_or_no_device_work_gives_nothing():
    spans = [_ev("fed.window", 0, 10)]
    assert tr.summarize(tr.Trace({}, {}, spans)) is None
    assert tr.summarize(tr.Trace({"/device:TPU:0": [_ev("f", 1, 2)]}, {}, [])) is None


def test_recorded_tpu_trace():
    """Two rounds, each a small matrix product under ``fed.train`` and one
    ``masked_agg`` call (k = 3, P = 8,192) under ``fed.aggregate``."""
    s = tr.summarize(tr.load(str(RECORDED)))
    assert s is not None and list(s.busy_ns) == ["/device:TPU:0"]
    assert 0 < s.busy_s < s.window_s
    assert s.kernel_runs == {"masked_agg": 2}
    assert s.kernel_ns["masked_agg"] > 0
    assert set(s.idle_ns_by_span) <= {"round", "train", "aggregate", "outside spans"}
    assert sum(s.idle_ns_by_span.values()) == pytest.approx(s.window_ns - s.busy_ns["/device:TPU:0"])
