"""trace_reduce on synthetic traces: busy union, idle gaps, op time."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import trace_reduce as tr  # noqa: E402


def test_op_family_strips_hlo_text_and_suffix():
    assert tr.op_family("%mnf_event_matmul.349 = f32[1,1,4096]{2,1,0} "
                        "custom-call(s32[1152] %x)") == "mnf_event_matmul"
    assert tr.op_family("%fusion.6 = pred[16,7]{1,0} fusion(f32[128,784] "
                        "%copy.37), kind=kLoop") == "fusion"
    assert tr.op_family("%select_select_fusion = f32[16] fusion()") \
        == "select_select_fusion"
    assert tr.op_family("plain") == "plain"


def test_union_and_gaps():
    busy = tr.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert busy == [(0, 3), (5, 8)]
    assert tr.gaps(busy, 0, 10) == [(3, 5), (8, 10)]
    assert tr.gaps(busy, 1, 6) == [(3, 5)]
    assert tr.gaps([], 2, 4) == [(2, 4)]


def _trace(ops_per_device, spans):
    return tr.Trace(device_ops=ops_per_device,
                    spans=[("traced", 0, 100)] + spans)


def test_reduce_busy_ops_and_labelled_idle():
    ops = [("mnf_event_matmul", 10, 30), ("fusion", 25, 40),
           ("mnf_fire_compact", 60, 70), ("copy", 95, 120)]
    spans = [("submit", 0, 8), ("run_tick", 8, 75), ("wait", 75, 100)]
    s = tr.reduce(_trace([ops], spans))
    assert s.window_s == pytest.approx(100e-9)
    # union inside the window: [10, 40] + [60, 70] + [95, 100] = 45 ns
    assert s.busy_s == pytest.approx(45e-9)
    assert s.op_s["mnf_event_matmul"] == pytest.approx(20e-9)
    assert s.op_s["fusion"] == pytest.approx(15e-9)
    assert s.op_s["copy"] == pytest.approx(5e-9)     # clipped at the end
    assert s.kernel_s("mnf_") == pytest.approx(30e-9)
    # gaps: [0,10] submit 8 + run_tick 2 -> submit; [40,60] run_tick;
    # [70,95] wait 20 + run_tick 5 -> wait
    assert s.idle_s == pytest.approx({"submit": 10e-9, "run_tick": 20e-9,
                                      "wait": 25e-9})
    b = s.breakdown(top=2)
    assert [name for name, _ in b["device_ops"]] == ["mnf_event_matmul",
                                                     "fusion"]
    assert b["idle_gaps"][0][0] == "wait"


def test_reduce_averages_over_chips_and_labels_uncovered_gaps_other():
    a = [("mnf_event_pool", 0, 100)]
    b = [("mnf_event_pool", 0, 50)]
    s = tr.reduce(_trace([a, b], []))
    assert s.busy_s == pytest.approx(75e-9)
    assert s.idle_s == pytest.approx({"other": 25e-9})


def test_reduce_needs_one_window_and_some_device_op():
    with pytest.raises(ValueError, match="one bench.traced"):
        tr.reduce(tr.Trace(device_ops=[[("x", 0, 1)]], spans=[]))
    with pytest.raises(ValueError, match="no device operation"):
        tr.reduce(_trace([], []))
