"""program_trace: the program's spans and layer scopes, and their readers."""
import glob
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import counts, program_trace as pt, trace_reduce as tr  # noqa: E402
from bench.metrics import Run  # noqa: E402
from bench.metrics import (conv_roofline, fc_roofline,  # noqa: E402
                           pool_roofline, serve_host_ms)

S = 1_000_000_000          # ns per second

# One tick inside the window [0, 100]: the benchmark's spans, the program's
# nested inside run_tick, and two device ops inside the tick's wait.
BENCH_SPANS = [("bench.submit", 0, 10), ("bench.run_tick", 10, 90)]
PROGRAM_SPANS = [("mnf.serve.tick", 12, 88), ("mnf.serve.batch", 14, 86),
                 ("mnf.serve.pad", 15, 20), ("mnf.serve.place", 20, 25),
                 ("mnf.serve.dispatch", 25, 30), ("mnf.serve.wait", 30, 80),
                 ("mnf.serve.fetch", 80, 83), ("mnf.serve.complete", 83, 85)]
OPS = [("mnf_event_matmul.3", 32, 50), ("fusion.6", 55, 78)]


def _program(spans, ops=OPS, window=(0, 100), layers=None):
    return pt.Program(workload="w", window=window,
                      spans=sorted([(n, a, b, {}) for n, a, b in spans],
                                   key=lambda s: (s[1], -s[2])),
                      device_ops=[list(ops)], layers=layers)


def _reduced(spans, ops=OPS):
    """What trace_reduce makes of the same trace: families and the
    benchmark's spans only, prefixes off."""
    bench = [(n[len(tr.SPAN_PREFIX):], a, b) for n, a, b in spans
             if n.startswith(tr.SPAN_PREFIX)]
    return tr.reduce(tr.Trace(
        device_ops=[[(tr.op_family(f"%{n} = f32[] x()"), a, b)
                     for n, a, b in ops]],
        spans=[("traced", 0, 100)] + bench))


def test_program_spans_change_nothing_trace_reduce_reports():
    with_them = _reduced(BENCH_SPANS + PROGRAM_SPANS)
    without = _reduced(BENCH_SPANS)
    for field in ("window_s", "busy_s", "op_s", "idle_s"):
        assert getattr(with_them, field) == getattr(without, field), field
    # trace_reduce names each whole gap by the span covering most of it ...
    assert without.idle_s == pytest.approx({"run_tick": 59e-9})
    # ... the program's view apportions the same idle time, only finer.
    prog = _program(BENCH_SPANS + PROGRAM_SPANS)
    assert sum(prog.idle_by_span().values()) == pytest.approx(
        sum(without.idle_s.values()))
    busy_s = sum(b - a for a, b in prog.busy()[0]) * 1e-9
    assert busy_s == pytest.approx(without.busy_s)
    assert prog.window_s == pytest.approx(without.window_s)


def test_idle_by_span_goes_to_the_innermost_span():
    idle = _program(BENCH_SPANS + PROGRAM_SPANS).idle_by_span()
    want = {"bench.submit": 10, "bench.run_tick": 4, "mnf.serve.tick": 4,
            "mnf.serve.batch": 2, "mnf.serve.pad": 5, "mnf.serve.place": 5,
            "mnf.serve.dispatch": 5, "mnf.serve.wait": 2 + 5 + 2,
            "mnf.serve.fetch": 3, "mnf.serve.complete": 2, "other": 10}
    assert idle == pytest.approx({k: v * 1e-9 for k, v in want.items()})


def test_a_program_span_beats_a_benchmark_span_that_starts_inside_it():
    spans = [("mnf.serve.wait", 0, 60), ("bench.wait", 20, 100)]
    idle = _program(spans, ops=[("copy.1", 40, 45)]).idle_by_span()
    assert idle == pytest.approx({"mnf.serve.wait": 55e-9,
                                  "bench.wait": 40e-9})


def test_tick_host_time_leaves_out_the_wait():
    prog = _program(BENCH_SPANS + PROGRAM_SPANS)
    assert prog.tick_host_ms() == pytest.approx([(76 - 50) * 1e-6])
    # A tick cut by the window's end is not counted.
    assert _program(PROGRAM_SPANS, window=(0, 50)).tick_host_ms() == []


def test_layer_op_time_through_the_map():
    prog = _program(BENCH_SPANS, ops=OPS + [("copy.9", 85, 95)],
                    layers={"mnf_event_matmul.3": "mnf.L00.fc",
                            "fusion.6": "mnf.L01.fc"})
    assert prog.layer_op_s() == pytest.approx(
        {"mnf.L00.fc": 18e-9, "mnf.L01.fc": 23e-9, pt.UNSCOPED: 10e-9})
    b = prog.breakdown()
    assert b["layers"][0][0] == "mnf.L01.fc"
    assert b["layer_share"] == pytest.approx(41 / 51)
    assert b["unscoped_ops"] == [["copy", pytest.approx(10e-9)]]


def test_instruction_names_of_device_ops():
    assert pt.instruction("%mnf_event_matmul.349 = f32[1,4096]{1,0} "
                          "custom-call(s32[1152] %x)") \
        == "mnf_event_matmul.349"
    assert pt.instruction("%fusion.6 = pred[16,7]{1,0} fusion()") == "fusion.6"
    assert pt.instruction("copy-start.2") == "copy-start.2"


def test_layer_bounds_sum_to_the_step_bound():
    cnt = _counts()
    peaks = dict(bf16_flops_per_s=1e3, hbm_bytes_per_s=1e2)
    idx = np.array([0, 1])
    per_layer = pt.layer_bound_s(cnt, idx, peaks)
    assert per_layer == pytest.approx([9.6, 4.0, 1.12])
    assert per_layer.sum() == pytest.approx(
        counts.step_bound_s(cnt, idx, peaks))


def _counts():
    return dict(macs=np.array([[10, 20], [0, 0], [5, 5]], np.int64),
                elements=np.array([[100, 100], [50, 50], [10, 10]],
                                  np.int64),
                weight_nnz=np.array([40, 0, 8], np.int64),
                kinds=["conv", "pool", "fc"])


def _run():
    """A run that served one batch of pool inputs 0 and 1 inside the trace:
    per-layer bounds 9.6 s (conv), 4.0 s (pool), 1.12 s (fc)."""
    from bench.traffic import Window
    win = Window(start=0.0, end=10.0, done=np.array([5.0, 5.0]),
                 pool_idx=np.array([0, 1]), tick_sizes=[2])
    summary = tr.Summary(window_s=2000.0, busy_s=1700.0, op_s={},
                         idle_s={})
    return Run(setup_s=1.0, window=win, buckets=(2,), traced_until=10.0,
               trace=summary, counts=_counts(),
               peaks=dict(bf16_flops_per_s=1e3, hbm_bytes_per_s=1e2))


def _served_program():
    ops = [("mnf_event_matmul.1", 0, 480 * S), ("fusion.2", 480 * S, 960 * S),
           ("mnf_event_pool.3", 1000 * S, 1800 * S),
           ("mnf_event_matmul.4", 1900 * S, 2000 * S),
           ("copy.5", 1800 * S, 1900 * S)]
    layers = {"mnf_event_matmul.1": "mnf.L00.conv",
              "fusion.2": "mnf.L00.conv", "mnf_event_pool.3": "mnf.L01.pool",
              "mnf_event_matmul.4": "mnf.L02.fc"}
    spans = [("mnf.serve.tick", 0, 2000 * S), ("mnf.serve.wait", 10 * S,
                                               1990 * S)]
    return _program(spans, ops=ops, window=(0, 2000 * S), layers=layers)


def test_readers_on_a_hand_built_run(monkeypatch):
    monkeypatch.setattr(pt, "for_run", lambda run: _served_program())
    run = _run()
    assert serve_host_ms.read(run) == pytest.approx(20e3)
    assert conv_roofline.read(run) == pytest.approx(100 * 9.6 / 960)
    assert pool_roofline.read(run) == pytest.approx(100 * 4.0 / 800)
    assert fc_roofline.read(run) == pytest.approx(100 * 1.12 / 100)


def test_readers_find_nothing_in_a_program_without_spans(monkeypatch):
    run = _run()
    monkeypatch.setattr(pt, "newest", lambda workload=None: "saved")
    monkeypatch.setitem(pt._LOADED, "saved", _served_program())
    assert pt.for_run(run) is not None and serve_host_ms.read(run) > 0
    # Another window than the run's: not this run's trace.
    run.trace.window_s = 1999.0
    assert pt.for_run(run) is None
    assert all(m.read(run) is None for m in (serve_host_ms, conv_roofline,
                                             pool_roofline, fc_roofline))
    # A program that names no ticks (an older checkout).
    run.trace.window_s = 2000.0
    monkeypatch.setitem(pt._LOADED, "saved", _program(
        BENCH_SPANS, window=(0, 2000 * S)))
    assert pt.for_run(run) is None
    assert all(m.read(run) is None for m in (serve_host_ms, conv_roofline,
                                             pool_roofline, fc_roofline))
    # An untraced run.
    run.trace = None
    assert serve_host_ms.read(run) is None and fc_roofline.read(run) is None


def test_a_network_without_the_kind_reads_nothing(monkeypatch):
    monkeypatch.setattr(pt, "for_run", lambda run: _served_program())
    run = _run()
    run.counts["kinds"] = ["fc", "fc", "fc"]
    assert conv_roofline.read(run) is None
    assert pool_roofline.read(run) is None


def test_a_real_trace_keeps_program_spans_apart(tmp_path):
    """On a CPU profile of one served tick inside the benchmark's spans:
    trace_reduce sees the benchmark's spans alone, program_trace the
    program's too, with their counters, on the same window."""
    import jax
    from bench import system
    system.require_program()
    from repro.models.mlp import MLP_MINI, init_mlp_params
    from repro.serving import ServeEngine, ServeEngineConfig
    eng = ServeEngine(MLP_MINI, init_mlp_params(jax.random.PRNGKey(0),
                                                MLP_MINI, 0.5),
                      ServeEngineConfig(buckets=(4,)))
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.traced"):
            with jax.profiler.TraceAnnotation("bench.submit"):
                for _ in range(3):
                    eng.submit(np.ones(MLP_MINI.in_features, np.float32))
            with jax.profiler.TraceAnnotation("bench.run_tick"):
                eng.run_tick()
    finally:
        jax.profiler.stop_trace()
    path = tr.find_xplane(str(tmp_path))
    bench = tr.load(path)
    assert sorted(s[0] for s in bench.spans) == ["run_tick", "submit",
                                                 "traced"]
    prog = pt.load(path, "w")
    window, = [(a, b) for n, a, b in bench.spans if n == "traced"]
    assert prog.window == window
    names = [s[0] for s in prog.spans]
    assert names[:2] == ["bench.submit", "bench.run_tick"]
    assert names.count("mnf.serve.tick") == 1 and prog.has_program_spans()
    batch, = [s[3] for s in prog.spans if s[0] == "mnf.serve.batch"]
    assert (batch["bucket"], batch["rows"]) == (4, 3)
    assert len(prog.tick_host_ms()) == 1


def test_op_layers_of_a_cell_follow_its_configuration():
    layers = pt.op_layers("lenet300-b128-closed")
    assert set(layers.values()) == {"mnf.L00.fc", "mnf.L01.fc",
                                    "mnf.L02.fc"}


def test_cli_names_its_usage_and_a_missing_trace(capsys):
    assert pt.main([]) == 2
    assert "usage" in capsys.readouterr().err
    assert pt.main(["no-such-cell"]) == 1
    assert glob.glob(os.path.join(pt.TRACES, "no-such-cell")) == []
