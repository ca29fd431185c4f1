"""The program's own spans and layer scopes in a traced run's profile.

``run.py --trace 1`` saves the profile under ``bench/out/trace/<cell>/`` and
hands the metric readers its summary (``trace_reduce``), which keeps only
the benchmark's ``bench.*`` spans and op families. The program names more
than that, where it has them:

* host spans ``mnf.serve.*`` per tick and per batch (``tick``, ``batch``,
  ``pad``, ``place``, ``dispatch``, ``wait``, ``fetch``, ``complete``,
  ``compile``), their counters as event stats;
* a named scope ``mnf.L<i>.<kind>`` per network layer (``i`` the index in
  the configuration's ``layers``) and ``mnf.out``, which reach the compiled
  step's instructions; ``ServeEngine.op_layers(bucket)`` maps the
  instruction names that the trace gives each device op to those scopes.

This module reads both back from the same profile:

* ``tick_host_ms``: per traced tick, ``mnf.serve.tick`` less its
  ``mnf.serve.wait`` children, the host's own time in the tick;
* ``layer_op_s``: device op time per layer scope, averaged over the chips;
* ``idle_by_span``: each stretch of the window in which the device ran
  nothing, split across the innermost span covering each instant, a
  program span before a benchmark span, ``other`` where none covers it;
* ``layer_bound_s``: the least device seconds of each layer of one step.

A program without those spans or that method gives nothing to read, and
the readers built on this module return None.

    python3 bench/program_trace.py <workload>

prints the breakdown of the cell's last saved trace as JSON (on the chip
that ran it: the layer map is read from the compiled step).
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import json
import os
import re
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import counts, trace_reduce  # noqa: E402

TRACES = os.path.join(ROOT, "bench", "out", "trace")
PROGRAM = "mnf."
BENCH = trace_reduce.SPAN_PREFIX
WINDOW = BENCH + trace_reduce.WINDOW_SPAN
TICK, WAIT = "mnf.serve.tick", "mnf.serve.wait"
#: Where an op's instruction has no layer scope in the map.
UNSCOPED = "unscoped"
_INSTRUCTION = re.compile(r"^%?([^\s=]+)\s*=")
_SUFFIX = re.compile(r"\.\d+$")
USAGE = "usage: python3 bench/program_trace.py <workload>"


def instruction(event_name: str) -> str:
    """``%mnf_event_matmul.3 = f32[...] custom-call(...)`` ->
    ``mnf_event_matmul.3``."""
    m = _INSTRUCTION.match(event_name)
    return m.group(1) if m else event_name.split(" ")[0].lstrip("%")


@dataclasses.dataclass
class Program:
    """One traced window, intervals in ns on the trace's clock."""

    workload: str
    window: tuple             # (start, end) of the bench.traced span
    spans: list               # [(name, start, end, stats)], by start
    device_ops: list          # per chip: [(instruction, start, end)]
    layers: dict | None = None    # instruction -> scope (op_layers)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def has_program_spans(self) -> bool:
        return any(s[0] == TICK for s in self.spans)

    def tick_host_ms(self) -> list[float]:
        """Host self time of every tick inside the window, in ms."""
        lo, hi = self.window
        waits = sorted((s[1], s[2]) for s in self.spans if s[0] == WAIT)
        starts = [w[0] for w in waits]
        out = []
        for name, t0, t1, _ in self.spans:
            if name != TICK or t0 < lo or t1 > hi:
                continue
            waited = sum(min(w1, t1) - w0 for w0, w1 in
                         waits[bisect.bisect_left(starts, t0):
                               bisect.bisect_left(starts, t1)])
            out.append((t1 - t0 - waited) * 1e-6)
        return out

    def busy(self) -> list[list]:
        """Per chip, the union of its op intervals inside the window."""
        lo, hi = self.window
        return [trace_reduce.union((max(a, lo), min(b, hi))
                                   for _, a, b in ops if b > lo and a < hi)
                for ops in self.device_ops]

    def idle_by_span(self) -> dict[str, float]:
        """Idle seconds under the innermost span, averaged over chips."""
        lo, hi = self.window
        out: dict[str, float] = {}
        for busy in self.busy():
            for name, ns in _apportion(trace_reduce.gaps(busy, lo, hi),
                                       self.spans).items():
                out[name] = out.get(name, 0.0) + ns * 1e-9
        n = max(len(self.device_ops), 1)
        return {k: v / n for k, v in out.items()}

    def layer_op_s(self) -> dict[str, float]:
        """Device op seconds per layer scope (``UNSCOPED`` for ops the map
        does not hold), averaged over the chips."""
        lo, hi = self.window
        layers = self.layers or {}
        out: dict[str, float] = {}
        for ops in self.device_ops:
            for name, a, b in ops:
                if b > lo and a < hi:
                    scope = layers.get(name, UNSCOPED)
                    out[scope] = (out.get(scope, 0.0)
                                  + (min(b, hi) - max(a, lo)) * 1e-9)
        n = max(len(self.device_ops), 1)
        return {k: v / n for k, v in out.items()}

    def breakdown(self, top: int = 12) -> dict:
        layer_s = self.layer_op_s()
        busy_s = sum(sum(b - a for a, b in chip) for chip in self.busy())
        busy_s *= 1e-9 / max(len(self.device_ops), 1)
        op_s = sum(layer_s.values())
        ticks = self.tick_host_ms()
        unscoped: dict[str, float] = {}
        for ops in self.device_ops:
            for name, a, b in ops:
                if name not in (self.layers or {}):
                    fam = _SUFFIX.sub("", name)
                    unscoped[fam] = unscoped.get(fam, 0.0) + (b - a) * 1e-9

        def largest(d):
            return [[k, v] for k, v in sorted(d.items(),
                                              key=lambda kv: -kv[1])][:top]
        return dict(
            window_s=self.window_s, busy_s=busy_s,
            ticks=len(ticks),
            tick_host_ms_median=float(np.median(ticks)) if ticks else None,
            layers=largest(layer_s),
            layer_share=(op_s - layer_s.get(UNSCOPED, 0.0)) / op_s
            if op_s else None,
            unscoped_ops=largest(unscoped),
            idle_by_span=largest(self.idle_by_span()))


def _innermost(active: dict, spans: list) -> str:
    """The program span that started last, else the benchmark span that
    started last, among the active ones."""
    best = max(active, key=lambda i: (spans[i][0].startswith(PROGRAM),
                                      spans[i][1], -spans[i][2]))
    return spans[best][0]


def _apportion(gaps: list, spans: list) -> dict[str, float]:
    """ns of ``gaps`` (sorted, disjoint) under the innermost span covering
    each instant; ``other`` where none does."""
    events = [(g0, 1, -1) for g0, _ in gaps] + [(g1, 0, -1) for _, g1 in gaps]
    for i, (_, s0, s1, _) in enumerate(spans):
        if s1 > s0:
            events += [(s0, 1, i), (s1, 0, i)]
    events.sort(key=lambda e: (e[0], e[1]))
    out: dict[str, float] = {}
    active: dict[int, None] = {}
    in_gap, t_prev = False, None
    for t, opens, i in events:
        if in_gap and t > t_prev:
            name = _innermost(active, spans) if active else "other"
            out[name] = out.get(name, 0.0) + (t - t_prev)
        t_prev = t
        if i < 0:
            in_gap = bool(opens)
        elif opens:
            active[i] = None
        else:
            active.pop(i, None)
    return out


def load(path: str, workload: str = "") -> Program:
    """Read a ``*.xplane.pb``: the window, the host spans of the program
    and the benchmark, and each chip's ops by instruction name."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, spans, windows = [], [], []
    for plane in data.planes:
        if trace_reduce.DEVICE_PLANE.match(plane.name):
            ops = [(instruction(e.name), e.start_ns,
                    e.start_ns + e.duration_ns)
                   for line in plane.lines
                   if line.name == trace_reduce.OPS_LINE
                   for e in line.events]
            if ops:
                devices.append(ops)
        elif plane.name == trace_reduce.HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    name = e.name
                    if name == WINDOW:
                        windows.append((e.start_ns,
                                        e.start_ns + e.duration_ns))
                    elif name.startswith(PROGRAM):
                        spans.append((name, e.start_ns,
                                      e.start_ns + e.duration_ns,
                                      dict(e.stats)))
                    elif name.startswith(BENCH):
                        spans.append((name, e.start_ns,
                                      e.start_ns + e.duration_ns, {}))
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW} span, found {len(windows)}")
    spans.sort(key=lambda s: (s[1], -s[2]))
    return Program(workload=workload, window=windows[0], spans=spans,
                   device_ops=devices)


def op_layers(workload: str) -> dict | None:
    """Instruction -> layer scope of the compiled step of every bucket of
    ``workload``, from a replica built like the served one, with zero
    weights: the same module, so the same executable, read back from the
    compile cache. None where the program has no ``op_layers``."""
    from bench import catalog, system
    from bench import reference as ref
    system.require_program()
    from repro.serving import ServeEngine
    if not hasattr(ServeEngine, "op_layers"):
        return None
    cell = catalog.load_cell(workload)
    cfg = cell.config
    params = [None if s is None else np.zeros(s, np.float32)
              for s in (ref.weight_shape(layer, shape) for layer, shape in
                        zip(cfg["layers"], ref.layer_inputs(cfg)))]
    replica = system.Replica(cfg, params, cell.traffic["buckets"],
                             require_tpu=False)
    merged: dict[str, str] = {}
    clash = set()
    for b in cell.traffic["buckets"]:
        for name, scope in replica.engine.op_layers(b).items():
            if merged.setdefault(name, scope) != scope:
                clash.add(name)
    # Buckets whose executables give one name two scopes: such ops stay
    # unattributed rather than guessed.
    return {k: v for k, v in merged.items() if k not in clash}


#: ``run.py`` hands each metric's reader the run alone, so the readers of
#: one run share the trace (and its layer map) through this cache.
_LOADED: dict[str, Program] = {}


def newest(workload: str | None = None) -> str | None:
    """The most recently written trace of ``workload`` (of any cell)."""
    found = glob.glob(os.path.join(TRACES, workload or "*", "plugins",
                                   "profile", "*", "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def program(path: str) -> Program:
    """The trace at ``path``, loaded once per process."""
    if path not in _LOADED:
        workload = os.path.relpath(path, TRACES).split(os.sep)[0]
        _LOADED[path] = load(path, workload)
    return _LOADED[path]


def for_run(run) -> Program | None:
    """The program's view of ``run``'s traced window: the newest saved
    trace, if its window is the one ``run.trace`` summarizes and the
    program named its ticks in it. None otherwise."""
    if run.trace is None:
        return None
    path = newest()
    if path is None:
        return None
    prog = program(path)
    if abs(prog.window_s - run.trace.window_s) > 1e-9 \
            or not prog.has_program_spans():
        return None
    return prog


def with_layers(prog: Program | None) -> Program | None:
    """``prog`` with its layer map, or None where the program has none."""
    if prog is None:
        return None
    if prog.layers is None:
        prog.layers = op_layers(prog.workload) or {}
    return prog if prog.layers else None


def layer_bound_s(cnt: dict, pool_idx: np.ndarray, peaks: dict) -> np.ndarray:
    """Least device seconds of each layer of one step that serves the pool
    inputs ``pool_idx``: the larger of its useful operations over the peak
    and its least bytes over the bandwidth (``counts.step_bound_s`` is
    their sum)."""
    flops = 2.0 * cnt["macs"][:, pool_idx].sum(axis=1)
    nbytes = counts.BYTES_PER_ELEMENT * (
        cnt["elements"][:, pool_idx].sum(axis=1) + cnt["weight_nnz"])
    return np.maximum(flops / peaks["bf16_flops_per_s"],
                      nbytes / peaks["hbm_bytes_per_s"])


def kind_roofline(run, kind: str) -> float | None:
    """Σ over the layers of ``kind`` of their least seconds for the batches
    served in the trace ÷ device op time under those layers' scopes, %."""
    from bench.metrics import batches
    if run.counts is None or run.peaks is None:
        return None
    idx = [i for i, k in enumerate(run.counts["kinds"]) if k == kind]
    prog = with_layers(for_run(run)) if idx else None
    if prog is None:
        return None
    layer_s = prog.layer_op_s()
    op_s = sum(layer_s.get(f"{PROGRAM}L{i:02d}.{kind}", 0.0) for i in idx)
    bound = sum(float(layer_bound_s(run.counts, served, run.peaks)[idx].sum())
                for _, served in batches(run))
    return 100.0 * bound / op_s if op_s > 0 and bound > 0 else None


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    if len(args) != 1:
        print(USAGE, file=sys.stderr)
        return 2
    path = newest(args[0])
    if path is None:
        print(f"no saved trace of {args[0]} under {TRACES}", file=sys.stderr)
        return 1
    prog = program(path)
    if prog.has_program_spans():
        with_layers(prog)
    print(json.dumps(dict(workload=args[0], trace=path, **prog.breakdown())))
    return 0


if __name__ == "__main__":
    sys.exit(main())
