"""Reduce a profiler trace to device busy time, op time and idle gaps.

A TPU trace (``*.xplane.pb``) has one plane per chip, ``/device:TPU:<n>``,
whose line ``XLA Ops`` holds every operation the chip ran, named by its HLO
text (``%mnf_event_matmul.3 = f32[...] custom-call(...)``). The host plane
``/host:CPU`` holds the benchmark's own spans (``bench.<name>``, from
``jax.profiler.TraceAnnotation``) on the same clock.

* busy: the union of the op intervals inside the traced window, averaged
  over the chips;
* op time: each op family's summed duration (``mnf_event_matmul.3`` and
  ``mnf_event_matmul.7`` are the family ``mnf_event_matmul``);
* idle gaps: each stretch of the window in which no op ran, named by the
  benchmark span that covers most of it (``other`` where none does).
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
#: The span that marks the traced window itself.
WINDOW_SPAN = "traced"
_FAMILY = re.compile(r"^%?([^\s=]+?)(?:\.\d+)?\s*=")


def op_family(name: str) -> str:
    m = _FAMILY.match(name)
    return m.group(1) if m else name.split(" ")[0].lstrip("%")


@dataclasses.dataclass
class Trace:
    """Intervals in ns on the trace's clock."""

    device_ops: list          # per chip: [(family, start, end)]
    spans: list               # [(name without prefix, start, end)]


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float             # averaged over the chips
    op_s: dict                # family -> seconds, averaged over the chips
    idle_s: dict              # span name -> idle seconds, averaged

    def kernel_s(self, prefix: str) -> float:
        return sum(s for name, s in self.op_s.items()
                   if name.startswith(prefix))

    def breakdown(self, top: int = 10) -> dict:
        def largest(d):
            return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                    ][:top]
        return dict(device_ops=largest(self.op_s),
                    idle_gaps=largest(self.idle_s))


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, spans = [], []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = [(op_family(e.name), e.start_ns, e.start_ns + e.duration_ns)
                   for line in plane.lines if line.name == OPS_LINE
                   for e in line.events]
            if ops:
                devices.append(ops)
        elif plane.name == HOST_PLANE:
            spans.extend((e.name[len(SPAN_PREFIX):], e.start_ns,
                          e.start_ns + e.duration_ns)
                         for line in plane.lines for e in line.events
                         if e.name.startswith(SPAN_PREFIX))
    return Trace(device_ops=devices, spans=spans)


def union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def gaps(busy: list, lo: float, hi: float) -> list[tuple[float, float]]:
    """The stretches of [lo, hi] that ``busy`` (a union) leaves free."""
    out, t = [], lo
    for b0, b1 in busy:
        if b0 > t:
            out.append((t, min(b0, hi)))
        t = max(t, b1)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(a, b) for a, b in out if b > a]


def _label(gap, spans, starts) -> str:
    """The span overlapping ``gap`` the most. The benchmark's spans follow
    one another without nesting, sorted by start, so the search walks back
    from the gap's end until a span ends before the gap begins."""
    g0, g1 = gap
    best, best_cover = "other", 0.0
    for i in range(bisect.bisect_left(starts, g1) - 1, -1, -1):
        name, s0, s1 = spans[i]
        if s1 <= g0:
            break
        cover = min(s1, g1) - max(s0, g0)
        if cover > best_cover:
            best, best_cover = name, cover
    return best


def reduce(trace: Trace) -> Summary:
    """Summarize ``trace`` over the window its ``bench.traced`` span marks."""
    windows = [(s0, s1) for name, s0, s1 in trace.spans
               if name == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {SPAN_PREFIX}{WINDOW_SPAN} span, "
                         f"found {len(windows)}")
    lo, hi = windows[0]
    if not trace.device_ops:
        raise ValueError("the trace holds no device operation")
    spans = sorted((s for s in trace.spans if s[0] != WINDOW_SPAN),
                   key=lambda s: s[1])
    starts = [s[1] for s in spans]
    n = len(trace.device_ops)
    busy_ns, op_ns, idle_ns = 0.0, {}, {}
    for ops in trace.device_ops:
        inside = [(max(a, lo), min(b, hi), f) for f, a, b in ops
                  if b > lo and a < hi]
        for a, b, f in inside:
            op_ns[f] = op_ns.get(f, 0.0) + (b - a)
        busy = union((a, b) for a, b, _ in inside)
        busy_ns += sum(b - a for a, b in busy)
        for gap in gaps(busy, lo, hi):
            name = _label(gap, spans, starts)
            idle_ns[name] = idle_ns.get(name, 0.0) + (gap[1] - gap[0])
    return Summary(window_s=(hi - lo) * 1e-9, busy_s=busy_ns * 1e-9 / n,
                   op_s={k: v * 1e-9 / n for k, v in op_ns.items()},
                   idle_s={k: v * 1e-9 / n for k, v in idle_ns.items()})
