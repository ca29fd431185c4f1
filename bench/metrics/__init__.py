"""Metric readers, one file per family: ``<family>.py`` defines
``read(run) -> float | None``. ``run`` is a :class:`Run`. A reader that
finds nothing to read returns ``None``, and the metric is left out."""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Run:
    setup_s: float
    window: object              # traffic.Window
    buckets: tuple
    traced_until: float | None = None   # host clock the trace stopped at
    trace: object = None        # trace_reduce.Summary
    counts: dict | None = None  # counts.pool_counts of the input pool
    peaks: dict | None = None   # peaks.json entry of the device


def batches(run: Run) -> list[tuple[int, np.ndarray]]:
    """(bucket, pool indices) of every batch the server executed inside
    the trace, in order. A tick drains
    its queue head first in chunks of at most the largest bucket, each
    padded to the smallest bucket that holds it."""
    w = run.window
    out, lo = [], 0
    top = max(run.buckets)
    for n in w.tick_sizes:
        if w.done[lo + n - 1] > run.traced_until:
            break
        for c0 in range(0, n, top):
            take = min(top, n - c0)
            bucket = min(b for b in run.buckets if b >= take)
            out.append((bucket, w.pool_idx[lo + c0:lo + c0 + take]))
        lo += n
    return out


def latency_ms(run: Run, q: float) -> float | None:
    lat = run.window.latency_s
    return float(np.percentile(lat, q) * 1e3) if len(lat) else None
