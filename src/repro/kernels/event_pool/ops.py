"""Public wrappers around the event-native max-pool kernel (DESIGN.md §7).

``event_max_pool2d`` consumes a conv ``EventStream`` (pixel-granular or
strip-aligned) and computes the pooled feature-map rows in **one** Pallas
launch per SMEM chunk (``kernels.smem`` — one launch at every width whose
plan fits) — the engine registry's "pallas" backend of
``maxpool2d_events``.
``pool_plan`` exposes the static launch accounting (window taps, event grid
consumed vs the dense window read) that benchmarks record in
BENCH_engine.json.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import events as ev
from repro.kernels.event_pool.kernel import (event_pool_launches,
                                             event_pool_pallas,
                                             event_pool_window_pallas)

__all__ = ["event_max_pool2d", "event_max_pool2d_window", "pool_plan",
           "pool_window_plan"]


def event_max_pool2d(stream, k: int, stride: int, *,
                     interpret: bool = False) -> jax.Array:
    """Event-native max-pool, one Pallas launch per SMEM chunk.  Returns
    (B·OH·OW, C).

    ``stream`` must carry an NHWC ``logical_shape``; the engine API gates
    eligibility (ReLU-family fire — non-negative events — and window within
    the map) before dispatching here.
    """
    b, h, w, c = stream.logical_shape
    bev = stream.events
    src, row, live = ev.pool_window_map(stream.logical_shape, k, stride,
                                        stream.blk_m)
    p_n = src.shape[0]
    nkb, bk = bev.num_k_blocks, stream.blk_k
    if p_n == 0:                       # degenerate batch/map: no launch
        return jnp.zeros((0, c), bev.values.dtype)
    cnt = jnp.where(jnp.asarray(live), bev.counts[jnp.asarray(src)], 0)
    y = event_pool_pallas(bev.values, bev.block_idx, row, src,
                          cnt.astype(jnp.int32), nkb=nkb,
                          interpret=interpret)
    return y.reshape(p_n, nkb * bk)[:, :c]


def event_max_pool2d_window(stream, k: int, stride: int, *,
                            interpret: bool = False) -> jax.Array:
    """Window-major event pool, one Pallas launch per SMEM chunk.  Returns
    (B·OH·OW, C).

    The strip rework of :func:`event_max_pool2d`: the grid walks output
    *strips* (8 pooled pixels each — 8x fewer steps) and every subtap
    consumes the whole gathered tile through the strip-masked affine
    remap.  Requires a strip stream on an eligible geometry
    (``core.events.pool_window_ineligible_reason``); the engine gates.
    """
    b, h, w, c = stream.logical_shape
    bev = stream.events
    bm = stream.blk_m
    assert bm == ev.STRIP_W, (bm, "window-major pool wants a strip stream")
    src, live, shift, _ = ev.pool_strip_map(stream.logical_shape, k, stride)
    g_n = src.shape[0]
    nkb, bk = bev.num_k_blocks, stream.blk_k
    if g_n == 0:                       # degenerate batch/map: no launch
        return jnp.zeros((0, c), bev.values.dtype)
    cnt = jnp.where(jnp.asarray(live), bev.counts[jnp.asarray(src)], 0)
    y = event_pool_window_pallas(bev.values, bev.block_idx, shift, src,
                                 cnt.astype(jnp.int32), nkb=nkb,
                                 row_stride=stride, interpret=interpret)
    return y.reshape(g_n * bm, nkb * bk)[:, :c]


def pool_plan(logical_shape: tuple, k: int, stride: int, *,
              nkb: int, capacity: int | None = None, blk_m: int = 1) -> dict:
    """Static launch accounting for one event-pool layer vs the dense pool.

    ``event_grid`` counts the (window tap × event slot) steps the kernel's
    grid walks per output pixel; ``dense_reads`` is what the dense
    ``reduce_window`` pool touches (k·k·C per output pixel).  The ratio is
    the work the event encoding skips when the map is sparse.  The grid is
    granularity-independent (pixel and strip inputs walk the same
    (P_out, k·k, E) steps — only the source tile a step DMAs differs);
    ``launches`` counts the SMEM chunks for a ``blk_m`` input stream.
    """
    b, h, w, c = logical_shape
    oh = (h - k) // stride + 1
    ow = (w - k) // stride + 1
    e = nkb if capacity is None else min(capacity, nkb)
    p_out = b * oh * ow
    src, _, _ = ev.pool_window_map(logical_shape, k, stride, blk_m)
    return dict(
        launches=event_pool_launches(src, e), window_taps=k * k,
        grid=(p_out, k * k, e),
        event_grid=p_out * k * k * e,
        dense_reads=p_out * k * k * c,
        out_rows=p_out)


def pool_window_plan(logical_shape: tuple, k: int, stride: int, *,
                     nkb: int, capacity: int | None = None) -> dict:
    """Launch accounting of the window-major grid vs the per-event one.

    ``grid_reduction`` is the step-count ratio the rework buys: the
    per-event grid walks P_out·k²·E steps, the window-major grid
    (P_out/8)·k²·parts·E — a strip serves 8 output pixels per step while
    straddle parts multiply taps by ``parts`` (2 at stride 1, ≤3 at
    stride ≤ 3 for k ≤ 3), so the net is 8/parts ≈ 2.7–4x fewer DMAs plus
    full-tile row use instead of 1-of-8 row picks.
    """
    b, h, w, c = logical_shape
    reason = ev.pool_window_ineligible_reason(logical_shape, k, stride,
                                              ev.STRIP_W)
    assert reason is None, (logical_shape, k, stride, reason)
    oh = (h - k) // stride + 1
    ow = (w - k) // stride + 1
    e = nkb if capacity is None else min(capacity, nkb)
    parts = ((ev.STRIP_W - 1) * stride + k - 1) // ev.STRIP_W + 1
    g_out = b * oh * (ow // ev.STRIP_W)
    p_out = b * oh * ow
    src, _, _, _ = ev.pool_strip_map(logical_shape, k, stride)
    return dict(
        launches=event_pool_launches(src, e, window=True),
        window_taps=k * k, parts=parts,
        grid=(g_out, k * k * parts, e),
        event_grid=g_out * k * k * parts * e,
        pixel_event_grid=p_out * k * k * e,
        grid_reduction=(p_out * k * k * e)
        / max(g_out * k * k * parts * e, 1),
        dense_reads=p_out * k * k * c,
        out_rows=p_out)
