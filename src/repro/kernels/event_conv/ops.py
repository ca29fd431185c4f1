"""Public wrappers around the fused-tap strip conv kernel.

``fused_event_conv2d`` consumes a strip-aligned conv ``EventStream``
(blk_m == STRIP_W, NHWC ``logical_shape``) and computes the whole conv layer
in **one** Pallas launch per SMEM chunk (``kernels.smem`` — one launch at
every width whose plan fits) — the engine registry's "pallas" backend of
``conv2d_events_strip``.  ``fused_conv_plan`` exposes the static launch
accounting (grid size, launches, event-grid reduction vs the per-tap path)
that the benchmarks and BENCH_engine.json report.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import events as ev
from repro.core.mnf_conv import conv_out_size
from repro.kernels.event_conv.kernel import (event_conv_int8_pallas,
                                             event_conv_launches,
                                             event_conv_pallas, lane_blk_k)

__all__ = ["fused_event_conv2d", "fused_conv_plan"]


def _stacked_weights(w: jax.Array, bk: int, nkb: int,
                     blk_n: int) -> jax.Array:
    """(K, K, CI, CO) -> (k*k*nkb*lane_k, N): per-tap weight slabs, K-block
    rows, ``lane_k = lane_blk_k(bk)``.

    Block row ``tap * nkb + kb`` of the result is W[dy, dx] rows
    [kb*bk, (kb+1)*bk), then ``lane_k - bk`` zero rows — the tile the
    kernel's index map addresses from an event's direct K-block address.
    """
    k, k2, ci, co = w.shape
    assert k == k2, w.shape
    wf = w.reshape(k * k, ci, co)
    wf = ev.pad_to_block_multiple(wf, bk, 1)
    assert wf.shape[1] == nkb * bk, (wf.shape, nkb, bk)
    wf = wf.reshape(k * k, nkb, bk, co)
    ws = ev.pad_to_block_multiple(wf, lane_blk_k(bk), 2).reshape(-1, co)
    return ev.pad_to_block_multiple(ws, blk_n, 1)


def fused_event_conv2d(stream, w: jax.Array, *, stride: int = 1,
                       padding: int = 0, blk_n: int = 128,
                       interpret: bool = False,
                       remap: str = "matmul") -> jax.Array:
    """Strip-tiled fused-tap conv, one Pallas launch per SMEM chunk.
    Returns (B*OY*OX, CO).

    ``stream`` must be strip-aligned (blk_m == STRIP_W) and the layer
    strip-eligible (stride in STRIP_STRIDES — see
    ``core.events.strip_eligible``; the engine API enforces this before
    dispatching here).  Streams carrying int8 event values (``qparams``
    set) dispatch to the dequantize-at-load kernel variant (DESIGN.md §12).
    """
    b, h, wd, ci = stream.logical_shape
    k, _, ci2, co = w.shape
    assert ci == ci2, (stream.logical_shape, w.shape)
    assert stream.blk_m == ev.STRIP_W, stream.blk_m
    bev = stream.events
    bk = stream.blk_k
    nkb = bev.num_k_blocks
    src, live, shift, tap = ev.strip_tap_map((b, h, wd, ci), k, padding,
                                             stride)
    cnt = jnp.where(jnp.asarray(live), bev.counts[jnp.asarray(src)], 0)
    cnt = cnt.astype(jnp.int32)
    ws = _stacked_weights(w, bk, nkb, blk_n)
    if stream.qparams is not None:
        y = event_conv_int8_pallas(
            bev.values, bev.block_idx, tap, shift, src, cnt,
            stream.qparams.scale, stream.qparams.zero_point, ws, nkb=nkb,
            blk_n=blk_n, row_stride=stride, interpret=interpret, remap=remap)
    else:
        y = event_conv_pallas(bev.values, bev.block_idx, tap, shift, src, cnt,
                              ws, nkb=nkb, blk_n=blk_n, row_stride=stride,
                              interpret=interpret, remap=remap)
    oy = conv_out_size(h, k, stride, padding)
    ox = conv_out_size(wd, k, stride, padding)
    return y.reshape(-1, y.shape[-1])[:b * oy * ox, :co]


def fused_conv_plan(logical_shape: tuple, k: int, padding: int,
                    nkb: int, capacity: int | None = None,
                    stride: int = 1) -> dict:
    """Static launch accounting for one strip conv layer vs the per-tap path.

    event_grid counts (row groups x event slots) of the stream each path
    consumes — the gather grid the per-tap path inflates k*k-fold and the
    strip encoding shrinks STRIP_W-fold.  ``subtaps`` is the compacted
    inner-grid length the kernel actually launches (dead straddle parts
    dropped at plan time); ``subtaps_worst`` the uncompacted
    ``strip_parts(stride)*k*k`` it would have launched, ``compaction``
    their ratio (1.0 = nothing to drop).
    """
    b, h, wd, _ = logical_shape
    e = nkb if capacity is None else min(capacity, nkb)
    oh = conv_out_size(h, k, stride, padding)
    ow = conv_out_size(wd, k, stride, padding)
    g_pix = b * h * wd
    g_strip = g_pix // ev.STRIP_W
    g_out = b * oh * (ow // ev.STRIP_W)
    subtaps, subtaps_worst = ev.strip_subtap_counts(k, padding, stride)
    src, _, _, _ = ev.strip_tap_map(logical_shape, k, padding, stride)
    return dict(
        launches_fused=event_conv_launches(src, e), launches_per_tap=k * k,
        grid_fused=(g_out, subtaps, e),
        subtaps=subtaps, subtaps_worst=subtaps_worst,
        compaction=subtaps / subtaps_worst,
        event_grid_strip=g_strip * e, event_grid_pixel=g_pix * e,
        grid_reduction=float(g_pix) / float(g_strip),
        gathered_groups_per_tap=k * k * b * oh * ow,
        gathered_groups_fused=0)
