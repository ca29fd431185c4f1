"""Pure-jnp twin of the fused-tap strip conv kernel.

Walks the same static subtap plan (``core.events.strip_tap_map``) in the
same order, realizing each subtap as a ``gather_row_strips`` (exact row
moves) + the block-event contraction ``block_event_linear_from_events`` —
the engine registry's "block" backend of ``conv2d_events_strip``.

Bit-exactness contract (tested in tests/test_conv_strips.py): because the
plan visits taps in the per-tap oracle's (dy, dx) order, straddle halves
contribute exact zeros to rows they don't source, and strip-live-but-
pixel-dead event slots contribute exact zeros to the contraction, this twin
is bit-identical to the pixel-granular per-tap path — strips only shrink
the event grid, they never reorder the arithmetic (DESIGN.md §6).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import events as ev
from repro.core.mnf_conv import conv_out_size
from repro.core.mnf_linear import block_event_linear_from_events

__all__ = ["fused_event_conv2d_ref"]


def fused_event_conv2d_ref(stream, w: jax.Array, *, stride: int = 1,
                           padding: int = 0) -> jax.Array:
    """Strip-tiled fused-tap conv, pure jnp.  Returns (B*OY*OX, CO)."""
    b, h, wd, ci = stream.logical_shape
    k, _, ci2, co = w.shape
    assert ci == ci2, (stream.logical_shape, w.shape)
    assert stream.blk_m == ev.STRIP_W, stream.blk_m
    src, live, shift, tap = ev.strip_tap_map((b, h, wd, ci), k, padding,
                                             stride)
    oy = conv_out_size(h, k, stride, padding)
    ox = conv_out_size(wd, k, stride, padding)
    wtap = w.reshape(k * k, ci, co)
    acc = jnp.zeros((b * oy * ox, co),
                    jnp.promote_types(stream.events.values.dtype, w.dtype))
    for t in range(src.shape[1]):
        gat = ev.gather_row_strips(stream.events, jnp.asarray(src[:, t]),
                                   jnp.asarray(live[:, t]), int(shift[t]),
                                   row_stride=stride)
        acc = acc + block_event_linear_from_events(
            _pixel_rows(gat), wtap[int(tap[t])], qparams=stream.qparams)
    return acc


def _pixel_rows(bev: ev.BlockEvents) -> ev.BlockEvents:
    """A strip's events as one event row per pixel: (G, E, 8, bk) tiles
    become (G·8, E, 1, bk), each row keeping its strip's slots.  XLA:CPU
    sums a one-row dot's K products in another order than an eight-row
    dot's when N is narrow (a matrix-vector kernel against a matrix-matrix
    one), so contracting per pixel, as the per-tap path does, keeps the two
    bitwise equal on every host."""
    g, e, bm, bk = bev.values.shape
    return ev.BlockEvents(
        values=bev.values.transpose(0, 2, 1, 3).reshape(g * bm, e, 1, bk),
        block_idx=jnp.repeat(bev.block_idx, bm, axis=0),
        counts=jnp.repeat(bev.counts, bm), num_k_blocks=bev.num_k_blocks)
