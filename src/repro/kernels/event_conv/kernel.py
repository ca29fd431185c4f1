"""Fused-tap Pallas TPU kernel for strip-tiled event convolution.

One launch computes an entire conv layer from a strip-aligned event stream
(DESIGN.md §6).  The per-tap path re-dispatches ``event_matmul`` k*k times
per layer and materializes a gathered event grid per tap; this kernel keeps
the k*k tap loop *inside* the launch as two grid dimensions (subtap, event)
and never materializes a gather at all — scalar-prefetched plan arrays
(``src``/``cnt``/``shift``/``tap`` from ``core.events.strip_tap_map``) drive
the indirection through BlockSpec index maps:

  a_vals (G_in, E, bm, bk)   strip event tiles, consumed in place — the
                             a-tile DMA'd for grid step (g, ., t, e) is
                             ``a_vals[src[g, t], e]``.
  ws     (k*k*nkb*bk, N)     tap-stacked weights; the w-tile is block row
                             ``tap[t] * nkb + a_idx[src[g, t], e]`` — the
                             event's direct weight address offset into its
                             tap's slab.

Grid (G_out, N/bn, T, E), T the **compacted** subtap count of the plan
(``strip_subtap_counts(k, p, stride)[0]``): each tap splits into its
``strip_parts(stride)`` strip-straddle parts — two adjacent-strip halves at
stride 1, up to three interleaved half-strips at stride 2, up to five
quarter-strips at stride 4 — and parts whose affine map sources no row are
*dropped from the plan* rather than idled over, so the inner grid axis
shrinks from ``strip_parts(stride)*k*k`` toward ``k*k``.  E innermost.
Per subtap a scratch ``tap_acc`` accumulates events exactly like the
per-tap ``event_matmul`` kernel does, then flushes into the layer
accumulator — reproducing the per-tap oracle's reduction tree bit-for-bit
(the straddle part that does not source a given output row contributes
exact zeros).  The in-tile affine row remap of a straddling tap (out row
i <- src row stride*i + d) is applied as a 0/1 selection matmul
(``sel @ a``), which moves rows exactly (no rounding) and rides the MXU;
``remap="select"`` swaps in an 8-step vselect ladder (broadcast row m,
select where stride*i + d == m) — same exact row moves on the VPU, kept
for the Mosaic lowering cost comparison recorded in DESIGN.md §6.

``@pl.when(e < cnt[g, t])`` idles the unit on padded event slots and on
border subtaps (zero-padding reads outside the map) — the paper's
low-power idle, now covering the whole tap loop of a layer.  The plan
tables ride the scalar prefetch flat; where they outgrow SMEM the layer
splits into launches over chunks of output strips (``kernels.smem``),
each output tile still reduced by one launch in plan order.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.event_matmul.kernel import HIGHEST
from repro.kernels.smem import src_chunks

__all__ = ["event_conv_kernel", "event_conv_pallas",
           "event_conv_int8_kernel", "event_conv_int8_pallas",
           "event_conv_launches", "event_conv_grid_steps"]


def _shift_rows(a, d, *, row_stride: int, remap: str):
    """Exact affine row remap: out row i <- src row row_stride*i + d
    (strided straddle parts pick their interleaved partial strip).
    Rows the map doesn't source come out exact f32 zeros."""
    bm = a.shape[0]
    if remap == "select":
        # vselect ladder: bm row-broadcasts + masked selects (VPU).
        want = (jax.lax.broadcasted_iota(jnp.int32, (bm, a.shape[1]), 0)
                * row_stride + d)
        shifted = jnp.zeros(a.shape, jnp.float32)
        for m in range(bm):
            row = jax.lax.broadcast_in_dim(a[m].astype(jnp.float32),
                                           a.shape, (1,))
            shifted = jnp.where(want == m, row, shifted)
        return shifted
    # 0/1 selection matmul: one (bm, bm) @ (bm, bk) MXU op.
    i = jax.lax.broadcasted_iota(jnp.int32, (bm, bm), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (bm, bm), 1)
    sel = (j == i * row_stride + d).astype(a.dtype)
    return jnp.dot(sel, a, preferred_element_type=jnp.float32,
                   precision=HIGHEST)


def event_conv_kernel(tap_ref, shift_ref, src_ref, cnt_ref, a_idx_ref,
                      # ^ scalar-prefetch refs (plan + event addresses)
                      a_vals_ref, w_ref,       # VMEM inputs
                      out_ref,                 # VMEM output
                      acc_ref, tap_acc_ref,    # VMEM scratch (bm, bn) f32
                      *, row_stride: int = 1, remap: str = "matmul"):
    g = pl.program_id(0)
    t = pl.program_id(2)
    e = pl.program_id(3)
    num_t = pl.num_programs(2)
    num_e = pl.num_programs(3)
    gt = g * num_t + t

    @pl.when((t == 0) & (e == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(e == 0)
    def _tap_init():
        tap_acc_ref[...] = jnp.zeros_like(tap_acc_ref)

    @pl.when(e < cnt_ref[gt])
    def _mac():
        a = a_vals_ref[0, 0]                     # (bm, bk) source strip tile
        shifted = _shift_rows(a, shift_ref[t], row_stride=row_stride,
                              remap=remap)
        tap_acc_ref[...] += jnp.dot(shifted, w_ref[...],
                                    preferred_element_type=jnp.float32,
                                    precision=HIGHEST)

    @pl.when(e == num_e - 1)
    def _tap_flush():
        # Matches the per-tap oracle's outer `acc = acc + tap_result`;
        # dead subtaps flush exact zeros (bitwise no-op).
        acc_ref[...] += tap_acc_ref[...]

    @pl.when((t == num_t - 1) & (e == num_e - 1))
    def _writeback():
        out_ref[0] = acc_ref[...].astype(out_ref.dtype)


def event_conv_int8_kernel(tap_ref, shift_ref, src_ref, cnt_ref, a_idx_ref,
                           scale_ref, zp_ref,
                           # ^ scalar-prefetch refs (plan + QParams)
                           a_vals_ref, w_ref,       # VMEM inputs
                           out_ref,                 # VMEM output
                           acc_ref, tap_acc_ref,    # VMEM scratch (bm, bn)
                           *, row_stride: int = 1, remap: str = "matmul"):
    """Int8-value lowering of :func:`event_conv_kernel` (DESIGN.md §12).

    Strip tiles arrive as int8 codes; the kernel dequantizes at tile load
    — ``(q - zp) * scale`` in f32, the exact floats ``quantize.dequantize``
    produces — *before* the affine row remap, so unsourced rows stay exact
    f32 zeros whatever the zero point, and the selection matmul / vselect
    ladder then runs on the same floats the f32 kernel sees when fed the
    fake-quant twin.  TPU int8 min tiles are (32, 128); upcasting at load
    keeps the sub-tile remap structure intact instead of forcing int8 MXU
    alignment.
    """
    g = pl.program_id(0)
    t = pl.program_id(2)
    e = pl.program_id(3)
    num_t = pl.num_programs(2)
    num_e = pl.num_programs(3)
    gt = g * num_t + t

    @pl.when((t == 0) & (e == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(e == 0)
    def _tap_init():
        tap_acc_ref[...] = jnp.zeros_like(tap_acc_ref)

    @pl.when(e < cnt_ref[gt])
    def _mac():
        a = a_vals_ref[0, 0].astype(jnp.float32)   # (bm, bk) int8 codes
        a = (a - zp_ref[0].astype(jnp.float32)) * scale_ref[0]
        shifted = _shift_rows(a, shift_ref[t], row_stride=row_stride,
                              remap=remap)
        tap_acc_ref[...] += jnp.dot(shifted, w_ref[...],
                                    preferred_element_type=jnp.float32,
                                    precision=HIGHEST)

    @pl.when(e == num_e - 1)
    def _tap_flush():
        acc_ref[...] += tap_acc_ref[...]

    @pl.when((t == num_t - 1) & (e == num_e - 1))
    def _writeback():
        out_ref[0] = acc_ref[...].astype(out_ref.dtype)


def _chunks(src: np.ndarray, e: int, nq: int) -> list[tuple]:
    """SMEM chunks of a strip plan (``kernels.smem``).  A launch prefetches
    tap + shift (T each) and ``nq`` quant scalars once, src + cnt per
    (output strip, subtap), and E addresses per source strip it reads."""
    t_n = src.shape[1]
    return src_chunks(src, row_words=2 * t_n, src_words=e,
                      fixed_words=2 * t_n + nq)


def event_conv_launches(src: np.ndarray, e: int, *, int8: bool = False) -> int:
    """Pallas launches one fused strip conv issues for plan ``src``
    (G_out × T) over E event slots — what trace records report."""
    return len(_chunks(src, e, 2 if int8 else 0))


def event_conv_grid_steps(src: np.ndarray, e: int, n: int,
                          blk_n: int) -> int:
    """Grid steps one fused strip conv takes for plan ``src`` (G_out × T)
    over E event slots against N output channels, over all its launches:
    the grid is (G_out, N/blk_n, T, E)."""
    return src.size * -(-n // blk_n) * e


def _launch(kernel, name: str, a_vals, a_idx, tap, shift, src, cnt, quant,
            ws, *, nkb: int, blk_n: int, interpret: bool, out_dtype):
    g_in, e, bm, bk = a_vals.shape
    g_out, t_n = src.shape
    rows, n = ws.shape
    assert rows % (nkb * bk) == 0, (ws.shape, nkb, bk)  # k*k weight slabs
    assert n % blk_n == 0, (n, blk_n)
    outs = []
    for r0, r1, s0, s1, src_c in _chunks(src, e, len(quant)):
        spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5 + len(quant),
            grid=(r1 - r0, n // blk_n, t_n, e),
            in_specs=[
                pl.BlockSpec((1, 1, bm, bk),
                             lambda gi, ni, ti, ei, tp, sh, sr, ct, ai, *_,
                             s0=s0: (s0 + sr[gi * t_n + ti], ei, 0, 0)),
                pl.BlockSpec((bk, blk_n),
                             lambda gi, ni, ti, ei, tp, sh, sr, ct, ai, *_:
                             (tp[ti] * nkb + ai[sr[gi * t_n + ti] * e + ei],
                              ni)),
            ],
            out_specs=pl.BlockSpec((1, bm, blk_n),
                                   lambda gi, ni, ti, ei, *_: (gi, 0, ni)),
            scratch_shapes=[pltpu.VMEM((bm, blk_n), jnp.float32),
                            pltpu.VMEM((bm, blk_n), jnp.float32)],
        )
        outs.append(pl.pallas_call(
            kernel,
            grid_spec=spec,
            out_shape=jax.ShapeDtypeStruct((r1 - r0, bm, n), out_dtype),
            interpret=interpret,
            name=name,
        )(jnp.asarray(tap), jnp.asarray(shift), jnp.asarray(src_c),
          cnt[r0:r1].reshape(-1), a_idx[s0:s1].reshape(-1), *quant,
          a_vals, ws))
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs)


def event_conv_pallas(a_vals: jax.Array, a_idx: jax.Array, tap: np.ndarray,
                      shift: np.ndarray, src: np.ndarray, cnt: jax.Array,
                      ws: jax.Array, *, nkb: int, blk_n: int = 128,
                      row_stride: int = 1, interpret: bool = False,
                      out_dtype=jnp.float32, remap: str = "matmul") -> jax.Array:
    """Fused launches: y[g] = sum_t sum_e remap_t(a[src[g,t], e]) @ W_tile.

    a_vals/a_idx: strip-encoded events (G_in, E, bm, bk) / (G_in, E).
    tap/shift: static (T,) subtap plan, T the plan's **compacted** subtap
    count (dead straddle parts already dropped — the grid axis is sized by
    the plan handed in, not the worst case); src: static (G_out, T) source
    strip per (output strip, subtap); cnt: (G_out, T) live event counts.
    ws: tap-stacked weights (k*k*nkb*bk, N), N a multiple of blk_n.
    ``row_stride`` is the conv stride: out row i reads src row
    row_stride*i + shift[t].  ``remap`` picks the in-tile row-remap
    lowering ("matmul" | "select" — bit-identical; see the kernel
    docstring).  One launch per SMEM chunk of output strips.  Returns
    (G_out, bm, N).
    """
    g_in, e, bm, bk = a_vals.shape
    rows = ws.shape[0]
    t_n = src.shape[1]
    assert remap in ("matmul", "select"), remap
    assert t_n <= (rows // (nkb * bk)) * \
        (((bm - 1) * row_stride + bm - 1) // bm + 1), \
        (t_n, ws.shape, nkb, bk, row_stride)
    kernel = functools.partial(event_conv_kernel, row_stride=row_stride,
                               remap=remap)
    return _launch(kernel, "mnf_event_conv_fused", a_vals, a_idx, tap, shift,
                   src, cnt, (), ws, nkb=nkb, blk_n=blk_n,
                   interpret=interpret, out_dtype=out_dtype)


def event_conv_int8_pallas(a_vals: jax.Array, a_idx: jax.Array,
                           tap: np.ndarray, shift: np.ndarray,
                           src: np.ndarray, cnt: jax.Array, scale: jax.Array,
                           zero_point: jax.Array, ws: jax.Array, *, nkb: int,
                           blk_n: int = 128, row_stride: int = 1,
                           interpret: bool = False, out_dtype=jnp.float32,
                           remap: str = "matmul") -> jax.Array:
    """Fused strip conv on int8 event payloads (DESIGN.md §12).

    Same launch/plan structure as :func:`event_conv_pallas`; ``a_vals`` are
    int8 codes and ``scale``/``zero_point`` the stream's QParams, riding
    the scalar prefetch next to the plan arrays.  Returns (G_out, bm, N)
    in f32 accumulation, bit-identical to the f32 kernel fed the
    fake-quant twin.
    """
    assert remap in ("matmul", "select"), remap
    assert a_vals.dtype == jnp.int8, a_vals.dtype
    kernel = functools.partial(event_conv_int8_kernel, row_stride=row_stride,
                               remap=remap)
    quant = (scale.reshape(1).astype(jnp.float32),
             zero_point.reshape(1).astype(jnp.int32))
    return _launch(kernel, "mnf_event_conv_fused_int8", a_vals, a_idx, tap,
                   shift, src, cnt, quant, ws, nkb=nkb, blk_n=blk_n,
                   interpret=interpret, out_dtype=out_dtype)
