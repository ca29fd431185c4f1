"""Event-native max-pool Pallas TPU kernel (DESIGN.md §7).

One launch pools an entire layer straight from its fired ``EventStream`` —
the dense feature map is never read.  (At widths whose window plan outgrows
SMEM the layer splits into a few launches over chunks of output pixels —
``kernels.smem``; each output pixel is still pooled by one launch.)  The
grid is (P_out, T, E): output pixel × window tap (T = k·k) × event slot,
mirroring the fused conv kernel's plan-driven indirection:

  a_vals (G_in, E, bm, bk)   the stream's event tiles, consumed in place —
                             the tile DMA'd for step (p, t, e) is
                             ``a_vals[src[p, t], e]`` (scalar-prefetched
                             window plan from ``core.events.pool_window_map``).
  out    (P_out, nkb, bk)    pooled rows, written once per pixel from a
                             VMEM segment-max scratch.

Per live event the kernel picks the window pixel's row out of the (bm, bk)
tile with a 0/1 selection matmul (exact value move, same idiom as the
fused conv kernel's row shifts) and max-accumulates it into the scratch
row named by the event's direct K-block address — a segment max keyed by
weight-tile address, identity 0.  Because fire emits non-negative values
and event-absent positions are exactly 0, the result is bit-identical to
the dense ``reduce_window`` max of the fired map.

``@pl.when(e < cnt[p, t])`` idles the unit on padded event slots — the
paper's low-power idle, now covering the pool windows too: a fully dead
window does zero work and emits the exact-0 pooled row.
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

__all__ = ["event_pool_kernel", "event_pool_pallas",
           "event_pool_window_kernel", "event_pool_window_pallas",
           "event_pool_launches"]


def event_pool_kernel(row_ref, src_ref, cnt_ref, a_idx_ref,
                      # ^ flat scalar-prefetch refs (window plan + addresses)
                      a_vals_ref,              # VMEM input (1, 1, bm, bk)
                      out_ref,                 # VMEM output (1, nkb, bk)
                      acc_ref):                # VMEM scratch (nkb, bk) f32
    p = pl.program_id(0)
    t = pl.program_id(1)
    e = pl.program_id(2)
    num_t = pl.num_programs(1)
    num_e = pl.num_programs(2)
    pt = p * num_t + t

    @pl.when((t == 0) & (e == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(e < cnt_ref[pt])
    def _segmax():
        a = a_vals_ref[0, 0]                  # (bm, bk) source event tile
        bm = a.shape[0]
        if bm == 1:                           # pixel tile: the row itself
            picked = a.astype(jnp.float32)
        else:
            # Exact row pick: 0/1 selection matmul at fp32 contract
            # precision (no rounding, rides the MXU — the same move idiom
            # as the fused conv kernel's straddle shifts).
            sel = (jax.lax.broadcasted_iota(jnp.int32, (1, bm), 1)
                   == row_ref[pt]).astype(a.dtype)
            picked = jnp.dot(sel, a, preferred_element_type=jnp.float32,
                             precision=HIGHEST)
        kb = a_idx_ref[src_ref[pt] * num_e + e]   # direct K-block address
        acc_ref[pl.ds(kb, 1), :] = jnp.maximum(acc_ref[pl.ds(kb, 1), :],
                                               picked)

    @pl.when((t == num_t - 1) & (e == num_e - 1))
    def _writeback():
        out_ref[0] = acc_ref[...].astype(out_ref.dtype)


def _chunks(src: np.ndarray, e: int, *, window: bool) -> list[tuple]:
    """SMEM chunks of a pool plan (``kernels.smem``).  The per-event pool
    prefetches row + src + cnt per (output pixel, tap); the window-major
    pool the shift (T) once and src + cnt per (output strip, subtap); both
    E addresses per source row group they read."""
    t_n = src.shape[1]
    fixed, row = (t_n, 2 * t_n) if window else (0, 3 * t_n)
    return src_chunks(src, row_words=row, src_words=e, fixed_words=fixed)


def event_pool_launches(src: np.ndarray, e: int, *,
                        window: bool = False) -> int:
    """Pallas launches one pool layer issues for plan ``src`` (P_out or
    G_out rows × T taps) over E event slots — what trace records report."""
    return len(_chunks(src, e, window=window))


def event_pool_pallas(a_vals: jax.Array, a_idx: jax.Array, row: np.ndarray,
                      src: np.ndarray, cnt: jax.Array, *, nkb: int,
                      interpret: bool = False,
                      out_dtype=jnp.float32) -> jax.Array:
    """Fused pool launches: y[p] = max_t max_e rowpick(a[src[p,t], e]), id 0.

    a_vals/a_idx: event tiles (G_in, E, bm, bk) / addresses (G_in, E).
    row/src: the static (P_out, T) window plan — source group and row within
    its tile; cnt: (P_out, T) live event count per (output pixel, window
    tap).  One launch per SMEM chunk of output pixels.  Returns
    (P_out, nkb, bk) pooled rows in K-block layout.
    """
    g_in, e, bm, bk = a_vals.shape
    p_out, t_n = src.shape
    assert row.shape == src.shape == cnt.shape, (row.shape, src.shape,
                                                 cnt.shape)
    outs = []
    for r0, r1, s0, s1, src_c in _chunks(src, e, window=False):
        spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(r1 - r0, t_n, e),
            in_specs=[
                pl.BlockSpec((1, 1, bm, bk),
                             lambda pi, ti, ei, rw, sr, ct, ai, s0=s0:
                             (s0 + sr[pi * t_n + ti], ei, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, nkb, bk),
                                   lambda pi, ti, ei, rw, sr, ct, ai:
                                   (pi, 0, 0)),
            scratch_shapes=[pltpu.VMEM((nkb, bk), jnp.float32)],
        )
        outs.append(pl.pallas_call(
            event_pool_kernel,
            grid_spec=spec,
            out_shape=jax.ShapeDtypeStruct((r1 - r0, nkb, bk), out_dtype),
            interpret=interpret,
            name="mnf_event_pool",
        )(jnp.asarray(row[r0:r1].reshape(-1)), jnp.asarray(src_c),
          cnt[r0:r1].reshape(-1), a_idx[s0:s1].reshape(-1), a_vals))
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs)


# ---------------------------------------------------------------------------
# Window-major grid (DESIGN.md §7): one grid step per *output strip* —
# 8 pooled pixels — instead of per output pixel, and every subtap consumes
# the whole gathered (bm, bk) tile through a strip-masked affine row remap
# (out row i <- src row stride*i + shift; unsourced rows are exact 0, the
# max identity).  8x fewer grid steps than the per-event kernel, no wasted
# row picks — the raw-steady-state rework the ROADMAP calls out.
# ---------------------------------------------------------------------------

def event_pool_window_kernel(shift_ref, src_ref, cnt_ref, a_idx_ref,
                             # ^ flat scalar-prefetch refs (plan + addrs)
                             a_vals_ref,           # VMEM input (1, 1, bm, bk)
                             out_ref,              # VMEM out (1, bm, nkb, bk)
                             acc_ref,              # VMEM scratch (nkb, bm, bk)
                             *, row_stride: int):
    g = pl.program_id(0)
    t = pl.program_id(1)
    e = pl.program_id(2)
    num_t = pl.num_programs(1)
    num_e = pl.num_programs(2)
    gt = g * num_t + t

    @pl.when((t == 0) & (e == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(e < cnt_ref[gt])
    def _segmax():
        a = a_vals_ref[0, 0]                  # (bm, bk) source event tile
        bm = a.shape[0]
        d = shift_ref[t]
        # Strip-masked affine remap as a 0/1 selection matmul (the fused
        # conv kernel's exact-move idiom): out row i takes src row
        # stride*i + d; rows whose source leaves [0, bm) get an all-zero
        # selection row — the exact 0 the segment max treats as identity.
        rows = jax.lax.broadcasted_iota(jnp.int32, (bm, bm), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (bm, bm), 1)
        sel = (cols == rows * row_stride + d).astype(a.dtype)
        remap = jnp.dot(sel, a, preferred_element_type=jnp.float32,
                        precision=HIGHEST)
        kb = a_idx_ref[src_ref[gt] * num_e + e]   # direct K-block address
        acc_ref[pl.ds(kb, 1)] = jnp.maximum(acc_ref[pl.ds(kb, 1)],
                                            remap[None])

    @pl.when((t == num_t - 1) & (e == num_e - 1))
    def _writeback():
        # Scratch is K-block-major (segment addresses lead — the dslice
        # axis); the output strip wants rows leading.  One VMEM transpose
        # per strip at writeback, amortized over the whole tap walk.
        out_ref[0] = acc_ref[...].transpose(1, 0, 2).astype(out_ref.dtype)


def event_pool_window_pallas(a_vals: jax.Array, a_idx: jax.Array,
                             shift: np.ndarray, src: np.ndarray,
                             cnt: jax.Array, *, nkb: int, row_stride: int,
                             interpret: bool = False,
                             out_dtype=jnp.float32) -> jax.Array:
    """Fused launches over the window-major grid (G_out, T, E).

    a_vals/a_idx: event tiles (G_in, E, bm, bk) / addresses (G_in, E).
    shift/src: the static ``core.events.pool_strip_map`` plan — per-subtap
    row offset (T,), source strip group (G_out, T); cnt: live event count
    (G_out, T).  One launch per SMEM chunk of output strips.  Returns
    (G_out, bm, nkb, bk): pooled rows per output strip, rows-leading
    (reshape to (P_out, nkb·bk) outside).
    """
    g_in, e, bm, bk = a_vals.shape
    g_out, t_n = src.shape
    assert cnt.shape == src.shape, (cnt.shape, src.shape)
    assert shift.shape == (t_n,), (shift.shape, t_n)
    kernel = functools.partial(event_pool_window_kernel,
                               row_stride=row_stride)
    outs = []
    for r0, r1, s0, s1, src_c in _chunks(src, e, window=True):
        spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(r1 - r0, t_n, e),
            in_specs=[
                pl.BlockSpec((1, 1, bm, bk),
                             lambda gi, ti, ei, sh, sr, ct, ai, s0=s0:
                             (s0 + sr[gi * t_n + ti], ei, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, bm, nkb, bk),
                                   lambda gi, ti, ei, sh, sr, ct, ai:
                                   (gi, 0, 0, 0)),
            scratch_shapes=[pltpu.VMEM((nkb, bm, bk), jnp.float32)],
        )
        outs.append(pl.pallas_call(
            kernel,
            grid_spec=spec,
            out_shape=jax.ShapeDtypeStruct((r1 - r0, bm, nkb, bk), out_dtype),
            interpret=interpret,
            name="mnf_event_pool_window",
        )(jnp.asarray(shift), jnp.asarray(src_c), cnt[r0:r1].reshape(-1),
          a_idx[s0:s1].reshape(-1), a_vals))
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs)
