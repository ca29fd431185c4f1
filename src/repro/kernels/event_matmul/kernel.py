"""Pallas TPU kernel for the MNF multiply phase (block-event sparse matmul).

Computes y = a @ W where ``a`` is supplied as *block events* — the paper's
event encoding adapted to TPU tiling (DESIGN.md §2):

  a_vals    (G, E, bm, bk)  compacted live activation tiles
  a_idx     (G, E) int32    direct weight-tile address per event (the paper's
                            start_weight_address); padding slots repeat the
                            last live address so their DMA is elided by
                            Mosaic's revisit-skip.  Prefetched flat.
  counts    (G,)  int32     live event count per row group (the paper's
                            end-of-data event).
  w         (K, N)          dense weights, tiled (bk, bn).

Grid (G, N/bn, E), E innermost so the accumulator tile (= the paper's
accumulate SRAM) stays resident in VMEM while events stream through; the
weight tile named by each event is scalar-prefetch-indexed
(PrefetchScalarGridSpec), so only event-addressed weight tiles are DMA'd from
HBM — the TPU image of "memory accesses occur only when a PE detects an
event".  ``@pl.when(e < count)`` idles the MXU on padded slots (the paper's
low-power idle on no events).  The prefetched tables grow with G, so a
launch is split over row-group chunks that fit SMEM (``kernels.smem``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.smem import plan_chunks

__all__ = ["event_matmul_kernel", "event_matmul_pallas",
           "event_matmul_int8_kernel", "event_matmul_int8_pallas",
           "event_matmul_launches", "event_matmul_grid_steps"]

#: Every in-kernel dot contracts f32 at full precision: the MXU's default
#: single bf16 pass would round event values and weights, and the 0/1
#: selection matmuls of the conv and pool kernels are exact row moves only
#: at fp32 contract precision.
HIGHEST = jax.lax.Precision.HIGHEST


def event_matmul_kernel(a_idx_ref, counts_ref,   # scalar-prefetch refs
                        a_vals_ref, w_ref,       # VMEM inputs
                        out_ref,                 # VMEM output
                        acc_ref):                # VMEM scratch (bm, bn) f32
    g = pl.program_id(0)
    e = pl.program_id(2)
    num_e = pl.num_programs(2)

    @pl.when(e == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(e < counts_ref[g])
    def _mac():
        # Multiply phase: one dense MXU burst per event tile.
        a = a_vals_ref[0, 0]                     # (bm, bk)
        w = w_ref[...]                           # (bk, bn)
        acc_ref[...] += jnp.dot(a, w, preferred_element_type=jnp.float32,
                                precision=HIGHEST)

    @pl.when(e == num_e - 1)
    def _flush():
        out_ref[0] = acc_ref[...].astype(out_ref.dtype)


def event_matmul_int8_kernel(a_idx_ref, counts_ref, scale_ref, zp_ref,
                             # ^ scalar-prefetch refs (addresses + QParams)
                             a_vals_ref, w_ref,       # VMEM inputs
                             out_ref,                 # VMEM output
                             acc_ref):                # VMEM scratch f32
    """Int8-value lowering of :func:`event_matmul_kernel` (DESIGN.md §12).

    Event tiles arrive as int8 codes; the kernel dequantizes at tile load
    — ``(q - zp) * scale`` in f32, the exact floats ``quantize.dequantize``
    produces — and accumulates in f32, so the result is bit-identical to
    the f32 kernel fed the fake-quant twin.  scale/zp ride the scalar
    prefetch next to the event addresses (one QParams per stream —
    dynamic per-layer calibration).
    """
    g = pl.program_id(0)
    e = pl.program_id(2)
    num_e = pl.num_programs(2)

    @pl.when(e == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(e < counts_ref[g])
    def _mac():
        a = a_vals_ref[0, 0].astype(jnp.float32)          # (bm, bk) codes
        a = (a - zp_ref[0].astype(jnp.float32)) * scale_ref[0]
        acc_ref[...] += jnp.dot(a, w_ref[...],
                                preferred_element_type=jnp.float32,
                                precision=HIGHEST)

    @pl.when(e == num_e - 1)
    def _flush():
        out_ref[0] = acc_ref[...].astype(out_ref.dtype)


def _launch(kernel, name: str, a_vals, a_idx, counts, w, quant, *, blk_n: int,
            interpret: bool, out_dtype) -> jax.Array:
    """One launch per SMEM-bounded chunk of row groups (``kernels.smem``).

    ``a_idx`` rides the scalar prefetch flat — ``idx[g * E + e]`` — and a
    chunk over row groups [g0, g1) offsets the a-tile index map by the
    static ``g0``; each output tile is still reduced by one launch over the
    same event order.  ``quant`` holds the int8 kernel's extra (1,)
    scalar-prefetch operands (scale, zero point), or nothing.
    """
    g, e, bm, bk = a_vals.shape
    k, n = w.shape
    assert k % bk == 0 and n % blk_n == 0, (k, n, bk, blk_n)
    nq = len(quant)
    outs = []
    for g0, g1, _, _ in plan_chunks(g, e + 1, fixed_words=nq):
        spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2 + nq,
            grid=(g1 - g0, n // blk_n, e),
            in_specs=[
                pl.BlockSpec((1, 1, bm, bk),
                             lambda gi, ni, ei, idx, cnt, *_, g0=g0:
                             (g0 + gi, ei, 0, 0)),
                # Block (bk, blk_n) at block index (a_idx[g, e], ni) ==
                # elements [a_idx*bk : (a_idx+1)*bk, ni*blk_n : ...].
                pl.BlockSpec((bk, blk_n),
                             lambda gi, ni, ei, idx, cnt, *_:
                             (idx[gi * e + ei], ni)),
            ],
            out_specs=pl.BlockSpec((1, bm, blk_n),
                                   lambda gi, ni, ei, idx, cnt, *_:
                                   (gi, 0, ni)),
            scratch_shapes=[pltpu.VMEM((bm, blk_n), jnp.float32)],
        )
        outs.append(pl.pallas_call(
            kernel,
            grid_spec=spec,
            out_shape=jax.ShapeDtypeStruct((g1 - g0, bm, n), out_dtype),
            interpret=interpret,
            name=name,
        )(a_idx[g0:g1].reshape(-1), counts[g0:g1], *quant, a_vals, w))
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs)


def event_matmul_launches(g: int, e: int, *, int8: bool = False) -> int:
    """Pallas launches :func:`event_matmul_pallas` issues for G row groups
    of E events (the SMEM chunk count — what trace records report)."""
    return len(plan_chunks(g, e + 1, fixed_words=2 if int8 else 0))


def event_matmul_grid_steps(g: int, e: int, n: int, blk_n: int) -> int:
    """Grid steps :func:`event_matmul_pallas` takes for G row groups of E
    event slots against N output columns, over all its launches: the grid
    is (G, N/blk_n, E) and the SMEM split only divides G."""
    return g * -(-n // blk_n) * e


@functools.partial(jax.jit, static_argnames=("blk_n", "interpret",
                                             "out_dtype"))
def event_matmul_int8_pallas(a_vals: jax.Array, a_idx: jax.Array,
                             counts: jax.Array, scale: jax.Array,
                             zero_point: jax.Array, w: jax.Array, *,
                             blk_n: int = 128, interpret: bool = False,
                             out_dtype=jnp.float32) -> jax.Array:
    """y[g, bm, n] = sum_e dequant(a_vals[g, e]) @ W[a_idx[g, e]].

    ``a_vals`` are int8 codes; ``scale``/``zero_point`` the stream's
    QParams (scalars — reshaped to (1,) scalar-prefetch operands).
    """
    quant = (scale.reshape(1).astype(jnp.float32),
             zero_point.reshape(1).astype(jnp.int32))
    return _launch(event_matmul_int8_kernel, "mnf_event_matmul_int8", a_vals,
                   a_idx, counts, w, quant, blk_n=blk_n, interpret=interpret,
                   out_dtype=out_dtype)


@functools.partial(jax.jit, static_argnames=("blk_n", "interpret", "out_dtype"))
def event_matmul_pallas(a_vals: jax.Array, a_idx: jax.Array,
                        counts: jax.Array, w: jax.Array, *,
                        blk_n: int = 128, interpret: bool = False,
                        out_dtype=jnp.float32) -> jax.Array:
    """y[g, bm, n] = sum_e a_vals[g, e] @ W[a_idx[g, e]] (live events only)."""
    return _launch(event_matmul_kernel, "mnf_event_matmul", a_vals, a_idx,
                   counts, w, (), blk_n=blk_n, interpret=interpret,
                   out_dtype=out_dtype)
