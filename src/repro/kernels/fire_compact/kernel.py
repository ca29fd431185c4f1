"""Pallas TPU kernel for the MNF fire phase (paper §4.2), fused.

One VMEM pass over the accumulator tensor performs:
  1. the fire decision (threshold compare; ReLU- or magnitude-mode),
  2. optional int8 fake-quantization of fired values (paper §5.2.3 step 2),
  3. per-tile event occupancy (does this (blk_m, blk_k) tile fire ≥1 event?)
     — the metadata the next layer's multiply phase compacts on.

Fusing 1–3 means the accumulator is read exactly once from HBM, the analogue
of the paper's fire module reading each output neuron once from the
accumulate SRAM.  Blocks are TPU-aligned row slabs over the full K (a
conv's (1, 8) event tile is far below the (8, 128) vreg tile); the event
tile occupancy is reduced inside each slab.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["fire_compact_kernel", "fire_compact_pallas", "fire_block_rows"]


#: Bytes of accumulator one fire block holds.  Its input, fired output
#: and their double buffers then use 4x this of VMEM — well inside the
#: default scoped limit, at any K the networks here reach.
BLOCK_BYTES = 1 << 20


def fire_block_rows(m: int, k: int, blk_m: int) -> int:
    """Rows of one TPU-aligned fire block over an (M, K) accumulator.

    The block spans the full K (so the occupancy block spans every K
    block — no lane-alignment constraint on K/blk_k) and a multiple of
    8·blk_m rows (so its (rows/blk_m, K/blk_k) occupancy block is
    sublane-aligned), sized to ~BLOCK_BYTES, and to at most 64 row groups
    when blk_m > 1 (the row-group sum is a (rows/blk_m, rows) matmul); an
    M that fits one block is one full-extent block.  ``m`` is a multiple
    of ``blk_m``.
    """
    unit = 8 * blk_m
    rows = max(BLOCK_BYTES // (4 * k) // unit, 1) * unit
    if blk_m > 1:
        rows = min(rows, 8 * unit)
    return m if m <= rows else rows


def fire_compact_kernel(acc_ref, fired_ref, occ_ref, *, threshold: float,
                        magnitude: bool, qscale: float | None, blk_m: int,
                        blk_k: int):
    acc = acc_ref[...]                          # (tm, K)
    if magnitude:
        live = jnp.abs(acc) > threshold
    else:
        live = acc > threshold
    fired = jnp.where(live, acc, 0)
    if qscale is not None:
        # Symmetric int8 fake-quant with a static calibration scale.
        q = jnp.clip(jnp.round(fired / qscale), -128, 127)
        fired = q * qscale
    fired_ref[...] = fired.astype(fired_ref.dtype)
    # Fine (blk_m, blk_k) occupancy inside the block, as 0/1 group-sum
    # matmuls: for blk_m > 1 the row-group indicator (tm/blk_m, tm) times
    # live (tm, K), then that contracted with the column-group indicator
    # (K/blk_k, K).  Operands are 0/1 and the sums integers <= 128 (bf16
    # exact, f32 accumulate), so the counts are exact at any MXU precision;
    # a tile is live iff its count is non-zero.  A single K block is a lane
    # max instead: Mosaic refuses the N = 1 dot.
    tm, k = acc.shape
    gm, gk = occ_ref.shape
    hit = live
    if blk_m > 1:
        row = jax.lax.broadcasted_iota(jnp.int32, (gm, tm), 1)
        grp = jax.lax.broadcasted_iota(jnp.int32, (gm, tm), 0) * blk_m
        rows = ((row >= grp) & (row < grp + blk_m)).astype(jnp.bfloat16)
        hit = jnp.dot(rows, hit.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32) > 0
    if gk == 1:
        hit = jnp.max(hit.astype(jnp.float32), axis=1, keepdims=True) > 0
    else:
        col = jax.lax.broadcasted_iota(jnp.int32, (gk, k), 1)
        grp = jax.lax.broadcasted_iota(jnp.int32, (gk, k), 0) * blk_k
        cols = ((col >= grp) & (col < grp + blk_k)).astype(jnp.bfloat16)
        hit = jax.lax.dot_general(hit.astype(jnp.bfloat16), cols,
                                  (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32) > 0
    occ_ref[...] = hit.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("blk_m", "blk_k", "threshold",
                                             "magnitude", "qscale",
                                             "interpret"))
def fire_compact_pallas(acc: jax.Array, *, blk_m: int = 8, blk_k: int = 128,
                        threshold: float = 0.0, magnitude: bool = False,
                        qscale: float | None = None,
                        interpret: bool = False):
    """Returns (fired (M, K), occupancy (M/blk_m, K/blk_k) int32).

    The grid walks TPU-aligned row blocks (:func:`fire_block_rows`); the
    (blk_m, blk_k) event tiles are a finer grain computed inside each block.
    """
    m, k = acc.shape
    assert m % blk_m == 0 and k % blk_k == 0, (m, k, blk_m, blk_k)
    tm = fire_block_rows(m, k, blk_m)
    mp = -(-m // tm) * tm
    if mp != m:                # whole blocks; zero rows fire nothing
        acc = jnp.pad(acc, ((0, mp - m), (0, 0)))
    kernel = functools.partial(fire_compact_kernel, threshold=threshold,
                               magnitude=magnitude, qscale=qscale,
                               blk_m=blk_m, blk_k=blk_k)
    fired, occ = pl.pallas_call(
        kernel,
        grid=(mp // tm,),
        in_specs=[pl.BlockSpec((tm, k), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((tm, k), lambda i: (i, 0)),
            pl.BlockSpec((tm // blk_m, k // blk_k), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((mp, k), acc.dtype),
            jax.ShapeDtypeStruct((mp // blk_m, k // blk_k), jnp.int32),
        ],
        interpret=interpret,
        name="mnf_fire_compact",
    )(acc)
    return fired[:m], occ[:m // blk_m]
