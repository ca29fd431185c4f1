"""Event encoding — the paper's §4 compressed-data-storage scheme, TPU-adapted.

The paper encodes every non-zero activation as an *event* carrying the value
plus direct addresses (start_weight_addr, start_neuron_addr, ...) so that a PE
can fetch exactly the weights it needs with O(1) address arithmetic instead of
CSR/CSC/COO pointer chasing.

On TPU the profitable event granularity is a VMEM tile, not a scalar (see
DESIGN.md §2).  This module provides both:

  * scalar events  — faithful Algorithm-1/2 semantics, used by the CNN
    reference path and the cost model (event counting);
  * block events   — `(values[B_blk, E, blk], block_idx[B_blk, E], count)`
    compacted K-blocks, the encoding consumed by the `event_matmul` Pallas
    kernel (block_idx is the direct weight-tile address).

All functions are pure jnp / jax.lax and jit-safe (static shapes: event lists
are padded to a static capacity, with an explicit count — the TPU analogue of
the paper's end-of-data event).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

__all__ = [
    "STRIP_CO_MIN",
    "STRIP_STRIDES",
    "STRIP_W",
    "ScalarEvents",
    "BlockEvents",
    "encode_scalar_events",
    "count_nonzero_events",
    "block_occupancy",
    "channel_blk_k",
    "encode_block_events",
    "decode_block_events",
    "gather_row_groups",
    "gather_row_strips",
    "live_block_mask",
    "pad_to_block_multiple",
    "pool_window_map",
    "retile_block_events",
    "retile_fc_addr_offsets",
    "retile_ineligible_reason",
    "scalar_event_rows",
    "strip_eligible",
    "strip_ineligible_reason",
    "strip_parts",
    "strip_shift_live",
    "strip_subtap_counts",
    "strip_tap_map",
]

#: Pixels per row strip of the strip-aligned conv encoding (DESIGN.md §6).
#: Matches the TPU sublane count so a strip event is one (8, blk_k) tile.
STRIP_W = 8


# ---------------------------------------------------------------------------
# Scalar events (paper-faithful; Algorithm 1 / 2 inputs)
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ScalarEvents:
    """Padded list of scalar events for one feature map / activation vector.

    values:  (capacity,)   event activation values (0 in padding slots)
    indices: (capacity,)   flat position of the activation (0 in padding)
    count:   ()            number of live events (<= capacity)
    """

    values: jax.Array
    indices: jax.Array
    count: jax.Array

    @property
    def capacity(self) -> int:
        return self.values.shape[0]


def encode_scalar_events(x: jax.Array, capacity: int | None = None,
                         threshold: float = 0.0) -> ScalarEvents:
    """Compact the non-zero (|x| > threshold) entries of ``x`` into events.

    This is the fire-module output format: each event is (value, address).
    ``capacity`` defaults to x.size (lossless).  Events are emitted in
    ascending address order — matching the paper's raster-order event stream.
    """
    flat = x.reshape(-1)
    n = flat.shape[0]
    if capacity is None:
        capacity = n
    live = jnp.abs(flat) > threshold
    count = jnp.sum(live, dtype=jnp.int32)
    # Stable compaction: sort (1 - live) keeps live entries first, in order.
    order = jnp.argsort(jnp.logical_not(live), stable=True)
    idx = order[:capacity].astype(jnp.int32)
    vals = flat[idx]
    slot_live = jnp.arange(capacity, dtype=jnp.int32) < count
    vals = jnp.where(slot_live, vals, 0)
    idx = jnp.where(slot_live, idx, 0)
    return ScalarEvents(values=vals, indices=idx, count=count)


def count_nonzero_events(x: jax.Array, threshold: float = 0.0) -> jax.Array:
    """Number of scalar events a tensor would fire (cost-model instrumentation)."""
    return jnp.sum(jnp.abs(x) > threshold, dtype=jnp.int64.dtype
                   if jax.config.read("jax_enable_x64") else jnp.int32)


# ---------------------------------------------------------------------------
# Block events (TPU-native; consumed by kernels/event_matmul)
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class BlockEvents:
    """Compacted K-block events for a batch-tiled activation matrix.

    For an activation matrix a[(M), (K)] tiled into K//blk blocks per row
    group:

    values:    (G, E, blk_m, blk_k)  the live activation tiles (padding = 0)
    block_idx: (G, E)                direct weight-tile address of each event
                                     (padding repeats the last live index so a
                                     consuming kernel's DMA is a no-op)
    counts:    (G,)                  number of live events per row group
    num_k_blocks: static int         K // blk_k
    """

    values: jax.Array
    block_idx: jax.Array
    counts: jax.Array
    num_k_blocks: int = dataclasses.field(metadata=dict(static=True))

    @property
    def capacity(self) -> int:
        return self.block_idx.shape[-1]


def pad_to_block_multiple(x: jax.Array, block: int, axis: int) -> jax.Array:
    """Zero-pad ``axis`` of ``x`` up to a multiple of ``block``."""
    size = x.shape[axis]
    rem = (-size) % block
    if rem == 0:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, rem)
    return jnp.pad(x, pads)


def block_occupancy(x: jax.Array, blk_k: int, threshold: float = 0.0) -> jax.Array:
    """Per-K-block liveness: any |x| > threshold inside the block.

    x: (..., K) -> bool (..., K // blk_k).  K must be a multiple of blk_k.
    """
    *lead, k = x.shape
    assert k % blk_k == 0, f"K={k} not a multiple of blk_k={blk_k}"
    xb = x.reshape(*lead, k // blk_k, blk_k)
    return jnp.any(jnp.abs(xb) > threshold, axis=-1)


def encode_block_events(a: jax.Array, *, blk_m: int, blk_k: int,
                        capacity: int | None = None,
                        threshold: float = 0.0) -> BlockEvents:
    """Encode activation matrix a (M, K) into block events.

    Rows are grouped into G = M // blk_m row groups.  A K-block is an event
    for a group iff any element in the (blk_m, blk_k) tile exceeds the
    threshold.  Live tiles are compacted (in ascending K-block order — the
    paper's raster event order) to a static ``capacity`` (default: all
    blocks, lossless).
    """
    m, k = a.shape
    assert m % blk_m == 0 and k % blk_k == 0, (m, k, blk_m, blk_k)
    g, nkb = m // blk_m, k // blk_k
    if capacity is None:
        capacity = nkb
    capacity = min(capacity, nkb)
    tiles = a.reshape(g, blk_m, nkb, blk_k).transpose(0, 2, 1, 3)  # (G, nkb, bm, bk)
    live = jnp.any(jnp.abs(tiles) > threshold, axis=(-1, -2))      # (G, nkb)
    counts = jnp.sum(live, axis=-1, dtype=jnp.int32)               # (G,)
    order = jnp.argsort(jnp.logical_not(live), axis=-1, stable=True)  # live first
    idx = order[:, :capacity].astype(jnp.int32)                    # (G, E)
    slot = jnp.arange(capacity, dtype=jnp.int32)[None, :]
    slot_live = slot < counts[:, None]
    # Padding index repeats the last live index (DMA no-op downstream);
    # all-empty groups point at block 0 with zero values.
    last_live = jnp.maximum(counts - 1, 0)
    gathered_last = jnp.take_along_axis(idx, last_live[:, None], axis=1)
    idx = jnp.where(slot_live, idx, gathered_last)
    vals = jnp.take_along_axis(tiles, idx[:, :, None, None], axis=1)  # (G,E,bm,bk)
    vals = jnp.where(slot_live[:, :, None, None], vals, 0)
    return BlockEvents(values=vals, block_idx=idx, counts=counts,
                       num_k_blocks=nkb)


def gather_row_groups(bev: BlockEvents, idx: jax.Array,
                      live: jax.Array) -> BlockEvents:
    """Re-index row groups of ``bev`` — the event-domain image of a row gather.

    idx:  (G',) int32   source row-group index per output group
    live: (G',) bool    False marks groups with no source (e.g. a conv tap
                        reading outside the padded feature map); their counts
                        are zeroed so consumers treat them as event-free.

    This is what lets a conv tap consume the *fired feature-map events* of
    the previous layer directly: a shifted spatial slice of a pixel-granular
    (blk_m == 1) encoding is exactly a gather of its row groups — no dense
    map is ever materialized (DESIGN.md §5).
    """
    counts = jnp.where(live, bev.counts[idx], 0)
    return BlockEvents(values=bev.values[idx], block_idx=bev.block_idx[idx],
                       counts=counts, num_k_blocks=bev.num_k_blocks)


def gather_row_strips(bev: BlockEvents, idx: jax.Array, live: jax.Array,
                      shift: int, row_stride: int = 1) -> BlockEvents:
    """Tap-shifted strip gather — the strip analogue of :func:`gather_row_groups`.

    Gathers row-strip groups (``idx``/``live`` exactly as in
    ``gather_row_groups``) and then remaps rows *within* each (blk_m, blk_k)
    tile by the static affine map: output row i takes source row
    ``row_stride * i + shift``, rows whose source falls outside [0, blk_m)
    are zero.  A conv tap at stride 1 whose x-offset is not a multiple of
    STRIP_W straddles two adjacent strips; at stride 2 an output strip reads
    every other input pixel, so the 8 sources of one tap spread over up to
    three strips as *interleaved half-strips* (4 same-parity pixels each).
    One ``gather_row_strips`` per (tap, straddle part) realizes the strided
    slice in the event domain (DESIGN.md §6).

    The row remap is a pure gather + zero mask — no FP arithmetic — so
    gathered values are bit-identical to the source rows.
    """
    g = gather_row_groups(bev, idx, live)
    bm = g.values.shape[2]
    d = int(shift)
    rs = int(row_stride)
    if rs == 1 and d == 0:
        return g
    rows = [rs * i + d for i in range(bm)]
    if not any(0 <= r < bm for r in rows):
        return dataclasses.replace(g, values=jnp.zeros_like(g.values),
                                   counts=jnp.zeros_like(g.counts))
    if rs == 1:      # contiguous shift: slice + zero-pad
        if d > 0:    # out rows [0, bm-d) <- src rows [d, bm)
            vals = jnp.pad(g.values[:, :, d:, :],
                           ((0, 0), (0, 0), (0, d), (0, 0)))
        else:        # out rows [-d, bm) <- src rows [0, bm+d)
            vals = jnp.pad(g.values[:, :, :bm + d, :],
                           ((0, 0), (0, 0), (-d, 0), (0, 0)))
        return dataclasses.replace(g, values=vals)
    take = jnp.asarray([min(max(r, 0), bm - 1) for r in rows], jnp.int32)
    ok = jnp.asarray([0 <= r < bm for r in rows], bool)
    vals = jnp.where(ok[None, None, :, None], g.values[:, :, take, :], 0)
    return dataclasses.replace(g, values=vals)


def live_block_mask(bev: BlockEvents) -> jax.Array:
    """Per-K-block liveness of an event set, (G, num_k_blocks) bool.

    Scatter of the compacted slots back onto the block grid.  Padding slots
    repeat the *last live* block index (the DMA-no-op convention), so they
    are masked out before the scatter — a dead block stays dead even when a
    padding slot points at its neighbour.  This is the skip mask the
    event-gated recurrent step kernels consult per state row-block
    (DESIGN.md §13): ``decode_block_events(bev) != 0`` implies the mask is
    live at that block, never the reverse.
    """
    g, e = bev.block_idx.shape
    mask = jnp.zeros((g, bev.num_k_blocks), jnp.int32)
    if g == 0 or bev.num_k_blocks == 0:
        return mask > 0
    slot_live = jnp.arange(e, dtype=jnp.int32)[None, :] < bev.counts[:, None]
    garr = jnp.broadcast_to(jnp.arange(g, dtype=jnp.int32)[:, None], (g, e))
    mask = mask.at[garr.reshape(-1), bev.block_idx.reshape(-1)].add(
        slot_live.reshape(-1).astype(jnp.int32))
    return mask > 0


def scalar_event_rows(bev: BlockEvents) -> jax.Array:
    """Per-row scalar-event (non-zero activation) counts, (G * blk_m,) f32.

    Derived from the compacted event values alone — no dense twin needed —
    because the block encoding is lossless at threshold 0: every non-zero
    activation sits in exactly one live tile (twin-free instrumentation).
    """
    g, e, bm, bk = bev.values.shape
    slot_live = jnp.arange(e, dtype=jnp.int32)[None, :] < bev.counts[:, None]
    nz = (bev.values != 0) & slot_live[:, :, None, None]
    return jnp.sum(nz, axis=(1, 3), dtype=jnp.float32).reshape(g * bm)


#: Output-channel granule for the strip path.  The bit-exactness contract
#: (strip == per-tap, bitwise) relies on the backend lowering the
#: (8, bk) @ (bk, n) and (1, bk) @ (bk, n) dots with the same per-element
#: K-reduction; XLA picks M-dependent strategies when n has a ragged lane
#: remainder (observed divergence at n = 2 and n = 9, while n = 8, 12, 16
#: hold), so strips require whole sublane groups of output channels.
#: Real conv nets (AlexNet/VGG co in {64, 96, ..., 512}) always qualify.
STRIP_CO_MIN = 8


#: Strides the strip plan covers: output pixel x maps affinely to input
#: pixel stride*x, so each tap gathers at most ``strip_parts(stride)``
#: straddle parts (two adjacent-strip halves at stride 1; up to three
#: interleaved half-strips — 4 same-parity pixels each — at stride 2; up
#: to five quarter-strips — 2 same-residue pixels each — at stride 4, the
#: AlexNet conv1 case).  The plan math is stride-generic; this tuple is
#: the *validated* set (each member carries a bitwise strip == per-tap
#: test suite), not a structural limit.
STRIP_STRIDES = (1, 2, 4)


def strip_parts(stride: int) -> int:
    """Worst-case straddle parts per tap at ``stride``.

    Output row i of a strip reads input pixel ``stride*i + s`` (s the tap
    x-offset), so the 8 sources span ``7*stride + 1`` pixels and touch at
    most ``(7*stride + STRIP_W - 1)//STRIP_W + 1`` input strips.  Equals
    ``stride + 1`` for every stride in STRIP_STRIDES.
    """
    return ((STRIP_W - 1) * stride + STRIP_W - 1) // STRIP_W + 1


def strip_shift_live(shift: int, stride: int) -> bool:
    """True iff the affine row map ``out row i <- src row stride*i + shift``
    sources at least one row in [0, STRIP_W).  Depends only on (shift,
    stride) — never on the output strip — which is what makes dead
    straddle parts *columns* of the plan, droppable at plan time."""
    return any(0 <= stride * i + shift < STRIP_W for i in range(STRIP_W))


def strip_subtap_counts(k: int, padding: int, stride: int) -> tuple[int, int]:
    """(compacted, worst-case) subtap column counts of a strip conv plan.

    ``worst = strip_parts(stride) * k * k`` is the uncompacted grid the
    pre-compaction kernels launched; ``compacted`` keeps only parts whose
    affine row map sources a row (``strip_shift_live``).  Pure arithmetic
    twin of :func:`strip_tap_map`'s column enumeration — engine traces and
    benches report both without building a plan.
    """
    parts = strip_parts(stride)
    live = 0
    for dx in range(k):
        r = (dx - padding) % STRIP_W
        live += sum(strip_shift_live(r - j * STRIP_W, stride)
                    for j in range(parts))
    return live * k, parts * k * k


def strip_ineligible_reason(width: int, k: int, stride: int, padding: int,
                            co: int | None = None) -> str | None:
    """Why a conv layer cannot consume a strip-aligned stream (None = it can).

    Strip tiling (blk_m == STRIP_W) needs every tap's strided slice to be
    an affine row remap of at most ``strip_parts(stride)`` straddle parts:
    stride in STRIP_STRIDES (output pixel x maps to input pixel
    stride*x + dx - p, so the 8 sources of one output strip interleave
    with step ``stride``), input and output widths tiling into whole
    strips, padding at most k // 2 (so output strips never outnumber the
    input strips the straddle plan pairs them with), and tap x-offsets
    within one strip of the origin.  When the output-channel count ``co``
    is known it must be a multiple of STRIP_CO_MIN (see its note) so
    strip == per-tap stays bitwise.

    Every message is derived from STRIP_STRIDES / STRIP_W / STRIP_CO_MIN —
    never a hardcoded stride set — so extending STRIP_STRIDES can't ship a
    stale error message (``test_strip_ineligible_reason_message_table``
    pins the rendered strings against the same constants).
    """
    if stride not in STRIP_STRIDES:
        return (f"stride {stride} not in {set(STRIP_STRIDES)} (strip plans "
                f"gather up to (7*stride + 7)//8 + 1 interleaved straddle "
                f"parts per tap; only these strides are validated bitwise)")
    out_w = (width + 2 * padding - k) // stride + 1
    if width <= 0 or width % STRIP_W:
        return f"input width {width} not a multiple of STRIP_W={STRIP_W}"
    if out_w <= 0 or out_w % STRIP_W:
        return (f"output width {out_w} ((W + 2p - k)//stride + 1) not a "
                f"multiple of STRIP_W={STRIP_W}")
    if padding > k // 2:
        return (f"padding {padding} > k//2 = {k // 2}: the output map "
                f"outgrows the input and a tap shift can index outside the "
                f"planned straddle parts (strip plans pair each output "
                f"strip with its aligned input strips)")
    if padding > STRIP_W or k - 1 - padding > STRIP_W:
        return (f"tap x-offsets [-{padding}, {k - 1 - padding}] leave the "
                f"adjacent-strip window (|dx - p| <= {STRIP_W})")
    if co is not None and (co < STRIP_CO_MIN or co % STRIP_CO_MIN):
        return (f"output channels {co} not a multiple of "
                f"STRIP_CO_MIN={STRIP_CO_MIN} (bitwise contract needs an "
                f"M-invariant dot lowering — ragged lane remainders break it)")
    return None


def strip_eligible(width: int, k: int, stride: int, padding: int,
                   co: int | None = None) -> bool:
    """True iff a k x k / stride / padding conv over maps of width ``width``
    (and, when given, ``co`` output channels) can consume a strip-aligned
    (blk_m == STRIP_W) event stream."""
    return strip_ineligible_reason(width, k, stride, padding, co) is None


def strip_tap_map(logical_shape: tuple, k: int, padding: int,
                  stride: int = 1):
    """Static *compacted* subtap gather plan for the fused strip conv
    (DESIGN.md §6).

    For each output strip and each live subtap (tap (dy, dx) split into
    its straddle parts, dead parts dropped — see below), the plan names
    the source strip group and the in-tile affine row map that realize
    the tap's strided slice:

      src   (G_out, T) int32  source strip group (clamped when dead)
      live  (G_out, T) bool   False = no source (zero-padding border)
      shift (T,)       int32  signed row offset d: out row i <- src row
                              stride*i + d
      tap   (T,)       int32  flat filter index dy*k + dx of the subtap

    Output row i of strip (b, oy, sx) reads input pixel
    ``8*stride*sx + stride*i + s`` for tap x-offset s = dx - p: the 8
    sources span ``7*stride + 1`` pixels, i.e. up to
    ``strip_parts(stride)`` input strips, part j contributing the rows
    its affine map ``out row i <- src row stride*i + d`` (d = s%8 - 8j)
    lands inside [0, 8).  At stride 1 that is the familiar two
    adjacent-strip halves; at stride 2, up to three interleaved
    half-strips (4 same-parity pixels each); at stride 4, up to five
    quarter-strips (2 same-residue pixels each — AlexNet conv1).

    **Dead-subtap compaction**: a part whose (d, stride) sources no row
    is dead for *every* output strip (``strip_shift_live`` depends on the
    shift alone), so its column is dropped from the plan instead of
    carried as an always-idle grid step — r == 0 taps lose their second
    half at stride 1, r < 2 taps their third part at stride 2, r < 4
    taps their fifth part at stride 4.  T is therefore the *compacted*
    count ``strip_subtap_counts(k, padding, stride)[0]`` <= worst-case
    ``strip_parts(stride)*k*k``, and consumers size their inner grid by
    the plan they are handed.  Dropping a dead column only removes
    additions of exact zeros from fixed reduction slots, so the
    compacted plan stays bit-identical to the uncompacted one (and to
    the per-tap oracle).

    Subtaps are ordered tap-major (dy, dx ascending — the per-tap
    oracle's loop order), surviving straddle parts left-to-right, so a
    consumer accumulating in plan order reproduces the per-tap reduction
    tree bit-for-bit.  Everything here is shape-derived — plain numpy,
    evaluated at trace time.
    """
    import numpy as np

    b, h, w, _ = logical_shape
    assert stride in STRIP_STRIDES, (stride, "strip_ineligible_reason gates")
    assert w % STRIP_W == 0, (logical_shape, "strip encoding needs W % 8 == 0")
    assert padding <= k // 2, (k, padding, "strip plans pair each output "
                               "strip with its aligned input strips; "
                               "strip_ineligible_reason gates this")
    oh = (h + 2 * padding - k) // stride + 1
    ow = (w + 2 * padding - k) // stride + 1
    assert ow > 0 and ow % STRIP_W == 0, (logical_shape, k, padding, stride)
    nsx_in = w // STRIP_W
    nsx_out = ow // STRIP_W
    g_out = b * oh * nsx_out
    gidx = np.arange(g_out, dtype=np.int64)
    sx = gidx % nsx_out
    oy = (gidx // nsx_out) % oh
    bb = gidx // (nsx_out * oh)
    parts = strip_parts(stride)
    t_n, t_worst = strip_subtap_counts(k, padding, stride)
    src = np.zeros((g_out, t_n), np.int32)
    live = np.zeros((g_out, t_n), bool)
    shift = np.zeros((t_n,), np.int32)
    tap = np.zeros((t_n,), np.int32)
    t = 0
    for dy in range(k):
        for dx in range(k):
            iy = oy * stride + dy - padding
            s = dx - padding                       # tap x-offset
            base = stride * sx + (s // STRIP_W)    # first straddled strip
            r = s % STRIP_W                        # in-strip row offset
            for j in range(parts):
                d = r - j * STRIP_W
                if not strip_shift_live(d, stride):
                    continue                       # dead part: column dropped
                tx = base + j
                ok = (iy >= 0) & (iy < h) & (tx >= 0) & (tx < nsx_in)
                src[:, t] = ((bb * h + np.clip(iy, 0, h - 1)) * nsx_in
                             + np.clip(tx, 0, nsx_in - 1)).astype(np.int32)
                live[:, t] = ok
                shift[t] = d
                tap[t] = dy * k + dx
                t += 1
    assert t == t_n <= t_worst, (t, t_n, t_worst)
    return src, live, shift, tap


def pool_window_map(logical_shape: tuple, k: int, stride: int, blk_m: int):
    """Static window gather plan for the event-native max-pool (DESIGN.md §7).

    Maps each output pixel of a VALID k×k / ``stride`` max-pool over a
    (B, H, W, C) feature map onto the event row groups of the input stream:
    for output pixel p = (b, oy, ox) and window tap t = (dy, dx), the source
    input pixel is q = (b, oy·stride + dy, ox·stride + dx), and the plan
    names where q lives in the encoding:

      src  (P_out, T) int32  row group holding q (q // blk_m — pixel groups
                             at blk_m == 1, 8-pixel raster strips at
                             blk_m == STRIP_W; both tile raster order, which
                             is what makes the decomposition uniform)
      row  (P_out, T) int32  q's row within the group's (blk_m, blk_k) tile
      live (P_out, T) bool   False = no source pixel.  Always True for
                             VALID pooling (the window never leaves the
                             map); carried so a SAME-padded variant reuses
                             the plan shape.

    T = k·k window taps, ordered (dy, dx) ascending — the raster order the
    dense ``reduce_window`` walks.  Max is order-invariant, so consumers
    need no ordering contract; the order only keeps plans deterministic.
    Everything here is shape-derived — plain numpy, evaluated at trace time.
    """
    import numpy as np

    b, h, w, _ = logical_shape
    assert k >= 1 and stride >= 1, (k, stride)
    assert h >= k and w >= k, (logical_shape, k, "VALID window exceeds map")
    if blk_m == STRIP_W:
        assert w % STRIP_W == 0, (logical_shape,
                                  "strip encoding needs W % 8 == 0")
    oh = (h - k) // stride + 1
    ow = (w - k) // stride + 1
    p_out = b * oh * ow
    pidx = np.arange(p_out, dtype=np.int64)
    ox = pidx % ow
    oy = (pidx // ow) % oh
    bb = pidx // (ow * oh)
    t_n = k * k
    src = np.zeros((p_out, t_n), np.int32)
    row = np.zeros((p_out, t_n), np.int32)
    live = np.zeros((p_out, t_n), bool)
    t = 0
    for dy in range(k):
        for dx in range(k):
            iy = oy * stride + dy
            ix = ox * stride + dx
            q = (bb * h + iy) * w + ix          # raster flat pixel index
            src[:, t] = (q // blk_m).astype(np.int32)
            row[:, t] = (q % blk_m).astype(np.int32)
            live[:, t] = (iy < h) & (ix < w)    # always true for VALID
            t += 1
    return src, row, live


def pool_window_ineligible_reason(logical_shape: tuple, k: int, stride: int,
                                  blk_m: int) -> str | None:
    """Why the *window-major* strip pool cannot consume this stream
    (None = it can; the per-event segment max remains the general path).

    The window-major grid walks output strips — 8 consecutive pooled pixels
    of one output row — so it needs a strip-aligned input stream
    (blk_m == STRIP_W, W % 8 == 0) and a pooled width that tiles into whole
    strips (OW % 8 == 0: every grid step's 8 output pixels are real).
    """
    if blk_m != STRIP_W:
        return f"stream not strip-aligned (blk_m={blk_m} != STRIP_W)"
    b, h, w, _ = logical_shape
    if w <= 0 or w % STRIP_W:
        return f"input width {w} not a multiple of STRIP_W={STRIP_W}"
    if h < k or w < k:
        return f"VALID {k}x{k} window exceeds the {h}x{w} map"
    ow = (w - k) // stride + 1
    if ow <= 0 or ow % STRIP_W:
        return (f"pooled width {ow} ((W - k)//stride + 1) not a multiple "
                f"of STRIP_W={STRIP_W}")
    return None


def pool_strip_map(logical_shape: tuple, k: int, stride: int):
    """Window-major gather plan for the strip event pool (DESIGN.md §7).

    Where :func:`pool_window_map` walks output *pixels* (grid P_out · k²·E),
    this plan walks output *strips* — 8 consecutive pooled pixels of one
    output row — so the consumer's grid shrinks 8-fold to
    (B·OH·(OW/8), T, E).  Output row i of strip (b, oy, sx) pools input
    pixel ix = 8·stride·sx + stride·i + dx at window tap (dy, dx); the 8
    strided sources span up to ``parts = (7·stride + k - 1)//8 + 1`` input
    strips, each contributing an interleaved part realized by the same
    affine row remap as the fused conv plan (out row i <- src row
    stride·i + d; rows outside [0, 8) are exact zeros — the max identity):

      src   (G_out, T) int32  source input strip group (clamped when dead)
      live  (G_out, T) bool   False = part sources no row (masked to 0)
      shift (T,)       int32  signed row offset d = dx - 8·j of part j
      tap   (T,)       int32  flat window index dy·k + dx of the subtap

    T = k·k·parts subtaps, tap-major then parts left-to-right — the same
    ordering discipline as ``strip_tap_map`` (max needs no order contract;
    determinism keeps plans comparable).  Requires a strip-eligible
    geometry (:func:`pool_window_ineligible_reason`); everything here is
    shape-derived — plain numpy, evaluated at trace time.
    """
    import numpy as np

    b, h, w, _ = logical_shape
    reason = pool_window_ineligible_reason(logical_shape, k, stride, STRIP_W)
    assert reason is None, (logical_shape, k, stride, reason)
    oh = (h - k) // stride + 1
    ow = (w - k) // stride + 1
    nsx_in = w // STRIP_W
    nsx_out = ow // STRIP_W
    g_out = b * oh * nsx_out
    parts = ((STRIP_W - 1) * stride + k - 1) // STRIP_W + 1
    t_n = k * k * parts
    gidx = np.arange(g_out, dtype=np.int64)
    sx = gidx % nsx_out
    oy = (gidx // nsx_out) % oh
    bb = gidx // (nsx_out * oh)
    src = np.zeros((g_out, t_n), np.int32)
    live = np.zeros((g_out, t_n), bool)
    shift = np.zeros((t_n,), np.int32)
    tap = np.zeros((t_n,), np.int32)
    t = 0
    for dy in range(k):
        for dx in range(k):
            iy = oy * stride + dy              # always in-map (VALID)
            for j in range(parts):
                tx = stride * sx + dx // STRIP_W + j
                d = dx % STRIP_W - j * STRIP_W
                # Part j is live iff its affine map sources at least one of
                # the strip's 8 rows; a live row's input pixel is a real
                # VALID window read, so tx is automatically in-map.
                ok = any(0 <= stride * i + d < STRIP_W
                         for i in range(STRIP_W))
                src[:, t] = ((bb * h + iy) * nsx_in
                             + np.clip(tx, 0, nsx_in - 1)).astype(np.int32)
                live[:, t] = ok & (tx >= 0) & (tx < nsx_in)
                shift[t] = d
                tap[t] = dy * k + dx
                t += 1
    return src, live, shift, tap


def channel_blk_k(c: int, blk_k: int) -> int:
    """K-block width of a conv stream over ``c`` channels under a
    configured ``blk_k`` (DESIGN.md §5): all ``c`` channels in one block
    when they fit, else the widest multiple of 8 that divides ``c`` and is
    at most ``blk_k`` — so the conv→FC re-tiler's ``C % blk_k == 0`` holds
    wherever such a width exists — else ``blk_k``.  The one clamp the
    conv, pool and fire boundaries, the encoder and the chain planner
    share, so a stream and its round-trip twin always tile alike.
    """
    if c <= blk_k:
        return max(c, 1)
    for d in range(blk_k - blk_k % 8, 0, -8):
        if c % d == 0:
            return d
    return blk_k


def retile_ineligible_reason(logical_shape: tuple | None, blk_m: int,
                             blk_k: int) -> str | None:
    """Why a conv stream cannot re-tile to the FC view (None = it can).

    The conv→FC re-tile maps a (B·H·W, C)-tiled stream onto the flattened
    (B, H·W·C) view by address arithmetic alone (DESIGN.md §12): FC K-block
    ``pix·nkb + j`` is conv tile ``j`` of raster pixel ``pix``, which only
    works when every conv K-block lands intact inside the flattened row.
    That needs a conv stream (NHWC logical shape), a channel depth that
    tiles into whole K-blocks (C % blk_k == 0 — otherwise the conv
    encoding's K-padding columns would interleave into the middle of the
    FC row), and pixel- or strip-granular rows (blk_m in {1, STRIP_W} —
    the two granularities fire emits; a strip splits into 8 per-pixel
    events before re-tiling).

    Messages are derived from STRIP_W and the offending shape — never
    hardcoded — and are pinned verbatim by
    ``test_retile_ineligible_reason_message_table``.
    """
    if logical_shape is None or len(logical_shape) != 4:
        return ("stream has no NHWC logical shape (not a conv stream; "
                "nothing to re-tile)")
    c = logical_shape[-1]
    if c % blk_k:
        return (f"channel depth {c} not a multiple of blk_k={blk_k} (the "
                f"conv encoding's K-padding columns would interleave into "
                f"the flattened FC row)")
    if blk_m not in (1, STRIP_W):
        return (f"row granularity blk_m={blk_m} is neither pixel (1) nor "
                f"strip (STRIP_W={STRIP_W})")
    return None


def retile_fc_addr_offsets(logical_shape: tuple, num_k_blocks: int,
                           capacity: int):
    """Static address plan for the conv→FC re-tile (DESIGN.md §12).

    For a pixel-granular (blk_m == 1) conv stream over (B, H, W, C) with
    ``num_k_blocks`` K-blocks per pixel and ``capacity`` event slots per
    row group, the flattened (B, H·W·C) view puts conv tile ``j`` of
    raster pixel ``pix`` at FC K-block ``pix·num_k_blocks + j``.  Slots of
    one batch row are laid out pixel-major (all slots of pixel 0, then
    pixel 1, ...), so the per-slot address offset is a pure function of
    the slot position:

      off (H·W·capacity,) int32   off[s] = (s // capacity) · num_k_blocks

    The re-tiled address of slot s is ``off[s] + block_idx[pix, slot]`` —
    a static offset add, no decode.  Everything here is shape-derived —
    plain numpy, evaluated at trace time (the ``strip_tap_map`` idiom).
    """
    import numpy as np

    _, h, w, _ = logical_shape
    slots = h * w * capacity
    off = (np.arange(slots, dtype=np.int64) // capacity) * num_k_blocks
    return off.astype(np.int32)


def retile_block_events(bev: BlockEvents, logical_shape: tuple,
                        blk_m: int) -> BlockEvents:
    """Re-tile a (B·H·W, C) conv block stream to the (B, H·W·C) FC view.

    Exactness contract (pinned by tests/test_retile.py): for a stream
    produced by ``encode_block_events`` at threshold 0 (every live tile
    holds a non-zero and block addresses are unique per group),

        retile_block_events(bev, (B, H, W, C), blk_m)
          == encode_block_events(decoded.reshape(B, H*W*C), blk_m=1,
                                 blk_k=bk, capacity=H*W*E, threshold=0.0)

    array for array (values, block_idx, counts) — where ``decoded`` is the
    dense (B·H·W, C) twin and E the input capacity.  With the lossless
    default capacity (E == num_k_blocks) the re-tiled capacity H·W·E is
    exactly the FC view's block count, i.e. the lossless default again.

    The pipeline is the encode pipeline run over pre-compacted slots:
    strip tiles first split into 8 per-pixel events (a pure transpose —
    rows move, values don't), per-slot FC addresses come from the static
    :func:`retile_fc_addr_offsets` plan, live slots (in-count and holding
    a non-zero) compact live-first by stable argsort — pixel-major slot
    order with ascending per-group addresses means ascending FC addresses,
    encode's raster event order — and padding repeats the last live
    address with zeroed values, exactly as encode pads.  Values move by
    gather only (any dtype, int8 included); no FP arithmetic touches them.
    """
    b, h, w, c = logical_shape
    g, e, bm, bk = bev.values.shape
    reason = retile_ineligible_reason(logical_shape, blk_m, bk)
    assert reason is None, reason
    assert bm == blk_m and g * blk_m == b * h * w, (bev.values.shape,
                                                   logical_shape, blk_m)
    nkb = bev.num_k_blocks
    vals, idx, counts = bev.values, bev.block_idx, bev.counts
    if blk_m != 1:          # split strips into per-pixel events: rows move,
        vals = vals.transpose(0, 2, 1, 3).reshape(g * bm, e, 1, bk)
        idx = jnp.repeat(idx, bm, axis=0)          # values don't.
        counts = jnp.repeat(counts, bm)
    slots = h * w * e
    off = jnp.asarray(retile_fc_addr_offsets(logical_shape, nkb, e))
    addr = idx.reshape(b, slots) + off[None, :]
    in_count = (jnp.arange(e, dtype=jnp.int32)[None, :]
                < counts[:, None]).reshape(b, slots)
    live = in_count & jnp.any(vals.reshape(b, slots, bk) != 0, axis=-1)
    order = jnp.argsort(jnp.logical_not(live), axis=-1, stable=True)
    addr = jnp.take_along_axis(addr, order, axis=1)
    live = jnp.take_along_axis(live, order, axis=1)
    vals = jnp.take_along_axis(vals.reshape(b, slots, 1, bk),
                               order[:, :, None, None], axis=1)
    counts_fc = jnp.sum(live, axis=-1, dtype=jnp.int32)
    last_live = jnp.maximum(counts_fc - 1, 0)
    gathered_last = jnp.take_along_axis(addr, last_live[:, None], axis=1)
    addr = jnp.where(live, addr, gathered_last).astype(jnp.int32)
    vals = jnp.where(live[:, :, None, None], vals, 0)
    return BlockEvents(values=vals, block_idx=addr, counts=counts_fc,
                       num_k_blocks=h * w * nkb)


def decode_block_events(ev: BlockEvents, *, blk_m: int, blk_k: int,
                        m: int, k: int) -> jax.Array:
    """Inverse of :func:`encode_block_events` (up to thresholded-away values).

    Scatter the event tiles back into a dense (M, K) matrix.  Property-tested:
    decode(encode(x)) == x whenever threshold == 0.
    """
    g, e = ev.block_idx.shape
    nkb = ev.num_k_blocks
    assert m == g * blk_m and k == nkb * blk_k
    dense = jnp.zeros((g, nkb, blk_m, blk_k), ev.values.dtype)
    slot_live = jnp.arange(e, dtype=jnp.int32)[None, :] < ev.counts[:, None]
    vals = jnp.where(slot_live[:, :, None, None], ev.values, 0)
    garr = jnp.arange(g, dtype=jnp.int32)[:, None].repeat(e, axis=1)
    dense = dense.at[garr.reshape(-1), ev.block_idx.reshape(-1)].add(
        vals.reshape(g * e, blk_m, blk_k))
    return dense.transpose(0, 2, 1, 3).reshape(m, k)
