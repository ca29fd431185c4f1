"""EventStream — the inter-layer currency of the MNF pipeline (DESIGN.md §5).

The paper's point is that activations stay *compressed between layers*: the
fire phase of layer L emits events, and the multiply phase of layer L+1
consumes them directly — no dense round-trip.  ``EventStream`` carries the
``BlockEvents`` of a fired activation matrix together with the logical shape
and tile geometry needed to consume (or, for oracle backends, to decode)
them.  ``engine.fire`` produces one; ``engine.linear`` accepts one.

One stream type is the currency for both FC and conv layers: a conv feature
map rides the same flattened (M, K) = (B·H·W, C) event view, with the
batched NHWC ``logical_shape`` carried alongside so ``conv2d`` can address
row groups spatially (pixel-granular ``blk_m == 1`` encoding — each row
group is one pixel, so a shifted tap slice is a gather of groups).

A pytree (jit/vmap/scan-safe): ``events`` and the optional cached ``fired``
dense twin are children; shape and tile geometry are static.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core import events as ev
# Import by submodule path: ``repro.core`` re-exports the ``quantize``
# *function* under the same name, shadowing the module attribute.
from repro.core.quantize import QParams as _QParams
from repro.core.quantize import dequantize as _dequantize
from repro.engine import trace

__all__ = ["EventStream"]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class EventStream:
    """Block events of a fired (M, K) activation matrix, plus geometry.

    events: BlockEvents over the block-padded matrix (Mp = ceil(M/blk_m),
            Kp = ceil(K/blk_k) multiples).
    fired:  optional cached dense twin (M, K) — kept when produced for free
            (the fire phase computes it anyway); ``None`` after transforms
            that only exist in event form.
    shape:  logical (M, K) before padding          [static]
    blk_m, blk_k: tile geometry of the encoding    [static]
    logical_shape: batched pre-flatten shape       [static] — (B, H, W, C)
            for conv feature maps (rows are raster-order pixels, K is the
            channel axis); ``None`` for plain (M, K) FC activations.
    qparams: quantization parameters of the event values (DESIGN.md §12) —
            set when ``values`` carry int8 (symmetric, zero_point == 0, so
            absent events are exact zeros in both domains); the kept
            ``fired`` twin is always the dequantized f32 map.  ``None``
            for f32 streams.
    signed: the producing fire rule can emit *negative* event values
            [static] — set by signed/magnitude fire (DESIGN.md §13).  The
            ReLU-fire invariant (every event value >= 0) underpins the
            pool's bitwise segment-max argument, so consumers that rely on
            it gate on this flag (``engine.pool_ineligible_reason``); the
            recurrent decode path *requires* it (two-sided per-token
            deltas).
    """

    events: ev.BlockEvents
    fired: jax.Array | None
    shape: tuple[int, int] = dataclasses.field(metadata=dict(static=True))
    blk_m: int = dataclasses.field(metadata=dict(static=True))
    blk_k: int = dataclasses.field(metadata=dict(static=True))
    logical_shape: tuple | None = dataclasses.field(
        default=None, metadata=dict(static=True))
    qparams: _QParams | None = None
    signed: bool = dataclasses.field(default=False,
                                     metadata=dict(static=True))

    # -- construction -------------------------------------------------------

    @classmethod
    def empty(cls, shape: tuple[int, int], *, blk_m: int, blk_k: int,
              capacity: int | None = None, fired: jax.Array | None = None,
              dtype=jnp.float32,
              logical_shape: tuple | None = None) -> "EventStream":
        """An explicitly event-free stream for a degenerate (M, K) shape.

        A zero-row activation (empty batch, fully-dead layer) has a
        zero-size event grid; building it here — instead of running the
        encode machinery or a fire backend over it — keeps 0-extent
        launches away from Pallas (which rejects zero-size grid slices).
        The array shapes match what :meth:`encode` would produce.
        """
        m, k = shape
        g = -(-m // blk_m) if m > 0 else 0
        nkb = -(-k // blk_k) if k > 0 else 0
        cap = nkb if capacity is None else min(capacity, nkb)
        cap = max(cap, 1) if nkb > 0 else 1
        bev = ev.BlockEvents(
            values=jnp.zeros((g, cap, blk_m, blk_k), dtype),
            block_idx=jnp.zeros((g, cap), jnp.int32),
            counts=jnp.zeros((g,), jnp.int32),
            num_k_blocks=nkb)
        return cls(events=bev, fired=fired, shape=(m, k), blk_m=blk_m,
                   blk_k=blk_k, logical_shape=logical_shape)

    @classmethod
    def encode(cls, x: jax.Array, *, blk_m: int, blk_k: int,
               capacity: int | None = None, threshold: float = 0.0,
               keep_dense: bool = True) -> "EventStream":
        """Encode a dense (M, K) activation matrix into a stream."""
        m, k = x.shape
        if m == 0 or k == 0:
            return cls.empty((m, k), blk_m=blk_m, blk_k=blk_k,
                             capacity=capacity, dtype=x.dtype,
                             fired=x if keep_dense else None)
        xp = ev.pad_to_block_multiple(x, blk_m, 0)
        xp = ev.pad_to_block_multiple(xp, blk_k, 1)
        bev = ev.encode_block_events(xp, blk_m=blk_m, blk_k=blk_k,
                                     capacity=capacity, threshold=threshold)
        return cls(events=bev, fired=x if keep_dense else None,
                   shape=(m, k), blk_m=blk_m, blk_k=blk_k)

    @classmethod
    def encode_nhwc(cls, x: jax.Array, *, blk_k: int, blk_m: int = 1,
                    capacity: int | None = None, threshold: float = 0.0,
                    keep_dense: bool = True) -> "EventStream":
        """Encode a dense (B, H, W, C) feature map into a conv stream.

        Rows of the event view are raster-order pixels; K is the channel
        axis.  ``blk_m == 1`` (default) is the pixel-granular encoding the
        per-tap ``conv2d`` path gathers; ``blk_m == STRIP_W`` is the
        strip-aligned encoding (each row group is an 8-pixel strip along W,
        which must divide W) that the fused-tap kernel consumes with an
        STRIP_W-fold smaller event grid (DESIGN.md §6).
        """
        b, h, w, c = x.shape
        assert blk_m == 1 or (blk_m == ev.STRIP_W and w % ev.STRIP_W == 0), \
            (blk_m, x.shape, "strip encoding needs blk_m == STRIP_W and "
                             "W % STRIP_W == 0")
        flat = x.reshape(b * h * w, c)
        s = cls.encode(flat, blk_m=blk_m, blk_k=ev.channel_blk_k(c, blk_k),
                       capacity=capacity, threshold=threshold,
                       keep_dense=keep_dense)
        return dataclasses.replace(s, logical_shape=(b, h, w, c))

    # -- views --------------------------------------------------------------

    @property
    def num_events(self) -> jax.Array:
        """Total live block events (the quantity the cost model prices)."""
        return self.events.counts.sum()

    def per_row_scalar_events(self) -> jax.Array:
        """Non-zero activation count per logical row, (M,) f32 — derived
        from the compacted event values alone (no dense twin), lossless at
        threshold 0.  For conv streams, row r is raster-order pixel r, so
        ``.reshape(B, H, W)`` is the per-pixel fired-event map the cost
        model weights by receptive-field fan-out."""
        return ev.scalar_event_rows(self.events)[:self.shape[0]]

    @property
    def num_scalar_events(self) -> jax.Array:
        """Total non-zero activations (the paper's event count), twin-free."""
        return self.per_row_scalar_events().sum()

    def occupancy(self) -> jax.Array:
        """Live fraction of the (row-group × K-block) event grid.

        A degenerate stream (0-row or 0-column logical shape) has an empty
        grid; its occupancy is defined as 0.0 rather than 0/0.
        """
        g = self.events.block_idx.shape[0]
        denom = g * self.events.num_k_blocks
        if denom == 0:
            return jnp.zeros((), jnp.float32)
        return self.num_events / denom

    def dense(self) -> jax.Array:
        """Dense (M, K) view.  Free if the fired twin was kept; otherwise a
        decode (the round-trip the chained path exists to avoid — oracle
        backends only).  Real decodes are visible to ``trace_dispatch``."""
        if self.fired is not None:
            return self.fired
        trace.record(op="stream.dense", decode=True, shape=self.shape)
        m, k = self.shape
        g = self.events.block_idx.shape[0]
        y = ev.decode_block_events(self.events, blk_m=self.blk_m,
                                   blk_k=self.blk_k, m=g * self.blk_m,
                                   k=self.events.num_k_blocks * self.blk_k)
        if self.qparams is not None:
            y = _dequantize(y, self.qparams)
        return y[:m, :k]

    def dense_nhwc(self) -> jax.Array:
        """Dense (B, H, W, C) view of a conv stream (``logical_shape`` set).

        Same cost semantics as :meth:`dense`: free via the cached fired twin,
        a recorded decode otherwise.
        """
        assert self.logical_shape is not None and \
            len(self.logical_shape) == 4, self.logical_shape
        return self.dense().reshape(self.logical_shape)

    def without_dense(self) -> "EventStream":
        """Drop the cached dense twin — events-only from here on (what a
        chained-layer test uses to prove no densify happens)."""
        return dataclasses.replace(self, fired=None)

    # -- transforms ---------------------------------------------------------

    def retile_fc(self) -> "EventStream":
        """Re-tile a conv stream to the flattened (B, H·W·C) FC view.

        Event-domain image of ``dense_nhwc().reshape(B, -1)``: the static
        address plan of :func:`repro.core.events.retile_block_events` moves
        block events — values travel by gather only — so no decode happens
        and the result equals encoding the flattened dense twin at the same
        (blk_m=1, blk_k) geometry, array for array (DESIGN.md §12).  The
        cached twin and ``qparams`` ride along.  Asserts eligibility; gate
        with :func:`repro.core.events.retile_ineligible_reason`.
        """
        reason = ev.retile_ineligible_reason(self.logical_shape, self.blk_m,
                                             self.blk_k)
        assert reason is None, reason
        b, h, w, c = self.logical_shape
        bev = ev.retile_block_events(self.events, self.logical_shape,
                                     self.blk_m)
        fired = None
        if self.fired is not None:
            fired = self.fired.reshape(b, h * w * c)
        return EventStream(events=bev, fired=fired, shape=(b, h * w * c),
                           blk_m=1, blk_k=self.blk_k, logical_shape=None,
                           qparams=self.qparams, signed=self.signed)

    def dequantize_events(self) -> "EventStream":
        """Dequantize int8 event values in place — still event-domain.

        A per-tile scalar multiply (symmetric: zero stays zero, padding
        slots stay exact zeros), not a decode: consumers that want f32
        values (the pool's segment max) read the same floats the kept twin
        carries, bitwise.  No-op on f32 streams.
        """
        if self.qparams is None:
            return self
        vals = _dequantize(self.events.values, self.qparams)
        bev = dataclasses.replace(self.events, values=vals)
        return dataclasses.replace(self, events=bev, qparams=None)
