"""Engine front-door ops (DESIGN.md §3) — the only entry points the model
stack uses for MNF compute.

Every op takes an :class:`EngineConfig` and dispatches through the backend
registry; ``linear`` additionally accepts an :class:`EventStream` so
consecutive MNF layers chain events without a decode→re-encode round-trip
(the paper's end-to-end event dataflow).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core import events as ev
from repro.costmodel import crossover as xover
from repro.engine import trace
from repro.engine.config import EngineConfig
from repro.engine.registry import dispatch, get_backend, list_backends
from repro.engine.stream import EventStream
from repro.kernels.event_conv.kernel import (event_conv_grid_steps,
                                             event_conv_launches)
from repro.kernels.event_matmul.kernel import (event_matmul_grid_steps,
                                               event_matmul_launches)
from repro.kernels.event_pool.kernel import (event_pool_grid_steps,
                                             event_pool_launches)

__all__ = ["matmul", "linear", "conv2d", "maxpool2d",
           "pool_ineligible_reason", "route_conv", "route_pool",
           "route_linear", "route_recurrent", "recurrent_ineligible_reason",
           "recurrent_step", "fire", "fire_conv", "fire_delta", "sparsify",
           "describe"]

_DEFAULT = EngineConfig()


# ---------------------------------------------------------------------------
# Boundary routing (DESIGN.md §11).  One decision function per op kind,
# used both by the dispatching op below *and* by the model planners
# (models/cnn aligns keep_dense / emitted granularity with the route a
# boundary will take) — same inputs, same decision, so plan time and
# dispatch time can never disagree.  Every input is a trace-time Python
# value (geometry, cfg.occupancy_hint, the installed crossover table);
# the traced ``EventStream.occupancy()`` is deliberately never consulted,
# which is what makes each compiled boundary's route static.
# ---------------------------------------------------------------------------

def route_conv(logical_shape: tuple, w_shape: tuple, cfg: EngineConfig, *,
               stride: int = 1, padding: int = 0,
               blk_m: int = 1) -> "xover.RouteDecision":
    """Routing decision for a conv boundary consuming an event stream of
    granularity ``blk_m`` (STRIP_W = strip-aligned, 1 = pixel-granular).

    The event flavor is granularity-bound — a strip stream can only ride
    the fused strip kernel, a pixel stream only the per-tap path — so the
    decision is event-flavor vs dense; the strip/pixel *choice* is made by
    the producer (``models.cnn`` emits the granularity the consumer's
    geometry wants).
    """
    from repro.core.mnf_conv import conv_out_size
    name = cfg.resolve_backend()
    bsz, h, wd, ci = logical_shape
    kh, kw, _, co = w_shape
    if blk_m == ev.STRIP_W:
        event_route = "strip" if (
            ev.strip_eligible(wd, kh, stride, padding, co=co)
            and name in list_backends("conv2d_events_strip")) else None
    else:
        event_route = "pixel" if name in list_backends("conv2d_events") \
            else None
    oy = conv_out_size(h, kh, stride, padding)
    ox = conv_out_size(wd, kw, stride, padding)
    dec = xover.decide_route(
        cfg.route, "conv", occupancy=cfg.occupancy_hint,
        event_route=event_route,
        dense_macs=float(bsz * oy * ox * kh * kw * ci * co),
        avg_touched=(oy * ox * kh * kw) / max(bsz * h * wd, 1) * bsz,
        c_out=co, backend=name, shape_class=f"k{kh}s{stride}")
    if dec.is_event and dec.route != event_route:
        # Forced event flavor the stream's granularity cannot serve:
        # take the flavor that exists (the trace shows what ran).
        dec = _with_route(dec, event_route or "dense")
    return dec


def route_pool(logical_shape: tuple, k: int, stride: int,
               cfg: EngineConfig, *, blk_m: int = 1,
               eligible: bool = True) -> "xover.RouteDecision":
    """Routing decision for a max-pool boundary.

    Two event flavors exist: the window-major strip grid ("window" — strip
    streams whose pooled width tiles into whole strips) and the per-event
    segment max ("pixel" — the general path).  Geometry prefers "window"
    where it applies; ``eligible=False`` (magnitude fire, degenerate
    window, backend without the op — ``pool_ineligible_reason``) forces
    the visible dense fallback whatever the mode.

    The shape class is channel-aware (``k2s2c128``): a dense pool's cost
    scales with C while ``k``/``stride`` stay fixed across a net, so
    pooling boundaries of different widths sit at different crossovers —
    merging their measured curves under one key misroutes the narrow one
    (the wide shape's event win pollutes the aggregate).
    """
    name = cfg.resolve_backend()
    b, h, w, c = logical_shape
    oh = max((h - k) // stride + 1, 0)
    ow = max((w - k) // stride + 1, 0)
    if not eligible:
        event_route = None
    elif (ev.pool_window_ineligible_reason(logical_shape, k, stride,
                                           blk_m) is None
          and name in list_backends("maxpool2d_events_window")
          and cfg.route != "pixel"):
        event_route = "window"
    else:
        event_route = "pixel"
    dec = xover.decide_route(
        cfg.route, "pool", occupancy=cfg.occupancy_hint,
        event_route=event_route,
        dense_macs=float(b * oh * ow * k * k * c),
        avg_touched=(oh * ow * k * k) / max(h * w, 1), c_out=c,
        backend=name, shape_class=f"k{k}s{stride}c{c}")
    if dec.is_event and dec.route != event_route:
        dec = _with_route(dec, event_route or "dense")
    return dec


def route_linear(m: int, k: int, n: int, cfg: EngineConfig, *,
                 eligible: bool = True) -> "xover.RouteDecision":
    """Routing decision for an FC boundary consuming a fire stream.

    For a conv→FC seam pass the *flattened* FC shape (m = B, k = H·W·C);
    ``eligible=False`` (a conv stream whose geometry cannot re-tile to the
    FC view — ``core.events.retile_ineligible_reason``) forces the visible
    dense fallback whatever the mode.  The shape class comes from
    :func:`costmodel.crossover.linear_shape_class`, so FC boundaries of one
    (N, K-bucket) family share a measured crossover curve.
    """
    name = cfg.resolve_backend()
    event_route = "event" if (eligible and
                              name in list_backends("linear_events")) \
        else None
    dec = xover.decide_route(
        cfg.route, "linear", occupancy=cfg.occupancy_hint,
        event_route=event_route, dense_macs=float(m * k * n),
        avg_touched=1.0, c_out=n, backend=name,
        shape_class=xover.linear_shape_class(m, k, n))
    if dec.is_event and dec.route != event_route:
        dec = _with_route(dec, event_route or "dense")
    return dec


def route_recurrent(kind: str, g: int, d: int, n: int, cfg: EngineConfig, *,
                    eligible: bool = True) -> "xover.RouteDecision":
    """Routing decision for a fire-gated recurrent decode step.

    ``kind`` is "wkv6" or "mamba"; ``g`` the flattened row count (B·H for
    wkv6, B for mamba), ``d`` the drive width (head_dim / d_inner), ``n``
    the state's trailing width (head_dim / d_state).  The dense step's work
    is the decay + increment over the full (G, D, N) state — 2·G·D·N MACs —
    and the event path scales the increment half by occupancy.
    ``eligible=False`` (see :func:`recurrent_ineligible_reason`) forces the
    visible dense fallback whatever the mode.
    """
    name = cfg.resolve_backend()
    event_route = "event" if (
        eligible and name in list_backends(f"recurrent_step_{kind}")) \
        else None
    dec = xover.decide_route(
        cfg.route, "recurrent", occupancy=cfg.occupancy_hint,
        event_route=event_route, dense_macs=float(2 * g * d * n),
        avg_touched=1.0, c_out=n, backend=name,
        shape_class=f"{kind}d{d}")
    if dec.is_event and dec.route != event_route:
        dec = _with_route(dec, event_route or "dense")
    return dec


def _with_route(dec, route: str):
    return dataclasses.replace(dec, route=route)


def _route_fields(dec: "xover.RouteDecision", shape_class: str,
                  **tiling) -> dict:
    """The per-decision trace fields every boundary record carries
    (satellite contract pinned by tests/test_routing.py).  Conv, pool and
    linear records add their tiling: ``blk_k``, the incoming stream's
    K-block width, and ``grid_steps``, the static total of Pallas grid
    steps the event route takes over all its launches and taps (0 on a
    dense route, which launches no event kernel on the stream) — counted
    from shapes at trace time, whatever backend runs."""
    return dict(route=dec.route, est_event_cost=dec.est_event_cost,
                est_dense_cost=dec.est_dense_cost, occupancy=dec.occupancy,
                route_source=dec.source, shape_class=shape_class, **tiling)


def matmul(a: jax.Array, w: jax.Array,
           cfg: EngineConfig = _DEFAULT) -> jax.Array:
    """y = a @ W via the configured backend.  a: (M, K), w: (K, N)."""
    return dispatch("matmul", cfg)(a, w, cfg)


def linear(x, w: jax.Array, b: jax.Array | None = None,
           cfg: EngineConfig = _DEFAULT) -> jax.Array:
    """y = x @ W (+ b).  ``x`` is a dense (..., K) array or an EventStream.

    EventStream inputs are consumed *directly* by event-native backends
    (block, pallas) — the chained-layer fast path.  A *conv* stream (NHWC
    ``logical_shape``) is first re-tiled to the flattened (B, H·W·C) FC
    view by static address plan — the event-domain image of
    ``dense_nhwc().reshape(B, -1)`` (DESIGN.md §12) — so the conv→FC seam
    chains events-only; re-tile-ineligible geometry decodes visibly with a
    named ``retile_ineligible_reason``.  Oracle backends (dense, scalar)
    decode once; that round-trip is exactly what they exist to measure
    against.
    """
    if isinstance(x, EventStream):
        is_conv_stream = (x.logical_shape is not None
                          and len(x.logical_shape) == 4)
        if is_conv_stream and 0 in x.logical_shape:
            # Degenerate conv stream (empty batch / 0-extent map): the FC
            # view is (B, H·W·C) — exact zero result, no backend dispatch.
            y = jnp.zeros((x.logical_shape[0], w.shape[-1]),
                          jnp.promote_types(jnp.result_type(
                              x.events.values.dtype, jnp.float32), w.dtype))
            return y if b is None else y + b
        if x.shape[0] == 0:
            # Zero-row stream (empty batch / dead layer): exact empty
            # result, no backend dispatch — Pallas must not see a 0-extent
            # launch.  Same accumulator dtype as the dispatch path, so the
            # output dtype does not flip with the batch size.
            y = jnp.zeros((0, w.shape[-1]),
                          jnp.promote_types(x.events.values.dtype, w.dtype))
            return y if b is None else y + b
        retile_reason = None
        retiled = False
        if is_conv_stream:
            retile_reason = ev.retile_ineligible_reason(
                x.logical_shape, x.blk_m, x.blk_k)
            if retile_reason is None:
                x = x.retile_fc()
                retiled = True
        if retile_reason is None:
            m, k = x.shape
        else:
            bsz, hh, ww, cc = x.logical_shape
            m, k = bsz, hh * ww * cc
        name = cfg.resolve_backend()
        n = w.shape[-1]
        dec = route_linear(m, k, n, cfg, eligible=retile_reason is None)
        steps = event_matmul_grid_steps(
            x.events.values.shape[0], x.events.capacity, n,
            cfg.blk_n) if dec.is_event else 0
        fields = _route_fields(dec, xover.linear_shape_class(m, k, n),
                               blk_k=x.blk_k, grid_steps=steps)
        if retiled:
            fields["retile"] = True
        if dec.is_event:
            trace.record(op="linear", backend=name, chained=True, **fields)
            return get_backend("linear_events", name)(x, w, b, cfg)
        if dec.source == "geometry":
            # No event path serves this stream (re-tile-ineligible conv
            # geometry or backend without the op): visible decode, with
            # the named rule when a re-tile was refused.
            if retile_reason is not None:
                fields["reason"] = retile_reason
            trace.record(op="linear", backend=name, fallback_decode=True,
                         **fields)
        else:
            # Dense by *choice* (adaptive / forced): the cost model says
            # dense wins here — not a fallback, and the smoke gate must
            # not count it as one.
            trace.record(op="linear", backend=name, routed_dense=True,
                         **fields)
        xd = x.dense_nhwc().reshape(m, k) if (is_conv_stream and
                                              not retiled) else x.dense()
        return linear(xd, w, b, cfg)
    lead = x.shape[:-1]
    y = dispatch("linear", cfg)(x.reshape(-1, x.shape[-1]), w, b, cfg)
    return y.reshape(*lead, w.shape[-1])


def conv2d(x, w: jax.Array, b: jax.Array | None = None,
           cfg: EngineConfig = _DEFAULT, *, stride: int = 1,
           padding: int = 0) -> jax.Array:
    """2-D convolution.  x: (B, H, W, CI) dense or a conv ``EventStream``
    (NHWC ``logical_shape`` — what ``fire_conv`` emits), w: (KH, KW, CI, CO).

    Conv streams are consumed *directly* by event-native backends — layer
    L's fired feature-map events feed layer L+1's k·k taps with no dense map
    materialized (DESIGN.md §5).  A strip-aligned stream (blk_m == STRIP_W)
    on a strip-eligible layer rides ``conv2d_events_strip`` — the fused-tap
    path: one kernel launch for the whole layer, event grid STRIP_W-fold
    smaller (DESIGN.md §6); downsampling convs (stride 2 and AlexNet's
    stride-4 conv1 alike) ride it too, each tap gathering interleaved
    partial strips (``core.events.STRIP_STRIDES``), dead straddle parts
    compacted out of the inner grid at plan time.
    A pixel-granular stream takes the per-tap ``conv2d_events`` path (k·k
    row-group gathers — the oracle the fused kernel is bit-exact against).
    Backends without the matching event op, and strip streams whose
    geometry cannot ride the fused kernel, decode once; every such fallback
    is visible to ``trace_dispatch``.  Under ``cfg.route`` ("adaptive" or a
    forced label) the boundary instead takes the :func:`route_conv`
    decision — the chosen route and its cost estimates ride every record
    (DESIGN.md §11).
    """
    if isinstance(x, EventStream):
        name = cfg.resolve_backend()
        is_conv_stream = (x.logical_shape is not None
                          and len(x.logical_shape) == 4)
        if is_conv_stream and x.shape[0] == 0:
            # Empty batch: exact empty output, no backend dispatch (Pallas
            # must not see a 0-extent launch).  Accumulator dtype matches
            # the dispatch path (batch size must not change the dtype).
            bsz, h, wd, _ = x.logical_shape
            from repro.core.mnf_conv import conv_out_size
            oy = conv_out_size(h, w.shape[0], stride, padding)
            ox = conv_out_size(wd, w.shape[1], stride, padding)
            y = jnp.zeros((bsz, oy, ox, w.shape[-1]),
                          jnp.promote_types(x.events.values.dtype, w.dtype))
            return y if b is None else y + b
        k = w.shape[0]
        co = w.shape[-1]
        e = x.events.capacity
        if is_conv_stream:
            dec = route_conv(x.logical_shape, w.shape, cfg, stride=stride,
                             padding=padding, blk_m=x.blk_m)
            fields = _route_fields(dec, f"k{k}s{stride}", blk_k=x.blk_k,
                                   grid_steps=0)
            if dec.route == "strip":
                # Compacted inner-grid accounting rides every strip record
                # (the BENCH per-layer compaction column reads these).
                subtaps, subtaps_worst = ev.strip_subtap_counts(
                    k, padding, stride)
                src = ev.strip_tap_map(x.logical_shape, k, padding,
                                       stride)[0]
                launches = event_conv_launches(
                    src, e, int8=x.qparams is not None)
                fields["grid_steps"] = event_conv_grid_steps(src, e, co,
                                                             cfg.blk_n)
                trace.record(op="conv2d", backend=name, chained=True,
                             strip=True, launches=launches, stride=stride,
                             subtaps=subtaps, subtaps_worst=subtaps_worst,
                             compaction=subtaps / subtaps_worst,
                             **fields)
                return get_backend("conv2d_events_strip", name)(
                    x, w, b, cfg, stride, padding)
            if dec.route == "pixel":
                from repro.core.mnf_conv import conv_out_size
                bsz, h, wd, _ = x.logical_shape
                rows = bsz * conv_out_size(h, k, stride, padding) \
                    * conv_out_size(wd, k, stride, padding)
                launches = k * k * event_matmul_launches(
                    rows, e, int8=x.qparams is not None)
                fields["grid_steps"] = k * k * event_matmul_grid_steps(
                    rows, e, co, cfg.blk_n)
                trace.record(op="conv2d", backend=name, chained=True,
                             launches=launches, **fields)
                return get_backend("conv2d_events", name)(x, w, b, cfg,
                                                          stride, padding)
            if dec.source == "geometry":
                # No event path serves this stream (ineligible geometry or
                # backend without the op): visible decode, never a silent
                # re-tile.
                trace.record(op="conv2d", backend=name, fallback_decode=True,
                             strip=x.blk_m == ev.STRIP_W, **fields)
            else:
                # Dense by *choice* (adaptive / forced): the cost model says
                # dense wins this boundary — recorded as routed_dense, not a
                # fallback.  ``dense_nhwc`` reads the kept twin when the
                # producer kept it; otherwise it decodes (the planner keeps
                # twins at boundaries it knows will route dense).
                trace.record(op="conv2d", backend=name, routed_dense=True,
                             **fields)
            x = x.dense_nhwc()
        else:
            # Not a conv stream at all (no NHWC logical_shape): rough
            # estimates so even this record carries the routing schema.
            dec = xover.decide_route(
                cfg.route, "conv", occupancy=cfg.occupancy_hint,
                event_route=None,
                dense_macs=float(x.shape[0] * x.shape[1] * w.shape[-1]),
                avg_touched=1.0, c_out=w.shape[-1], backend=name)
            trace.record(op="conv2d", backend=name, fallback_decode=True,
                         **_route_fields(dec, f"k{k}s{stride}",
                                         blk_k=x.blk_k, grid_steps=0))
            x = x.dense()
    return dispatch("conv2d", cfg)(x, w, b, cfg, stride, padding)


def pool_ineligible_reason(x, k: int, stride: int | None = None,
                           cfg: EngineConfig = _DEFAULT) -> str | None:
    """Why ``maxpool2d`` cannot pool ``x`` in the event domain (None = can).

    ``x`` is an :class:`EventStream` or an NHWC ``logical_shape`` tuple
    (models decide boundary formats statically, before the stream exists).
    The segment max runs with identity 0, so it needs a ReLU-family stream:
    every event value non-negative (``magnitude`` fire can emit negative
    events and is ineligible), event-absent positions exactly 0.  The
    geometry must give the VALID window at least one output pixel, and the
    resolved backend must register the ``maxpool2d_events`` op.
    """
    stride = k if stride is None else stride
    shape = x.logical_shape if isinstance(x, EventStream) else x
    if shape is None or len(shape) != 4:
        return "not a conv stream (no NHWC logical_shape)"
    b, h, w, c = shape
    if k < 1 or stride < 1:
        return f"degenerate window k={k}, stride={stride}"
    if h < k or w < k:
        return (f"VALID {k}x{k} window exceeds the {h}x{w} map "
                f"(no output pixels)")
    if cfg.magnitude:
        return ("magnitude fire can emit negative events; the segment max "
                "runs with identity 0 and needs a ReLU-family stream")
    if isinstance(x, EventStream) and x.signed:
        return ("stream carries signed event values (signed/magnitude "
                "fire); the segment max runs with identity 0 and needs a "
                "ReLU-family stream")
    name = cfg.resolve_backend()
    if name not in list_backends("maxpool2d_events"):
        return f"backend {name!r} has no maxpool2d_events op"
    return None


def maxpool2d(x, k: int, stride: int | None = None,
              cfg: EngineConfig = _DEFAULT, *, keep_dense: bool = True):
    """VALID max-pool.  x: (B, H, W, C) dense or a conv ``EventStream``.

    Conv streams are pooled *in the event domain* by eligible backends
    (``maxpool2d_events``): a segment max over the stream's pixel/strip
    events — fire emits non-negative values and event-absent positions are
    exactly 0, so the result is bit-identical to the dense
    ``reduce_window`` pool — re-emitted through the fire phase as a pooled
    ``EventStream`` at ``cfg.blk_m`` granularity (pick it from the
    consuming conv via :meth:`EngineConfig.for_pool`).  Conv→pool→conv
    boundaries therefore stay events-only end to end (DESIGN.md §7).
    Ineligible streams (see :func:`pool_ineligible_reason`) decode once —
    visibly, never silently — and dense inputs return the dense pooled map.

    Routing (DESIGN.md §11): :func:`route_pool` picks between the
    window-major strip grid ("window"), the per-event segment max
    ("pixel"), and — under adaptive/forced modes — a dense-by-choice pool
    of the kept twin; the dense route still re-emits through the fire
    phase, so the boundary's type and bits never depend on the route.
    """
    stride = k if stride is None else stride
    if isinstance(x, EventStream):
        qp_in = x.qparams
        if qp_in is not None:
            # Int8 stream: the segment max consumes the *dequantized* event
            # values (a per-tile scalar multiply — still event-domain, not
            # a decode), so it sees the same floats the fake-quant twin
            # pools, bitwise.  The pooled stream re-quantizes below under
            # the SAME QParams — quantize∘dequantize is exact on in-range
            # int8, so pooling never recalibrates (DESIGN.md §12).
            x = x.dequantize_events()
        name = cfg.resolve_backend()
        reason = pool_ineligible_reason(x, k, stride, cfg)
        shape_ok = (x.logical_shape is not None
                    and len(x.logical_shape) == 4)
        if shape_ok:
            dec = route_pool(x.logical_shape, k, stride, cfg, blk_m=x.blk_m,
                             eligible=reason is None)
        else:
            dec = xover.decide_route(
                cfg.route, "pool", occupancy=cfg.occupancy_hint,
                event_route=None, dense_macs=float(x.shape[0] * x.shape[1]),
                avg_touched=1.0, c_out=x.shape[1], backend=name)
        fields = _route_fields(
            dec, f"k{k}s{stride}c{x.logical_shape[3]}" if shape_ok
            else f"k{k}s{stride}", blk_k=x.blk_k, grid_steps=0)
        if reason is None:
            b, h, w, c = x.logical_shape
            oh = (h - k) // stride + 1
            ow = (w - k) // stride + 1
            # Emitted granularity: cfg.blk_m (the for_pool config path); a
            # pooled width that cannot tile strips stays pixel-granular —
            # consumers trust blk_m == STRIP_W implies W % STRIP_W == 0.
            bm = cfg.blk_m if cfg.blk_m == 1 or (
                cfg.blk_m == ev.STRIP_W and ow % ev.STRIP_W == 0) else 1
            if x.shape[0] == 0:        # degenerate stream: exact empty out
                return EventStream.empty(
                    (b * oh * ow, c), blk_m=bm, blk_k=cfg.blk_k,
                    dtype=x.events.values.dtype,
                    logical_shape=(b, oh, ow, c))
            if dec.is_event:
                # "window" rides the window-major strip grid (one step per
                # output strip); "pixel" the general per-event segment max.
                window = dec.route == "window"
                op_name = ("maxpool2d_events_window" if window
                           else "maxpool2d_events")
                src = (ev.pool_strip_map(x.logical_shape, k, stride)[0]
                       if window else
                       ev.pool_window_map(x.logical_shape, k, stride,
                                          x.blk_m)[0])
                launches = event_pool_launches(src, x.events.capacity,
                                               window=window)
                fields["grid_steps"] = event_pool_grid_steps(
                    src, x.events.capacity)
                trace.record(op="maxpool2d", backend=name, chained=True,
                             pool_events=True, launches=launches, **fields)
                rows = get_backend(op_name, name)(x, k, stride, cfg)
            else:
                # Dense by *choice* (adaptive / forced): pool the dense twin
                # — free when the producer kept it — through the dense
                # dispatch.  Bit-identical to the segment max, and the
                # boundary stays type-stable (re-emitted stream below).
                trace.record(op="maxpool2d", backend=name, routed_dense=True,
                             **fields)
                rows = dispatch("maxpool2d", cfg)(
                    x.dense_nhwc(), k, stride, cfg).reshape(b * oh * ow, c)
            # Pooled values are already fired (non-negative, sub-threshold
            # zeroed upstream): fire at threshold 0 is the identity
            # re-emission at the consumer's granularity.
            if qp_in is None:
                return fire_conv(rows.reshape(b, oh, ow, c),
                                 cfg.replace(threshold=0.0, int8_events=False),
                                 keep_dense=keep_dense, blk_m=bm)
            # Int8 passthrough: every pooled value is a dequantized event
            # value, so quantizing under the incoming QParams recovers the
            # original int8 codes exactly — no calibration, no new scale.
            from repro.core.quantize import quantize
            q_rows = quantize(rows, qp_in, bits=cfg.int8_bits)
            s = EventStream.encode_nhwc(q_rows.reshape(b, oh, ow, c),
                                        blk_k=cfg.blk_k, blk_m=bm,
                                        capacity=cfg.capacity, threshold=0.0,
                                        keep_dense=False)
            return dataclasses.replace(
                s, fired=rows if keep_dense else None, qparams=qp_in)
        trace.record(op="maxpool2d", backend=name, fallback_decode=True,
                     reason=reason, **fields)
        x = x.dense_nhwc() if x.logical_shape is not None else x.dense()
    return dispatch("maxpool2d", cfg)(x, k, stride, cfg)


# ---------------------------------------------------------------------------
# Fire-gated recurrent decode (DESIGN.md §13): the per-token state-update
# *increment drive* (wkv6's key vector, Mamba's Δt·x gate) is thresholded
# by signed fire and the state update skips dead channel-blocks — the decay
# applies everywhere (it is input-independent).  At threshold 0 the gated
# step is float-equal to the dense step (the decode-time twin of the CNN
# chain's threshold-0 invariant).
# ---------------------------------------------------------------------------

def recurrent_ineligible_reason(stream, kind: str = "wkv6",
                                cfg: EngineConfig = _DEFAULT) -> str | None:
    """Why ``recurrent_step`` cannot consume ``stream`` in the event domain
    (None = can).

    The recurrent step wants a per-token row stream: one row per flattened
    (batch × head), ``blk_m == 1``, *signed* event values (per-token deltas
    are two-sided — an unsigned/ReLU-fired stream already dropped every
    negative delta, silently corrupting the state), f32 values (state
    updates accumulate in f32), and a resolved backend registering the
    ``recurrent_step_{kind}`` op.
    """
    if stream.logical_shape is not None and len(stream.logical_shape) == 4:
        return ("conv stream (NHWC logical_shape) — the recurrent step "
                "consumes per-token (G, D) row streams")
    if stream.blk_m != 1:
        return (f"recurrent drives are one row per (batch x head): blk_m "
                f"must be 1, stream has blk_m={stream.blk_m}")
    if not stream.signed:
        return ("recurrent deltas are signed; this stream was fired "
                "unsigned (ReLU fire), so negative deltas were already "
                "dropped")
    if stream.qparams is not None:
        return ("int8 event values are not supported by the recurrent "
                "step (state updates accumulate in f32)")
    name = cfg.resolve_backend()
    if name not in list_backends(f"recurrent_step_{kind}"):
        return f"backend {name!r} has no recurrent_step_{kind} op"
    return None


def fire_delta(drive: jax.Array, cfg: EngineConfig = _DEFAULT, *,
               keep_dense: bool = True) -> EventStream:
    """Signed fire over a per-token increment drive (G, D) -> row stream.

    The recurrent twin of :func:`fire`: gates on |Δ| > threshold and emits
    the *signed* value — a negative supra-threshold delta is an event, not
    a drop — at the recurrent tile geometry (``EngineConfig.for_recurrent``:
    blk_m == 1, narrow K blocks).  The emitted stream is flagged ``signed``
    so ReLU-family consumers (the pool's segment max) reject it by name and
    :func:`recurrent_step` accepts it.
    """
    from repro.core.fire import FireConfig
    from repro.core.fire import fire as jnp_fire

    c = cfg.for_recurrent(drive.shape[-1]).for_width(*drive.shape)
    if 0 in drive.shape:
        # Degenerate drive (empty batch / zero-width channel axis): explicit
        # empty stream, no encode machinery (Pallas consumers must not see
        # a 0-extent launch).
        s = EventStream.empty(drive.shape, blk_m=1, blk_k=c.blk_k,
                              capacity=c.capacity, dtype=drive.dtype,
                              fired=drive if keep_dense else None)
        return dataclasses.replace(s, signed=True)
    fired = jnp_fire(drive, FireConfig(threshold=c.threshold, signed=True))
    s = EventStream.encode(fired, blk_m=1, blk_k=c.blk_k,
                           capacity=c.capacity, threshold=0.0,
                           keep_dense=keep_dense)
    return dataclasses.replace(s, signed=True)


def _recurrent_dense_step(kind: str, drive: jax.Array, state: jax.Array,
                          ops: dict):
    """The dense oracle of one recurrent step (the fallback path — same
    formulation the event backends use, so the route never changes bits at
    threshold 0 on the block backend)."""
    if kind == "wkv6":
        from repro.kernels.wkv6.step import wkv6_step_ref
        return wkv6_step_ref(ops["r"], drive, ops["v"], ops["w"], ops["u"],
                             state)
    from repro.kernels.mamba_scan.step import mamba_step_ref
    return mamba_step_ref(drive, ops["da"], ops["bmat"], ops["cmat"], state)


def recurrent_step(kind: str, stream: EventStream, state: jax.Array,
                   cfg: EngineConfig = _DEFAULT, **ops):
    """One fire-gated recurrent decode step (DESIGN.md §13).

    kind:    "wkv6" (ops r, v, w, u; state (G, D, D)) or
             "mamba" (ops da, bmat, cmat; state (B, DI, N)).
    stream:  signed row stream of the increment drive (``fire_delta``).
    Returns (readout, new_state) — for wkv6 the per-row output o (G, D)
    and S'; for mamba the state readout y (B, DI) (skip/gate terms are the
    model's) and h'.

    Event-eligible streams (see :func:`recurrent_ineligible_reason`)
    dispatch to the backend's gated kernel, which skips the state-update
    increment on dead channel-blocks; ineligible streams fall back to the
    dense oracle on the stream's dense view — visibly, with the named rule
    on the trace record.  Zero-extent steps (empty batch, zero-width
    drive) short-circuit to the oracle before any dispatch — Pallas must
    not see a 0-extent launch.
    """
    assert kind in ("wkv6", "mamba"), kind
    g, d = stream.shape
    if g == 0 or d == 0:
        drive = stream.fired if stream.fired is not None \
            else jnp.zeros(stream.shape, jnp.float32)
        return _recurrent_dense_step(kind, drive, state, ops)
    name = cfg.resolve_backend()
    reason = recurrent_ineligible_reason(stream, kind, cfg)
    n = state.shape[-1]
    dec = route_recurrent(kind, g, d, n, cfg, eligible=reason is None)
    fields = _route_fields(dec, f"{kind}d{d}")
    if dec.is_event:
        trace.record(op="recurrent_step", kind=kind, backend=name,
                     chained=True, **fields)
        return get_backend(f"recurrent_step_{kind}", name)(
            stream, state, ops, cfg)
    if dec.source == "geometry":
        # No event path serves this stream (ineligible stream or backend
        # without the op): visible fallback with the named rule.
        if reason is not None:
            fields["reason"] = reason
        trace.record(op="recurrent_step", kind=kind, backend=name,
                     fallback_decode=True, **fields)
    else:
        # Dense by *choice* (adaptive / forced): not a fallback.
        trace.record(op="recurrent_step", kind=kind, backend=name,
                     routed_dense=True, **fields)
    return _recurrent_dense_step(kind, stream.dense(), state, ops)


def _fire_int8(acc2: jax.Array, cfg: EngineConfig, c2: EngineConfig,
               keep_dense: bool, logical_shape: tuple | None = None
               ) -> EventStream:
    """Int8 fire (DESIGN.md §12): threshold the accumulator, dynamically
    calibrate a *symmetric* QParams over the fired map (zero point 0 — an
    absent event must be an exact zero in both domains), requantize the
    accumulator into it (unit input/weight scales: the engine dequantizes
    at tile load, so accumulators carry real values), and encode the int8
    codes at threshold 0.  The kept twin is the dequantized map — exactly
    the fake-quant round-trip's values, which is what makes the int8 chain
    bitwise against its fake-quant twin within a backend."""
    from repro.core.fire import FireConfig
    from repro.core.fire import fire as jnp_fire
    from repro.core.quantize import (QParams, calibrate, dequantize,
                                     requantize_accumulator)

    fired = jnp_fire(acc2, FireConfig(threshold=c2.threshold,
                                      magnitude=c2.magnitude,
                                      signed=c2.signed))
    qp = calibrate(fired, symmetric=True, bits=cfg.int8_bits)
    unit = QParams.symmetric(1.0)
    q = requantize_accumulator(fired, unit, unit, qp, bits=cfg.int8_bits)
    s = EventStream.encode(q, blk_m=c2.blk_m, blk_k=c2.blk_k,
                           capacity=c2.capacity, threshold=0.0,
                           keep_dense=False)
    return dataclasses.replace(
        s, fired=dequantize(q, qp) if keep_dense else None, qparams=qp,
        logical_shape=logical_shape,
        signed=c2.magnitude or c2.signed)


def fire(acc: jax.Array, cfg: EngineConfig = _DEFAULT, *,
         keep_dense: bool = True) -> EventStream:
    """Fire phase: threshold ``acc`` (M, K) and emit next-layer events.

    Returns an EventStream ready to feed ``linear`` with no re-encode.
    ``keep_dense=False`` drops the dense twin so downstream code provably
    runs event-only.  With ``cfg.int8_events`` the emitted values are int8
    codes carrying a symmetric ``QParams`` on the stream (the jnp fire +
    encode lowering — the fused Pallas fire kernel stays f32); consumers
    dequantize at tile load (DESIGN.md §12).
    """
    # Clamp once here and hand the backend the *same* geometry the stream
    # records — a custom fire backend must see the tile sizes the consuming
    # linear will assume.
    c = cfg.for_width(*acc.shape)
    signed = cfg.magnitude or cfg.signed
    if 0 in acc.shape:
        # Degenerate accumulator: explicit empty stream, no backend dispatch
        # (a Pallas fire backend must not see a 0-extent launch).
        s = EventStream.empty(acc.shape, blk_m=c.blk_m, blk_k=c.blk_k,
                              capacity=c.capacity, dtype=acc.dtype,
                              fired=acc if keep_dense else None)
        return dataclasses.replace(s, signed=signed)
    if cfg.int8_events:
        return _fire_int8(acc, cfg, c, keep_dense)
    fired, bev = dispatch("fire", cfg)(acc, c)
    stream = EventStream(events=bev, fired=fired if keep_dense else None,
                         shape=acc.shape, blk_m=c.blk_m, blk_k=c.blk_k,
                         signed=signed)
    return stream


def fire_conv(acc: jax.Array, cfg: EngineConfig = _DEFAULT, *,
              keep_dense: bool = True, blk_m: int = 1) -> EventStream:
    """Fire phase over a conv accumulator (B, OY, OX, CO) -> conv stream.

    ``blk_m`` picks the emitted granularity: 1 (default) is pixel-granular —
    the per-tap path's row-group gather unit; STRIP_W emits a strip-aligned
    stream (8-pixel row strips, requires W % STRIP_W == 0) for a consumer
    the fused-tap kernel can serve (DESIGN.md §6) — choose it from the
    *next* layer's geometry (``core.events.strip_eligible``).  Either way
    ``engine.conv2d`` accepts the stream with no re-encode.
    ``keep_dense=False`` drops the fired twin so a conv→conv boundary
    provably runs event-only; keep it when the consumer is a pool (the pool
    reads the twin for free — the fire phase computes it anyway).
    """
    b, h, w, c = acc.shape
    assert blk_m == 1 or (blk_m == ev.STRIP_W and w % ev.STRIP_W == 0), \
        (blk_m, acc.shape, "strip streams need blk_m == STRIP_W and "
                           "W % STRIP_W == 0")
    acc2 = acc.reshape(b * h * w, c)
    c2 = cfg.for_conv(c).replace(blk_m=blk_m).for_width(*acc2.shape)
    signed = cfg.magnitude or cfg.signed
    if 0 in acc2.shape:
        s = EventStream.empty(acc2.shape, blk_m=c2.blk_m, blk_k=c2.blk_k,
                              capacity=c2.capacity, dtype=acc.dtype,
                              fired=acc2 if keep_dense else None,
                              logical_shape=(b, h, w, c))
        return dataclasses.replace(s, signed=signed)
    if cfg.int8_events:
        return _fire_int8(acc2, cfg, c2, keep_dense,
                          logical_shape=(b, h, w, c))
    fired, bev = dispatch("fire_conv", cfg)(acc2, c2)
    return EventStream(events=bev, fired=fired if keep_dense else None,
                       shape=acc2.shape, blk_m=c2.blk_m, blk_k=c2.blk_k,
                       logical_shape=(b, h, w, c), signed=signed)


def sparsify(h: jax.Array, cfg: EngineConfig = _DEFAULT) -> jax.Array:
    """Shape-preserving fire + dead-tile masking on (..., K) activations.

    The pure-XLA image of the MNF multiply phase used inside LM blocks
    (models/layers.mnf_sparsify): with threshold 0 and a ReLU-family
    activation it is the identity; with threshold > 0 whole event-free
    (blk_m, blk_k) tiles are zeroed, matching what the event_matmul kernel
    skips — HLO FLOPs stay truthful for the dry-run (DESIGN.md §2).
    """
    from repro.core.fire import FireConfig
    from repro.core.fire import fire as jnp_fire
    from repro.kernels.event_matmul.ref import mask_dead_blocks

    fired = jnp_fire(h, FireConfig(threshold=cfg.threshold,
                                   magnitude=cfg.magnitude))
    if cfg.threshold <= 0.0:
        return fired
    shp = h.shape
    h2 = fired.reshape(-1, shp[-1])
    pad_m = (-h2.shape[0]) % cfg.blk_m
    pad_k = (-h2.shape[1]) % cfg.blk_k
    h2 = jnp.pad(h2, ((0, pad_m), (0, pad_k)))
    h2 = mask_dead_blocks(h2, blk_m=cfg.blk_m, blk_k=cfg.blk_k, threshold=0.0)
    return h2[:h2.shape[0] - pad_m or None, :shp[-1]].reshape(shp)


def describe(cfg: EngineConfig = _DEFAULT) -> dict:
    """Resolved engine configuration (what serve/dry-run report)."""
    r = cfg.resolved()
    return dict(backend=r.backend, interpret=r.interpret, blk_m=r.blk_m,
                blk_k=r.blk_k, blk_n=r.blk_n, capacity=r.capacity,
                threshold=r.threshold, magnitude=r.magnitude,
                device=jax.default_backend())
