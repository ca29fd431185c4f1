"""The paper's evaluation workloads: AlexNet and VGG16 with MNF inference.

Two execution paths over identical params, both dispatched through
``repro.engine`` (DESIGN.md §3):
  * dense  — the engine's dense backend + ReLU (the oracle),
  * mnf    — event-resident: one ``EventStream`` threads the whole network.
             Each conv's fire phase emits a conv stream
             (``engine.fire_conv``) that the next conv consumes directly:
             strip-aligned (8-pixel row strips) whenever the consumer can
             ride the fused-tap kernel — one launch per layer, 8x smaller
             event grid — and pixel-granular per-tap row-group gathers
             otherwise (DESIGN.md §5/§6).  The dense feature map is never
             materialized between conv layers.  Pools run **in the event
             domain** too (``engine.maxpool2d`` — a segment max over the
             stream's events, bit-identical to the dense pool, DESIGN.md
             §7), so conv→pool→conv boundaries carry no dense twin and no
             re-encode.  The conv→FC seam re-tiles the conv stream to the
             flattened (B, H·W·C) view by static address plan
             (``EventStream.retile_fc``, DESIGN.md §12) and FC layers
             chain ``EventStream``s onward — the whole forward has zero
             densify points, input encode to logits.

``make_cnn_pipeline`` wraps the whole forward in a **single jitted
function** with a donated input buffer — one jit per network, no per-layer
dispatch or retracing (DESIGN.md §5.1).  ``run_with_stats`` rides the same
single-jit body and instruments every layer with the event counts the cost
model needs: input events fired (non-zero activations), MACs a dense
accelerator would do, and MACs the MNF multiply phase actually does
(Σ_events touched_outputs × C_out — Algorithm 1's walk length).  All
counters derive from ``EventStream``'s compacted event values, so the
instrumented pipeline runs twin-free — same event-resident graph as
serving, just with counter outputs.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro import engine
from repro.core.fire import FireConfig, fire
from repro.core.mnf_conv import conv_out_size
from repro.models.layers import max_pool_nhwc

__all__ = ["ConvSpec", "FCSpec", "PoolSpec", "CNNSpec", "ALEXNET", "VGG16",
           "ALEXNET_DS", "ALEXNET_FF", "VGG16_DS", "MINI", "MINI_S4",
           "conv_downsampled", "init_cnn_params", "cnn_forward",
           "make_cnn_forward", "make_cnn_pipeline", "run_with_stats",
           "layer_dense_macs", "chain_boundary_summary", "fc_in_events",
           "layer_scope"]


@dataclasses.dataclass(frozen=True)
class ConvSpec:
    out_ch: int
    k: int
    stride: int = 1
    padding: int = 0


@dataclasses.dataclass(frozen=True)
class PoolSpec:
    k: int = 2
    stride: int = 2


@dataclasses.dataclass(frozen=True)
class FCSpec:
    out: int


_KINDS = {ConvSpec: "conv", PoolSpec: "pool", FCSpec: "fc"}


def layer_scope(i: int, layer) -> str:
    """The named scope of layer ``i`` of ``spec.layers``: ``L<i>.<kind>``
    (``L03.conv``), with ``i`` the index in the configuration's layer list
    and ``kind`` one of conv, pool, fc."""
    return f"L{i:02d}.{_KINDS[type(layer)]}"


@dataclasses.dataclass(frozen=True)
class CNNSpec:
    name: str
    input_size: int
    in_ch: int
    layers: tuple
    num_classes: int = 1000

    def scaled(self, input_size: int) -> "CNNSpec":
        """Same topology at a smaller input resolution (CPU tests)."""
        return dataclasses.replace(self, input_size=input_size)


ALEXNET = CNNSpec(
    "alexnet", 224, 3,
    (ConvSpec(96, 11, 4, 2), PoolSpec(3, 2),
     ConvSpec(256, 5, 1, 2), PoolSpec(3, 2),
     ConvSpec(384, 3, 1, 1), ConvSpec(384, 3, 1, 1), ConvSpec(256, 3, 1, 1),
     PoolSpec(3, 2),
     FCSpec(4096), FCSpec(4096), FCSpec(1000)))

VGG16 = CNNSpec(
    "vgg16", 224, 3,
    (ConvSpec(64, 3, 1, 1), ConvSpec(64, 3, 1, 1), PoolSpec(),
     ConvSpec(128, 3, 1, 1), ConvSpec(128, 3, 1, 1), PoolSpec(),
     ConvSpec(256, 3, 1, 1), ConvSpec(256, 3, 1, 1), ConvSpec(256, 3, 1, 1),
     PoolSpec(),
     ConvSpec(512, 3, 1, 1), ConvSpec(512, 3, 1, 1), ConvSpec(512, 3, 1, 1),
     PoolSpec(),
     ConvSpec(512, 3, 1, 1), ConvSpec(512, 3, 1, 1), ConvSpec(512, 3, 1, 1),
     PoolSpec(),
     FCSpec(4096), FCSpec(4096), FCSpec(1000)))


def conv_downsampled(spec: CNNSpec, *, k: int = 3) -> CNNSpec:
    """All-conv downsampling variant: every max-pool becomes a stride-2
    k×k conv (padding k//2, channel-preserving) — the "VGG-style stride-2
    block" of all-convolutional nets (Springenberg et al.) and of SCNN-class
    sparse accelerators, where the downsampling layer itself must ride the
    compressed dataflow.  These are exactly the layers the stride-2 strip
    plan keeps on the fused event path (DESIGN.md §6): each replacement
    conv consumes its producer's strip stream with interleaved half-strip
    gathers instead of falling back to the pixel-granular grid.
    """
    layers = []
    c = spec.in_ch
    for layer in spec.layers:
        if isinstance(layer, PoolSpec):
            layers.append(ConvSpec(c, k, 2, k // 2))
        else:
            layers.append(layer)
            if isinstance(layer, ConvSpec):
                c = layer.out_ch
    return dataclasses.replace(spec, name=spec.name + "_ds",
                               layers=tuple(layers))


#: Downsampling variants of the paper workloads: pools replaced by stride-2
#: conv blocks.  At the CPU harness sizes (ALEXNET_DS@68, VGG16_DS@32) their
#: chained forwards put every eligible downsampling conv on the fused strip
#: path — the layer class that used to be stride-1-only fallback.
ALEXNET_DS = conv_downsampled(ALEXNET)
VGG16_DS = conv_downsampled(VGG16)

#: Fully-fused AlexNet: the geometry variant whose *entire* chained forward
#: rides the fused strip kernel — zero pixel-granular conv layers, the
#: stride-4 conv1 included (one launch instead of its 121 per-tap event
#: matmuls; the chained path strip-encodes the input image itself).  Two
#: deviations from stock AlexNet@224 make every layer width tile into
#: 8-pixel strips, and both are forced by arithmetic, not taste:
#:   * conv1 padding 2 -> 4: at stride 4 an input width of 8m yields
#:     OW = 2m - 1 with p = 2 (odd — never a strip multiple at ANY input
#:     size, 224 included), but OW = (W - 3)//4 + 1 with p = 4;
#:   * input 224 -> 256 with stride-2 conv downsampling blocks: the three
#:     halvings after conv1 need conv1's output width to be 8·2³ = 64,
#:     i.e. W = 256 (the smallest fully-fused size; stock 224 -> 56 -> 28
#:     breaks at the second stage).
#: Same depth/channel plan as ALEXNET_DS otherwise.
ALEXNET_FF = CNNSpec(
    "alexnet_ff", 256, 3,
    (ConvSpec(96, 11, 4, 4), ConvSpec(96, 3, 2, 1),
     ConvSpec(256, 5, 1, 2), ConvSpec(256, 3, 2, 1),
     ConvSpec(384, 3, 1, 1), ConvSpec(384, 3, 1, 1), ConvSpec(256, 3, 1, 1),
     ConvSpec(256, 3, 2, 1),
     FCSpec(4096), FCSpec(4096), FCSpec(1000)))

#: Seconds-scale smoke network exercising every chain seam — conv→conv,
#: the event-native conv→pool→conv boundary, pool→FC.  The serving-tier
#: smoke loop and the benchmark smoke both bucket-serve this net.
MINI = CNNSpec("mini", 8, 3,
               (ConvSpec(8, 3, 1, 1), ConvSpec(8, 3, 1, 1), PoolSpec(),
                ConvSpec(8, 3, 1, 1), FCSpec(10)), num_classes=10)

#: Stride-4 smoke network: a strip-eligible stride-4 downsampling conv
#: (32 -> 8, the AlexNet-conv1 layer class at toy scale) between two
#: stride-1 convs.  Every conv is strip-eligible, so its chained forward
#: must report zero fallback_decode — the CI gate for the stride-4 plan
#: (``kernel_bench --smoke``).
MINI_S4 = CNNSpec("mini_s4", 32, 3,
                  (ConvSpec(8, 3, 1, 1), ConvSpec(8, 3, 4, 1),
                   ConvSpec(8, 3, 1, 1), FCSpec(10)), num_classes=10)


def _trace_shapes(spec: CNNSpec):
    """(H, W, C) entering each layer, plus flattened FC input size."""
    h = w = spec.input_size
    c = spec.in_ch
    shapes = []
    for layer in spec.layers:
        shapes.append((h, w, c))
        if isinstance(layer, ConvSpec):
            h = conv_out_size(h, layer.k, layer.stride, layer.padding)
            w = conv_out_size(w, layer.k, layer.stride, layer.padding)
            c = layer.out_ch
        elif isinstance(layer, PoolSpec):
            h = (h - layer.k) // layer.stride + 1
            w = (w - layer.k) // layer.stride + 1
        elif isinstance(layer, FCSpec):
            h, w, c = 1, 1, layer.out
    return shapes


def init_cnn_params(key: jax.Array, spec: CNNSpec,
                    weight_sparsity: float = 0.0):
    """He-initialized params; optional unstructured weight pruning (the
    paper prunes to ~50-60% weight density before deployment)."""
    shapes = _trace_shapes(spec)
    params = []
    for i, layer in enumerate(spec.layers):
        k = jax.random.fold_in(key, i)
        h, w, c = shapes[i]
        if isinstance(layer, ConvSpec):
            fan_in = layer.k * layer.k * c
            wgt = jax.random.normal(
                k, (layer.k, layer.k, c, layer.out_ch), jnp.float32)
            wgt = wgt * (2.0 / fan_in) ** 0.5
        elif isinstance(layer, FCSpec):
            fan_in = h * w * c
            wgt = jax.random.normal(k, (fan_in, layer.out), jnp.float32)
            wgt = wgt * (2.0 / fan_in) ** 0.5
        else:
            params.append(None)
            continue
        if weight_sparsity > 0.0:
            keep = jax.random.uniform(jax.random.fold_in(k, 1), wgt.shape)
            wgt = jnp.where(keep >= weight_sparsity, wgt, 0.0)
        params.append(wgt)
    return params


def _touched_outputs(h: int, w: int, k: int, stride: int, padding: int):
    """(H, W) map: #output positions each input pixel contributes to."""
    oy = conv_out_size(h, k, stride, padding)
    ox = conv_out_size(w, k, stride, padding)
    iy = jnp.arange(h)[:, None]
    ix = jnp.arange(w)[None, :]

    def jumps(i, osz):
        lo = jnp.maximum(0, -(-(i + padding - k + 1) // stride))
        hi = jnp.minimum(osz - 1, (i + padding) // stride)
        return jnp.maximum(hi - lo + 1, 0)

    return jumps(iy, oy) * jumps(ix, ox)


def layer_dense_macs(spec: CNNSpec):
    """Per-compute-layer dense MAC counts (what a dense accelerator does)."""
    shapes = _trace_shapes(spec)
    out = []
    for i, layer in enumerate(spec.layers):
        h, w, c = shapes[i]
        if isinstance(layer, ConvSpec):
            oy = conv_out_size(h, layer.k, layer.stride, layer.padding)
            ox = conv_out_size(w, layer.k, layer.stride, layer.padding)
            out.append(oy * ox * layer.k * layer.k * c * layer.out_ch)
        elif isinstance(layer, FCSpec):
            out.append(h * w * c * layer.out)
    return out


def chain_boundary_summary(spec: CNNSpec, *, batch: int = 1,
                           fire_cfg: FireConfig = FireConfig(),
                           engine_cfg: engine.EngineConfig | None = None
                           ) -> dict:
    """Static per-boundary accounting of the chained pipeline.

    Shape-derived (no tracing): how many compute layers of each kind, how
    many pool boundaries ride the event-native segment max
    (``pool_events``), how many conv→FC seams ride the re-tiler
    (``retile``), and how many densify points remain on the chain
    (``densify`` — dense-pool fallbacks plus re-tile-ineligible FC seams;
    0 when every boundary is eligible, the DESIGN.md §7/§12 invariant
    serving and benchmarks report).  ``routes`` lists, in chain order, the
    routing decision of every boundary that consumes an EventStream — the
    same ``engine.route_conv`` / ``engine.route_pool`` /
    ``engine.route_linear`` calls the dispatch makes (DESIGN.md §11), so
    serving's boundary report can state each compiled boundary's route
    without tracing.
    """
    cfg = _layer_cfg(engine_cfg, mnf=True, fire_cfg=fire_cfg)
    conv_base = _conv_base(cfg)
    shapes = _trace_shapes(spec)
    out = dict(conv=0, fc=0, pool=0, pool_events=0, densify=0,
               input_encode=0, retile=0, routes=[])
    # Mirrors _forward's chained dataflow: a pool sees a *conv stream* only
    # when fed by a conv or by a pool that itself chained; a conv with a
    # dense input (the chain head) strip-encodes it when the fused kernel
    # can consume it (``input_encode`` counts those seams), and FC streams
    # take the dense-pool fallback.  ``blk_m`` tracks the granularity of
    # the stream currently in flight — what _next_conv_blk_m made the
    # producer emit.
    conv_stream_in = False
    fc_stream_in = False
    blk_m = 1
    for i, layer in enumerate(spec.layers):
        h, w, c = shapes[i]
        nxt = spec.layers[i + 1] if i + 1 < len(spec.layers) else None
        if isinstance(layer, ConvSpec):
            out["conv"] += 1
            if not conv_stream_in:
                bm_in = _input_stream_blk_m(layer, (batch, h, w, c),
                                            conv_base)
                if bm_in:
                    out["input_encode"] += 1
                    conv_stream_in = True
                    blk_m = bm_in
            if conv_stream_in:
                dec = engine.route_conv(
                    (batch, h, w, c), (layer.k, layer.k, c, layer.out_ch),
                    conv_base, stride=layer.stride, padding=layer.padding,
                    blk_m=blk_m)
                out["routes"].append(dict(
                    op="conv2d", route=dec.route, occupancy=dec.occupancy,
                    est_event_cost=dec.est_event_cost,
                    est_dense_cost=dec.est_dense_cost, source=dec.source,
                    shape_class=f"k{layer.k}s{layer.stride}"))
            oy = conv_out_size(h, layer.k, layer.stride, layer.padding)
            ox = conv_out_size(w, layer.k, layer.stride, layer.padding)
            blk_m = _next_conv_blk_m(nxt, (batch, oy, ox, layer.out_ch))
            conv_stream_in = True
        elif isinstance(layer, FCSpec):
            out["fc"] += 1
            if conv_stream_in or fc_stream_in:
                kf = h * w * c
                reason = None
                if conv_stream_in:
                    reason = engine.retile_ineligible_reason(
                        (batch, h, w, c), blk_m, conv_base.for_conv(c).blk_k)
                dec = engine.route_linear(batch, kf, layer.out, cfg,
                                          eligible=reason is None)
                rec = dict(op="linear", route=dec.route,
                           occupancy=dec.occupancy,
                           est_event_cost=dec.est_event_cost,
                           est_dense_cost=dec.est_dense_cost,
                           source=dec.source,
                           shape_class=engine.linear_shape_class(
                               batch, kf, layer.out))
                if conv_stream_in and reason is None:
                    rec["retile"] = True
                    out["retile"] += 1
                if reason is not None:
                    rec["reason"] = reason
                    out["densify"] += 1
                out["routes"].append(rec)
            conv_stream_in = False
            fc_stream_in = layer is not spec.layers[-1]
        elif isinstance(layer, PoolSpec):
            out["pool"] += 1
            if conv_stream_in and engine.pool_ineligible_reason(
                    (batch, h, w, c), layer.k, layer.stride,
                    conv_base) is None:
                out["pool_events"] += 1
                dec = engine.route_pool((batch, h, w, c), layer.k,
                                        layer.stride, conv_base,
                                        blk_m=blk_m)
                out["routes"].append(dict(
                    op="maxpool2d", route=dec.route,
                    occupancy=dec.occupancy,
                    est_event_cost=dec.est_event_cost,
                    est_dense_cost=dec.est_dense_cost, source=dec.source,
                    shape_class=f"k{layer.k}s{layer.stride}c{c}"))
                oh = (h - layer.k) // layer.stride + 1
                ow = (w - layer.k) // layer.stride + 1
                blk_m = _next_conv_blk_m(nxt, (batch, oh, ow, c))
            else:
                out["densify"] += 1
                conv_stream_in = False
    return out


def _conv_base(cfg: engine.EngineConfig) -> engine.EngineConfig:
    """The conv chain's dispatch config, shared by ``_forward`` and
    ``chain_boundary_summary`` (DESIGN.md §5).

    ``blk_m`` stays 1 (pixel rows) so the round-trip twin multiplies
    identical tiles in identical order as the chained path — bit-for-bit
    equality, not just allclose; the chained path's strip granularity
    rides the *stream* instead.  The K block is ``cfg.blk_k`` as each
    boundary clamps it to its channel count (``core.events.channel_blk_k``:
    one block of C channels when C <= blk_k, lane-wide blocks above).
    Narrower blocks would buy no skipping on real maps: after a ReLU conv
    nearly every 8-channel block of a pixel holds a non-zero (99.6% on
    AlexNet@224 at 50% element density), so the width only sets the grid
    — 12-16x more kernel steps at 8 channels than at the lane width.
    """
    return cfg.replace(blk_m=1)


def _layer_cfg(base: engine.EngineConfig | None, *, mnf: bool,
               fire_cfg: FireConfig) -> engine.EngineConfig:
    cfg = base or engine.EngineConfig()
    if not mnf:
        cfg = cfg.replace(backend="dense")
    return cfg.replace(threshold=fire_cfg.threshold,
                       magnitude=fire_cfg.magnitude,
                       int8_events=cfg.int8_events
                       or fire_cfg.quantize_to_int8)


def _dense(x) -> jax.Array:
    return x.dense() if isinstance(x, engine.EventStream) else x


def _dense_nhwc(x) -> jax.Array:
    return x.dense_nhwc() if isinstance(x, engine.EventStream) else x


def _next_conv_blk_m(nxt, out_shape: tuple) -> int:
    """Granularity of the stream a fired conv layer emits, chosen from its
    *consumer*: strip-aligned (STRIP_W-pixel row strips — the fused-tap
    kernel's unit, one launch per layer and an 8x smaller event grid) when
    the next layer is a strip-eligible conv or a window-eligible pool (the
    window-major pool grid consumes strip streams, DESIGN.md §7),
    pixel-granular otherwise.  ``out_shape`` is the emitted map's NHWC
    shape."""
    out_w = out_shape[2]
    if isinstance(nxt, ConvSpec) and engine.strip_eligible(
            out_w, nxt.k, nxt.stride, nxt.padding, co=nxt.out_ch):
        return engine.STRIP_W
    if isinstance(nxt, PoolSpec) and engine.pool_window_ineligible_reason(
            tuple(out_shape), nxt.k, nxt.stride, engine.STRIP_W) is None:
        return engine.STRIP_W
    return 1


def _input_stream_blk_m(layer: "ConvSpec", x_shape: tuple,
                        cfg: engine.EngineConfig) -> int:
    """Granularity at which the chained path encodes a *dense* conv input
    (the input image at the chain head, or a densified seam): STRIP_W when
    the conv is strip-eligible off an encoded strip stream *and* the
    boundary routes to the event path, 0 = stay dense (the per-tap dense
    dispatch).  This is what puts AlexNet-class stride-4 first layers on
    the fused kernel — 1 launch instead of k² — and it is bitwise-safe
    because the encoded stream is lossless at threshold 0 and the fused
    kernel is bit-exact against the per-tap oracle the dense dispatch runs
    (DESIGN.md §6).  Pixel-granular encoding is never chosen: it would
    trade the dense per-tap path for an identical-launch-count event
    per-tap path.
    """
    b, h, w, c = x_shape
    if not engine.strip_eligible(w, layer.k, layer.stride, layer.padding,
                                 co=layer.out_ch):
        return 0
    dec = engine.route_conv((b, h, w, c),
                            (layer.k, layer.k, c, layer.out_ch), cfg,
                            stride=layer.stride, padding=layer.padding,
                            blk_m=engine.STRIP_W)
    return engine.STRIP_W if dec.route == "strip" else 0


def _next_boundary_route(nxt, out_shape: tuple, cfg: engine.EngineConfig,
                         blk_m: int):
    """The routing decision the *next* boundary will take on the stream a
    layer is about to emit — the same ``engine.route_conv`` /
    ``engine.route_pool`` / ``engine.route_linear`` call the dispatch
    makes, with identical inputs, so the planner's keep-twin choices and
    the dispatcher's routes can never disagree (DESIGN.md §11)."""
    if isinstance(nxt, ConvSpec):
        return engine.route_conv(
            out_shape, (nxt.k, nxt.k, out_shape[3], nxt.out_ch), cfg,
            stride=nxt.stride, padding=nxt.padding, blk_m=blk_m)
    if isinstance(nxt, FCSpec):
        b, oh, ow, c = out_shape
        return engine.route_linear(b, oh * ow * c, nxt.out, cfg)
    return engine.route_pool(out_shape, nxt.k, nxt.stride, cfg, blk_m=blk_m)


def _fc_chains(nxt, out_shape: tuple, cfg: engine.EngineConfig,
               blk_m: int) -> bool:
    """Whether a conv/pool stream emitted at ``blk_m`` granularity chains
    into a next-layer FC through the re-tiler — the same
    ``retile_ineligible_reason`` rule ``engine.linear`` applies at
    dispatch, so the planner drops the twin exactly when the seam will
    stay events-only (DESIGN.md §12)."""
    if not isinstance(nxt, FCSpec):
        return False
    return engine.retile_ineligible_reason(
        tuple(out_shape), blk_m, cfg.for_conv(out_shape[-1]).blk_k) is None


def _pixel_events(x):
    """(B, H, W) fired-activation counts per pixel + the NHWC shape.

    Stream inputs derive the map from the compacted event values
    (twin-free — DESIGN.md §6); dense inputs count non-zeros directly.
    """
    if isinstance(x, engine.EventStream):
        b, h, w, c = x.logical_shape
        return x.per_row_scalar_events().reshape(b, h, w), (b, h, w, c)
    nz = jnp.sum(jnp.abs(x) > 0, axis=-1, dtype=jnp.float32)
    return nz, x.shape


def fc_in_events(x, threshold: float = 0.0) -> jax.Array:
    """Events entering an FC boundary — the one counting rule CNN and MLP
    stats share (Algorithm 2 charges ``in_events * out`` MACs).

    Stream inputs count their compacted non-zero event values (twin-free);
    dense inputs count activations at the *configured* fire threshold,
    matching the chained stream's semantics (its events are the
    supra-threshold survivors).  Counting ``|x| > 0`` on the dense side
    would also count int8 dequantization artifacts below the threshold and
    diverge from the chained path for threshold > 0; int8 *streams* count
    quantized events, the one documented divergence (DESIGN.md §12).
    """
    if isinstance(x, engine.EventStream):
        return x.num_scalar_events
    return jnp.sum(jnp.abs(x) > threshold, dtype=jnp.float32)


def _density(x) -> jax.Array:
    """Fired fraction of an activation (stream: twin-free event count).

    Zero-row streams / empty tensors (dead layer, empty batch) have no
    elements; their density is defined as 0, not 0/0.
    """
    if isinstance(x, engine.EventStream):
        m, k = x.shape
        if m * k == 0:
            return jnp.zeros((), jnp.float32)
        return x.num_scalar_events / (m * k)
    if x.size == 0:
        return jnp.zeros((), jnp.float32)
    return jnp.mean(jnp.abs(x) > 0)


def _forward(params, x, spec: CNNSpec, *, mnf: bool, fire_cfg: FireConfig,
             cfg: engine.EngineConfig, chain: bool, stats: list | None = None):
    """The one traced forward body behind ``cnn_forward`` /
    ``make_cnn_pipeline`` / ``run_with_stats``.

    ``chain=True`` threads one EventStream through conv→fire→conv→…→FC:
    conv→conv boundaries stay event-only (the fired twin is dropped) and
    pools run in the event domain (``engine.maxpool2d`` segment max,
    DESIGN.md §7) — conv→pool→conv carries no twin and no re-encode, so
    the chain densifies nowhere between the first conv and the FC head.
    Only an *ineligible* pool (magnitude fire, degenerate window) falls
    back to the dense pool + re-encode, visibly.  ``chain=False`` is the
    per-layer round-trip twin (dense at every boundary, identical compute
    geometry) that the chained path is measured against — its dense pool
    is the event pool's bitwise oracle.  ``stats`` (a list to append to)
    requests per-layer event accounting, derived from the compacted event
    values themselves on the chained path (twin-free — no dense twin, no
    decode).
    """
    layers = spec.layers
    # Fired streams are strip-aligned (blk_m == STRIP_W) whenever the
    # consuming layer can ride the fused-tap kernel, which only interleaves
    # exact zeros into the same reduction tree, so bitwise equality with
    # the per-tap twin survives (DESIGN.md §6).
    conv_base = _conv_base(cfg)
    for i, (layer, wgt) in enumerate(zip(layers, params)):
        with engine.scope(layer_scope(i, layer)):
            nxt = layers[i + 1] if i + 1 < len(layers) else None
            if isinstance(layer, ConvSpec):
                if chain and not isinstance(x, engine.EventStream):
                    # Chain head (or densified seam): strip-encode the
                    # dense input when this conv can ride the fused kernel
                    # off it — the stride-4 AlexNet conv1 goes from k²
                    # per-tap event matmuls to one launch.  Lossless at
                    # threshold 0, bitwise vs the dense dispatch (see
                    # _input_stream_blk_m).
                    bm_in = _input_stream_blk_m(layer, tuple(x.shape),
                                                conv_base)
                    if bm_in:
                        x = engine.EventStream.encode_nhwc(
                            x, blk_k=conv_base.blk_k, blk_m=bm_in,
                            keep_dense=False)
                ci = x.logical_shape[-1] if isinstance(x, engine.EventStream) \
                    else x.shape[-1]
                ccfg = conv_base.replace(threshold=0.0).for_conv(ci)
                if stats is not None:
                    # twin-free on chain
                    nzmap, (b, h, w, c) = _pixel_events(x)
                    touched = _touched_outputs(h, w, layer.k, layer.stride,
                                               layer.padding)
                    stats.append(dict(
                        event_macs=jnp.sum(nzmap * touched[None].astype(
                            jnp.float32)) * layer.out_ch,
                        in_events=jnp.sum(nzmap)))
                acc = engine.conv2d(x, wgt, cfg=ccfg, stride=layer.stride,
                                    padding=layer.padding)
                if chain:
                    # Drop the dense twin at conv→conv boundaries, at
                    # conv→pool boundaries the event-native pool will
                    # consume, AND at conv→FC seams the re-tiler serves
                    # (events-only — instrumentation reads event values,
                    # never the twin); keep it only where an ineligible
                    # consumer genuinely reads it densely.
                    pool_chains = (isinstance(nxt, PoolSpec)
                                   and engine.pool_ineligible_reason(
                                       tuple(acc.shape), nxt.k, nxt.stride,
                                       conv_base) is None)
                    bm_next = _next_conv_blk_m(nxt, tuple(acc.shape))
                    keep = not (isinstance(nxt, ConvSpec) or pool_chains
                                or _fc_chains(nxt, tuple(acc.shape),
                                              conv_base, bm_next))
                    if not keep and conv_base.route != "auto":
                        # Adaptive/forced routing may send the next
                        # boundary dense; keep the twin so its
                        # ``dense_nhwc`` is a free read, not a decode.  Same
                        # decision function the dispatch uses — plan and
                        # dispatch cannot disagree.
                        keep = not _next_boundary_route(
                            nxt, tuple(acc.shape), conv_base,
                            bm_next).is_event
                    x = engine.fire_conv(acc, conv_base, keep_dense=keep,
                                         blk_m=bm_next)
                else:
                    x = fire(acc, fire_cfg)      # fire phase == ReLU @ 0
                if stats is not None:
                    stats[-1]["out_density"] = _density(x)
            elif isinstance(layer, PoolSpec):
                if chain and isinstance(x, engine.EventStream) \
                        and engine.pool_ineligible_reason(
                            x, layer.k, layer.stride, conv_base) is None:
                    # Event-native pool (DESIGN.md §7): segment max over
                    # the stream's events, re-emitted at the granularity
                    # the consumer wants — conv→pool→conv stays
                    # events-only (no twin, no re-encode).  The pooled twin
                    # is kept only when the FC head (or the network output)
                    # reads it densely.
                    c = x.logical_shape[-1]
                    oh = (x.logical_shape[1] - layer.k) // layer.stride + 1
                    pw = (x.logical_shape[2] - layer.k) // layer.stride + 1
                    pooled_shape = (x.logical_shape[0], oh, pw, c)
                    # Emitted granularity from the consumer (same rule as
                    # the conv fire): strips for a strip-eligible conv *or*
                    # a window-eligible next pool, pixels otherwise.
                    pcfg = conv_base.for_pool(c).replace(
                        blk_m=_next_conv_blk_m(nxt, pooled_shape))
                    keep_pool = not (isinstance(nxt, ConvSpec)
                                     or _fc_chains(nxt, pooled_shape,
                                                   conv_base, pcfg.blk_m))
                    if not keep_pool and conv_base.route != "auto":
                        keep_pool = not _next_boundary_route(
                            nxt, pooled_shape, conv_base,
                            pcfg.blk_m).is_event
                    x = engine.maxpool2d(x, layer.k, layer.stride, cfg=pcfg,
                                         keep_dense=keep_pool)
                else:
                    pooled = max_pool_nhwc(_dense_nhwc(x), layer.k,
                                           layer.stride)
                    if chain and isinstance(nxt, ConvSpec):
                        # Dense-pool fallback (round-trip twin, or a stream
                        # the event pool cannot consume): re-encode at the
                        # granularity the next conv consumes.
                        x = engine.EventStream.encode_nhwc(
                            pooled, blk_k=conv_base.blk_k,
                            blk_m=_next_conv_blk_m(nxt, tuple(pooled.shape)),
                            keep_dense=False)
                    else:
                        x = pooled
            elif isinstance(layer, FCSpec):
                # Conv-derived inputs (a chained conv stream, or the
                # round-trip twin's dense NHWC map) dispatch under the
                # *re-tiled* geometry: blk_m = 1 and the conv chain's
                # channel-clamped blk_k, so the twin's encode of the
                # flattened map produces the exact BlockEvents the re-tiler
                # emits — bitwise equality across the conv→FC seam, not
                # just allclose (DESIGN.md §12).  FC→FC boundaries keep the
                # plain cfg (the fire emitted that geometry).
                if isinstance(x, engine.EventStream) \
                        and x.logical_shape is not None:
                    fcfg = cfg.replace(threshold=0.0, blk_m=1, blk_k=x.blk_k)
                elif not isinstance(x, engine.EventStream) and x.ndim == 4:
                    fcfg = conv_base.replace(threshold=0.0).for_conv(
                        x.shape[-1])
                else:
                    fcfg = cfg.replace(threshold=0.0)
                flat = x if isinstance(x, engine.EventStream) \
                    else x.reshape(x.shape[0], -1)
                if stats is not None:
                    in_ev = fc_in_events(flat, fire_cfg.threshold)
                    stats.append(dict(event_macs=in_ev * layer.out,  # Alg. 2
                                      in_events=in_ev))
                acc = engine.linear(flat, wgt, cfg=fcfg)
                last = layer is spec.layers[-1]
                if last:
                    x = acc
                elif chain:
                    x = engine.fire(acc, cfg, keep_dense=False)
                else:
                    x = fire(acc, fire_cfg)
                if stats is not None:
                    stats[-1]["out_density"] = _density(x)
    with engine.scope("out"):
        if isinstance(x, engine.EventStream) and x.logical_shape is not None:
            return x.dense_nhwc()    # conv-final spec: keep the NHWC view
        return _dense(x)


def cnn_forward(params, x: jax.Array, spec: CNNSpec, *, mnf: bool = True,
                fire_cfg: FireConfig = FireConfig(),
                engine_cfg: engine.EngineConfig | None = None,
                chain: bool | None = None):
    """x: (B, H, W, C) -> logits (B, classes).  mnf=False is the oracle.

    All compute dispatches through ``repro.engine``; ``engine_cfg`` picks
    the backend (default ``"auto"``: the Pallas kernels on a TPU, pure-jnp
    block events elsewhere).  ``chain`` selects the
    event-resident path (default: on for MNF; int8 requantization chains
    too — fire emits int8 event values and the round-trip twin is the
    fake-quant forward, DESIGN.md §12); ``chain=False`` forces the
    per-layer dense round-trip twin.
    """
    cfg = _layer_cfg(engine_cfg, mnf=mnf, fire_cfg=fire_cfg)
    if chain is None:
        chain = mnf
    return _forward(params, x, spec, mnf=mnf, fire_cfg=fire_cfg, cfg=cfg,
                    chain=chain and mnf)


def make_cnn_forward(spec: CNNSpec, *, mnf: bool = True,
                     fire_cfg: FireConfig = FireConfig(),
                     engine_cfg: engine.EngineConfig | None = None,
                     chain: bool | None = None):
    """The un-jitted whole-network closure: ``fwd(params, x) -> logits``.

    The seam the serving tier wraps: a bucket-shaped jit, or a
    batch-parallel ``shard_map`` body (each device runs this closure over
    its batch shard — the forward is per-sample independent, so the
    sharded result is bitwise the unsharded one).  ``make_cnn_pipeline``
    is exactly ``jax.jit`` of this.
    """
    cfg = _layer_cfg(engine_cfg, mnf=mnf, fire_cfg=fire_cfg)
    if chain is None:
        chain = mnf
    chain = chain and mnf

    def fwd(params, x):
        return _forward(params, x, spec, mnf=mnf, fire_cfg=fire_cfg,
                        cfg=cfg, chain=chain)

    return fwd


def make_cnn_pipeline(spec: CNNSpec, *, mnf: bool = True,
                      fire_cfg: FireConfig = FireConfig(),
                      engine_cfg: engine.EngineConfig | None = None,
                      chain: bool | None = None, donate: bool = True):
    """One jitted forward per network: ``fn(params, x) -> logits``.

    The whole conv→fire→…→FC pipeline compiles as a single ``jax.jit`` —
    no per-layer dispatch, one trace per input shape (DESIGN.md §5.1).
    ``donate=True`` donates the input image buffer (serving never reuses a
    consumed batch; pass ``donate=False`` when the caller does).
    """
    fwd = make_cnn_forward(spec, mnf=mnf, fire_cfg=fire_cfg,
                           engine_cfg=engine_cfg, chain=chain)
    return jax.jit(fwd, donate_argnums=(1,) if donate else ())


def _static_layer_stats(spec: CNNSpec, batch: int):
    """Shape-derived stats fields (no tracing): dense MACs, element counts.

    ``dense_macs`` comes from :func:`layer_dense_macs` (one accounting,
    shared with the cost model) scaled by the batch size.
    """
    shapes = _trace_shapes(spec)
    macs = iter(layer_dense_macs(spec))
    out = []
    for i, layer in enumerate(spec.layers):
        h, w, c = shapes[i]
        if isinstance(layer, (ConvSpec, FCSpec)):
            out.append(dict(
                kind="conv" if isinstance(layer, ConvSpec) else "fc",
                c_out=layer.out_ch if isinstance(layer, ConvSpec)
                else layer.out,
                dense_macs=float(batch * next(macs)),
                in_elems=float(batch * h * w * c)))
    return out


@functools.lru_cache(maxsize=64)
def _stats_pipeline(spec: CNNSpec, fire_cfg: FireConfig,
                    cfg: engine.EngineConfig):
    """Cached single-jit instrumented forward for ``run_with_stats``."""

    def fwd(params, x):
        stats: list = []
        logits = _forward(params, x, spec, mnf=True, fire_cfg=fire_cfg,
                          cfg=cfg, chain=True, stats=stats)
        return logits, tuple(stats)

    return jax.jit(fwd)


def run_with_stats(params, x: jax.Array, spec: CNNSpec,
                   fire_cfg: FireConfig = FireConfig(),
                   engine_cfg: engine.EngineConfig | None = None):
    """MNF forward + per-layer event accounting (via ``repro.engine``).

    One jitted call per (network, shape): the traced body returns per-layer
    event counters alongside the logits; shape-only quantities are derived
    statically.  Returns (logits, stats list).  Each compute layer's stats:
      dense_macs  — MACs of the dense dataflow
      event_macs  — MACs the MNF multiply phase performs (Algorithm 1 walk)
      in_events   — input events fired into the layer
      in_elems    — dense input element count
      out_density — fraction of outputs that fire
    """
    cfg = _layer_cfg(engine_cfg, mnf=True, fire_cfg=fire_cfg)
    logits, traced = _stats_pipeline(spec, fire_cfg, cfg)(params, x)
    stats = []
    for st, tr in zip(_static_layer_stats(spec, x.shape[0]), traced):
        d = dict(st)
        d.update({k: float(v) for k, v in tr.items()})
        d["avg_touched"] = (
            d["event_macs"] / max(d["in_events"] * d["c_out"], 1.0)
            if d["kind"] == "conv" else 1.0)
        stats.append(d)
    return logits, stats
