"""Event-resident conv chaining (DESIGN.md §5/§5.1): conv streams feed the
next layer's taps with no dense round-trip; the whole CNN runs as one jit."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine
from repro.core.events import channel_blk_k
from repro.core.mnf_conv import dense_conv2d
from repro.models.cnn import (ALEXNET, ALEXNET_FF, VGG16, CNNSpec, ConvSpec,
                              FCSpec, PoolSpec, chain_boundary_summary,
                              cnn_forward, init_cnn_params, make_cnn_forward,
                              make_cnn_pipeline)
from repro.models.mlp import (LENET_300_100, MLPSpec, init_mlp_params,
                              make_mlp_forward)

KEY = jax.random.PRNGKey(0)

MINI = CNNSpec("mini", 8, 3,
               (ConvSpec(8, 3, 1, 1), ConvSpec(8, 3, 1, 1), PoolSpec(),
                FCSpec(10)))


def _fired_map(seed, shape=(2, 6, 5, 3), sparsity=0.5):
    r = np.random.default_rng(seed)
    x = r.normal(size=shape) * (r.random(shape) > sparsity)
    return jax.nn.relu(jnp.asarray(x.astype(np.float32)))


# ---------------------------------------------------------------------------
# single layer: conv on a stream == conv on its dense twin == oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["block", "pallas"])
@pytest.mark.parametrize("stride,padding", [(1, 1), (2, 0), (2, 2)])
def test_conv2d_events_matches_dense_oracle(backend, stride, padding):
    r = np.random.default_rng(1)
    x = _fired_map(1)
    w = jnp.asarray(r.normal(size=(3, 3, 3, 4)).astype(np.float32))
    cfg = engine.EngineConfig(backend=backend, blk_m=4, blk_k=8, blk_n=4)
    stream = engine.fire_conv(x, cfg)
    with engine.trace_dispatch() as recs:
        y = engine.conv2d(stream.without_dense(), w, cfg=cfg, stride=stride,
                          padding=padding)
    assert not any(rec.get("decode") for rec in recs), "chained conv decoded"
    assert any(rec.get("chained") and rec["op"] == "conv2d" for rec in recs)
    ref = dense_conv2d(x, w, stride=stride, padding=padding)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=2e-4,
                               rtol=2e-4)


def test_conv2d_events_bitwise_equals_reencoded_roundtrip():
    """Consuming fired events directly == decode→re-encode, bit for bit
    (same pixel-granular geometry, same tiles, same order)."""
    r = np.random.default_rng(2)
    x = _fired_map(2)
    w = jnp.asarray(r.normal(size=(3, 3, 3, 4)).astype(np.float32))
    cfg = engine.EngineConfig(backend="block", blk_m=4, blk_k=8)
    stream = engine.fire_conv(x, cfg)
    y_chain = engine.conv2d(stream.without_dense(), w, cfg=cfg, padding=1)
    redone = engine.EventStream.encode_nhwc(stream.dense_nhwc(), blk_k=8)
    y_round = engine.conv2d(redone, w, cfg=cfg, padding=1)
    assert bool(jnp.all(y_chain == y_round)), "paths diverged bitwise"


# ---------------------------------------------------------------------------
# whole networks: event-resident == per-layer round-trip (bitwise) == oracle
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("spec,size", [(ALEXNET, 64), (VGG16, 32)])
def test_event_resident_forward_bitwise_and_boundaries(spec, size):
    """At threshold 0, batch ≥ 2: the chained forward is bit-identical to
    the per-layer round-trip (the dense-boundary twin of the same event
    dataflow), allclose to the dense-backend oracle, and every boundary
    between the first conv and the FC head runs events-only — pools
    included (the event-native segment max, DESIGN.md §7): zero densify
    points on the chain.
    """
    s = spec.scaled(size)
    params = init_cnn_params(KEY, s, weight_sparsity=0.5)
    x = jax.nn.relu(jax.random.normal(KEY, (2, size, size, s.in_ch)))

    with engine.trace_dispatch() as recs:
        ym = cnn_forward(params, x, s, mnf=True, chain=True)
    n_conv = sum(isinstance(l, ConvSpec) for l in s.layers)
    n_fc = sum(isinstance(l, FCSpec) for l in s.layers)
    n_pool = sum(isinstance(l, PoolSpec) for l in s.layers)
    # Zero densify points between the first conv and the FC head: no
    # decode, no fallback, and every pool rides the event-native path.
    assert sum(1 for r in recs if r.get("decode")) == 0
    assert sum(1 for r in recs if r.get("fallback_decode")) == 0
    assert sum(1 for r in recs if r.get("pool_events")
               and r["op"] == "maxpool2d") == n_pool
    # Every conv consumes events except a chain head whose geometry cannot
    # strip-encode the dense input image (input_encode counts the heads
    # that can — AlexNet's stride-4 conv1 at 64 px cannot, VGG16@32 can).
    n_enc = chain_boundary_summary(s, batch=2)["input_encode"]
    assert sum(1 for r in recs if r.get("chained")
               and r["op"] == "conv2d") == n_conv - 1 + n_enc
    # Every FC consumes events — the first through the conv→FC re-tiler
    # (DESIGN.md §12), the rest as chained fire streams.
    assert sum(1 for r in recs if r.get("chained")
               and r["op"] == "linear") == n_fc

    yr = cnn_forward(params, x, s, mnf=True, chain=False)
    assert bool(jnp.all(ym == yr)), "chained != round-trip bitwise"
    yd = cnn_forward(params, x, s, mnf=False)
    np.testing.assert_allclose(np.asarray(ym), np.asarray(yd), atol=5e-3,
                               rtol=5e-3)


def test_one_jit_pipeline_matches_eager_and_caches():
    params = init_cnn_params(KEY, MINI, weight_sparsity=0.5)
    x = jax.nn.relu(jax.random.normal(KEY, (2, 8, 8, 3)))
    fn = make_cnn_pipeline(MINI, donate=False)
    y1 = fn(params, x)
    y2 = fn(params, x)
    assert bool(jnp.all(y1 == y2))
    assert bool(jnp.all(y1 == cnn_forward(params, x, MINI, mnf=True)))
    try:
        n = fn._cache_size()
    except AttributeError:
        n = 1            # older jax: no cache introspection — shape check only
    assert n == 1, "pipeline retraced for identical input shapes"


def test_pipeline_pallas_backend_runs_under_one_jit():
    cfg = engine.EngineConfig(backend="pallas", blk_m=4, blk_k=8, blk_n=8)
    params = init_cnn_params(KEY, MINI, weight_sparsity=0.5)
    x = jax.nn.relu(jax.random.normal(KEY, (2, 8, 8, 3)))
    fn = make_cnn_pipeline(MINI, engine_cfg=cfg, donate=False)
    y = fn(params, x)
    yd = cnn_forward(params, x, MINI, mnf=False)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yd), atol=5e-3,
                               rtol=5e-3)


# ---------------------------------------------------------------------------
# stream geometry / registry seams
# ---------------------------------------------------------------------------

def test_fire_conv_stream_geometry():
    x = _fired_map(3, shape=(2, 4, 5, 6))
    cfg = engine.EngineConfig(backend="block", blk_k=8)
    s = engine.fire_conv(x, cfg)
    assert s.blk_m == 1 and s.blk_k == 6          # pixel rows, clamped K
    assert s.logical_shape == (2, 4, 5, 6) and s.shape == (40, 6)
    np.testing.assert_array_equal(np.asarray(s.dense_nhwc()), np.asarray(x))
    # events-only view still reconstructs exactly (threshold 0 is lossless)
    np.testing.assert_array_equal(
        np.asarray(s.without_dense().dense_nhwc()), np.asarray(x))


def test_conv_event_ops_registered():
    for op in ("conv2d_events", "conv2d_events_strip"):
        assert set(engine.list_backends(op)) == {"block", "pallas"}, op
    assert set(engine.BACKENDS) <= set(engine.list_backends("fire_conv"))


def test_occupancy_zero_grid_is_zero():
    s = engine.EventStream.encode(jnp.zeros((0, 8)), blk_m=1, blk_k=8)
    assert float(s.occupancy()) == 0.0


def test_zero_row_streams_never_reach_pallas():
    """Empty batches / fully-dead layers: encode returns an explicit empty
    stream, and fire/linear/conv2d short-circuit instead of handing Pallas
    a 0-extent launch (regression: slice_sizes > operand shape)."""
    cfg = engine.EngineConfig(backend="pallas", blk_m=8, blk_k=8, blk_n=4)
    s = engine.fire(jnp.zeros((0, 8)), cfg)            # used to raise
    assert s.shape == (0, 8) and float(s.num_scalar_events) == 0.0
    assert float(s.occupancy()) == 0.0
    y = engine.linear(s, jnp.ones((8, 4)), cfg=cfg)
    assert y.shape == (0, 4)
    y = engine.linear(s, jnp.ones((8, 4)), b=jnp.ones((4,)), cfg=cfg)
    assert y.shape == (0, 4)
    # dtype must not flip with batch size: empty shortcut promotes like the
    # dispatch path (f32 events @ bf16 weights -> f32)
    yb = engine.linear(s, jnp.ones((8, 4), jnp.bfloat16), cfg=cfg)
    assert yb.dtype == jnp.float32
    sc = engine.fire_conv(jnp.zeros((0, 6, 6, 4)), cfg)
    assert sc.logical_shape == (0, 6, 6, 4)
    yc = engine.conv2d(sc, jnp.ones((3, 3, 4, 8)), cfg=cfg, padding=1)
    assert yc.shape == (0, 6, 6, 8)
    # the block-event grid of the empty stream is explicitly empty
    assert s.events.counts.shape == (0,)
    assert s.events.values.shape[0] == 0


def test_for_conv_clamps_blk_k():
    cfg = engine.EngineConfig(blk_k=128)
    assert cfg.for_conv(3).blk_k == 3
    assert cfg.for_conv(512).blk_k == 128
    assert cfg.for_conv(0).blk_k == 1             # degenerate channel depth


@pytest.mark.parametrize("c,blk_k,want", [
    (96, 128, 96),      # C fits: one block of C channels
    (384, 128, 128),    # lane-wide blocks that divide C
    (320, 128, 80),     # 128 does not divide 320: widest 8k divisor
    (300, 128, 128),    # no multiple of 8 divides 300: blk_k, padded
    (6, 4, 4),          # nothing to search below 8
    (0, 128, 1),        # degenerate channel depth
])
def test_channel_blk_k(c, blk_k, want):
    """The one K-block clamp of conv and pool streams."""
    assert channel_blk_k(c, blk_k) == want
    cfg = engine.EngineConfig(blk_k=blk_k)
    assert cfg.for_conv(c).blk_k == cfg.for_pool(c).blk_k == want


# ---------------------------------------------------------------------------
# fallback visibility: no more invisible round-trips
# ---------------------------------------------------------------------------

def test_linear_events_fallback_is_bit_identical_and_marked():
    """A backend without ``linear_events`` must decode-fallback to a result
    bit-identical to the explicit dense path, and the fallback must surface
    a ``fallback_decode=True`` record (the silent round-trip is visible)."""
    r = np.random.default_rng(5)
    a = jax.nn.relu(jnp.asarray(r.normal(size=(8, 16)).astype(np.float32)))
    w = jnp.asarray(r.normal(size=(16, 6)).astype(np.float32))
    cfg_b = engine.EngineConfig(backend="block", blk_m=4, blk_k=8)
    stream = engine.fire(a, cfg_b)

    engine.register_backend("matmul", "nochain", lambda x, wt, c: x @ wt)
    engine.register_backend(
        "linear", "nochain",
        lambda x, wt, b, c: x @ wt if b is None else x @ wt + b)
    try:
        cfg = cfg_b.replace(backend="nochain")
        with engine.trace_dispatch() as recs:
            y = engine.linear(stream, w, cfg=cfg)
        marks = [rec for rec in recs if rec.get("fallback_decode")]
        assert marks and marks[0]["op"] == "linear" \
            and marks[0]["backend"] == "nochain"
        y_dense = engine.linear(stream.dense(), w, cfg=cfg)
        assert bool(jnp.all(y == y_dense)), "fallback diverged from dense"
    finally:
        engine.registry._REGISTRY.pop(("matmul", "nochain"))
        engine.registry._REGISTRY.pop(("linear", "nochain"))


def test_conv2d_events_fallback_decodes_with_marker():
    """Backends without ``conv2d_events`` (oracles) decode conv streams —
    correct result, visible marker."""
    r = np.random.default_rng(6)
    x = _fired_map(6)
    w = jnp.asarray(r.normal(size=(3, 3, 3, 4)).astype(np.float32))
    stream = engine.fire_conv(x, engine.EngineConfig(backend="block",
                                                     blk_k=8))
    cfg = engine.EngineConfig(backend="dense")
    with engine.trace_dispatch() as recs:
        y = engine.conv2d(stream, w, cfg=cfg, padding=1)
    assert any(rec.get("fallback_decode") and rec["op"] == "conv2d"
               for rec in recs)
    ref = dense_conv2d(x, w, stride=1, padding=1)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-5,
                               rtol=1e-5)


# ---------------------------------------------------------------------------
# chain tile geometry: K blocks of min(blk_k, C) channels under the default
# EngineConfig, read off the dispatch records (DESIGN.md §5)
# ---------------------------------------------------------------------------

#: AlexNet's channel plan (96, 256, 384, 384, 256) at a CPU size: every
#: width misses STRIP_W, so every boundary takes the per-tap pixel path
#: the served AlexNet@224 takes.  Maps: 28 -> 14 -> 6 -> 6 -> 2 -> 2.
ALEX_LANES = CNNSpec(
    "alexnet_lanes", 28, 3,
    (ConvSpec(96, 5, 2, 2), PoolSpec(3, 2), ConvSpec(256, 5, 1, 2),
     PoolSpec(3, 2), ConvSpec(384, 3, 1, 1), ConvSpec(384, 3, 1, 1),
     ConvSpec(256, 3, 1, 1), FCSpec(10)), num_classes=10)

#: (op, blk_k, grid_steps) of each stream-consuming boundary of ALEX_LANES
#: at batch 2 with blk_k = blk_n = 128.  Pixel conv: taps · B·OY·OX ·
#: ⌈CO/128⌉ · nkb; pool: B·OH·OW · k² · nkb; the re-tiled FC: B · ⌈N/128⌉
#: · H·W·nkb.  conv1 reads the dense image and writes no record.
ALEX_LANES_STEPS = [
    ("maxpool2d", 96, 2 * 6 * 6 * 9 * 1),
    ("conv2d", 96, 25 * (2 * 6 * 6) * 2 * 1),
    ("maxpool2d", 128, 2 * 2 * 2 * 9 * 2),
    ("conv2d", 128, 9 * (2 * 2 * 2) * 3 * 2),
    ("conv2d", 128, 9 * (2 * 2 * 2) * 3 * 3),
    ("conv2d", 128, 9 * (2 * 2 * 2) * 2 * 3),
    ("linear", 128, 2 * 1 * (2 * 2 * 2)),
]


@pytest.mark.parametrize("backend", ["block", "pallas"])
def test_chain_tiles_at_lane_width(backend):
    """Under the default EngineConfig every conv and pool stream of an
    AlexNet-class chain tiles K at min(128, C) channels, each record
    carries that blk_k and the grid steps of the route, and the chained
    forward stays bitwise its round-trip twin."""
    cfg = engine.EngineConfig(backend=backend)
    params = init_cnn_params(KEY, ALEX_LANES, weight_sparsity=0.5)
    x = jax.nn.relu(jax.random.normal(KEY, (2, 28, 28, 3)))
    with engine.trace_dispatch() as recs:
        y = cnn_forward(params, x, ALEX_LANES, engine_cfg=cfg)
    assert not any(r.get("fallback_decode") or r.get("decode")
                   for r in recs)
    got = [(r["op"], r["blk_k"], r["grid_steps"]) for r in recs
           if r.get("op") in ("conv2d", "maxpool2d", "linear")]
    assert got == ALEX_LANES_STEPS
    yr = cnn_forward(params, x, ALEX_LANES, engine_cfg=cfg, chain=False)
    assert bool(jnp.all(y == yr)), "chained != round-trip bitwise"


def _dispatch_records(spec, batch, cfg):
    """Dispatch records of one abstract trace of the chained forward."""
    params = jax.eval_shape(lambda: init_cnn_params(KEY, spec))
    x = jax.ShapeDtypeStruct(
        (batch, spec.input_size, spec.input_size, spec.in_ch), jnp.float32)
    with engine.trace_dispatch() as recs:
        jax.eval_shape(make_cnn_forward(spec, engine_cfg=cfg), params, x)
    return recs


#: A last conv whose 320 channels the default 128-wide block does not
#: divide: the chain tiles it at 80 so the conv→FC seam stays re-tileable.
RAGGED = CNNSpec("ragged320", 9, 3,
                 (ConvSpec(64, 3, 1, 1), ConvSpec(320, 3, 1, 1),
                  PoolSpec(3, 3), FCSpec(10)), num_classes=10)


@pytest.mark.parametrize("spec", [ALEXNET, VGG16, ALEXNET_FF, RAGGED],
                         ids=lambda s: s.name)
def test_chain_boundary_summary_matches_dispatch(spec):
    """At the spec's own input size (224 and 256 for the paper's nets) and
    the default EngineConfig, the planner's summary shows no densify point
    and no fallback, and its routes are the dispatch's routes, boundary for
    boundary — one conv_base behind both."""
    cfg = engine.EngineConfig()
    summary = chain_boundary_summary(spec, batch=1, engine_cfg=cfg)
    assert summary["densify"] == 0 and summary["retile"] == 1
    assert not any("reason" in r for r in summary["routes"])
    recs = _dispatch_records(spec, 1, cfg)
    assert not any(r.get("fallback_decode") or r.get("decode")
                   for r in recs)
    keys = ("op", "route", "shape_class", "occupancy")
    planned = [tuple(r[k] for k in keys) + (r["source"],)
               for r in summary["routes"]]
    dispatched = [tuple(r[k] for k in keys) + (r["route_source"],)
                  for r in recs if r.get("route") is not None]
    assert planned == dispatched


def test_alexnet224_dispatch_grid_steps_match_step_table():
    """AlexNet@224 at bucket 8, default EngineConfig: the records carry
    the per-layer K blocks and grid steps of the step model (PERF.md §5).
    At 8-channel blocks conv2..conv5 took 3.50 M, 1.17 M, 1.75 M and
    1.17 M steps, pool1 0.63 M and fc6 0.29 M."""
    recs = _dispatch_records(ALEXNET, 8, engine.EngineConfig())
    got = [(r["op"], r["blk_k"], r["grid_steps"]) for r in recs
           if r.get("op") in ("conv2d", "maxpool2d", "linear")]
    assert got == [
        ("maxpool2d", 96, 8 * 27 * 27 * 9 * 1),      # pool1   52 488
        ("conv2d", 96, 25 * 5832 * 2 * 1),           # conv2  291 600
        ("maxpool2d", 128, 8 * 13 * 13 * 9 * 2),     # pool2   24 336
        ("conv2d", 128, 9 * 1352 * 3 * 2),           # conv3   73 008
        ("conv2d", 128, 9 * 1352 * 3 * 3),           # conv4  109 512
        ("conv2d", 128, 9 * 1352 * 2 * 3),           # conv5   73 008
        ("maxpool2d", 128, 8 * 6 * 6 * 9 * 2),       # pool3    5 184
        ("linear", 128, 8 * 32 * (6 * 6 * 2)),       # fc6     18 432
        ("linear", 128, 1 * 32 * 32),                # fc7
        ("linear", 128, 1 * 8 * 32),                 # fc8
    ]


#: (layer, op, route, blk_k, grid_steps) of every boundary one abstract
#: trace of the chained forward dispatches, default EngineConfig, at the
#: bucket of each benchmark cell.  VGG16@224's conv1_1 is its strip-encoded
#: 3-channel input (K block 3, padded to an 8-row weight tile inside the
#: fused kernel, same grid); pool1/pool2 ride the window-major pool, the
#: 28- and 14-wide convs the per-tap pixel path.  AlexNet's conv1 takes a
#: dense input and writes no record.
PINNED_RECORDS = {
    "vgg16_224-b1": (VGG16, 1, [
        (0, "conv2d", "strip", 3, 94080),
        (1, "conv2d", "strip", 64, 94080),
        (2, "maxpool2d", "window", 64, 12544),
        (3, "conv2d", "strip", 64, 23520),
        (4, "conv2d", "strip", 128, 23520),
        (5, "maxpool2d", "window", 128, 3136),
        (6, "conv2d", "strip", 128, 11760),
        (7, "conv2d", "strip", 128, 23520),
        (8, "conv2d", "strip", 128, 23520),
        (9, "maxpool2d", "pixel", 128, 6272),
        (10, "conv2d", "pixel", 128, 9 * 784 * 4 * 2),
        (11, "conv2d", "pixel", 128, 9 * 784 * 4 * 4),
        (12, "conv2d", "pixel", 128, 9 * 784 * 4 * 4),
        (13, "maxpool2d", "pixel", 128, 3136),
        (14, "conv2d", "pixel", 128, 9 * 196 * 4 * 4),
        (15, "conv2d", "pixel", 128, 9 * 196 * 4 * 4),
        (16, "conv2d", "pixel", 128, 9 * 196 * 4 * 4),
        (17, "maxpool2d", "pixel", 128, 784),
        (18, "linear", "event", 128, 32 * 196),
        (19, "linear", "event", 128, 32 * 32),
        (20, "linear", "event", 128, 8 * 32),
    ]),
    # The first FC takes the dense input and writes no record.
    "lenet300-b128": (LENET_300_100, 128, [
        (1, "linear", "event", 128, 16 * 3),
        (2, "linear", "event", 100, 16 * 1),
    ]),
    "alexnet224-b1": (ALEXNET, 1, [
        (1, "maxpool2d", "pixel", 96, 27 * 27 * 9 * 1),
        (2, "conv2d", "pixel", 96, 25 * 729 * 2 * 1),
        (3, "maxpool2d", "pixel", 128, 13 * 13 * 9 * 2),
        (4, "conv2d", "pixel", 128, 9 * 169 * 3 * 2),
        (5, "conv2d", "pixel", 128, 9 * 169 * 3 * 3),
        (6, "conv2d", "pixel", 128, 9 * 169 * 2 * 3),
        (7, "maxpool2d", "pixel", 128, 6 * 6 * 9 * 2),
        (8, "linear", "event", 128, 32 * 72),
        (9, "linear", "event", 128, 32 * 32),
        (10, "linear", "event", 128, 8 * 32),
    ]),
}


@pytest.mark.parametrize("cell", sorted(PINNED_RECORDS))
def test_dispatch_records_pinned(cell):
    """Route, K block, grid steps and layer of every record, per cell.
    VGG16@224 at b1 walks 694 336 grid steps a frame."""
    spec, batch, want = PINNED_RECORDS[cell]
    if isinstance(spec, MLPSpec):
        params = jax.eval_shape(lambda: init_mlp_params(KEY, spec, 0.5))
        x = jax.ShapeDtypeStruct((batch, spec.in_features), jnp.float32)
        with engine.trace_dispatch() as recs:
            jax.eval_shape(make_mlp_forward(
                spec, engine_cfg=engine.EngineConfig()), params, x)
    else:
        recs = _dispatch_records(spec, batch, engine.EngineConfig())
    got = [(r["layer"], r["op"], r["route"], r["blk_k"], r["grid_steps"])
           for r in recs if r.get("op") in ("conv2d", "maxpool2d", "linear")]
    assert got == want
    if spec is VGG16:
        assert sum(g[-1] for g in got) == 694336
