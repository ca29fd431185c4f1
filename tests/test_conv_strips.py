"""Strip-tiled fused-tap event conv (DESIGN.md §6).

The fused kernel consumes a strip-aligned (blk_m == STRIP_W) conv stream in
one launch per layer; it must be *bit-identical* to the pixel-granular
per-tap path (the oracle) — strips only interleave exact zeros into the
same reduction tree.  Strides 1, 2 and 4 all ride it (a stride-s tap
gathers up to strip_parts(s) interleaved partial strips, dead parts
compacted out of the plan).  Ineligible geometry (stride not in
STRIP_STRIDES, W % 8 != 0, odd widths, misaligned output width) must
degrade visibly, never silently.
"""
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine
from repro.core import events as ev
from repro.core.mnf_conv import dense_conv2d
from repro.kernels.event_conv import fused_conv_plan
from repro.models.cnn import (ALEXNET_DS, ALEXNET_FF, MINI_S4, VGG16,
                              VGG16_DS, CNNSpec, ConvSpec,
                              FCSpec, PoolSpec, cnn_forward,
                              conv_downsampled, init_cnn_params)

KEY = jax.random.PRNGKey(0)


def _fired(seed, shape, sparsity=0.5):
    r = np.random.default_rng(seed)
    x = r.normal(size=shape) * (r.random(shape) > sparsity)
    return jax.nn.relu(jnp.asarray(x.astype(np.float32)))


# ---------------------------------------------------------------------------
# bit-exactness: fused strip path == per-tap pixel path, per backend
# ---------------------------------------------------------------------------

ELIGIBLE = [  # (B, H, W, CI, CO, k, padding, stride) — all strip-eligible
    (2, 6, 8, 5, 8, 3, 1, 1),
    (1, 8, 16, 3, 16, 3, 1, 1),
    (2, 5, 8, 4, 16, 5, 2, 1),   # odd height
    (1, 9, 16, 2, 8, 1, 0, 1),   # 1x1 conv
    (1, 4, 16, 3, 8, 9, 4, 1),   # widest eligible filter (max tap shift)
    (1, 8, 16, 5, 8, 3, 1, 2),   # stride-2 "VGG-ds" 3x3 block
    (2, 7, 16, 4, 8, 5, 2, 2),   # stride-2 5x5, odd height
    (1, 9, 16, 3, 8, 1, 0, 2),   # stride-2 1x1 projection conv
    (1, 6, 16, 2, 8, 9, 4, 2),   # stride-2 widest filter (3-part straddles)
    (1, 8, 32, 5, 8, 3, 1, 4),   # stride-4 3x3 (5-part straddle plan)
    (1, 11, 32, 3, 8, 11, 4, 4),  # stride-4 k=11: the AlexNet conv1 class
    (2, 9, 32, 4, 8, 1, 0, 4),   # stride-4 1x1 projection conv
]


@pytest.mark.parametrize("backend", ["block", "pallas"])
@pytest.mark.parametrize("shape", ELIGIBLE)
def test_strip_bitwise_equals_pertap_and_oracle(backend, shape):
    b, h, w0, ci, co, k, p, s = shape
    x = _fired(sum(shape), (b, h, w0, ci))
    r = np.random.default_rng(1)
    wgt = jnp.asarray(r.normal(size=(k, k, ci, co)).astype(np.float32))
    cfg = engine.EngineConfig(backend=backend, blk_m=1, blk_k=4, blk_n=4)
    strip = engine.fire_conv(x, cfg, blk_m=engine.STRIP_W, keep_dense=False)
    pixel = engine.fire_conv(x, cfg, blk_m=1, keep_dense=False)
    assert strip.events.block_idx.shape[0] * engine.STRIP_W \
        == pixel.events.block_idx.shape[0]          # 8x smaller event grid
    with engine.trace_dispatch() as recs:
        y_strip = engine.conv2d(strip, wgt, cfg=cfg, stride=s, padding=p)
    assert any(rec.get("strip") and rec.get("chained")
               and rec.get("launches") == 1 and rec.get("stride") == s
               for rec in recs), recs
    assert not any(rec.get("decode") or rec.get("fallback_decode")
                   for rec in recs)
    y_pix = engine.conv2d(pixel, wgt, cfg=cfg, stride=s, padding=p)
    assert bool(jnp.all(y_strip == y_pix)), "fused strip != per-tap bitwise"
    ref = dense_conv2d(x, wgt, stride=s, padding=p)
    np.testing.assert_allclose(np.asarray(y_strip), np.asarray(ref),
                               atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("backend", ["block", "pallas"])
@pytest.mark.parametrize("co", [16, 64])
def test_padded_strip_head_bitwise_equals_pertap_oracle(backend, co):
    """The chain head of VGG16: a dense 3-channel image strip-encoded at
    the chain's blk_k tiles K at 3, and the fused kernel pads each K block
    to an 8-row weight tile (``lane_blk_k``) whose zero rows never enter
    the dot — bit-identical to the per-tap dispatch of the dense input."""
    from repro.kernels.event_conv.kernel import lane_blk_k
    x = jnp.asarray(np.random.default_rng(co).random((2, 16, 16, 3),
                                                     np.float32))
    wgt = jnp.asarray(np.random.default_rng(1).normal(
        size=(3, 3, 3, co)).astype(np.float32))
    cfg = engine.EngineConfig(backend=backend, blk_m=1)
    head = engine.EventStream.encode_nhwc(x, blk_k=cfg.blk_k,
                                          blk_m=engine.STRIP_W,
                                          keep_dense=False)
    assert head.blk_k == 3 and lane_blk_k(head.blk_k) == 8
    ccfg = cfg.for_conv(3)
    with engine.trace_dispatch() as recs:
        y_strip = engine.conv2d(head, wgt, cfg=ccfg, padding=1)
    assert [(r["route"], r["blk_k"]) for r in recs] == [("strip", 3)]
    y_tap = engine.conv2d(x, wgt, cfg=ccfg, padding=1)
    assert bool(jnp.all(y_strip.reshape(y_tap.shape) == y_tap))


# ---------------------------------------------------------------------------
# eligibility rules + EngineConfig.for_conv strip selection/validation
# ---------------------------------------------------------------------------

def test_strip_eligibility_rules():
    assert engine.strip_eligible(8, 3, 1, 1)
    assert engine.strip_eligible(16, 9, 1, 4)          # OX == W
    assert engine.strip_eligible(16, 3, 2, 1)          # stride-2 ds block
    assert engine.strip_eligible(16, 1, 2, 0)          # stride-2 projection
    assert engine.strip_eligible(32, 3, 4, 1)          # stride-4 ds block
    assert engine.strip_eligible(32, 11, 4, 4)         # AlexNet-class conv1
    assert not engine.strip_eligible(8, 3, 2, 1)       # OX = 4, misaligned
    assert not engine.strip_eligible(16, 3, 4, 1)      # OX = 4, misaligned
    assert not engine.strip_eligible(24, 3, 3, 1)      # stride 3 unvalidated
    assert not engine.strip_eligible(12, 3, 1, 1)      # W % 8 != 0
    assert not engine.strip_eligible(7, 3, 1, 1)       # odd width
    assert not engine.strip_eligible(16, 3, 1, 0)      # OX = 14, misaligned
    # ragged/tiny CO voids the bitwise contract (M-dependent dot lowering)
    assert engine.strip_eligible(8, 3, 1, 1, co=engine.STRIP_CO_MIN)
    assert engine.strip_eligible(8, 3, 1, 1, co=64)
    assert not engine.strip_eligible(8, 3, 1, 1, co=2)
    assert not engine.strip_eligible(8, 3, 1, 1, co=9)
    assert not engine.strip_eligible(8, 3, 1, 1, co=12)
    assert "stride" in engine.strip_ineligible_reason(24, 3, 3, 1)
    assert "output width" in engine.strip_ineligible_reason(16, 3, 4, 1)
    assert "width 12" in engine.strip_ineligible_reason(12, 3, 1, 1)
    assert "output width" in engine.strip_ineligible_reason(16, 3, 1, 0)
    assert "output width" in engine.strip_ineligible_reason(8, 3, 2, 1)
    assert "output channels" in engine.strip_ineligible_reason(8, 3, 1, 1,
                                                               co=2)
    assert "output channels" in engine.strip_ineligible_reason(16, 3, 2, 1,
                                                               co=12)
    assert "padding" in engine.strip_ineligible_reason(8, 3, 1, 5)


def test_strip_ineligible_reason_message_table():
    """Regression-pin the exact rule strings: `for_conv(strips=True)` embeds
    them in its ValueError and callers grep them in CI logs — the stride
    rule used to claim `stride != 1` even after stride 2 joined the plan
    (and `{1, 2}` after stride 4 did), so each message is pinned verbatim
    here and the stride set is derived from STRIP_STRIDES, never
    hardcoded."""
    r = engine.strip_ineligible_reason
    assert r(16, 3, 3, 1) == (
        f"stride 3 not in {set(ev.STRIP_STRIDES)} (strip plans gather up "
        f"to (7*stride + 7)//8 + 1 interleaved straddle parts per tap; "
        f"only these strides are validated bitwise)")
    assert str(set(ev.STRIP_STRIDES)) == "{1, 2, 4}"   # pins the verbatim text
    assert r(12, 3, 1, 1) == "input width 12 not a multiple of STRIP_W=8"
    assert r(16, 3, 1, 0) == (
        "output width 14 ((W + 2p - k)//stride + 1) not a multiple of "
        "STRIP_W=8")
    assert r(8, 3, 2, 1) == (
        "output width 4 ((W + 2p - k)//stride + 1) not a multiple of "
        "STRIP_W=8")
    assert r(8, 1, 2, 4) == (
        "padding 4 > k//2 = 0: the output map outgrows the input and a tap "
        "shift can index outside the planned straddle parts (strip plans "
        "pair each output strip with its aligned input strips)")
    assert r(24, 19, 1, 9) == (
        "tap x-offsets [-9, 9] leave the adjacent-strip window "
        "(|dx - p| <= 8)")
    assert r(8, 3, 1, 1, co=12) == (
        "output channels 12 not a multiple of STRIP_CO_MIN=8 (bitwise "
        "contract needs an M-invariant dot lowering — ragged lane "
        "remainders break it)")
    # every rule string above is the exact text for_conv(strips=True) raises
    with pytest.raises(ValueError, match="not in \\{1, 2, 4\\}"):
        engine.EngineConfig().for_conv(8, width=16, k=3, stride=3,
                                       padding=1, strips=True)


def test_strip_rejects_padding_beyond_half_window():
    """padding > k//2 grows the output map beyond the input, so a tap shift
    can index outside the planned straddle halves: such geometry must be
    ineligible (named rule), for_conv(strips=True) must raise, and a strip
    stream hitting it must take the visible decode fallback — never the
    fused plan."""
    # (k, p) pairs that pass every *other* rule (out_w % 8 == 0 at W = 8)
    for k, p in ((1, 4), (1, 8), (3, 5), (9, 8)):
        reason = engine.strip_ineligible_reason(8, k, 1, p, co=8)
        assert reason is not None and "padding" in reason, (k, p, reason)
        assert not engine.strip_eligible(8, k, 1, p, co=8)
        with pytest.raises(ValueError, match="padding"):
            engine.EngineConfig().for_conv(8, width=8, k=k, stride=1,
                                           padding=p, strips=True)
    # boundary: padding == k//2 stays eligible (the real-net "same" conv)
    assert engine.strip_eligible(8, 9, 1, 4, co=8)
    # behavior: the stream degrades visibly and stays correct
    x = _fired(11, (1, 6, 8, 4))
    r = np.random.default_rng(11)
    wgt = jnp.asarray(r.normal(size=(3, 3, 4, 8)).astype(np.float32))
    cfg = engine.EngineConfig(backend="block", blk_k=4)
    s = engine.fire_conv(x, cfg, blk_m=engine.STRIP_W)  # twin: free decode
    with engine.trace_dispatch() as recs:
        y = engine.conv2d(s, wgt, cfg=cfg, padding=5)
    assert any(rec.get("fallback_decode") and rec.get("strip")
               for rec in recs), recs
    ref = dense_conv2d(x, wgt, stride=1, padding=5)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=2e-4,
                               rtol=2e-4)


def test_tiny_co_strip_stream_falls_back_visibly():
    """A strip stream fed to a conv with CO < STRIP_CO_MIN must take the
    visible decode fallback (the bitwise contract does not hold there)."""
    x = _fired(8, (1, 6, 8, 4))
    r = np.random.default_rng(8)
    wgt = jnp.asarray(r.normal(size=(3, 3, 4, 2)).astype(np.float32))
    cfg = engine.EngineConfig(backend="block", blk_k=4)
    s = engine.fire_conv(x, cfg, blk_m=engine.STRIP_W)
    with engine.trace_dispatch() as recs:
        y = engine.conv2d(s, wgt, cfg=cfg, padding=1)
    assert any(rec.get("fallback_decode") and rec.get("strip")
               for rec in recs), recs
    ref = dense_conv2d(x, wgt, stride=1, padding=1)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=2e-4,
                               rtol=2e-4)


def test_for_conv_strip_selection():
    cfg = engine.EngineConfig(blk_k=128)
    assert cfg.for_conv(3).blk_k == 3                  # legacy clamp intact
    assert cfg.for_conv(16, width=16, k=3, stride=1, padding=1).blk_m \
        == engine.STRIP_W
    # stride-2/4 downsampling convs resolve to strips too (DESIGN.md §6)
    assert cfg.for_conv(16, width=16, k=3, stride=2, padding=1).blk_m \
        == engine.STRIP_W
    assert cfg.for_conv(16, width=32, k=3, stride=4, padding=1).blk_m \
        == engine.STRIP_W
    # auto mode silently (and correctly) degrades to pixel granularity
    assert cfg.for_conv(16, width=12, k=3, stride=1, padding=1).blk_m == 1
    assert cfg.for_conv(16, width=8, k=3, stride=2, padding=1).blk_m == 1
    assert cfg.for_conv(16, width=16, k=3, stride=4, padding=1).blk_m == 1
    assert cfg.for_conv(16, width=24, k=3, stride=3, padding=1).blk_m == 1
    # strips=False forces pixels even on eligible geometry
    assert cfg.for_conv(16, width=16, k=3, stride=1, padding=1,
                        strips=False).blk_m == 1


def test_for_conv_rejects_degrading_strip_request():
    """strips=True on geometry that would silently fall back to pixel
    granularity must raise with the failing rule, not degrade."""
    cfg = engine.EngineConfig()
    with pytest.raises(ValueError, match="stride"):
        cfg.for_conv(16, width=24, k=3, stride=3, padding=1, strips=True)
    with pytest.raises(ValueError, match="output width"):
        cfg.for_conv(16, width=16, k=3, stride=4, padding=1, strips=True)
    with pytest.raises(ValueError, match="not a multiple"):
        cfg.for_conv(16, width=12, k=3, stride=1, padding=1, strips=True)
    with pytest.raises(ValueError, match="output width"):
        cfg.for_conv(16, width=16, k=3, stride=1, padding=0, strips=True)
    with pytest.raises(ValueError, match="output width"):
        cfg.for_conv(16, width=8, k=3, stride=2, padding=1, strips=True)
    with pytest.raises(ValueError, match="width= and k="):
        cfg.for_conv(16, strips=True)
    # eligible geometry passes and picks strips — every validated stride
    assert cfg.for_conv(16, width=16, k=3, stride=1, padding=1,
                        strips=True).blk_m == engine.STRIP_W
    assert cfg.for_conv(16, width=16, k=3, stride=2, padding=1,
                        strips=True).blk_m == engine.STRIP_W
    assert cfg.for_conv(16, width=32, k=3, stride=4, padding=1,
                        strips=True).blk_m == engine.STRIP_W


# ---------------------------------------------------------------------------
# fallback boundaries: W % 8 != 0, misaligned downsampled width, stride 4 —
# visible, never silent
# ---------------------------------------------------------------------------

def test_strip_stream_stride2_misaligned_out_falls_back_visibly():
    """Stride 2 itself is strip-eligible now, but a downsampled output
    width that doesn't tile strips (here 8 -> 4) must still take the
    visible decode fallback."""
    x = _fired(3, (1, 6, 8, 4))
    r = np.random.default_rng(3)
    wgt = jnp.asarray(r.normal(size=(3, 3, 4, 5)).astype(np.float32))
    cfg = engine.EngineConfig(backend="block", blk_k=4)
    s = engine.fire_conv(x, cfg, blk_m=engine.STRIP_W)   # twin kept: free decode
    with engine.trace_dispatch() as recs:
        y = engine.conv2d(s, wgt, cfg=cfg, stride=2, padding=1)
    assert any(rec.get("fallback_decode") and rec.get("strip")
               for rec in recs), recs
    ref = dense_conv2d(x, wgt, stride=2, padding=1)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=2e-4,
                               rtol=2e-4)


def test_strip_stream_stride3_falls_back_visibly():
    """Strides beyond STRIP_STRIDES (3: unvalidated) stay a named-rule
    fallback even on geometry whose widths would tile (W=24 -> OW=8)."""
    x = _fired(13, (1, 9, 24, 4))
    r = np.random.default_rng(13)
    wgt = jnp.asarray(r.normal(size=(3, 3, 4, 8)).astype(np.float32))
    cfg = engine.EngineConfig(backend="block", blk_k=4)
    s = engine.fire_conv(x, cfg, blk_m=engine.STRIP_W)
    with engine.trace_dispatch() as recs:
        y = engine.conv2d(s, wgt, cfg=cfg, stride=3, padding=1)
    assert any(rec.get("fallback_decode") and rec.get("strip")
               for rec in recs), recs
    ref = dense_conv2d(x, wgt, stride=3, padding=1)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=2e-4,
                               rtol=2e-4)


def test_strip_stream_stride4_misaligned_out_falls_back_visibly():
    """Stride 4 is strip-eligible now, but a downsampled output width that
    doesn't tile strips (here 16 -> 4) must still take the visible decode
    fallback."""
    x = _fired(13, (1, 9, 16, 4))
    r = np.random.default_rng(13)
    wgt = jnp.asarray(r.normal(size=(3, 3, 4, 8)).astype(np.float32))
    cfg = engine.EngineConfig(backend="block", blk_k=4)
    s = engine.fire_conv(x, cfg, blk_m=engine.STRIP_W)
    with engine.trace_dispatch() as recs:
        y = engine.conv2d(s, wgt, cfg=cfg, stride=4, padding=1)
    assert any(rec.get("fallback_decode") and rec.get("strip")
               for rec in recs), recs
    ref = dense_conv2d(x, wgt, stride=4, padding=1)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=2e-4,
                               rtol=2e-4)


# ---------------------------------------------------------------------------
# degenerate stride-2 geometries: short-circuit or fall back visibly, never
# crash
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["block", "pallas"])
def test_stride2_zero_event_stream(backend):
    """An all-dead feature map rides the fused stride-2 path with zero live
    events: every subtap idles and the result is exactly the bias plane."""
    x = jnp.zeros((1, 8, 16, 4), jnp.float32)
    r = np.random.default_rng(21)
    wgt = jnp.asarray(r.normal(size=(3, 3, 4, 8)).astype(np.float32))
    bias = jnp.asarray(r.normal(size=(8,)).astype(np.float32))
    cfg = engine.EngineConfig(backend=backend, blk_m=1, blk_k=4, blk_n=4)
    strip = engine.fire_conv(x, cfg, blk_m=engine.STRIP_W, keep_dense=False)
    assert int(strip.num_events) == 0
    with engine.trace_dispatch() as recs:
        y = engine.conv2d(strip, wgt, bias, cfg=cfg, stride=2, padding=1)
    assert any(rec.get("strip") and rec.get("chained") for rec in recs), recs
    assert not any(rec.get("fallback_decode") for rec in recs)
    want = jnp.broadcast_to(bias, (1, 4, 8, 8))
    assert bool(jnp.all(y == want))


@pytest.mark.parametrize("backend", ["block", "pallas"])
def test_stride4_zero_event_stream(backend):
    """An all-dead feature map rides the fused stride-4 path with zero live
    events: every compacted subtap idles and the result is exactly the
    bias plane."""
    x = jnp.zeros((1, 8, 32, 4), jnp.float32)
    r = np.random.default_rng(25)
    wgt = jnp.asarray(r.normal(size=(3, 3, 4, 8)).astype(np.float32))
    bias = jnp.asarray(r.normal(size=(8,)).astype(np.float32))
    cfg = engine.EngineConfig(backend=backend, blk_m=1, blk_k=4, blk_n=4)
    strip = engine.fire_conv(x, cfg, blk_m=engine.STRIP_W, keep_dense=False)
    assert int(strip.num_events) == 0
    with engine.trace_dispatch() as recs:
        y = engine.conv2d(strip, wgt, bias, cfg=cfg, stride=4, padding=1)
    assert any(rec.get("strip") and rec.get("chained") for rec in recs), recs
    assert not any(rec.get("fallback_decode") for rec in recs)
    want = jnp.broadcast_to(bias, (1, 2, 8, 8))
    assert bool(jnp.all(y == want))


def test_stride4_odd_downsampled_width_falls_back_visibly():
    """(24 - 3)//4 + 1 = 6: W misaligned after stride-4 downsampling cannot
    tile strips — named output-width rule, visible decode, correct
    result."""
    reason = engine.strip_ineligible_reason(24, 3, 4, 0)
    assert reason is not None and "output width 6" in reason
    x = _fired(26, (1, 7, 24, 4))
    r = np.random.default_rng(26)
    wgt = jnp.asarray(r.normal(size=(3, 3, 4, 8)).astype(np.float32))
    cfg = engine.EngineConfig(backend="block", blk_k=4)
    s = engine.fire_conv(x, cfg, blk_m=engine.STRIP_W)
    with engine.trace_dispatch() as recs:
        y = engine.conv2d(s, wgt, cfg=cfg, stride=4, padding=0)
    assert any(rec.get("fallback_decode") and rec.get("strip")
               for rec in recs), recs
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(dense_conv2d(x, wgt, stride=4)),
                               atol=2e-4, rtol=2e-4)


def test_stride2_empty_batch_short_circuits():
    """B == 0 never reaches a backend (Pallas must not see 0-extent
    launches): exact empty output with the stride-aware out shape."""
    stream = engine.EventStream.empty(
        (0, 4), blk_m=engine.STRIP_W, blk_k=4,
        logical_shape=(0, 8, 16, 4))
    wgt = jnp.ones((3, 3, 4, 8), jnp.float32)
    cfg = engine.EngineConfig(backend="pallas", blk_k=4)
    y = engine.conv2d(stream, wgt, cfg=cfg, stride=2, padding=1)
    assert y.shape == (0, 4, 8, 8)


def test_stride2_odd_downsampled_width_falls_back_visibly():
    """(16 - 3)//2 + 1 = 7: W odd after downsampling cannot tile strips —
    named output-width rule, visible decode, correct result."""
    reason = engine.strip_ineligible_reason(16, 3, 2, 0)
    assert reason is not None and "output width 7" in reason
    x = _fired(22, (1, 7, 16, 4))
    r = np.random.default_rng(22)
    wgt = jnp.asarray(r.normal(size=(3, 3, 4, 8)).astype(np.float32))
    cfg = engine.EngineConfig(backend="block", blk_k=4)
    s = engine.fire_conv(x, cfg, blk_m=engine.STRIP_W)
    with engine.trace_dispatch() as recs:
        y = engine.conv2d(s, wgt, cfg=cfg, stride=2, padding=0)
    assert any(rec.get("fallback_decode") and rec.get("strip")
               for rec in recs), recs
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(dense_conv2d(x, wgt, stride=2)),
                               atol=2e-4, rtol=2e-4)


def test_stride2_padding_beyond_half_window_falls_back_visibly():
    """p > k//2 at stride 2 (geometry passing every other rule): named
    padding rule, for_conv(strips=True) raises, stream decodes visibly."""
    # W=8, k=1, p=4, s=2: out_w = (8 + 8 - 1)//2 + 1 = 8 — only the
    # padding rule rejects it.
    reason = engine.strip_ineligible_reason(8, 1, 2, 4, co=8)
    assert reason is not None and "padding" in reason
    with pytest.raises(ValueError, match="padding"):
        engine.EngineConfig().for_conv(4, width=8, k=1, stride=2, padding=4,
                                       strips=True)
    x = _fired(23, (1, 6, 8, 4))
    r = np.random.default_rng(23)
    wgt = jnp.asarray(r.normal(size=(1, 1, 4, 8)).astype(np.float32))
    cfg = engine.EngineConfig(backend="block", blk_k=4)
    s = engine.fire_conv(x, cfg, blk_m=engine.STRIP_W)
    with engine.trace_dispatch() as recs:
        y = engine.conv2d(s, wgt, cfg=cfg, stride=2, padding=4)
    assert any(rec.get("fallback_decode") and rec.get("strip")
               for rec in recs), recs
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(dense_conv2d(x, wgt, stride=2, padding=4)),
        atol=2e-4, rtol=2e-4)
    # boundary: padding == k//2 stays eligible at stride 2
    assert engine.strip_eligible(16, 3, 2, 1, co=8)


def test_stride2_1x1_projection_misaligned_falls_back_visibly():
    """1x1/stride-2 projection over W=8 downsamples to 4 — short-circuits
    to the visible decode; the W=16 twin rides the fused path (ELIGIBLE)."""
    x = _fired(24, (1, 6, 8, 4))
    r = np.random.default_rng(24)
    wgt = jnp.asarray(r.normal(size=(1, 1, 4, 8)).astype(np.float32))
    cfg = engine.EngineConfig(backend="block", blk_k=4)
    s = engine.fire_conv(x, cfg, blk_m=engine.STRIP_W)
    with engine.trace_dispatch() as recs:
        y = engine.conv2d(s, wgt, cfg=cfg, stride=2, padding=0)
    assert any(rec.get("fallback_decode") and rec.get("strip")
               for rec in recs), recs
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(dense_conv2d(x, wgt, stride=2)),
        atol=2e-4, rtol=2e-4)


def test_fire_conv_strip_requires_aligned_width():
    x = _fired(4, (1, 4, 12, 3))
    with pytest.raises(AssertionError):
        engine.fire_conv(x, engine.EngineConfig(), blk_m=engine.STRIP_W)
    with pytest.raises(AssertionError):
        engine.EventStream.encode_nhwc(x, blk_k=3, blk_m=engine.STRIP_W)


def test_conv_downsampled_structure():
    """Pools become stride-2, channel-preserving conv blocks; everything
    else (and the FC head sizing, via _trace_shapes) is untouched."""
    spec = conv_downsampled(VGG16)
    assert spec.name == "vgg16_ds"
    assert not any(isinstance(l, PoolSpec) for l in spec.layers)
    ds = [l for l in spec.layers
          if isinstance(l, ConvSpec) and l.stride == 2]
    assert [d.out_ch for d in ds] == [64, 128, 256, 512, 512]
    assert all(d.k == 3 and d.padding == 1 for d in ds)


def test_downsampling_mini_net_fuses_stride2_layer():
    """conv -> stride-2 conv -> conv: the middle layer consumes its
    producer's strip stream on the fused stride-2 path (no fallback), and
    the chained forward stays bit-identical to the round-trip twin."""
    spec = CNNSpec("mini_ds", 16, 3,
                   (ConvSpec(8, 3, 1, 1),     # W 16 -> 16, strip producer
                    ConvSpec(8, 3, 2, 1),     # W 16 -> 8: fused stride-2
                    ConvSpec(8, 3, 1, 1),     # W 8 -> 8: fused stride-1
                    FCSpec(10)), num_classes=10)
    params = init_cnn_params(KEY, spec, weight_sparsity=0.5)
    x = jax.nn.relu(jax.random.normal(KEY, (2, 16, 16, 3)))
    with engine.trace_dispatch() as recs:
        ym = cnn_forward(params, x, spec, mnf=True, chain=True)
    s2 = [rec for rec in recs if rec.get("strip") and rec.get("chained")
          and rec.get("stride") == 2]
    s1 = [rec for rec in recs if rec.get("strip") and rec.get("chained")
          and rec.get("stride") == 1]
    # conv1 strip-encodes the dense image (input_encode), so both stride-1
    # layers fuse alongside the stride-2 one
    assert len(s2) == 1 and len(s1) == 2, recs
    assert not any(rec.get("fallback_decode") for rec in recs)
    yr = cnn_forward(params, x, spec, mnf=True, chain=False)
    assert bool(jnp.all(ym == yr)), "chained != round-trip with stride-2 strip"
    yd = cnn_forward(params, x, spec, mnf=False)
    np.testing.assert_allclose(np.asarray(ym), np.asarray(yd), atol=5e-3,
                               rtol=5e-3)


def test_ds_workloads_report_ten_fused_launches():
    """The paper workloads' conv-downsampled variants (pools -> stride-2
    conv blocks) keep >= 10 conv layers total on the fused strip path at
    the CPU harness sizes, with zero densify points on the chain — traced
    structurally (eval_shape: no numeric work)."""
    total_fused = 0
    for spec, size in ((VGG16_DS, 32), (ALEXNET_DS, 68)):
        spec = spec.scaled(size)
        assert not any(isinstance(l, PoolSpec) for l in spec.layers)
        params = jax.eval_shape(lambda k, s=spec: init_cnn_params(k, s), KEY)
        x = jax.ShapeDtypeStruct((1, size, size, spec.in_ch), jnp.float32)
        with engine.trace_dispatch() as recs:
            jax.eval_shape(lambda p, xx: cnn_forward(p, xx, spec, mnf=True,
                                                     chain=True), params, x)
        fused = [r for r in recs if r.get("strip") and r.get("chained")
                 and r.get("launches") == 1]
        assert not any(r.get("fallback_decode") or r.get("decode")
                       for r in recs), (spec.name, recs)
        if spec.name.startswith("vgg"):
            assert sum(1 for r in fused if r.get("stride") == 2) == 2
        total_fused += len(fused)
    assert total_fused >= 10, total_fused


def test_first_conv_input_encode_fuses_stride4_net_bitwise():
    """MINI_S4@32 (conv -> stride-4 conv -> conv): the chain strip-encodes
    the dense input image, so *every* conv — including the head — runs one
    fused launch (zero pixel-granular layers, no fallback), and the
    chained forward stays bit-identical to the per-tap round-trip twin."""
    spec = MINI_S4
    params = init_cnn_params(KEY, spec, weight_sparsity=0.5)
    x = jax.nn.relu(jax.random.normal(KEY, (2, 32, 32, 3)))
    with engine.trace_dispatch() as recs:
        ym = cnn_forward(params, x, spec, mnf=True, chain=True)
    strips = [rec for rec in recs if rec.get("strip") and rec.get("chained")]
    pertap = [rec for rec in recs if rec.get("chained")
              and rec["op"] == "conv2d" and not rec.get("strip")]
    assert len(strips) == 3 and not pertap, recs
    assert all(rec.get("launches") == 1 for rec in strips)
    s4 = [rec for rec in strips if rec.get("stride") == 4]
    assert len(s4) == 1, recs
    assert (s4[0]["subtaps"], s4[0]["subtaps_worst"]) == (39, 45)
    assert not any(rec.get("fallback_decode") for rec in recs)
    yr = cnn_forward(params, x, spec, mnf=True, chain=False)
    assert bool(jnp.all(ym == yr)), "chained != round-trip with stride-4 head"
    yd = cnn_forward(params, x, spec, mnf=False)
    np.testing.assert_allclose(np.asarray(ym), np.asarray(yd), atol=5e-3,
                               rtol=5e-3)


def test_alexnet_ff_fully_fused_structurally():
    """ALEXNET_FF@256: the fully-fused AlexNet variant — all 8 convs
    (stride-4 k=11 head included, strip-encoded straight off the dense
    image) run fused on the chain, zero pixel-granular conv layers, zero
    fallbacks; conv1 reports its compacted 561/605 subtap plan, split
    into a few launches over output-strip chunks so each launch's plan
    fits SMEM (121 per-tap launches -> 16), every other layer one launch.
    Traced structurally (eval_shape: no numeric work)."""
    spec = ALEXNET_FF
    params = jax.eval_shape(lambda k: init_cnn_params(k, spec), KEY)
    x = jax.ShapeDtypeStruct((1, 256, 256, 3), jnp.float32)
    with engine.trace_dispatch() as recs:
        jax.eval_shape(lambda p, xx: cnn_forward(p, xx, spec, mnf=True,
                                                 chain=True), params, x)
    conv = [r for r in recs if r.get("op") == "conv2d" and r.get("chained")]
    strips = [r for r in conv if r.get("strip")]
    assert len(strips) == 8 and len(conv) == 8, recs
    assert all(r.get("launches") == 1 for r in strips
               if r.get("stride") != 4)
    assert not any(r.get("fallback_decode") or r.get("decode")
                   for r in recs), recs
    head = [r for r in strips if r.get("stride") == 4]
    assert len(head) == 1, recs
    assert (head[0]["subtaps"], head[0]["subtaps_worst"]) == (561, 605)
    assert head[0]["launches"] == 16
    # compacted inner grid <= k^2 + live straddle parts beyond one per tap
    for r in strips:
        assert r["subtaps"] <= r["subtaps_worst"]
        assert r["compaction"] <= 1.0


@pytest.mark.slow
def test_alexnet_ff_chained_bitwise():
    """Whole-net ALEXNET_FF@256 numerics: the fully-fused chain (stride-4
    k=11 head on the compacted 5-part straddle plan) is bit-identical to
    the per-tap round-trip twin."""
    spec = ALEXNET_FF
    params = init_cnn_params(KEY, spec, weight_sparsity=0.8)
    x = jax.nn.relu(jax.random.normal(KEY, (1, 256, 256, 3)))
    ym = cnn_forward(params, x, spec, mnf=True, chain=True)
    yr = cnn_forward(params, x, spec, mnf=True, chain=False)
    assert bool(jnp.all(ym == yr))


@pytest.mark.slow
def test_vgg16_ds_chained_bitwise():
    """Whole-net VGG16_DS@32: every downsampling conv on the chain, chained
    == round-trip bit-for-bit across the stride-2 strip launches."""
    spec = VGG16_DS.scaled(32)
    params = init_cnn_params(KEY, spec, weight_sparsity=0.5)
    x = jax.nn.relu(jax.random.normal(KEY, (2, 32, 32, 3)))
    ym = cnn_forward(params, x, spec, mnf=True, chain=True)
    yr = cnn_forward(params, x, spec, mnf=True, chain=False)
    assert bool(jnp.all(ym == yr))


@pytest.mark.slow
def test_mixed_strip_pixel_network_bitwise():
    """Widths crossing the 8-boundary: strip and pixel conv layers mix on
    the chain, and the chained forward stays bit-identical to the per-tap
    round-trip twin across the fallback boundary."""
    spec = CNNSpec("edge", 12, 3,
                   (ConvSpec(8, 3, 1, 1),     # W 12 -> 12: ineligible (W%8)
                    ConvSpec(8, 5, 1, 0),     # W 12 -> 8: ineligible input
                    ConvSpec(8, 3, 1, 1),     # W 8 -> 8: strip-eligible
                    ConvSpec(8, 3, 1, 1),     # W 8 -> 8: strip-eligible
                    FCSpec(10)), num_classes=10)
    params = init_cnn_params(KEY, spec, weight_sparsity=0.5)
    x = jax.nn.relu(jax.random.normal(KEY, (2, 12, 12, 3)))
    with engine.trace_dispatch() as recs:
        ym = cnn_forward(params, x, spec, mnf=True, chain=True)
    strips = [rec for rec in recs if rec.get("strip") and rec.get("chained")]
    pertap = [rec for rec in recs if rec.get("chained")
              and rec["op"] == "conv2d" and not rec.get("strip")]
    assert len(strips) == 2 and len(pertap) == 1, recs
    assert not any(rec.get("fallback_decode") for rec in recs)
    yr = cnn_forward(params, x, spec, mnf=True, chain=False)
    assert bool(jnp.all(ym == yr)), "chained != round-trip across boundary"
    yd = cnn_forward(params, x, spec, mnf=False)
    np.testing.assert_allclose(np.asarray(ym), np.asarray(yd), atol=5e-3,
                               rtol=5e-3)


# ---------------------------------------------------------------------------
# strip encoding / gather primitives
# ---------------------------------------------------------------------------

def test_strip_encode_nhwc_roundtrip_and_grid():
    x = _fired(5, (2, 3, 16, 5))
    s = engine.EventStream.encode_nhwc(x, blk_k=4, blk_m=engine.STRIP_W,
                                       keep_dense=False)
    assert s.blk_m == engine.STRIP_W
    np.testing.assert_array_equal(np.asarray(s.dense_nhwc()), np.asarray(x))
    p = engine.EventStream.encode_nhwc(x, blk_k=4, blk_m=1, keep_dense=False)
    assert s.events.block_idx.shape[0] * engine.STRIP_W \
        == p.events.block_idx.shape[0]


def test_gather_row_strips_moves_rows_exactly():
    x = _fired(6, (1, 2, 16, 4), sparsity=0.3)
    s = engine.EventStream.encode_nhwc(x, blk_k=4, blk_m=engine.STRIP_W,
                                       keep_dense=False)
    g = s.events.block_idx.shape[0]
    idx = jnp.arange(g, dtype=jnp.int32)
    live = jnp.ones((g,), bool)
    for d in (-3, 0, 2, 5):
        gat = ev.gather_row_strips(s.events, idx, live, d)
        dec = ev.decode_block_events(gat, blk_m=engine.STRIP_W, blk_k=4,
                                     m=g * engine.STRIP_W, k=4)
        flat = np.asarray(x).reshape(-1, 4)
        want = np.zeros_like(flat)
        for strip in range(g):
            for i in range(engine.STRIP_W):
                jsrc = i + d
                if 0 <= jsrc < engine.STRIP_W:
                    want[strip * 8 + i] = flat[strip * 8 + jsrc]
        np.testing.assert_array_equal(np.asarray(dec), want)


def test_scalar_event_rows_twin_free_counts():
    x = _fired(7, (2, 3, 8, 5))
    s = engine.fire_conv(x, engine.EngineConfig(backend="block", blk_k=4),
                         blk_m=engine.STRIP_W, keep_dense=False)
    want = np.sum(np.abs(np.asarray(x)) > 0, axis=-1).reshape(-1)
    np.testing.assert_array_equal(np.asarray(s.per_row_scalar_events()),
                                  want.astype(np.float32))
    assert float(s.num_scalar_events) == float(want.sum())


def test_fused_conv_plan_grid_reduction():
    plan = fused_conv_plan((2, 8, 16, 8), 3, 1, nkb=2)
    assert plan["launches_fused"] == 1 and plan["launches_per_tap"] == 9
    assert plan["event_grid_pixel"] == 8 * plan["event_grid_strip"]
    assert plan["grid_reduction"] == 8.0
    assert plan["gathered_groups_fused"] == 0
    # the inner grid axis is sized by the *compacted* subtap count
    assert (plan["subtaps"], plan["subtaps_worst"]) == (15, 18)
    assert plan["grid_fused"][1] == plan["subtaps"]
    assert plan["compaction"] == 15 / 18
    plan4 = fused_conv_plan((1, 11, 32, 3), 11, 4, nkb=1, stride=4)
    assert (plan4["subtaps"], plan4["subtaps_worst"]) == (561, 605)
    assert plan4["grid_fused"][1] == 561


def test_remap_select_ladder_bitwise_equals_matmul():
    """The two in-tile row-remap lowerings of the fused kernel — the 0/1
    selection matmul (default, MXU) and the vselect ladder
    (remap="select", VPU) — move rows identically, bit for bit, at every
    validated stride.  The DESIGN.md §6 Mosaic cost verdict rests on this
    equivalence."""
    from repro.kernels.event_conv import fused_event_conv2d
    for shape in ((2, 6, 8, 5, 8, 3, 1, 1), (1, 8, 16, 5, 8, 5, 2, 2),
                  (1, 11, 32, 3, 8, 11, 4, 4)):
        b, h, w0, ci, co, k, p, s = shape
        x = _fired(sum(shape), (b, h, w0, ci))
        r = np.random.default_rng(2)
        wgt = jnp.asarray(r.normal(size=(k, k, ci, co)).astype(np.float32))
        cfg = engine.EngineConfig(backend="pallas", blk_k=4)
        stream = engine.fire_conv(x, cfg, blk_m=engine.STRIP_W,
                                  keep_dense=False)
        ym = fused_event_conv2d(stream, wgt, stride=s, padding=p, blk_n=8,
                                interpret=True, remap="matmul")
        ys = fused_event_conv2d(stream, wgt, stride=s, padding=p, blk_n=8,
                                interpret=True, remap="select")
        assert bool(jnp.all(ym == ys)), shape


# ---------------------------------------------------------------------------
# dead-subtap compaction: plan columns == live subtaps, no dead column
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k,p,s,want", [
    (3, 1, 1, (15, 18)),    # stride 1: r==0 taps lose their second half
    (5, 2, 2, (65, 75)),    # stride 2: r<2 taps lose their third part
    (3, 1, 4, (39, 45)),    # stride 4 ds block
    (11, 4, 4, (561, 605)),  # AlexNet conv1 class
    (1, 0, 2, (2, 3)),      # 1x1 projection
    (9, 4, 1, (153, 162)),  # widest stride-1 filter
])
def test_strip_subtap_counts_pinned(k, p, s, want):
    assert ev.strip_subtap_counts(k, p, s) == want
    live, worst = want
    assert worst == ev.strip_parts(s) * k * k
    assert live <= worst


@pytest.mark.parametrize("k,p,s,w", [
    (3, 1, 1, 16), (5, 2, 2, 16), (3, 1, 4, 32), (11, 4, 4, 32),
    (1, 0, 2, 16), (9, 4, 2, 16),
])
def test_strip_tap_map_compacted_no_dead_columns(k, p, s, w):
    """Every plan column sources at least one output row (strip_shift_live)
    and the column count equals strip_subtap_counts — dead straddle parts
    are dropped at plan time, not masked at run time."""
    shape = (1, 8, w, 4)
    src, live, shift, tap = ev.strip_tap_map(shape, k, p, s)
    t = src.shape[1]
    assert t == ev.strip_subtap_counts(k, p, s)[0]
    assert shift.shape == (t,) and tap.shape == (t,)
    for d in shift:
        assert ev.strip_shift_live(int(d), s), (int(d), s)
    # each tap appears with at most strip_parts(s) live parts
    per_tap = Counter(int(x) for x in tap)
    assert max(per_tap.values()) <= ev.strip_parts(s)
    assert set(per_tap) == set(range(k * k))
