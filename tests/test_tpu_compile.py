"""The main path's Pallas kernels compile for a v5e chip at the served
AlexNet@224 and VGG16@224 widths.

Interpret mode accepts block shapes and scalar-prefetch tables that the
chip's compiler refuses (tiling alignment, the 1 MiB SMEM), so these
tests compile each kernel of the served AlexNet@224 path with interpret
off for a *described* v5e chip — no chip needed, nothing runs — at the
largest served test bucket.  The topology is described inside a module
fixture only: describing it loads the TPU library, which one process at a
time may hold (DESIGN.md §4).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import events as ev
from repro.engine import EventStream
from repro.kernels.event_conv import fused_event_conv2d
from repro.kernels.event_matmul.kernel import event_matmul_pallas
from repro.kernels.event_pool.kernel import (event_pool_pallas,
                                             event_pool_window_pallas)
from repro.kernels.fire_compact.kernel import fire_compact_pallas
from repro.kernels.smem import SMEM_PLAN_WORDS, plan_chunks

BATCH = 8            # the serving tier's second bucket


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    from repro.serving.aot import persistent_cache_off
    # A compile for a described chip cannot be read back from the
    # persistent cache without one: keep these compiles out of it.
    with persistent_cache_off():
        yield SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes):
    """Lower + compile ``fn`` for the described chip; return the HLO text."""
    specs = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*specs).compile().as_text()


def test_fire_compact_conv1_width(one_chip):
    """conv1's fire: (55·55·B, 96) accumulator, one (1, 96) event tile a
    pixel (the chain's K block is min(blk_k, C))."""
    m = 55 * 55 * BATCH

    def fire(acc):
        return fire_compact_pallas(acc, blk_m=1, blk_k=96, interpret=False)

    assert "tpu_custom_call" in _compile(fire, one_chip,
                                         ((m, 96), jnp.float32))


@pytest.mark.parametrize("m,k,blk_m,blk_k", [
    (BATCH, 4096, 8, 128),    # fc6 / fc7 fire
    (BATCH, 1024, 8, 128),    # fc8's 1000 outputs, padded
    (BATCH, 100, 8, 100),     # one K block (LeNet-300-100's last hidden)
])
def test_fire_compact_fc_widths(one_chip, m, k, blk_m, blk_k):
    def fire(acc):
        return fire_compact_pallas(acc, blk_m=blk_m, blk_k=blk_k,
                                   interpret=False)

    assert "tpu_custom_call" in _compile(fire, one_chip,
                                         ((m, k), jnp.float32))


def test_event_matmul_conv1_tap(one_chip):
    """One of conv1's 121 per-tap event matmuls: 55·55·B pixel row groups,
    one 3-channel K block each, against the tap's (3, 96) weights padded
    to one 128-wide N tile."""
    g = 55 * 55 * BATCH

    def tap(vals, idx, cnt, w):
        return event_matmul_pallas(vals, idx, cnt, w, blk_n=128,
                                   interpret=False)

    assert "tpu_custom_call" in _compile(
        tap, one_chip, ((g, 1, 1, 3), jnp.float32), ((g, 1), jnp.int32),
        ((g,), jnp.int32), ((3, 128), jnp.float32))


@pytest.mark.parametrize("h,c", [(27, 256), (13, 256)],
                         ids=["pool2", "pool3"])
def test_event_pool_wide(one_chip, h, c):
    """pool2 / pool3: 3x3/2 over a 256-channel pixel stream, two 128-wide
    K blocks a pixel — (1, 2, 128) out blocks, (2, 128) scratch."""
    shape = (BATCH, h, h, c)
    src, row, _ = ev.pool_window_map(shape, 3, 2, 1)
    g, e = BATCH * h * h, c // 128

    def pool(vals, idx, cnt):
        return event_pool_pallas(vals, idx, row, src, cnt, nkb=e,
                                 interpret=False)

    assert "tpu_custom_call" in _compile(
        pool, one_chip, ((g, e, 1, 128), jnp.float32), ((g, e), jnp.int32),
        (src.shape, jnp.int32))


def test_event_pool_pool1(one_chip):
    """pool1: 3x3/2 over conv1's 55x55x96 pixel stream, one 96-wide K
    block a pixel."""
    shape = (BATCH, 55, 55, 96)
    src, row, _ = ev.pool_window_map(shape, 3, 2, 1)
    g, e = BATCH * 55 * 55, 1
    assert len(plan_chunks(src.shape[0], 3 * src.shape[1], src=src,
                           src_words=e)) > 1     # the SMEM split is exercised

    def pool(vals, idx, cnt):
        return event_pool_pallas(vals, idx, row, src, cnt, nkb=e,
                                 interpret=False)

    assert "tpu_custom_call" in _compile(
        pool, one_chip, ((g, e, 1, 96), jnp.float32), ((g, e), jnp.int32),
        (src.shape, jnp.int32))


@pytest.mark.parametrize("hw,ci,co", [
    (27, 96, 256),     # conv2: one (1, 96) a-tile, (96, 128) weight tiles
    (13, 256, 384),    # conv3: two (1, 128) a-tiles a pixel
    (13, 384, 384),    # conv4: three
    (13, 384, 256),    # conv5
], ids=["conv2", "conv3", "conv4", "conv5"])
def test_event_matmul_conv_tap(one_chip, hw, ci, co):
    """One per-tap event matmul of a chained conv: B·H·W pixel row groups
    of min(128, CI)-wide K blocks against the tap's (CI, CO) weights."""
    g, bk = BATCH * hw * hw, min(128, ci)
    e = ci // bk

    def tap(vals, idx, cnt, w):
        return event_matmul_pallas(vals, idx, cnt, w, blk_n=128,
                                   interpret=False)

    assert "tpu_custom_call" in _compile(
        tap, one_chip, ((g, e, 1, bk), jnp.float32), ((g, e), jnp.int32),
        ((g,), jnp.int32), ((ci, co), jnp.float32))


def test_event_matmul_fc6(one_chip):
    """fc6: the re-tiled 9216-wide conv stream (72 K blocks of 128) into
    4096 outputs."""
    e = 9216 // 128

    def fc(vals, idx, cnt, w):
        return event_matmul_pallas(vals, idx, cnt, w, blk_n=128,
                                   interpret=False)

    assert "tpu_custom_call" in _compile(
        fc, one_chip, ((BATCH, e, 1, 128), jnp.float32),
        ((BATCH, e), jnp.int32), ((BATCH,), jnp.int32),
        ((9216, 4096), jnp.float32))


@pytest.mark.parametrize("ci", [3, 64], ids=["conv1_1", "conv1_2"])
def test_event_conv_fused_vgg16_224(one_chip, ci):
    """VGG16's first two convs at b1: a 224x224 strip stream (6272 strips
    of 8 pixels, one K block a strip) into 64 channels through the fused
    strip kernel.  conv1_1's stream is the 3-channel input image, whose
    3-wide K block the kernel pads to an 8-row weight tile."""
    shape = (1, 224, 224, ci)
    g = 224 * 224 // ev.STRIP_W

    def conv(vals, idx, cnt, w):
        stream = EventStream(
            events=ev.BlockEvents(vals, idx, cnt, num_k_blocks=1),
            fired=None, shape=(224 * 224, ci), blk_m=ev.STRIP_W, blk_k=ci,
            logical_shape=shape)
        return fused_event_conv2d(stream, w, padding=1, blk_n=64,
                                  interpret=False)

    assert "tpu_custom_call" in _compile(
        conv, one_chip, ((g, 1, ev.STRIP_W, ci), jnp.float32),
        ((g, 1), jnp.int32), ((g,), jnp.int32), ((3, 3, ci, 64), jnp.float32))


def test_event_pool_window_vgg16_pool1(one_chip):
    """VGG16's pool1 at b1: 2x2/2 window-major over conv1_2's 224x224x64
    strip stream, one 64-wide K block a strip."""
    shape = (1, 224, 224, 64)
    src, _, shift, _ = ev.pool_strip_map(shape, 2, 2)
    g = 224 * 224 // ev.STRIP_W

    def pool(vals, idx, cnt):
        return event_pool_window_pallas(vals, idx, shift, src, cnt, nkb=1,
                                        row_stride=2, interpret=False)

    assert "tpu_custom_call" in _compile(
        pool, one_chip, ((g, 1, ev.STRIP_W, 64), jnp.float32),
        ((g, 1), jnp.int32), (src.shape, jnp.int32))


def test_vgg16_224_forward_b1(one_chip):
    """The whole chained VGG16@224 forward at b1 on the Pallas kernels:
    strip convs (the padded 3-channel head among them), window-major and
    per-event pools, per-tap pixel convs and the re-tiled FC head."""
    from repro import engine
    from repro.models.cnn import VGG16, init_cnn_params, make_cnn_forward
    cfg = engine.EngineConfig(backend="pallas", interpret=False)
    params = jax.eval_shape(lambda: init_cnn_params(jax.random.PRNGKey(0),
                                                    VGG16))
    specs = [(p.shape, p.dtype) for p in jax.tree.leaves(params)]
    treedef = jax.tree.structure(params)

    def fwd(x, *leaves):
        return make_cnn_forward(VGG16, engine_cfg=cfg)(
            jax.tree.unflatten(treedef, leaves), x)

    with engine.trace_dispatch() as recs:
        text = _compile(fwd, one_chip, ((1, 224, 224, 3), jnp.float32),
                        *specs)
    assert "mnf_event_conv_fused" in text and "mnf_event_pool_window" in text
    assert not any(r.get("fallback_decode") for r in recs)


@pytest.mark.parametrize("n_rows,row_words,src_words", [
    (1, 10, 0), (24200, 13, 0), (5832, 27, 12), (100, SMEM_PLAN_WORDS, 0)])
def test_plan_chunks_cover_rows_within_budget(n_rows, row_words, src_words):
    """Chunks tile [0, n_rows) contiguously and each fits the budget."""
    src = (np.arange(n_rows)[:, None] * 4 + np.arange(9)[None]
           if src_words else None)
    chunks = plan_chunks(n_rows, row_words, src=src, src_words=src_words)
    assert chunks[0][0] == 0 and chunks[-1][1] == n_rows
    for (r0, r1, s0, s1), nxt in zip(chunks, chunks[1:] + [None]):
        assert r1 > r0 and (nxt is None or nxt[0] == r1)
        if src is not None:
            assert (s0, s1) == (src[r0:r1].min(), src[r0:r1].max() + 1)
        assert (r1 - r0) * row_words + (s1 - s0) * src_words \
            <= SMEM_PLAN_WORDS


def test_plan_chunks_refuses_an_oversized_row():
    with pytest.raises(ValueError, match="SMEM budget"):
        plan_chunks(4, SMEM_PLAN_WORDS + 1)
