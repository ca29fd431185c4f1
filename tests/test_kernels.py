"""Pallas kernels vs pure-jnp oracles (interpret=True), shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import (event_matmul, event_matmul_int8,
                           event_matmul_int8_ref, event_matmul_ref,
                           fire_and_encode, fire_compact, fire_compact_ref,
                           wkv6, wkv6_ref)


@pytest.mark.parametrize("m,k,n,blk_m,blk_k,blk_n", [
    (8, 128, 128, 8, 128, 128),
    (16, 256, 256, 8, 128, 128),
    (32, 512, 384, 8, 128, 128),
    (24, 384, 200, 8, 128, 100),     # padded N
    (7, 130, 65, 8, 128, 128),       # everything ragged
])
@pytest.mark.parametrize("sparsity", [0.0, 0.8, 0.97])
def test_event_matmul_sweep(rng, m, k, n, blk_m, blk_k, blk_n, sparsity):
    a = (rng.normal(size=(m, k)) * (rng.random((m, k)) > sparsity))
    a = jnp.asarray(a.astype(np.float32))
    w = jnp.asarray(rng.normal(size=(k, n)).astype(np.float32))
    y = event_matmul(a, w, blk_m=blk_m, blk_k=blk_k, blk_n=blk_n,
                     interpret=True)
    import repro.core.events as ev
    ap = ev.pad_to_block_multiple(ev.pad_to_block_multiple(a, blk_m, 0),
                                  blk_k, 1)
    wp = ev.pad_to_block_multiple(w, blk_k, 0)
    ref = event_matmul_ref(ap, wp, blk_m=blk_m, blk_k=blk_k)[:m, :n]
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-3,
                               rtol=1e-3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_event_matmul_dtypes(rng, dtype):
    a = jnp.asarray(rng.normal(size=(8, 128)), dtype)
    w = jnp.asarray(rng.normal(size=(128, 128)), dtype)
    y = event_matmul(a, w, interpret=True)
    ref = jnp.asarray(a, jnp.float32) @ jnp.asarray(w, jnp.float32)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               atol=1.5 if dtype == jnp.bfloat16 else 1e-3)


@pytest.mark.parametrize("m,k,n,blk_m,blk_k", [
    (8, 128, 64, 8, 128),
    (16, 64, 24, 8, 16),
    (5, 33, 10, 8, 16),              # ragged M and K
])
@pytest.mark.parametrize("sparsity", [0.0, 0.6, 1.0])
def test_event_matmul_int8_vs_ref(rng, m, k, n, blk_m, blk_k, sparsity):
    """The int8-value lowering (DESIGN.md §12): codes dequantize at tile
    load, accumulation is f32 — the kernel must match the dense oracle
    (dequant live tiles, then matmul) up to f32 accumulation order, with
    all-zero streams in-distribution."""
    from repro.core.quantize import calibrate, quantize

    a = (rng.normal(size=(m, k)) * (rng.random((m, k)) > sparsity))
    a = jnp.asarray(a.astype(np.float32))
    qp = calibrate(a)
    q = quantize(a, qp)
    w = jnp.asarray(rng.normal(size=(k, n)).astype(np.float32))
    y = event_matmul_int8(q, w, qp, blk_m=blk_m, blk_k=blk_k, blk_n=32,
                          interpret=True)
    import repro.core.events as ev
    qpad = ev.pad_to_block_multiple(ev.pad_to_block_multiple(q, blk_m, 0),
                                    blk_k, 1)
    wp = ev.pad_to_block_multiple(w, blk_k, 0)
    ref = event_matmul_int8_ref(qpad, wp, qp, blk_m=blk_m,
                                blk_k=blk_k)[:m, :n]
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-3,
                               rtol=1e-3)


def test_event_matmul_threshold_drops_tiles(rng):
    a = np.full((8, 256), 1e-4, np.float32)
    a[:, :128] = 1.0
    w = jnp.asarray(rng.normal(size=(256, 128)).astype(np.float32))
    y = event_matmul(jnp.asarray(a), w, threshold=1e-2, interpret=True)
    ref = event_matmul_ref(jnp.asarray(a), w, blk_m=8, blk_k=128,
                           threshold=1e-2)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-3)


@pytest.mark.parametrize("kw", [dict(), dict(threshold=0.3),
                                dict(magnitude=True, threshold=0.2),
                                dict(qscale=0.1)])
def test_fire_compact_modes(rng, kw):
    acc = jnp.asarray(rng.normal(size=(24, 260)).astype(np.float32))
    f, occ = fire_compact(acc, blk_m=8, blk_k=128, interpret=True, **kw)
    # ref works on padded shape; compare the unpadded region
    import repro.core.events as ev
    ap = ev.pad_to_block_multiple(ev.pad_to_block_multiple(acc, 8, 0), 128, 1)
    fr, occr = fire_compact_ref(ap, blk_m=8, blk_k=128, **kw)
    np.testing.assert_allclose(np.asarray(f), np.asarray(fr)[:24, :260])
    np.testing.assert_array_equal(np.asarray(occ), np.asarray(occr))


def test_fire_and_encode_pipeline(rng):
    acc = jnp.asarray(rng.normal(size=(16, 256)).astype(np.float32))
    fired, bev = fire_and_encode(acc, blk_m=8, blk_k=128, interpret=True)
    assert np.all(np.asarray(fired) >= 0)
    assert int(bev.counts.max()) <= 2


@pytest.mark.parametrize("b,h,t,d,chunk", [(1, 1, 16, 8, 4), (2, 3, 40, 16, 16),
                                           (1, 2, 33, 8, 8)])
def test_wkv6_vs_ref(rng, b, h, t, d, chunk):
    r = jnp.asarray(rng.normal(size=(b, h, t, d)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(b, h, t, d)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(b, h, t, d)).astype(np.float32))
    w = jnp.asarray(rng.uniform(0.2, 0.99, (b, h, t, d)).astype(np.float32))
    u = jnp.asarray(rng.normal(size=(h, d)).astype(np.float32))
    o, s = wkv6(r, k, v, w, u, chunk=chunk, interpret=True)
    orf, srf = jax.vmap(wkv6_ref, in_axes=(1, 1, 1, 1, 0),
                        out_axes=(1, 1))(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(o), np.asarray(orf), atol=1e-3)
    np.testing.assert_allclose(np.asarray(s), np.asarray(srf), atol=1e-3)


def test_wkv6_initial_state(rng):
    b, h, t, d = 1, 2, 12, 8
    mk = lambda: jnp.asarray(rng.normal(size=(b, h, t, d)).astype(np.float32))
    r, k, v = mk(), mk(), mk()
    w = jnp.asarray(rng.uniform(0.5, 0.99, (b, h, t, d)).astype(np.float32))
    u = jnp.asarray(rng.normal(size=(h, d)).astype(np.float32))
    s0 = jnp.asarray(rng.normal(size=(b, h, d, d)).astype(np.float32))
    o, s = wkv6(r, k, v, w, u, s0, chunk=4, interpret=True)
    orf, srf = jax.vmap(wkv6_ref, in_axes=(1, 1, 1, 1, 0, 1),
                        out_axes=(1, 1))(r, k, v, w, u, s0)
    np.testing.assert_allclose(np.asarray(o), np.asarray(orf), atol=1e-3)
    np.testing.assert_allclose(np.asarray(s), np.asarray(srf), atol=1e-3)


@pytest.mark.parametrize("b,t,d,n,d_blk,chunk", [
    (1, 16, 8, 4, 8, 4), (2, 40, 24, 4, 8, 16), (1, 33, 130, 8, 128, 8)])
def test_mamba_scan_vs_ref(rng, b, t, d, n, d_blk, chunk):
    from repro.kernels import mamba_scan, mamba_scan_ref
    da = jnp.asarray(rng.uniform(0.3, 0.99, (b, t, d, n)).astype(np.float32))
    dbx = jnp.asarray(rng.normal(size=(b, t, d, n)).astype(np.float32))
    c = jnp.asarray(rng.normal(size=(b, t, n)).astype(np.float32))
    y, h = mamba_scan(da, dbx, c, d_blk=d_blk, chunk=chunk, interpret=True)
    yr, hr = mamba_scan_ref(da, dbx, c)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-3,
                               rtol=1e-3)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr), atol=1e-3,
                               rtol=1e-3)


def test_mamba_scan_initial_state(rng):
    from repro.kernels import mamba_scan, mamba_scan_ref
    b, t, d, n = 2, 12, 8, 4
    da = jnp.asarray(rng.uniform(0.5, 0.99, (b, t, d, n)).astype(np.float32))
    dbx = jnp.asarray(rng.normal(size=(b, t, d, n)).astype(np.float32))
    c = jnp.asarray(rng.normal(size=(b, t, n)).astype(np.float32))
    h0 = jnp.asarray(rng.normal(size=(b, d, n)).astype(np.float32))
    y, h = mamba_scan(da, dbx, c, h0, d_blk=8, chunk=4, interpret=True)
    yr, hr = mamba_scan_ref(da, dbx, c, h0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-3)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr), atol=1e-3)


@pytest.mark.parametrize("op", ["matmul", "pool_pixel", "pool_window",
                                "conv_strip"])
def test_smem_chunked_launches_bitwise(monkeypatch, op):
    """A launch split into SMEM chunks (kernels.smem) computes every output
    tile in one launch in the same order: bitwise the one-launch result,
    with the extra launches counted in the trace."""
    from repro import engine
    from repro.kernels import smem
    from repro.kernels.event_matmul.kernel import event_matmul_launches

    r = np.random.default_rng(7)
    shape = (2, 8, 16, 12)
    x = jax.nn.relu(jnp.asarray(r.normal(size=shape), jnp.float32))
    cfg = engine.EngineConfig(backend="pallas", blk_m=1, blk_k=4)
    if op == "matmul":
        w = jnp.asarray(r.normal(size=(256, 128)), jnp.float32)
        a = x.reshape(16, 192)
        a = jnp.pad(a, ((0, 0), (0, 64)))

        def run():   # 16 one-row groups of 32 K-block events
            return (event_matmul(a, w, blk_m=1, blk_k=8, interpret=True),
                    event_matmul_launches(16, 32))
    else:
        wc = jnp.asarray(r.normal(size=(3, 3, 12, 8)), jnp.float32)

        def run():
            stream = engine.fire_conv(x, cfg, keep_dense=False,
                                      blk_m=1 if op == "pool_pixel" else 8)
            with engine.trace_dispatch() as recs:
                if op == "conv_strip":
                    y = engine.conv2d(stream, wc, cfg=cfg.for_conv(12),
                                      padding=1)
                else:
                    y = engine.maxpool2d(stream, 2, 2, cfg=cfg).dense_nhwc()
            return y, sum(rec.get("launches", 0) for rec in recs)

    whole, n_whole = run()
    monkeypatch.setattr(smem, "SMEM_PLAN_WORDS", 160)
    jax.clear_caches()
    split, n_split = run()
    assert bool(jnp.array_equal(whole, split)), op
    assert n_split > n_whole >= 1, (n_split, n_whole)
