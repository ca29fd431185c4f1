"""Serving tier: batcher invariants, AOT engine, padding bitwiseness.

The policy half (ContinuousBatcher) is pure host-side state, tested
without compiling anything; the engine half compiles one tiny pipeline
per bucket once (module-scoped fixture) and every test reuses those
executables.
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.mesh import (MeshCapacityError, checked_mesh,
                               make_serve_mesh)
from repro.serving import (ContinuousBatcher, ServeEngine, ServeEngineConfig,
                           pad_bucket, smallest_bucket)

BUCKETS = (1, 2, 4)


# -- batcher policy (no jax) --------------------------------------------------

def test_smallest_admissible_bucket():
    buckets = (1, 8, 32, 128)
    assert smallest_bucket(1, buckets) == 1
    assert smallest_bucket(2, buckets) == 8
    assert smallest_bucket(8, buckets) == 8
    assert smallest_bucket(9, buckets) == 32
    assert smallest_bucket(128, buckets) == 128
    with pytest.raises(ValueError):
        smallest_bucket(129, buckets)


def test_pad_bucket_zero_rows():
    imgs = [np.full((3, 3, 2), i + 1, np.float32) for i in range(2)]
    out = pad_bucket(imgs, 4)
    assert out.shape == (4, 3, 3, 2) and out.dtype == np.float32
    np.testing.assert_array_equal(out[0], imgs[0])
    np.testing.assert_array_equal(out[1], imgs[1])
    assert not out[2:].any()            # padding rows are exactly zero


def test_plan_tick_routes_head_of_queue():
    b = ContinuousBatcher((1, 8, 32, 128))
    assert b.plan_tick(1) == [(1, 1)]
    assert b.plan_tick(5) == [(8, 5)]          # smallest admissible, padded
    assert b.plan_tick(128) == [(128, 128)]
    # overflow spills into a second head-of-queue batch
    assert b.plan_tick(200) == [(128, 128), (128, 72)]
    # tick budget truncates the plan, never reorders it
    b2 = ContinuousBatcher((1, 8, 32, 128), max_batches_per_tick=1)
    assert b2.plan_tick(200) == [(128, 128)]


def test_fifo_across_ticks():
    b = ContinuousBatcher(BUCKETS)
    for _ in range(3):
        b.submit(None)
    bucket, reqs = b.next_batch()
    assert bucket == 4 and [r.rid for r in reqs] == [0, 1, 2]
    b.end_tick()
    for _ in range(2):
        b.submit(None)
    bucket, reqs = b.next_batch()
    assert bucket == 2 and [r.rid for r in reqs] == [3, 4]
    assert b.next_batch() is None


def test_no_starvation_under_budget():
    """With a 1-batch tick budget and sustained overload, completion order
    is still exactly submission order — no request is passed over."""
    b = ContinuousBatcher((1, 2), max_batches_per_tick=1)
    done = []
    for _ in range(6):
        for _ in range(3):              # arrivals outpace the budget
            b.submit(None)
        batch = b.next_batch()          # engine honours the budget of 1
        if batch:
            done.extend(r.rid for r in batch[1])
        b.end_tick()
    assert done == list(range(len(done)))
    # backlog grew (overload), but strictly the newest requests wait
    assert min(r.rid for r in b._queue) == len(done)


def test_request_stamps():
    b = ContinuousBatcher(BUCKETS)
    r = b.submit(None, submit_time=1.5)
    assert r.arrival_tick == 0 and r.submit_time == 1.5
    b.end_tick()
    r2 = b.submit(None)
    assert r2.arrival_tick == 1 and r2.rid == r.rid + 1
    _, reqs = b.next_batch()
    assert all(q.bucket == 2 for q in reqs)


# -- mesh capacity ------------------------------------------------------------

def test_mesh_capacity_error_is_actionable():
    with pytest.raises(MeshCapacityError) as ei:
        checked_mesh((8192, 2), ("data", "model"))
    msg = str(ei.value)
    assert "16384" in msg and "xla_force_host_platform_device_count" in msg


def test_make_serve_mesh_oversized_raises():
    """An oversized serving mesh is an error, never a silent 1x1 replica."""
    need = len(jax.devices()) + 1
    with pytest.raises(MeshCapacityError, match=f"needs {need} devices"):
        make_serve_mesh(data=need)


def test_make_serve_mesh_spans_devices():
    mesh = make_serve_mesh()
    assert mesh.axis_names == ("data", "model")
    assert mesh.devices.size == len(jax.devices())


# -- the engine (one compile per bucket, shared across tests) -----------------

@pytest.fixture(scope="module")
def served():
    from repro.models.cnn import MINI, init_cnn_params
    spec = MINI.scaled(8)
    params = init_cnn_params(jax.random.PRNGKey(0), spec,
                             weight_sparsity=0.5)
    eng = ServeEngine(spec, params, ServeEngineConfig(buckets=BUCKETS))
    rng = np.random.default_rng(0)
    images = np.maximum(rng.standard_normal((16, 8, 8, 3),
                                            dtype=np.float32), 0.0)
    return spec, params, eng, images


def test_warmup_compiles_every_bucket(served):
    _, _, eng, _ = served
    assert eng.recompiles == len(BUCKETS)
    assert set(eng.warmup_s) == set(BUCKETS)


def test_padding_bitwise_per_bucket(served):
    """Real rows of every padded bucket == the unpadded chained forward."""
    from repro.models.cnn import make_cnn_pipeline
    spec, params, eng, images = served
    ref_fn = make_cnn_pipeline(spec, donate=False)
    for bucket in BUCKETS:
        for n in {1, bucket // 2 + 1}:
            got = np.asarray(eng._compiled(bucket)(
                eng.params,
                eng._place(bucket, pad_bucket(list(images[:n]), bucket))))
            ref = np.asarray(ref_fn(params, jnp.asarray(images[:n])))
            np.testing.assert_array_equal(got[:n], ref), (bucket, n)
            assert got.shape[0] == bucket


def test_padding_rows_cannot_leak_into_real_rows(served):
    """Within one bucket executable, a real row's logits are bitwise
    independent of the other rows' content (zeros vs real images)."""
    _, _, eng, images = served
    for bucket in BUCKETS[1:]:
        padded = np.asarray(eng._compiled(bucket)(
            eng.params,
            eng._place(bucket, pad_bucket([images[0]], bucket))))
        full = np.asarray(eng._compiled(bucket)(
            eng.params,
            eng._place(bucket, pad_bucket(list(images[:bucket]), bucket))))
        np.testing.assert_array_equal(padded[0], full[0])


def test_recompile_counter_flat_over_ticks(served):
    _, _, eng, images = served
    warm = eng.recompiles
    for arrivals in (1, 3, 0, 4, 2):
        for i in range(arrivals):
            eng.submit(images[i])
        eng.run_tick()
    assert eng.recompiles == warm        # no steady-state trace/compile


def test_completions_are_fifo_with_latency(served):
    _, _, eng, _ = served
    rids = [r.rid for r in eng.completed]
    assert rids == sorted(rids) and len(rids) == 10
    assert all(r.latency_s > 0 and r.result is not None
               for r in eng.completed)


def test_boundary_report_no_fallback(served):
    _, _, eng, _ = served
    for bucket in BUCKETS:
        rep = eng.boundary_report(bucket)
        assert rep["fallback_decodes"] == 0
        assert rep["chained"] >= 1 and rep["pool_events"] == 1


def test_boundary_report_routes_name_their_layer(served):
    """Each route of the report names the layer scope it was traced under:
    the same layers, in the same order, as the planner's routes."""
    from repro.models.cnn import chain_boundary_summary
    spec, _, eng, _ = served
    for bucket in BUCKETS:
        got = [(r["op"], r["layer"])
               for r in eng.boundary_report(bucket)["routes"]]
        planned = chain_boundary_summary(spec, batch=bucket,
                                         engine_cfg=eng.engine_cfg)["routes"]
        assert got == [(r["op"], r["layer"]) for r in planned]
        assert [layer for _, layer in got] == sorted({l for _, l in got})


def test_executable_snapshot_restore(served, tmp_path):
    """A restarted replica restores finished executables from cache_dir —
    zero recompiles, bitwise-identical logits."""
    spec, params, _, images = served
    cfg = ServeEngineConfig(buckets=(1,), cache_dir=str(tmp_path))
    first = ServeEngine(spec, params, cfg)
    assert first.recompiles == 1 and first.snapshot_hits == 0
    second = ServeEngine(spec, params, cfg)
    assert second.recompiles == 0 and second.snapshot_hits == 1
    assert "load_s" in second.warmup_s[1]
    x = pad_bucket([images[0]], 1)
    y1 = np.asarray(first._compiled(1)(first.params, first._place(1, x)))
    y2 = np.asarray(second._compiled(1)(second.params, second._place(1, x)))
    np.testing.assert_array_equal(y1, y2)


def test_executable_snapshot_restore_with_warm_compile_cache(
        served, tmp_path, monkeypatch):
    """With the XLA compile cache on and already holding the bucket, the
    snapshotting replica still compiles fresh: an executable read back from
    that cache does not survive serialize -> deserialize on XLA:CPU."""
    from jax.experimental.compilation_cache import compilation_cache

    from repro.serving import aot
    spec, params, _, images = served
    monkeypatch.setattr(aot, "DEFAULT_CACHE_DIR", str(tmp_path / "xla"))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    aot.configure_persistent_cache()
    try:
        ServeEngine(spec, params, ServeEngineConfig(buckets=(1,)))  # warms it
        cfg = ServeEngineConfig(buckets=(1,), cache_dir=str(tmp_path / "s"))
        first = ServeEngine(spec, params, cfg)
        second = ServeEngine(spec, params, cfg)
        assert second.snapshot_hits == 1 and second.recompiles == 0
        x = pad_bucket([images[0]], 1)
        y1 = np.asarray(first._compiled(1)(first.params, first._place(1, x)))
        y2 = np.asarray(second._compiled(1)(second.params,
                                            second._place(1, x)))
        np.testing.assert_array_equal(y1, y2)
    finally:
        jax.config.update("jax_compilation_cache_dir", None)
        compilation_cache.reset_cache()


def test_stats_report(served):
    _, _, eng, _ = served
    s = eng.stats()
    assert s["requests"] == 10 and s["requests_s"] > 0
    assert s["p99_ms"] >= s["p50_ms"] > 0
    assert set(s["per_bucket"]) == set(BUCKETS)
    assert sum(pb["requests"] for pb in s["per_bucket"].values()) == 10
    assert s["recompiles"] == len(BUCKETS)
