"""Serve AlexNet@224 through the Pallas event path on a TPU, and check it.

    python3 chip_smoke.py                # one chip
    python3 chip_smoke.py --four-chips   # four chips of one host

One chip: the event-pool kernels are checked bitwise against the dense
``reduce_window`` pool, then a ``serving.ServeEngine`` with buckets (1, 8)
is AOT-warmed and serves nine seeded AlexNet@224 requests (one tick of 1,
one of 8).  Their logits are compared with the plain dense forward
(``make_cnn_forward(..., mnf=False)`` at "highest" matmul precision):
argmax must agree and the relative error must stay under ``ERR_BOUND``.
The engine config must resolve to the Pallas kernels with interpret mode
off, no tick may compile, and no boundary may fall back to a decode.

``--four-chips`` runs only bucket 8 on a (4, 1) data mesh and on a
one-device mesh, in this one process, and requires bitwise-equal logits
(the batch-parallel replica is per-sample independent, DESIGN.md §10).

Each measurement is one ``name: value`` line.  The last line is the JSON
object ``{"ok": true, "device": {...}}``, printed only when every check
passed.  Without a TPU, or outside a checkout, the script exits non-zero
and prints no result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

#: Largest admitted max|logits - ref| / max|ref|.  Both sides multiply in
#: f32 at full precision and differ only in summation order, so each
#: layer adds at most ~sqrt(K) * 2^-24 relative error for its widest
#: reduction (K = 9216 at fc6: 96 * 6e-8 = 5.7e-6).  Eight compute layers
#: compound to ~4.6e-5; the bound doubles that.
ERR_BOUND = 1e-4

BUCKETS = (1, 8)
SEED = 0


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def emit(name: str, value) -> None:
    print(f"{name}: {json.dumps(value, default=str)}", flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def requests(spec, n: int, seed: int = SEED):
    """n seeded, ReLU'd standard-normal images (the serve driver's traffic)."""
    import numpy as np
    rng = np.random.default_rng(seed)
    shape = (n, spec.input_size, spec.input_size, spec.in_ch)
    return np.maximum(rng.standard_normal(shape, dtype=np.float32), 0.0)


def model(size: int = 224):
    import jax

    from repro.models.cnn import ALEXNET, init_cnn_params
    spec = ALEXNET.scaled(size)
    params = init_cnn_params(jax.random.PRNGKey(SEED), spec,
                             weight_sparsity=0.5)
    return spec, params


def pool_phase(cfg) -> None:
    """The event pools on the device == ``reduce_window``, bitwise: the
    per-event kernel at AlexNet pool1's shape, and the window-major kernel
    (whose row picks are 0/1 selection matmuls) on a strip stream."""
    import jax
    import jax.numpy as jnp

    from repro import engine
    from repro.models.layers import max_pool_nhwc

    key = jax.random.PRNGKey(SEED + 1)
    cases = (("pixel", (1, 55, 55, 96), 3, 2, 1),
             ("window", (1, 32, 32, 64), 2, 2, engine.STRIP_W))
    for name, shape, k, s, blk_m in cases:
        x = jax.nn.relu(jax.random.normal(key, shape, jnp.float32))

        def pooled(x, k=k, s=s, blk_m=blk_m):
            stream = engine.EventStream.encode_nhwc(
                x, blk_k=8, blk_m=blk_m, keep_dense=False)
            return engine.maxpool2d(stream, k, s, cfg=cfg.for_pool(
                shape[-1])).dense_nhwc()

        with engine.trace_dispatch() as recs:
            got = jax.jit(pooled)(x)
        ref = jax.jit(lambda x, k=k, s=s: max_pool_nhwc(x, k, s))(x)
        exact = bool(jnp.array_equal(got, ref))
        events = [r for r in recs if r.get("pool_events")]
        emit(f"pool.{name}", dict(shape=shape, k=k, stride=s,
                                  event_kernel=len(events) == 1,
                                  bitwise=exact))
        check(len(events) == 1, f"pool.{name}: no event-pool launch")
        check(exact, f"pool.{name}: event pool != reduce_window")


def serve_phase(spec, params) -> None:
    """Nine requests through the bucketed replica vs the dense reference."""
    import jax
    import numpy as np

    from repro import serving
    from repro.models.cnn import make_cnn_forward

    eng = serving.ServeEngine(
        spec, params, serving.ServeEngineConfig(buckets=BUCKETS))
    cfg = eng.engine_cfg
    emit("engine.config", dict(backend=cfg.backend, interpret=cfg.interpret,
                               route=cfg.route, blk_m=cfg.blk_m,
                               blk_k=cfg.blk_k, blk_n=cfg.blk_n))
    check(cfg.backend == "pallas" and cfg.interpret is False,
          f"engine resolved to backend={cfg.backend!r}, "
          f"interpret={cfg.interpret!r}, not the Pallas kernels")
    emit("serve.warmup_s", eng.warmup_s)
    warm = eng.recompiles
    check(warm == len(BUCKETS), f"{warm} compiles for {len(BUCKETS)} buckets")

    images = requests(spec, 1 + BUCKETS[-1])
    tick_s = []
    for batch in (images[:1], images[1:]):
        for img in batch:
            eng.submit(img)
        t0 = time.perf_counter()
        eng.run_tick()
        tick_s.append(time.perf_counter() - t0)
    stats = eng.stats()
    emit("serve.tick_s", tick_s)
    emit("serve.requests", stats["requests"])
    emit("serve.per_bucket", stats["per_bucket"])
    emit("serve.steady_recompiles", eng.recompiles - warm)
    check(stats["requests"] == len(images),
          f"served {stats['requests']}/{len(images)} requests")
    check(eng.recompiles == warm, "a steady-state tick compiled")

    for b in BUCKETS:
        rep = eng.boundary_report(b)
        emit(f"serve.boundary_report.bucket_{b}", dict(
            chained=rep["chained"], pool_events=rep["pool_events"],
            fallback_decodes=rep["fallback_decodes"],
            routed_dense=rep["routed_dense"],
            route_counts=rep["route_counts"],
            densify=rep["boundaries"].get("densify")))
        check(rep["fallback_decodes"] == 0,
              f"bucket {b}: {rep['fallback_decodes']} fallback decodes")

    with jax.default_matmul_precision("highest"):
        ref_fn = jax.jit(make_cnn_forward(spec, mnf=False))
        ref = np.asarray(ref_fn(params, images))
    got = np.stack([r.result for r in eng.completed])
    check(got.shape == ref.shape, f"logits {got.shape} != {ref.shape}")
    check(bool(np.isfinite(got).all()), "non-finite logits")
    rel = float(np.abs(got - ref).max() / np.abs(ref).max())
    agree = int((got.argmax(-1) == ref.argmax(-1)).sum())
    emit("reference.max_rel_err", rel)
    emit("reference.err_bound", ERR_BOUND)
    emit("reference.argmax_agree", f"{agree}/{len(images)}")
    check(agree == len(images), "argmax disagrees with the dense reference")
    check(rel <= ERR_BOUND, f"max relative error {rel} > {ERR_BOUND}")


def four_chip_phase(spec, params) -> None:
    """Bucket 8 on a (4, 1) data mesh vs one device: bitwise-equal logits."""
    import jax
    import numpy as np

    from repro import serving
    from repro.launch.mesh import make_serve_mesh

    check(len(jax.devices()) >= 4,
          f"--four-chips needs 4 devices, found {len(jax.devices())}")
    images = requests(spec, 8)
    logits = {}
    for name, mesh in (("mesh_4x1", make_serve_mesh(data=4)),
                       ("mesh_1x1", make_serve_mesh(data=1))):
        eng = serving.ServeEngine(
            spec, params, serving.ServeEngineConfig(buckets=(8,)), mesh=mesh)
        for img in images:
            eng.submit(img)
        t0 = time.perf_counter()
        eng.run_tick()
        emit(f"four_chips.{name}", dict(
            data_shards=eng.plans[8].data_shards, warmup_s=eng.warmup_s,
            tick_s=time.perf_counter() - t0, requests=len(eng.completed)))
        logits[name] = np.stack([r.result for r in eng.completed])
    check(logits["mesh_4x1"].shape == (8, spec.num_classes),
          f"logits shape {logits['mesh_4x1'].shape}")
    exact = bool(np.array_equal(logits["mesh_4x1"], logits["mesh_1x1"]))
    emit("four_chips.bitwise_equal", exact)
    emit("four_chips.max_abs_diff", float(np.abs(
        logits["mesh_4x1"] - logits["mesh_1x1"]).max()))
    check(exact, "(4, 1)-mesh logits differ from the one-device logits")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="only compare bucket 8 on a (4, 1) mesh with one "
                         "device (needs four chips)")
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no repro package under {SRC}; run this script "
              f"from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU chip found — JAX sees platform "
              f"{dev.platform!r} ({dev.device_kind}); this check runs only "
              f"on a TPU", file=sys.stderr)
        return 3

    from repro import engine, serving
    emit("device", dict(platform=dev.platform, kind=dev.device_kind,
                        count=len(devices), jax=jax.__version__))
    emit("compile_cache_dir", serving.configure_persistent_cache())
    t0 = time.perf_counter()
    try:
        spec, params = model()
        if args.four_chips:
            four_chip_phase(spec, params)
        else:
            pool_phase(engine.EngineConfig().resolved())
            serve_phase(spec, params)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    stats = dev.memory_stats() or {}
    emit("peak_bytes_in_use", stats.get("peak_bytes_in_use", "not reported"))
    emit("wall_s", time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
