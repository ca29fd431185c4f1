"""Batched serving driver: LM prefill + decode loop, or event-resident CNN.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --reduced \
      --batch 4 --prompt-len 32 --gen 16

CNN mode runs a full serving replica (``repro.serving`` — DESIGN.md §10):
a FIFO request queue continuously batched into padded buckets, one
AOT-warmed compiled pipeline per bucket, weights replicated and the batch
axis sharded over the (data, model) mesh.  MNF is the default; ``--dense``
serves the oracle path instead:

  PYTHONPATH=src python -m repro.launch.serve --cnn alexnet --cnn-size 64 \
      --rate 6 --ticks 8 --cache-dir snapshots

``--smoke`` serves the mini network through every bucket and **fails**
(exit 1) if any steady-state tick recompiles, or an eligible event
boundary reports fallback_decode, or padded-bucket logits drift bitwise
from the unpadded forward — the CI anti-rot gate for the serving tier.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.launch.mesh import checked_mesh, make_serve_mesh
from repro.launch.steps import make_prefill_step, make_serve_step
from repro.models import init_params


def _cnn_spec(name: str, size: int):
    from repro.models.cnn import (ALEXNET, ALEXNET_DS, MINI, VGG16,
                                  VGG16_DS)
    return {"alexnet": ALEXNET, "vgg16": VGG16, "alexnet_ds": ALEXNET_DS,
            "vgg16_ds": VGG16_DS, "mini": MINI}[name].scaled(size)


def _mlp_spec(name: str):
    from repro.models.mlp import LENET_300_100, MLP_MINI
    return {"lenet": LENET_300_100, "mini": MLP_MINI}[name]


def serve_cnn(args) -> None:
    """Continuously-batched CNN/MLP serving through the AOT-warmed replica.

    ``--mlp`` serves an FC network through the identical bucketed tier —
    flat ``(in_features,)`` request vectors instead of images; every
    boundary is FC→FC, so its report must state zero densify points
    (DESIGN.md §12)."""
    import numpy as np

    from repro import engine, serving
    from repro.core.fire import FireConfig
    from repro.models.cnn import init_cnn_params
    from repro.models.mlp import init_mlp_params

    serving.configure_persistent_cache()
    if args.mlp:
        spec = _mlp_spec(args.mlp)
    else:
        spec = _cnn_spec(args.cnn, args.cnn_size)
    buckets = tuple(int(b) for b in args.buckets.split(","))
    if args.route == "adaptive":
        # Adaptive routing consults the measured crossover table (written
        # by kernel_bench --sweep); installing it is explicit — the engine
        # never reads files implicitly.
        from repro.costmodel import crossover as xover
        xover.set_active_table(xover.load_crossover_table(args.bench))
    ecfg = engine.EngineConfig(
        backend="pallas" if args.mnf_pallas else "auto",
        threshold=args.mnf_threshold, route=args.route,
        occupancy_hint=args.occupancy_hint)
    key = jax.random.PRNGKey(0)
    init = init_mlp_params if args.mlp else init_cnn_params
    params = init(key, spec, weight_sparsity=args.weight_sparsity)

    eng = serving.ServeEngine(
        spec, params,
        serving.ServeEngineConfig(buckets=buckets, mnf=not args.dense,
                                  threshold=args.mnf_threshold,
                                  cache_dir=args.cache_dir),
        mesh=make_serve_mesh(), engine_cfg=ecfg,
        fire_cfg=FireConfig(threshold=args.mnf_threshold))

    # Synthetic traffic is generated AHEAD of the serving loop: requests/s
    # must measure the pipeline, not host-side jax.random throughput.
    rng = np.random.default_rng(0)
    n_requests = args.rate * args.ticks
    req_shape = (spec.in_features,) if args.mlp else \
        (spec.input_size, spec.input_size, spec.in_ch)
    images = np.maximum(
        rng.standard_normal((n_requests,) + req_shape, dtype=np.float32),
        0.0)

    warm_recompiles = eng.recompiles
    it = iter(images)
    for _ in range(args.ticks):
        for _ in range(args.rate):
            eng.submit(next(it))
        eng.run_tick()
    stats = eng.stats()

    failures = []
    if eng.recompiles != warm_recompiles:
        failures.append(
            f"steady-state recompiles: {eng.recompiles - warm_recompiles} "
            f"ticks compiled after warmup (the jit cache-miss counter must "
            f"stay flat)")
    report = eng.boundary_report()
    if not args.dense and report["fallback_decodes"]:
        failures.append(f"eligible boundary reported fallback_decode: "
                        f"{report}")

    # An MLP boundary report with any densify point is a serving bug: every
    # FC→FC boundary is structurally eligible (DESIGN.md §12).
    if args.mlp and eng.plans[buckets[0]].boundaries.get("densify", 0):
        failures.append(f"MLP replica reports densify points: "
                        f"{eng.plans[buckets[0]].boundaries}")

    print(json.dumps(dict(
        net=spec.name,
        input_size=spec.in_features if args.mlp else spec.input_size,
        buckets=list(buckets),
        mnf=not args.dense, engine=dataclasses.asdict(eng.engine_cfg),
        boundaries=report, **stats)))
    if failures:
        print("serve smoke FAILED:\n  " + "\n  ".join(failures),
              file=sys.stderr)
        raise SystemExit(1)


def serve_smoke(args) -> None:
    """CI gate: tiny bucketed serve loop + the tier's three invariants,
    plus the routing invariant of DESIGN.md §11: a snapshot-restored
    replica must report routes identical to the replica that compiled the
    executables (routes are trace-time static, so any drift means the
    restored executable no longer matches its report)."""
    import tempfile

    import numpy as np

    from repro import serving
    from repro.models.cnn import init_cnn_params, make_cnn_pipeline

    spec = _cnn_spec("mini", 8)
    buckets = (1, 2, 4)
    # Snapshots of this run only (the restart check must restore what this
    # replica compiled); the XLA compile cache keeps its one home.
    snapshots = tempfile.TemporaryDirectory(prefix="mnf_serve_smoke_")
    cache_dir = args.cache_dir or snapshots.name
    params = init_cnn_params(jax.random.PRNGKey(0), spec,
                             weight_sparsity=0.5)
    eng = serving.ServeEngine(
        spec, params, serving.ServeEngineConfig(buckets=buckets,
                                                cache_dir=cache_dir))
    warm = eng.recompiles
    rng = np.random.default_rng(0)
    images = np.maximum(rng.standard_normal((9, 8, 8, 3),
                                            dtype=np.float32), 0.0)
    arrivals = (1, 3, 0, 5)          # exercises buckets 1, 4, (idle), 4+1
    it = iter(images)
    for n in arrivals:
        for _ in range(n):
            eng.submit(next(it))
        eng.run_tick()

    failures = []
    if len(eng.completed) != 9:
        failures.append(f"served {len(eng.completed)}/9 requests")
    if [r.rid for r in eng.completed] != sorted(
            r.rid for r in eng.completed):
        failures.append("completion order is not FIFO")
    if eng.recompiles != warm:
        failures.append(f"{eng.recompiles - warm} steady-state recompiles "
                        f"(jit cache-miss counter must stay flat after "
                        f"warmup)")
    report = eng.boundary_report()
    if report["fallback_decodes"]:
        failures.append(f"eligible boundary reported fallback_decode: "
                        f"{report}")
    # Bitwise padding mask: real rows of every padded bucket == the
    # unpadded chained forward.
    ref_fn = make_cnn_pipeline(spec, donate=False)
    for n in (1, 3, 9):
        ref = np.asarray(ref_fn(params, jnp.asarray(images[:n])))
        got = np.stack([r.result for r in eng.completed[:n]])
        if not np.array_equal(ref, got):
            failures.append(f"padded-bucket logits not bitwise-equal to "
                            f"the unpadded forward at n={n}")
    # Snapshot-restart route identity: a second replica restored from the
    # first one's executable snapshots must report the exact same
    # per-boundary routes (and restore, not recompile).
    eng2 = serving.ServeEngine(
        spec, params, serving.ServeEngineConfig(buckets=buckets,
                                                cache_dir=cache_dir))
    if eng2.snapshot_hits != len(buckets):
        failures.append(f"restarted replica restored "
                        f"{eng2.snapshot_hits}/{len(buckets)} buckets from "
                        f"snapshot (restart must not recompile)")
    report2 = eng2.boundary_report()
    if report2["routes"] != report["routes"]:
        failures.append(f"snapshot-restored replica reports different "
                        f"routes: {report2['routes']} != {report['routes']}")

    # MLP tier: the FC family through the identical bucketed replica —
    # flat request vectors, every boundary FC→FC.  Zero densify points is
    # structural (DESIGN.md §12): any fallback_decode or densify count on
    # an MLP replica is a serving bug, and padded-bucket logits must stay
    # bitwise the unpadded chained forward's, same as the CNN tier.
    from repro.models.mlp import (MLP_MINI, init_mlp_params,
                                  make_mlp_pipeline)
    mspec = MLP_MINI
    mparams = init_mlp_params(jax.random.PRNGKey(0), mspec,
                              weight_sparsity=0.5)
    meng = serving.ServeEngine(
        mspec, mparams, serving.ServeEngineConfig(buckets=buckets))
    mwarm = meng.recompiles
    vecs = np.maximum(rng.standard_normal((7, mspec.in_features),
                                          dtype=np.float32), 0.0)
    it = iter(vecs)
    for n in (1, 2, 4):
        for _ in range(n):
            meng.submit(next(it))
        meng.run_tick()
    mreport = meng.boundary_report()
    if len(meng.completed) != 7:
        failures.append(f"MLP tier served {len(meng.completed)}/7 requests")
    if meng.recompiles != mwarm:
        failures.append(f"MLP tier: {meng.recompiles - mwarm} steady-state "
                        f"recompiles")
    if mreport["fallback_decodes"]:
        failures.append(f"MLP tier: eligible FC boundary reported "
                        f"fallback_decode: {mreport}")
    if mreport["boundaries"].get("densify", 0) or \
            mreport["boundaries"].get("retile", 0):
        failures.append(f"MLP tier: FC→FC chain reports densify/retile "
                        f"points: {mreport['boundaries']}")
    mref = np.asarray(make_mlp_pipeline(mspec, donate=False)(
        mparams, jnp.asarray(vecs)))
    mgot = np.stack([r.result for r in meng.completed])
    if not np.array_equal(mref, mgot):
        failures.append("MLP tier: padded-bucket logits not bitwise-equal "
                        "to the unpadded chained forward")

    print(json.dumps(dict(smoke="serve", boundaries=report,
                          mlp_boundaries=mreport, **eng.stats())))
    if failures:
        print("serve smoke FAILED:\n  " + "\n  ".join(failures),
              file=sys.stderr)
        raise SystemExit(1)
    print("serve smoke OK: no steady-state recompiles, no fallback_decode, "
          "padding bitwise-exact, snapshot-restart routes identical, MLP "
          "tier densify-free")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--mnf", action="store_true",
                    help="enable the MNF fire phase (LM MLP blocks / CNN "
                         "event pipeline)")
    ap.add_argument("--mnf-threshold", type=float, default=0.0)
    ap.add_argument("--mnf-pallas", action="store_true",
                    help="route the MNF multiply phase through the Pallas "
                         "engine backend (default: CNN/MLP mode resolves "
                         "per device — Pallas on a TPU, the pure-XLA block "
                         "backend elsewhere; LM mode uses the block "
                         "backend)")
    ap.add_argument("--cnn", choices=("alexnet", "vgg16", "alexnet_ds",
                                      "vgg16_ds", "mini"),
                    help="serve a CNN workload through the bucketed "
                         "serving replica instead of an LM (the _ds "
                         "variants downsample with stride-2 conv blocks — "
                         "the fused stride-2 strip path)")
    ap.add_argument("--cnn-size", type=int, default=64,
                    help="CNN input resolution (224 = paper scale)")
    ap.add_argument("--mlp", choices=("lenet", "mini"),
                    help="serve an FC network (lenet = LeNet-300-100, the "
                         "paper's MNIST-class workload) through the same "
                         "bucketed serving replica — flat request vectors, "
                         "every FC→FC boundary event-chained, zero densify "
                         "points (DESIGN.md §12)")
    ap.add_argument("--buckets", default="1,8,32,128",
                    help="CNN mode: compiled batch bucket sizes, ascending")
    ap.add_argument("--rate", type=int, default=8,
                    help="CNN mode: synthetic request arrivals per tick")
    ap.add_argument("--ticks", type=int, default=8,
                    help="CNN mode: number of serving ticks to run")
    ap.add_argument("--cache-dir", default=None,
                    help="executable snapshot directory — a restarted "
                         "replica re-warms its bucket executables from "
                         "disk in seconds (the XLA compile cache lives at "
                         "JAX_COMPILATION_CACHE_DIR, else .jax_cache/ in "
                         "the checkout)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate: tiny bucketed serve loop; exit 1 on any "
                         "steady-state recompile, fallback_decode, or "
                         "padding bitwise drift")
    ap.add_argument("--dense", action="store_true",
                    help="CNN mode: serve the dense oracle path instead of "
                         "MNF events (the default)")
    ap.add_argument("--weight-sparsity", type=float, default=0.5,
                    help="CNN mode: unstructured weight pruning density")
    ap.add_argument("--route", default="auto",
                    choices=("auto", "adaptive", "dense", "event", "strip",
                             "pixel", "window"),
                    help="CNN mode: per-boundary routing policy — auto "
                         "(geometry event-first), adaptive (cost-model / "
                         "crossover-table argmin at --occupancy-hint), or "
                         "a forced route (DESIGN.md §11)")
    ap.add_argument("--occupancy-hint", type=float, default=None,
                    help="CNN mode: static occupancy the adaptive router "
                         "decides at (routes are trace-time static; the "
                         "hint is the deployment's expected activation "
                         "density, default 1.0)")
    ap.add_argument("--bench", default="BENCH_engine.json",
                    help="CNN mode: BENCH file whose crossover entries "
                         "seed the adaptive routing table")
    args = ap.parse_args()

    if args.smoke:
        serve_smoke(args)
        return
    if args.cnn and args.mlp:
        ap.error("--cnn and --mlp are mutually exclusive")
    if args.cnn or args.mlp:
        if args.dense and (args.mnf or args.mnf_pallas
                           or args.mnf_threshold != 0.0):
            ap.error("--dense conflicts with --mnf/--mnf-pallas/"
                     "--mnf-threshold (CNN/MLP mode serves MNF by default)")
        serve_cnn(args)
        return

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    # --mnf-threshold / --mnf-pallas imply --mnf: a sub-flag alone must not
    # silently benchmark the dense path.
    if args.mnf or args.mnf_pallas or args.mnf_threshold != 0.0:
        cfg = dataclasses.replace(
            cfg, mnf=dataclasses.replace(cfg.mnf, enabled=True,
                                         threshold=args.mnf_threshold,
                                         use_pallas=args.mnf_pallas))
    max_len = args.prompt_len + args.gen
    shape = ShapeConfig("serve", max_len, args.batch, "decode")
    ndev = len(jax.devices())
    mesh = checked_mesh((ndev, 1), ("data", "model"))

    pre = make_prefill_step(cfg, ShapeConfig("pf", max_len, args.batch,
                                             "prefill"), mesh)
    srv = make_serve_step(cfg, shape, mesh)

    key = jax.random.PRNGKey(0)
    with mesh:
        params = jax.jit(lambda k: init_params(k, cfg)[0])(key)
        toks = jax.random.randint(key, (args.batch, max_len), 0,
                                  cfg.vocab_size, jnp.int32)
        batch = dict(tokens=toks)
        if cfg.vision_tokens:
            batch["vision_embeds"] = jnp.zeros(
                (args.batch, cfg.vision_tokens, cfg.d_model),
                jnp.dtype(cfg.compute_dtype))
        if cfg.encoder_decoder:
            batch["audio_frames"] = jnp.zeros(
                (args.batch, cfg.enc_frames, cfg.d_model),
                jnp.dtype(cfg.compute_dtype))

        t0 = time.time()
        logits, cache = pre.fn(params, batch)
        jax.block_until_ready(logits)
        t_prefill = time.time() - t0

        out_tokens = []
        # Fire-gated recurrent decode (DESIGN.md §13): each decode step
        # writes the per-layer fired-event count of the state update into
        # the cache — collect it per token for the events/token report.
        track_events = (cfg.mnf.enabled and isinstance(cache, dict)
                        and isinstance(cache.get("scan"), dict)
                        and "events" in cache["scan"])
        ev_steps = []
        cur = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
        t0 = time.time()
        for i in range(args.gen):
            step_batch = dict(batch, tokens=cur)
            logits, cache = srv.fn(params, cache, step_batch,
                                   jnp.asarray(args.prompt_len + i,
                                               jnp.int32))
            cur = jnp.argmax(logits[:, -1], axis=-1)[:, None]
            cur = cur.astype(jnp.int32)
            out_tokens.append(cur)
            if track_events:
                ev_steps.append(cache["scan"]["events"])
        jax.block_until_ready(cur)
        t_decode = time.time() - t0

    gen = jnp.concatenate(out_tokens, axis=1)
    stats = dict(
        arch=cfg.name, batch=args.batch, prompt_len=args.prompt_len,
        generated=args.gen,
        prefill_s=round(t_prefill, 3),
        decode_tok_per_s=round(args.gen * args.batch / t_decode, 1),
        mnf=cfg.mnf.enabled,
        engine=dataclasses.asdict(srv.engine),
        sample_tokens=[int(t) for t in gen[0][:8]])
    if track_events:
        evm = jnp.stack(ev_steps)                  # (gen, L) counts
        per_tok = evm.sum(axis=1)
        stats["events_per_token"] = round(float(per_tok.mean()), 2)
        stats["events_per_token_min"] = round(float(per_tok.min()), 2)
        stats["events_per_token_max"] = round(float(per_tok.max()), 2)
        stats["events_per_layer"] = [round(float(x), 2)
                                     for x in evm.mean(axis=0)]
    print(json.dumps(stats))


if __name__ == "__main__":
    main()
