"""Kernel microbenchmarks: event_matmul / fire_compact / wkv6 — plus engine
backend-comparison and CNN chained-pipeline modes.

Wall-times are interpret-mode on CPU (correctness harness, not TPU perf);
the derived columns carry the *structural* quantities that transfer to TPU:
fraction of weight-tile DMAs skipped (== event sparsity the kernel rides),
per-boundary decode counts, and the ref/kernel agreement.

Every jitted path is warmed before timing: the first call's wall-time is
recorded separately as ``compile_us`` (trace+compile dominated) and the
steady-state ``us`` is averaged over post-warm reps — compile time never
pollutes the trajectory numbers.

``--engine`` sweeps every registered ``EngineConfig.backend`` of
``engine.linear`` over a sparsity grid and compares the chained
(fire → EventStream → linear) path against the decode→re-encode round-trip.
``--cnn-chain`` times the event-resident CNN pipeline (one jit per network,
conv streams chained end-to-end) against the per-layer round-trip twin and
records where each path densifies plus per-conv-layer launch counts (taps
fused vs per-tap).  ``--conv-fused`` times the fused strip-tiled conv
kernel (one launch per layer, 8x smaller event grid) against the per-tap
chained path at matched shapes — both stride-1 and stride-2 downsampling
geometries (the interleaved half-strip plan).  ``--pool`` times the
event-native max-pool (segment max over stream events, one launch) against
the dense pool + re-encode round-trip.  ``--serve`` benchmarks the bucketed
AOT-warmed serving replica (``repro.serving``): requests/s and p50/p99 per
batch bucket, cold compile vs snapshot restore, and replica
time-to-first-response.  ``--sweep`` runs the occupancy sweep 0→1 over
conv/pool/linear boundaries: every route timed per point (``crossover``
entries — the calibrated table ``route="adaptive"`` dispatch consults,
DESIGN.md §11) and the adaptive router re-timed end-to-end against the
best static route (``adaptive`` entries).  All write/merge
BENCH_engine.json.
``--smoke`` runs a fast subset of everything (CI anti-rot) — including a
downsampling mini-net whose stride-2 layer must ride the fused strip
path — and **fails** if an eligible strip layer (either stride) or pool
boundary falls back to a decode (fallback_decode) — the silent-degrade
bug class — or if any adaptive routing decision contradicts the
committed crossover table beyond the hysteresis band (``route_gate``).
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import engine
from repro.kernels import (event_matmul, event_matmul_ref, fire_compact,
                           fire_compact_ref, wkv6, wkv6_ref)


def _time_thunk(fn, reps=3):
    """(steady_us, compile_us, out): first call timed apart as compile."""
    t0 = time.perf_counter()
    out = fn()
    jax.block_until_ready(out)
    compile_us = (time.perf_counter() - t0) * 1e6
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6, compile_us, out


def _timeit(fn, *args, reps=3, **kw):
    return _time_thunk(lambda: fn(*args, **kw), reps=reps)


def rows(reps=3):
    rng = np.random.default_rng(0)
    interp = engine.EngineConfig().resolve_interpret()
    out = []
    for sparsity in (0.0, 0.7, 0.95):
        m, k, n = 64, 1024, 512
        a = rng.normal(size=(m, k)).astype(np.float32)
        a *= rng.random((m, k)) > sparsity
        w = rng.normal(size=(k, n)).astype(np.float32)
        us, cus, y = _timeit(event_matmul, jnp.asarray(a), jnp.asarray(w),
                             blk_m=8, blk_k=128, interpret=interp, reps=reps)
        yr = event_matmul_ref(jnp.asarray(a), jnp.asarray(w), blk_m=8,
                              blk_k=128)
        live = np.abs(a.reshape(8, 8, 8, 128)).max(axis=(1, 3)) > 0
        out.append((f"event_matmul_s{sparsity}", us, cus,
                    f"tiles_skipped={1-live.mean():.2f};"
                    f"allclose={np.allclose(y, yr, atol=1e-4)}"))
    acc = jnp.asarray(rng.normal(size=(128, 1024)), jnp.float32)
    us, cus, (f, occ) = _timeit(fire_compact, acc, blk_m=8, blk_k=128,
                                interpret=interp, reps=reps)
    fr, occr = fire_compact_ref(acc, blk_m=8, blk_k=128)
    out.append(("fire_compact", us, cus,
                f"allclose={np.allclose(f, fr)};"
                f"occ_match={np.array_equal(np.asarray(occ), np.asarray(occr))}"))
    b, h, t, d = 2, 2, 64, 32
    r, k2, v = (jnp.asarray(rng.normal(size=(b, h, t, d)), jnp.float32)
                for _ in range(3))
    w6 = jnp.asarray(rng.uniform(0.3, 0.99, (b, h, t, d)), jnp.float32)
    u = jnp.asarray(rng.normal(size=(h, d)), jnp.float32)
    us, cus, (o, s) = _timeit(wkv6, r, k2, v, w6, u, chunk=16,
                              interpret=interp, reps=reps)
    orf, srf = jax.vmap(wkv6_ref, in_axes=(1, 1, 1, 1, 0),
                        out_axes=(1, 1))(r, k2, v, w6, u)
    out.append(("wkv6_chunked", us, cus,
                f"allclose={np.allclose(o, orf, atol=1e-4)};"
                f"state_ok={np.allclose(s, srf, atol=1e-4)}"))
    return out


def _seconds(warmup: dict) -> float:
    """Seconds of one bucket's ``ServeEngine.warmup_s`` entry."""
    return sum(v for k, v in warmup.items() if k.endswith("_s"))


def _merge_bench(out_path: str, entries, drop_kinds: set):
    """Read-modify-write BENCH_engine.json: each mode owns its entry kinds."""
    dev = jax.devices()[0]
    payload = dict(platform=dev.platform, device_kind=dev.device_kind,
                   device_count=len(jax.devices()), entries=[])
    if dev.platform != "tpu":
        payload["note"] = ("CPU interpret-mode wall-times; structural "
                           "columns (allclose, events, bit_exact, "
                           "boundaries) are what transfers; compile_us is "
                           "trace+compile, us is steady-state")
    if os.path.exists(out_path):
        try:
            with open(out_path) as f:
                prev = json.load(f)
            payload["entries"] = [e for e in prev.get("entries", [])
                                  if e.get("kind") not in drop_kinds]
        except (json.JSONDecodeError, OSError):
            pass
    payload["entries"].extend(entries)
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=2)


def engine_rows(out_path: str = "BENCH_engine.json", reps=3):
    """Backend comparison through the unified engine API.

    Every backend must agree with the dense oracle at threshold 0 — the
    sweep records that check alongside wall-time, then times the chained
    EventStream path vs the dense round-trip between two layers.
    """
    rng = np.random.default_rng(0)
    m, k, n = 32, 256, 128
    w = jnp.asarray(rng.normal(size=(k, n)).astype(np.float32))
    entries = []
    for sparsity in (0.0, 0.7, 0.95):
        a = rng.normal(size=(m, k)).astype(np.float32)
        a *= rng.random((m, k)) > sparsity
        aj = jnp.asarray(a)
        ref = a @ np.asarray(w)
        for name in engine.list_backends("linear"):
            cfg = engine.EngineConfig(backend=name, blk_m=8, blk_k=32,
                                      blk_n=32)
            us, cus, y = _time_thunk(
                lambda: engine.linear(aj, w, cfg=cfg), reps=reps)
            entries.append(dict(
                kind="linear", backend=name, sparsity=sparsity,
                m=m, k=k, n=n, us=round(us, 1), compile_us=round(cus, 1),
                allclose=bool(np.allclose(np.asarray(y), ref, atol=2e-3))))

    # chained vs round-trip: layer1 -> fire -> layer2
    w2 = jnp.asarray(rng.normal(size=(n, n)).astype(np.float32))
    a = rng.normal(size=(m, k)).astype(np.float32)
    a *= rng.random((m, k)) > 0.7
    aj = jnp.asarray(a)
    for name in engine.list_backends("linear_events"):
        cfg = engine.EngineConfig(backend=name, blk_m=8, blk_k=32, blk_n=32)
        acc = engine.linear(aj, w, cfg=cfg)
        stream = engine.fire(acc, cfg)

        def chained():
            return engine.linear(stream.without_dense(), w2, cfg=cfg)

        def roundtrip():
            return engine.linear(stream.dense(), w2, cfg=cfg)

        us_c, cus_c, yc = _time_thunk(chained, reps=reps)
        us_r, cus_r, yr = _time_thunk(roundtrip, reps=reps)
        entries.append(dict(
            kind="chained_vs_roundtrip", backend=name,
            events=int(stream.num_events), occupancy=float(stream.occupancy()),
            chained_us=round(us_c, 1), roundtrip_us=round(us_r, 1),
            chained_compile_us=round(cus_c, 1),
            roundtrip_compile_us=round(cus_r, 1),
            speedup=round(us_r / max(us_c, 1e-9), 3),
            bit_exact=bool(jnp.all(yc == yr))))
    _merge_bench(out_path, entries, {"linear", "chained_vs_roundtrip"})
    return entries


def _smoke_spec():
    """Tiny conv→conv→pool→conv→FC net: exercises every chain seam —
    conv→conv, the event-native conv→pool→conv boundary, pool→FC — in
    seconds."""
    from repro.models.cnn import CNNSpec, ConvSpec, FCSpec, PoolSpec
    return CNNSpec("mini", 8, 3,
                   (ConvSpec(8, 3, 1, 1), ConvSpec(8, 3, 1, 1), PoolSpec(),
                    ConvSpec(8, 3, 1, 1), FCSpec(10)))


def _smoke_ds_spec():
    """Tiny downsampling net: a stride-2 strip-eligible conv between two
    stride-1 convs.  Its middle layer must ride the fused stride-2 strip
    path — if it reports fallback_decode the smoke run fails CI (the
    silent-degrade bug class, extended to downsampling convs)."""
    from repro.models.cnn import CNNSpec, ConvSpec, FCSpec
    return CNNSpec("mini_ds", 16, 3,
                   (ConvSpec(8, 3, 1, 1), ConvSpec(8, 3, 2, 1),
                    ConvSpec(8, 3, 1, 1), FCSpec(10)))


def pool_rows(out_path: str = "BENCH_engine.json", *, smoke=False, reps=3):
    """Event-native max-pool (one launch, events in → events out) vs the
    dense pool + re-encode round-trip at matched shapes (pool entries).

    Same stream in, same pooled stream out (bit-exact vs the dense
    ``reduce_window`` oracle): the difference is purely the inter-layer
    format — the event path never materializes the input feature map.
    CI-fatal if an eligible stream falls back to a decode instead of
    riding the segment-max kernel (fallback_decode — the silent-degrade
    bug class, now covering pool boundaries too).
    """
    from repro.kernels.event_pool import pool_plan

    rng = np.random.default_rng(0)
    shapes = [(2, 8, 16, 8, 2, 2, 1)]
    if not smoke:
        shapes += [(2, 16, 16, 16, 2, 2, 8), (1, 15, 15, 8, 3, 2, 1)]
    entries = []
    for (b, h, w0, c, k, s, bm_in) in shapes:
        x = rng.normal(size=(b, h, w0, c)).astype(np.float32)
        x *= rng.random(x.shape) > 0.5
        xd = jnp.maximum(jnp.asarray(x), 0.0)
        for backend in ("pallas",):
            cfg = engine.EngineConfig(backend=backend, blk_m=1, blk_k=8)
            stream = engine.fire_conv(xd, cfg, blk_m=bm_in, keep_dense=False)
            with engine.trace_dispatch() as recs:
                jax.eval_shape(lambda st: engine.maxpool2d(st, k, s, cfg=cfg),
                               stream)
            if not any(r.get("pool_events") for r in recs) or \
                    any(r.get("fallback_decode") for r in recs):
                raise RuntimeError(
                    f"pool[{backend}]: eligible stream fell back instead of "
                    f"riding the event-native pool: {recs}")

            ev_fn = jax.jit(lambda st: engine.maxpool2d(st, k, s, cfg=cfg))
            dense_fn = jax.jit(lambda xx: engine.EventStream.encode_nhwc(
                engine.maxpool2d(xx, k, s, cfg=cfg), blk_k=cfg.blk_k,
                keep_dense=False))
            us_e, cus_e, ye = _time_thunk(lambda: ev_fn(stream), reps=reps)
            us_d, cus_d, yd = _time_thunk(lambda: dense_fn(xd), reps=reps)
            plan = pool_plan((b, h, w0, c), k, s,
                             nkb=stream.events.num_k_blocks)
            entries.append(dict(
                kind="pool", backend=backend, b=b, h=h, w=w0, c=c, k=k,
                stride=s, blk_m_in=bm_in,
                event_us=round(us_e, 1), dense_us=round(us_d, 1),
                event_compile_us=round(cus_e, 1),
                dense_compile_us=round(cus_d, 1),
                speedup=round(us_d / max(us_e, 1e-9), 3),
                bit_exact=bool(jnp.all(ye.dense_nhwc() == yd.dense_nhwc())),
                launches=plan["launches"], window_taps=plan["window_taps"],
                event_grid=plan["event_grid"],
                dense_reads=plan["dense_reads"]))
    _merge_bench(out_path, entries, {"pool"})
    return entries


def conv_fused_rows(out_path: str = "BENCH_engine.json", *, smoke=False,
                    reps=3):
    """Fused strip-tiled conv (one launch per layer) vs the per-tap chained
    path, matched shapes, per backend (conv_fused entries) — stride-1,
    stride-2 and stride-4 rows (the N-part interleaved straddle plan,
    k11s4 being the AlexNet conv1 class: 121 launches fused into 1).

    Same events in, same outputs (bit-exact): the difference is purely one
    fused launch over an 8x-smaller strip event grid vs k*k re-dispatches
    over per-tap gathered pixel grids.  Structural columns (event-grid
    reduction, launches, bit_exact) transfer to TPU; wall times are the
    CPU harness.  Only the pallas backend (the kernel under test) is
    timed — the block strip path is a correctness twin, pinned bitwise in
    tests/test_conv_strips.py, not a deployment path.  CI-fatal if an
    eligible strip layer (either stride) falls back (fallback_decode)
    instead of riding the fused path.
    """
    from repro.kernels.event_conv import fused_conv_plan

    rng = np.random.default_rng(0)
    # (B, H, W, CI, CO, k, padding, stride) — stride-2/4 rows are the
    # downsampling-conv classes the interleaved straddle plan covers; the
    # k11s4 row is AlexNet conv1's shape class (5 straddle parts,
    # 561/605 live subtaps after dead-part compaction).
    shapes = [(1, 8, 8, 8, 8, 3, 1, 1), (1, 8, 16, 8, 8, 3, 1, 2),
              (1, 8, 32, 8, 8, 3, 1, 4)]
    if not smoke:
        shapes += [(2, 16, 16, 8, 16, 3, 1, 1), (2, 9, 16, 8, 16, 5, 2, 2),
                   (1, 9, 16, 8, 8, 1, 0, 2), (1, 11, 32, 8, 8, 11, 4, 4)]
    entries = []
    for (b, h, w0, ci, co, k, p, st) in shapes:
        x = rng.normal(size=(b, h, w0, ci)).astype(np.float32)
        x *= rng.random(x.shape) > 0.5
        x = jnp.maximum(jnp.asarray(x), 0.0)
        wgt = jnp.asarray(rng.normal(size=(k, k, ci, co)).astype(np.float32))
        for backend in ("pallas",):
            cfg = engine.EngineConfig(backend=backend, blk_m=1, blk_k=8,
                                      blk_n=8)
            strip = engine.fire_conv(x, cfg, blk_m=engine.STRIP_W,
                                     keep_dense=False)
            pixel = engine.fire_conv(x, cfg, blk_m=1, keep_dense=False)

            fused_fn = jax.jit(lambda s: engine.conv2d(s, wgt, cfg=cfg,
                                                       stride=st, padding=p))
            pertap_fn = jax.jit(lambda s: engine.conv2d(s, wgt, cfg=cfg,
                                                        stride=st, padding=p))
            for stream, want_strip in ((strip, True), (pixel, False)):
                with engine.trace_dispatch() as recs:
                    jax.eval_shape(lambda s: engine.conv2d(
                        s, wgt, cfg=cfg, stride=st, padding=p), stream)
                ok = (not any(r.get("fallback_decode") for r in recs)
                      and any(r.get("chained")
                              and bool(r.get("strip")) == want_strip
                              for r in recs))
                if not ok:
                    raise RuntimeError(
                        f"conv_fused[{backend}]: "
                        f"{'strip' if want_strip else 'per-tap'} path "
                        f"(stride {st}) fell back instead of consuming "
                        f"events: {recs}")
            us_f, cus_f, yf = _time_thunk(lambda: fused_fn(strip), reps=reps)
            us_p, cus_p, yp = _time_thunk(lambda: pertap_fn(pixel), reps=reps)
            plan = fused_conv_plan((b, h, w0, ci), k, p,
                                   nkb=strip.events.num_k_blocks, stride=st)
            entries.append(dict(
                kind="conv_fused", backend=backend, b=b, h=h, w=w0, ci=ci,
                co=co, k=k, padding=p, stride=st,
                fused_us=round(us_f, 1), per_tap_us=round(us_p, 1),
                fused_compile_us=round(cus_f, 1),
                per_tap_compile_us=round(cus_p, 1),
                speedup=round(us_p / max(us_f, 1e-9), 3),
                bit_exact=bool(jnp.all(yf == yp)),
                launches_fused=plan["launches_fused"],
                launches_per_tap=plan["launches_per_tap"],
                subtaps=plan["subtaps"],
                subtaps_worst=plan["subtaps_worst"],
                compaction=round(plan["compaction"], 3),
                event_grid_strip=plan["event_grid_strip"],
                event_grid_pixel=plan["event_grid_pixel"],
                grid_reduction=plan["grid_reduction"],
                gathered_groups_per_tap=plan["gathered_groups_per_tap"],
                gathered_groups_fused=plan["gathered_groups_fused"]))
    _merge_bench(out_path, entries, {"conv_fused"})
    return entries


def cnn_chain_rows(out_path: str = "BENCH_engine.json", *, smoke=False,
                   batch=2, reps=3):
    """Event-resident CNN pipeline vs per-layer round-trip (one jit each).

    Chained and round-trip paths use identical compute geometry
    (pixel-granular conv tiles) so logits are bit-exact; the difference is
    purely the inter-layer format: events stay resident across conv
    boundaries vs a dense materialize + re-encode at every boundary.
    ``boundaries`` records where each compiled graph densifies.
    """
    from repro.core import events as ev
    from repro.models.cnn import (ALEXNET, ALEXNET_DS, ALEXNET_FF, MINI_S4,
                                  VGG16, VGG16_DS,
                                  ConvSpec, FCSpec, FireConfig, PoolSpec,
                                  _conv_base, _input_stream_blk_m, _layer_cfg,
                                  _trace_shapes, chain_boundary_summary,
                                  cnn_forward, init_cnn_params,
                                  make_cnn_pipeline)

    # AlexNet@64 keeps no strip-eligible interior layer (W=7/3 tails);
    # VGG16@32 runs six of its twelve chained convs on the fused strip path.
    # The _ds variants replace pools with stride-2 conv blocks (VGG16_DS@32
    # fuses 8/17 chained convs, ALEXNET_DS@68 both of its eligible layers).
    # ALEXNET_FF@256 is the fully-fused demonstration: every conv —
    # including the stride-4 k=11 head, strip-encoded straight off the
    # dense image — runs 1 launch (conv1: 1 vs 121); batch 1 keeps the
    # 121-launch round-trip twin affordable on the CPU harness.  MINI_S4@32
    # is its smoke twin: a stride-4 mid-layer that must ride the fused
    # path (fallback_decode there fails CI).
    nets = ([(_smoke_spec(), 8, batch), (_smoke_ds_spec(), 16, batch),
             (MINI_S4, 32, batch)] if smoke
            else [(ALEXNET, 64, batch), (VGG16, 32, batch),
                  (ALEXNET_DS, 68, batch), (VGG16_DS, 32, batch),
                  (ALEXNET_FF, 256, 1)])
    entries = []
    for spec, size, batch in nets:
        spec = spec.scaled(size)
        n_conv = sum(isinstance(l, ConvSpec) for l in spec.layers)
        n_fc = sum(isinstance(l, FCSpec) for l in spec.layers)
        n_pool = sum(isinstance(l, PoolSpec) for l in spec.layers)
        params = init_cnn_params(jax.random.PRNGKey(0), spec,
                                 weight_sparsity=0.5)
        x = jax.nn.relu(jax.random.normal(
            jax.random.PRNGKey(1), (batch, size, size, spec.in_ch)))

        # Structural accounting: abstract-trace one forward per mode
        # (records fire at trace time — eval_shape runs no numeric work).
        counts = {}
        for mode, chain in (("chained", True), ("roundtrip", False)):
            with engine.trace_dispatch() as recs:
                jax.eval_shape(
                    lambda p, xx, chain=chain: cnn_forward(
                        p, xx, spec, mnf=True, chain=chain), params, x)
            counts[mode] = dict(
                events_only_boundaries=sum(
                    1 for r in recs if r.get("chained")),
                decodes=sum(1 for r in recs if r.get("decode")),
                fallback_decodes=sum(
                    1 for r in recs if r.get("fallback_decode")),
                pool_events=sum(1 for r in recs if r.get("pool_events")),
                chained_conv_launches=sum(
                    r.get("launches", 0) for r in recs
                    if r.get("chained") and r.get("op") == "conv2d"))
        if counts["chained"]["fallback_decodes"]:
            raise RuntimeError(
                f"cnn_chain[{spec.name}]: chained pipeline hit "
                f"fallback_decode — an eligible strip layer (or a chained "
                f"boundary) silently densified")
        summary = chain_boundary_summary(spec, batch=batch)
        if counts["chained"]["pool_events"] != summary["pool_events"]:
            raise RuntimeError(
                f"cnn_chain[{spec.name}]: {summary['pool_events']} pool "
                f"boundaries are event-eligible but only "
                f"{counts['chained']['pool_events']} rode the event-native "
                f"pool — a conv→pool→conv boundary silently densified")

        # Per-layer launch accounting (taps fused vs per-tap): the strip
        # layers of the chained graph run 1 launch each — including a
        # dense-input conv whose input the chain strip-encodes
        # (_input_stream_blk_m, the AlexNet-head case) — everything else
        # (incl. the whole round-trip twin) pays k*k per conv layer.
        # Strip layers carry their compacted-vs-worst-case subtap counts
        # (dead straddle parts dropped at plan time).
        shapes = _trace_shapes(spec)
        conv_base = _conv_base(_layer_cfg(None, mnf=True,
                                          fire_cfg=FireConfig()))
        per_layer, stream_in, dense_head_launches = [], False, 0
        for i, layer in enumerate(spec.layers):
            h_in, w_in, c_in = shapes[i]
            if isinstance(layer, FCSpec):
                stream_in = False          # FC ends the conv chain
                continue
            if isinstance(layer, PoolSpec):
                # an ineligible pool densifies the chain (dense fallback)
                stream_in = stream_in and engine.pool_ineligible_reason(
                    (batch, h_in, w_in, c_in), layer.k, layer.stride,
                    conv_base) is None
                continue
            if stream_in:
                strip = bool(engine.strip_eligible(
                    w_in, layer.k, layer.stride, layer.padding,
                    co=layer.out_ch))
            else:
                # dense input (chain head / densified seam): strip only
                # when the chain strip-encodes it for the fused kernel
                strip = bool(_input_stream_blk_m(
                    layer, (batch, h_in, w_in, c_in), conv_base))
            if not (stream_in or strip):
                dense_head_launches += layer.k ** 2
            entry = dict(
                layer=i, k=layer.k, w_in=w_in, strip=strip,
                launches_chained=1 if strip else layer.k ** 2,
                launches_roundtrip=layer.k ** 2)
            if strip:
                subtaps, worst = ev.strip_subtap_counts(
                    layer.k, layer.padding, layer.stride)
                entry.update(subtaps=subtaps, subtaps_worst=worst,
                             compaction=round(subtaps / worst, 3))
            per_layer.append(entry)
            stream_in = True
        launches = dict(
            per_layer=per_layer,
            chained_total=sum(l["launches_chained"] for l in per_layer),
            roundtrip_total=sum(l["launches_roundtrip"] for l in per_layer))
        # convs consuming a dense input dispatch on the dense per-tap path
        # (no chained record) unless the chain strip-encoded that input —
        # strip-encoded heads do produce a chained record, so only
        # dense-input non-strip convs are excluded from the traced total
        want = launches["chained_total"] - dense_head_launches
        got = counts["chained"]["chained_conv_launches"]
        if got != want:
            raise RuntimeError(
                f"cnn_chain[{spec.name}]: launch accounting drifted from "
                f"the traced graph (static {want} != traced {got})")

        fns = {mode: make_cnn_pipeline(spec, mnf=True, chain=chain,
                                       donate=False)
               for mode, chain in (("chained", True), ("roundtrip", False))}
        # Compile each once (compile_us), then time the two pipelines in
        # interleaved rounds and keep the per-mode minimum: back-to-back
        # rep loops right after compilation catch allocator/scheduler
        # transients on share-capped CPUs and can swing 2-3x.
        compile_us, best, out = {}, {}, {}
        for mode, fn in fns.items():
            t0 = time.perf_counter()
            out[mode] = fn(params, x)
            jax.block_until_ready(out[mode])
            compile_us[mode] = (time.perf_counter() - t0) * 1e6
            best[mode] = float("inf")
        for _ in range(max(reps, 3)):
            for mode, fn in fns.items():
                t0 = time.perf_counter()
                jax.block_until_ready(fn(params, x))
                best[mode] = min(best[mode],
                                 (time.perf_counter() - t0) * 1e6)
        us_c, cus_c, yc = best["chained"], compile_us["chained"], \
            out["chained"]
        us_r, cus_r, yr = best["roundtrip"], compile_us["roundtrip"], \
            out["roundtrip"]
        entries.append(dict(
            kind="cnn_chain", net=spec.name, input_size=size, batch=batch,
            chained_us=round(us_c, 1), roundtrip_us=round(us_r, 1),
            chained_compile_us=round(cus_c, 1),
            roundtrip_compile_us=round(cus_r, 1),
            speedup=round(us_r / max(us_c, 1e-9), 3),
            bit_exact=bool(jnp.all(yc == yr)),
            launches=launches,
            boundaries=dict(
                conv=n_conv, fc=n_fc, pool=n_pool,
                # chained: pools ride the event-native segment max, so the
                # only densify points left are dense-pool fallbacks
                # (ineligible geometry — 0 on both paper workloads);
                # roundtrip: every boundary is dense.
                chained=dict(densify=summary["densify"],
                             input_encode=summary["input_encode"],
                             **counts["chained"]),
                roundtrip=dict(densify=n_conv + n_fc + n_pool - 1,
                               **counts["roundtrip"]))))
    _merge_bench(out_path, entries, {"cnn_chain"})
    return entries


def _fc_sweep_input(rng, shape, sparsity, blk=8, kblk=16):
    """``_sweep_input`` for flat (batch, features) activations.

    Same block-structured masking idea, but with a 16-wide feature block so
    MNIST-class widths (784 = 49·16) tile exactly — the 32-wide K-block of
    the conv variant does not divide them."""
    m, kd = shape
    x = np.abs(rng.normal(size=shape)).astype(np.float32) + 1e-3
    if sparsity >= 1.0:
        return jnp.zeros(shape, jnp.float32)
    mask = rng.random((max(m // blk, 1), max(kd // kblk, 1))) > sparsity
    mask = np.repeat(np.repeat(mask, blk, axis=0), kblk, axis=1)[:m, :kd]
    return jnp.asarray(x * mask)


def mlp_rows(out_path: str = "BENCH_engine.json", *, smoke=False, batch=8,
             reps=3):
    """Event-native MLP pipeline (mlp_chain entries): the FC family end to
    end — chained fire→EventStream→linear at every boundary, zero densify
    points by construction (DESIGN.md §12).

    Per (net, input sparsity) sweep point: events/token entering the chain
    (the paper's MNIST-class headline quantity), event vs dense MACs
    (Algorithm 2), f32 vs int8 steady-state wall time of the chained
    pipeline, and the exactness-contract flags — f32 chained bitwise ==
    the per-layer round-trip twin, int8 chained bitwise == the fake-quant
    twin (both CI-fatal when they break, like every structural gate here).
    Also CI-fatal: any FC boundary of the chained graph reporting
    fallback_decode — every FC→FC boundary is structurally eligible, so a
    fallback there is the silent-degrade bug class on the new seam.
    """
    from repro.core.fire import FireConfig
    from repro.models.mlp import (LENET_300_100, MLP_MINI, init_mlp_params,
                                  make_mlp_forward, make_mlp_pipeline,
                                  mlp_boundary_summary, run_mlp_with_stats)

    nets = [MLP_MINI] if smoke else [MLP_MINI, LENET_300_100]
    sparsities = (0.0, 0.9) if smoke else (0.0, 0.5, 0.75, 0.9, 0.98)
    rng = np.random.default_rng(0)
    entries = []
    for spec in nets:
        params = init_mlp_params(jax.random.PRNGKey(0), spec,
                                 weight_sparsity=0.5)
        # Structural gate first: abstract-trace the chained graph — every
        # FC boundary must consume events, none may fall back.
        x_sds = jax.ShapeDtypeStruct((batch, spec.in_features), jnp.float32)
        with engine.trace_dispatch() as recs:
            jax.eval_shape(make_mlp_forward(spec, mnf=True), params, x_sds)
        if any(r.get("fallback_decode") for r in recs):
            raise RuntimeError(
                f"mlp_chain[{spec.name}]: an eligible FC boundary reported "
                f"fallback_decode — every FC→FC boundary is structurally "
                f"event-eligible: {recs}")
        summary = mlp_boundary_summary(spec, batch=batch)
        if summary["densify"]:
            raise RuntimeError(
                f"mlp_chain[{spec.name}]: boundary summary reports densify "
                f"points on an all-FC chain: {summary}")

        fq = FireConfig(quantize_to_int8=True)
        fns = dict(
            f32_chained=make_mlp_pipeline(spec, chain=True, donate=False),
            f32_roundtrip=make_mlp_pipeline(spec, chain=False, donate=False),
            int8_chained=make_mlp_pipeline(spec, fire_cfg=fq, chain=True,
                                           donate=False),
            int8_roundtrip=make_mlp_pipeline(spec, fire_cfg=fq, chain=False,
                                             donate=False))
        for sp in sparsities:
            x = _fc_sweep_input(rng, (batch, spec.in_features), sp)
            out = {}
            for name, fn in fns.items():
                out[name] = fn(params, x)
                jax.block_until_ready(out[name])
            best = _interleaved_best(
                {name: (lambda fn=fn: fn(params, x))
                 for name, fn in fns.items()}, reps=reps)
            bit_f32 = bool(jnp.all(out["f32_chained"]
                                   == out["f32_roundtrip"]))
            bit_int8 = bool(jnp.all(out["int8_chained"]
                                    == out["int8_roundtrip"]))
            if not (bit_f32 and bit_int8):
                raise RuntimeError(
                    f"mlp_chain[{spec.name}@sparsity={sp}]: exactness "
                    f"contract broken — f32 bitwise={bit_f32}, int8 "
                    f"fake-quant bitwise={bit_int8} (DESIGN.md §12)")
            _, stats = run_mlp_with_stats(params, x, spec)
            entries.append(dict(
                kind="mlp_chain", net=spec.name, batch=batch,
                in_features=spec.in_features, widths=list(spec.widths),
                sparsity=sp,
                events_per_token=round(
                    sum(s["in_events"] for s in stats) / batch, 1),
                event_macs=round(sum(s["event_macs"] for s in stats), 1),
                dense_macs=round(sum(s["dense_macs"] for s in stats), 1),
                f32_chained_us=round(best["f32_chained"], 1),
                f32_roundtrip_us=round(best["f32_roundtrip"], 1),
                int8_chained_us=round(best["int8_chained"], 1),
                int8_roundtrip_us=round(best["int8_roundtrip"], 1),
                speedup=round(best["f32_roundtrip"]
                              / max(best["f32_chained"], 1e-9), 3),
                int8_vs_f32=round(best["f32_chained"]
                                  / max(best["int8_chained"], 1e-9), 3),
                bit_exact_f32=bit_f32, bit_exact_int8=bit_int8,
                densify=summary["densify"],
                routes=[r["route"] for r in summary["routes"]]))
    _merge_bench(out_path, entries, {"mlp_chain"})
    return entries


def serve_rows(out_path: str = "BENCH_engine.json", *, smoke=False, reps=3):
    """Serving-tier benchmark: the bucketed AOT-warmed replica
    (serve_bench entries, one per batch bucket, plus a replica summary).

    Two replicas are built against the same warm-start cache dir: the
    first with an empty cache (cold — every bucket pays a real trace +
    lower + XLA compile) and the second re-warming from disk (warm — the
    restarted-replica path: per-bucket executable snapshots restore
    finished executables with no trace/lower/compile at all).  Per
    bucket: steady-state requests/s and p50/p99
    latency through the full submit → route → pad → execute → unpad path,
    cold vs warmed compile time, and the bitwise padding check (one real
    row padded up to the bucket == the unpadded bucket-1 forward).  The
    summary row carries replica time-to-first-response cold vs warmed
    under progressive warmup (smallest bucket first, serve, warm the rest
    behind the first response) — the warmed TTFR is the number ROADMAP
    item 1 asks to be an order of magnitude under the cold ``cnn_chain``
    compile, and the ratio is recorded against the cnn_chain entry
    already in the file.  CI-fatal
    (like every mode here) if any steady-state tick recompiles or the
    padding drifts bitwise.
    """
    import tempfile

    from repro.models.cnn import ALEXNET, init_cnn_params
    from repro.serving import ServeEngine, ServeEngineConfig, pad_bucket

    if smoke:
        spec, buckets = _smoke_spec(), (1, 2, 4)
    else:
        spec, buckets = ALEXNET.scaled(64), (1, 8, 32, 128)
    params = init_cnn_params(jax.random.PRNGKey(0), spec,
                             weight_sparsity=0.5)
    rng = np.random.default_rng(0)
    images = np.maximum(rng.standard_normal(
        (max(buckets), spec.input_size, spec.input_size, spec.in_ch),
        dtype=np.float32), 0.0)
    img = images[0]
    # Snapshots go to a fresh directory, so the cold replica finds none
    # (and, with a cache_dir, compiles around the XLA compile cache).
    snapshots = tempfile.TemporaryDirectory(prefix="mnf_serve_bench_")
    cache_dir = snapshots.name

    def replica():
        """Fresh replica against the shared cache, warming progressively:
        the smallest bucket comes up first and answers the first request
        (TTFR), the remaining buckets warm behind it (full_warm)."""
        t0 = time.perf_counter()
        eng = ServeEngine(spec, params,
                          ServeEngineConfig(buckets=buckets,
                                            cache_dir=cache_dir,
                                            aot_warmup=False))
        eng.submit(img)
        eng.run_tick()                     # compiles/restores bucket 1 only
        ttfr_us = (time.perf_counter() - t0) * 1e6
        eng.warm()                         # the rest of the buckets
        return eng, ttfr_us, (time.perf_counter() - t0) * 1e6

    # empty cache: real trace+lower+XLA compiles
    eng_cold, ttfr_cold_us, full_warm_cold_us = replica()
    cold_warmup_s, cold_recompiles = eng_cold.warmup_s, eng_cold.recompiles
    # A restarted replica is a fresh process: drop the cold engine (and its
    # live per-bucket executables) before timing the restart, or the warm
    # snapshot loads pay the cold replica's memory pressure.
    del eng_cold
    gc.collect()
    # restarted replica: executable snapshots off disk
    eng, ttfr_warm_us, full_warm_warm_us = replica()
    warm_recompiles = eng.recompiles

    # Steady-state traffic: each bucket driven at exactly its batch size so
    # routing lands every tick on that bucket (smallest admissible).
    window_us = {}
    for b in buckets:
        t0 = time.perf_counter()
        for _ in range(reps):
            for i in range(b):
                eng.submit(images[i])
            eng.run_tick()
        window_us[b] = (time.perf_counter() - t0) * 1e6
    if eng.recompiles != warm_recompiles:
        raise RuntimeError(
            f"serve_bench[{spec.name}]: {eng.recompiles - warm_recompiles} "
            f"steady-state recompiles — the jit cache-miss counter must "
            f"stay flat after warmup")

    # Bitwise padding: within each bucket executable, a real row's logits
    # must not depend on what the other rows hold (zeros vs other real
    # images) — zero rows ride as event-free streams and per-sample row
    # groups are independent, so padding is bitwise-inert.  Cross-bucket
    # agreement (the same image through different bucket shapes) is
    # reported separately: XLA picks different GEMM kernels for the dense
    # FC head at different batch shapes, so it is allclose, and bitwise
    # only where the kernel choice coincides (asserted strictly on the
    # mini gate net in `serve --smoke`).
    ref = np.asarray(eng._compiled(1)(
        eng.params, eng._place(1, img[None])))[0]
    entries = []
    for b in buckets:
        got = np.asarray(eng._compiled(b)(
            eng.params, eng._place(b, pad_bucket([img], b))))[0]
        full = np.asarray(eng._compiled(b)(
            eng.params, eng._place(b, pad_bucket(list(images[:b]), b))))[0]
        bit_exact = bool(np.array_equal(got, full))
        if not bit_exact:
            raise RuntimeError(
                f"serve_bench[{spec.name}]: bucket {b} real-row logits "
                f"changed with the padding rows — padding is not "
                f"bitwise-inert")
        if not np.allclose(ref, got, atol=1e-4, rtol=1e-4):
            raise RuntimeError(
                f"serve_bench[{spec.name}]: bucket {b} logits diverged "
                f"from the bucket-1 forward beyond kernel-selection noise")
        stats_b = eng.stats()["per_bucket"][b]
        # cold warmup = lower+compile seconds; warm warmup = either an
        # executable-snapshot load_s or a cache-assisted recompile (the
        # entries also hold the memory plan's bytes: seconds end in _s).
        compile_cold_us = _seconds(cold_warmup_s[b]) * 1e6
        warm_us = _seconds(eng.warmup_s[b]) * 1e6
        entries.append(dict(
            kind="serve_bench", net=spec.name, input_size=spec.input_size,
            bucket=b, requests=stats_b["requests"],
            requests_s=round(b * reps / max(window_us[b] * 1e-6, 1e-9), 2),
            p50_ms=stats_b["p50_ms"], p99_ms=stats_b["p99_ms"],
            compile_cold_us=round(compile_cold_us, 1),
            warm_us=round(warm_us, 1),
            warm_mode=("snapshot" if "load_s" in eng.warmup_s[b]
                       else "compile"),
            warm_speedup=round(compile_cold_us / max(warm_us, 1e-9), 2),
            bit_exact_padding=bit_exact,
            data_shards=eng.plans[b].data_shards))

    # Replica summary: warmed TTFR vs the cold cnn_chain compile already
    # on file (the order-of-magnitude claim, stated as a ratio).
    chain_compile_us = None
    if os.path.exists(out_path):
        try:
            with open(out_path) as f:
                for e in json.load(f).get("entries", []):
                    if (e.get("kind") == "cnn_chain"
                            and e.get("net") == spec.name):
                        chain_compile_us = e["chained_compile_us"]
        except (json.JSONDecodeError, OSError):
            pass
    stats = eng.stats()
    entries.append(dict(
        kind="serve_bench_summary", net=spec.name,
        input_size=spec.input_size, buckets=list(buckets),
        devices=stats["devices"], mnf=True,
        ttfr_cold_us=round(ttfr_cold_us, 1),
        ttfr_warm_us=round(ttfr_warm_us, 1),
        full_warm_cold_us=round(full_warm_cold_us, 1),
        full_warm_warm_us=round(full_warm_warm_us, 1),
        restart_speedup=round(full_warm_cold_us
                              / max(full_warm_warm_us, 1e-9), 2),
        cold_cnn_chain_compile_us=chain_compile_us,
        warm_ttfr_vs_cold_compile=(
            round(ttfr_warm_us / chain_compile_us, 4)
            if chain_compile_us else None),
        recompiles_warmup=cold_recompiles,
        snapshot_hits_warm=eng.snapshot_hits,
        recompiles_steady=eng.recompiles - warm_recompiles))
    _merge_bench(out_path, entries, {"serve_bench", "serve_bench_summary"})
    return entries


def _lm_decode_gate():
    """Mini recurrent net (CI-fatal): the reduced rwkv6 + hymba decode
    steps run with MNF on — every eligible recurrent boundary must chain,
    none may fall back (the silent-degrade bug class on the new seam)."""
    import dataclasses

    from repro.configs import get_config
    from repro.models.ssm import (mamba_init, mamba_step, rwkv6_block_apply,
                                  rwkv6_block_decode, rwkv6_block_init)
    rng = np.random.default_rng(0)
    recs_all = []
    # rwkv6 token step
    cfg = get_config("rwkv6-7b").reduced()
    cfg = dataclasses.replace(cfg, compute_dtype="float32")
    p, _ = rwkv6_block_init(jax.random.PRNGKey(0), cfg)
    x = jnp.asarray(rng.normal(size=(2, 4, cfg.d_model)).astype(np.float32))
    _, st = rwkv6_block_apply(p, x, cfg)
    tok = jnp.asarray(rng.normal(size=(2, 1, cfg.d_model)).astype(np.float32))
    with engine.trace_dispatch() as recs:
        rwkv6_block_decode(p, tok, cfg, st)
    recs_all.extend(recs)
    # hymba mamba token step
    mcfg = get_config("hymba-1.5b").reduced()
    mcfg = dataclasses.replace(mcfg, compute_dtype="float32",
                               ssm=dataclasses.replace(mcfg.ssm, expand=1))
    mp, _ = mamba_init(jax.random.PRNGKey(1), mcfg, d_inner=mcfg.d_model)
    conv = jnp.zeros((2, mcfg.ssm.conv_dim - 1, mcfg.d_model), jnp.float32)
    h = jnp.zeros((2, mcfg.d_model, mcfg.ssm.state_dim), jnp.float32)
    xt = jnp.asarray(rng.normal(size=(2, 1, mcfg.d_model)).astype(np.float32))
    with engine.trace_dispatch() as recs:
        mamba_step(mp, xt, mcfg, (conv, h), with_events=True)
    recs_all.extend(recs)
    rec_recs = [r for r in recs_all if r.get("op") == "recurrent_step"]
    bad = [r for r in rec_recs if r.get("fallback_decode")]
    if bad:
        raise RuntimeError(
            f"lm_decode: an eligible recurrent boundary reported "
            f"fallback_decode — the token-step state update must consume "
            f"the fired event stream: {bad}")
    if not any(r.get("chained") for r in rec_recs):
        raise RuntimeError(
            f"lm_decode: no chained recurrent_step record — the gated "
            f"decode path did not dispatch at all: {rec_recs}")


def lm_decode_rows(out_path: str = "BENCH_engine.json", *, smoke=False,
                   reps=3):
    """Fire-gated recurrent decode (lm_decode entries, DESIGN.md §13).

    Per (kind, backend, threshold) sweep point: fired events/token of the
    delta-fired drive, gated vs dense-step steady-state wall time, output
    drift of the gated step against the ungated dense step (the
    threshold/quality trade the sweep exposes), and the exactness flags —
    ``bit_exact`` at threshold 0 is the block backend's cross-formulation
    contract (gated == dense step bitwise); the pallas kernel's contract is
    within-backend (``bit_within_backend``: gated == the same kernel on an
    all-live drive — interpret mode contracts mul-add chains into FMAs, a
    1-ulp formulation difference vs the jnp tree).  Also runs the mini
    recurrent net structural gate: CI-fatal on any eligible-boundary
    fallback_decode.
    """
    import dataclasses

    from repro.engine.stream import EventStream
    from repro.kernels.mamba_scan.step import mamba_step_ref
    from repro.kernels.wkv6.step import wkv6_step_ref

    _lm_decode_gate()
    rng = np.random.default_rng(0)
    if smoke:
        geoms = dict(wkv6=(8, 16), mamba=(4, 32, 8))
        thresholds = (0.0, 0.3)
        backends = ("block", "pallas")
    else:
        geoms = dict(wkv6=(32, 64), mamba=(8, 128, 16))
        thresholds = (0.0, 0.1, 0.3, 1.0)
        backends = ("block", "pallas")
    entries = []
    for kind in ("wkv6", "mamba"):
        if kind == "wkv6":
            g, d = geoms[kind]
            drive = jnp.asarray(rng.normal(size=(g, d)).astype(np.float32))
            state = jnp.asarray(
                rng.normal(size=(g, d, d)).astype(np.float32))
            ops = dict(
                r=jnp.asarray(rng.normal(size=(g, d)).astype(np.float32)),
                v=jnp.asarray(rng.normal(size=(g, d)).astype(np.float32)),
                w=jnp.asarray(
                    rng.uniform(0.3, 0.99, (g, d)).astype(np.float32)),
                u=jnp.asarray(rng.normal(size=(g, d)).astype(np.float32)))
            dense_ref = wkv6_step_ref
            dense_args = lambda dr: (ops["r"], dr, ops["v"], ops["w"],
                                     ops["u"], state)
            shape = dict(g=g, d=d)
        else:
            b, di, n = geoms[kind]
            g, d = b, di
            drive = jnp.asarray(rng.normal(size=(b, di)).astype(np.float32))
            state = jnp.asarray(
                rng.normal(size=(b, di, n)).astype(np.float32))
            ops = dict(
                da=jnp.asarray(
                    rng.uniform(0.3, 0.99, (b, di, n)).astype(np.float32)),
                bmat=jnp.asarray(
                    rng.normal(size=(b, n)).astype(np.float32)),
                cmat=jnp.asarray(
                    rng.normal(size=(b, n)).astype(np.float32)))
            dense_ref = mamba_step_ref
            dense_args = lambda dr: (dr, ops["da"], ops["bmat"],
                                     ops["cmat"], state)
            shape = dict(b=b, d_inner=di, state_dim=n)
        # The quality yardstick: the ungated dense step on the raw drive.
        # Timing is jitted; the exactness flags compare EAGER evaluations —
        # the contract is formulation-level (event path vs dense step) and
        # must not be confounded by XLA fusion-order differences between a
        # jitted and an un-jitted program.
        o_full = dense_ref(*dense_args(drive))[0]
        dense_us, dense_compile_us, _ = _timeit(
            jax.jit(lambda dr: dense_ref(*dense_args(dr))), drive,
            reps=reps)
        for backend in backends:
            for th in thresholds:
                cfg = engine.EngineConfig(
                    backend=backend,
                    threshold=th).for_recurrent(d).resolved()
                stream = engine.fire_delta(drive, cfg)
                events = float(stream.num_scalar_events)

                # The served token step jits fire + state update as one
                # program — time the same thing here.
                @jax.jit
                def gated(dr, cfg=cfg):
                    st = engine.fire_delta(dr, cfg)
                    return engine.recurrent_step(kind, st, state, cfg,
                                                 **ops)
                us, compile_us, _ = _timeit(gated, drive, reps=reps)
                o, _ = engine.recurrent_step(kind, stream, state, cfg,
                                             **ops)
                fired = jnp.where(jnp.abs(drive) > th, drive, 0.0)
                o_ref = dense_ref(*dense_args(fired))[0]
                bit = bool(jnp.all(o == o_ref)) if th == 0.0 else None
                al = dataclasses.replace(
                    EventStream.encode(stream.dense(), blk_m=1,
                                       blk_k=stream.blk_k, threshold=-1.0),
                    signed=True)
                o_al, _ = engine.recurrent_step(kind, al, state, cfg, **ops)
                drift = float(jnp.max(jnp.abs(o - o_full)))
                entries.append(dict(
                    kind="lm_decode", op=kind, backend=backend,
                    threshold=th, **shape,
                    events_per_token=round(events / max(g, 1), 2),
                    events_total=events,
                    density=round(events / max(g * d, 1), 4),
                    us=round(us, 1), compile_us=round(compile_us, 1),
                    dense_us=round(dense_us, 1),
                    dense_compile_us=round(dense_compile_us, 1),
                    speedup_vs_dense=round(dense_us / max(us, 1e-9), 3),
                    bit_exact=bit,
                    bit_within_backend=bool(jnp.all(o == o_al)),
                    max_drift_vs_dense=drift))
                if th == 0.0 and backend == "block" and not bit:
                    raise RuntimeError(
                        f"lm_decode[{kind}/block]: gated step is not "
                        f"bitwise the dense step at threshold 0 "
                        f"(DESIGN.md §13 contract)")
                if not entries[-1]["bit_within_backend"]:
                    raise RuntimeError(
                        f"lm_decode[{kind}/{backend}@{th}]: gating changed "
                        f"the numbers — gated != all-live through the same "
                        f"kernel (within-backend contract)")
    _merge_bench(out_path, entries, {"lm_decode"})
    return entries


def _adaptive_case(mk: dict, stream, *, op: str, reps=3):
    """One adaptive-vs-static contest on a shared input stream.

    ``mk`` maps route names (one of them "adaptive") to un-jitted
    single-arg callables differing only in their EngineConfig.route.
    Returns (paired_best_us, route, exec_identical):

      * paired_best_us — interleaved-minimum wall time per contender;
      * route — the route the adaptive dispatch actually took (traced
        records, no numeric work);
      * exec_identical — whether the adaptive jaxpr is *textually
        identical* to the chosen static route's jaxpr.  Routing is
        trace-time static, so this is normally True — and it proves the
        adaptive pick costs exactly what that static route costs,
        immunizing the gate against the CPU harness's wall-clock noise
        (identical executables re-timed here spread up to ~35%).
    """
    with engine.trace_dispatch() as recs:
        jax.eval_shape(mk["adaptive"], stream)
    routes = [r["route"] for r in recs if r.get("op") == op]
    route = routes[-1] if routes else None
    exec_identical = bool(
        route in mk and str(jax.make_jaxpr(mk["adaptive"])(stream))
        == str(jax.make_jaxpr(mk[route])(stream)))
    fns = {name: jax.jit(f) for name, f in mk.items()}
    best = _interleaved_best(
        {name: (lambda fn=fn: fn(stream)) for name, fn in fns.items()},
        reps=reps)
    return best, route, exec_identical


def _interleaved_best(fns: dict, reps=3) -> dict:
    """Per-key minimum over interleaved timing rounds.

    Ratios between the keys are what matters (adaptive vs each static
    route): interleaving means a scheduler transient hits every
    contender equally instead of whichever ran back-to-back (the
    ``cnn_chain_rows`` technique)."""
    for fn in fns.values():
        jax.block_until_ready(fn())            # compile outside timing
    best = {k: float("inf") for k in fns}
    for _ in range(max(reps, 3)):
        for k, fn in fns.items():
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            best[k] = min(best[k], (time.perf_counter() - t0) * 1e6)
    return best


def _sweep_input(rng, shape, sparsity, blk=8):
    """Non-negative activations with *block-structured* sparsity.

    The engine's occupancy is block-granular (live fraction of the
    row-group × K-block event grid), so elementwise masking saturates it —
    one live element keeps the whole block live.  Masking whole
    (8-row-strip × 8-channel-block) tiles makes stream occupancy track
    ``1 - sparsity`` with exact endpoints: sparsity 0.0 → occupancy 1.0,
    sparsity 1.0 → zero events."""
    x = np.abs(rng.normal(size=shape)).astype(np.float32) + 1e-3
    if sparsity >= 1.0:
        return jnp.zeros(shape, jnp.float32)
    if len(shape) == 4:
        b, h, w0, c = shape
        mask = rng.random((b, h, max(w0 // blk, 1),
                           max(c // blk, 1))) > sparsity
        mask = np.repeat(np.repeat(mask, blk, axis=2), blk, axis=3)
        mask = mask[:, :, :w0, :c]
    else:
        m, kd = shape
        mask = rng.random((max(m // blk, 1), max(kd // 32, 1))) > sparsity
        mask = np.repeat(np.repeat(mask, blk, axis=0), 32, axis=1)
        mask = mask[:m, :kd]
    return jnp.asarray(x * mask)


def sweep_rows(out_path: str = "BENCH_engine.json", *, smoke=False, reps=5):
    """Occupancy sweep 0 → 1 (exact endpoints) over conv / pool / linear
    boundaries: every route timed at matched shapes per sweep point.

    Two entry kinds come out of one pass:

      * ``crossover`` — per (boundary, backend, shape_class, occupancy)
        the measured per-route microseconds.  These seed the calibrated
        :class:`repro.costmodel.crossover.CrossoverTable` that adaptive
        routing consults — the sweep is the calibration run.
      * ``adaptive`` — the ``route="adaptive"`` dispatch re-timed
        end-to-end at each point with the just-measured table installed.
        Routing is trace-time static, so the adaptive executable *is* the
        chosen route's executable; ``overhead_vs_best`` states how far the
        router's pick sits from the best static route at that point
        (≤ 1.05 is the acceptance bar), and ``vs_static_event`` shows the
        win over always-event at the losing shapes (1×1/stride-2 conv,
        full-occupancy pallas linear).

    The pool rows additionally record the *raw* window-major kernel
    against the dense ``reduce_window`` (no re-encode on either side,
    capacity clamped to the probe's live-block maximum — lossless): the
    window-major grid (8/parts step reduction) + capacity clamp is the
    rework that wins on raw steady-state time at high sparsity.

    Raises if any adaptive pick is slower than the best static route by
    more than ROUTE_HYSTERESIS — an unambiguously wrong decision, not
    timing noise.
    """
    from repro.costmodel import crossover as xover
    from repro.kernels.event_pool.ops import pool_window_plan

    rng = np.random.default_rng(0)
    sparsities = (0.0, 0.5, 1.0) if smoke \
        else (0.0, 0.25, 0.5, 0.75, 0.9, 0.95, 1.0)
    entries: list[dict] = []
    adaptive_cases: list[dict] = []

    # -- conv boundaries: strip vs pixel vs dense ---------------------------
    # (B, H, W, CI, CO, k, padding, stride); the second row is the measured
    # losing shape (1×1/stride-2 — taps touch 1/4 of the map, event
    # overhead can't amortize) the adaptive router must route dense; the
    # k3s4 row calibrates the stride-4 straddle-plan class (5 parts,
    # dead-subtap-compacted grid) the AlexNet-head boundary prices.
    conv_shapes = [(2, 16, 16, 8, 16, 3, 1, 1)]
    if not smoke:
        conv_shapes += [(1, 9, 16, 8, 8, 1, 0, 2),
                        (1, 8, 32, 8, 8, 3, 1, 4)]
    for (b, h, w0, ci, co, k, p, st) in conv_shapes:
        wgt = jnp.asarray(rng.normal(size=(k, k, ci, co)).astype(np.float32))
        cfg = engine.EngineConfig(backend="block", blk_m=1, blk_k=8,
                                  blk_n=8)
        strip_ok = engine.strip_eligible(w0, k, st, p, co=co)
        for sp in sparsities:
            # Every route is timed through the engine on a twin-kept
            # stream of the granularity that can ride it (same as the
            # adaptive dispatch will see): the boundary's currency is an
            # EventStream, and dense-by-choice reads the kept twin — the
            # crossover table must price exactly that.
            x = _sweep_input(rng, (b, h, w0, ci), sp)
            pixel = engine.fire_conv(x, cfg, blk_m=1, keep_dense=True)
            occ = float(pixel.occupancy())
            strip = engine.fire_conv(x, cfg, blk_m=engine.STRIP_W,
                                     keep_dense=True) if strip_ok else None
            # Interleaved-minimum timing across the routes of one sweep
            # point: the table's anchors are *ratios* between these keys,
            # so a scheduler transient must hit every route equally — a
            # sequential outlier on one route would mis-teach the table
            # (and the adaptive pass would expose it as a wrong pick).
            fns = {}
            for route, stream_r in ([("strip", strip)] if strip_ok else []) \
                    + [("pixel", pixel),
                       ("dense", strip if strip_ok else pixel)]:
                rcfg = cfg.replace(route=route)
                fn = jax.jit(lambda s, rc=rcfg: engine.conv2d(
                    s, wgt, cfg=rc, stride=st, padding=p))
                fns[route] = (lambda f=fn, s=stream_r: f(s))
            us = _interleaved_best(fns, reps=reps)
            entries.append(dict(
                kind="crossover", boundary="conv", backend="block",
                shape_class=f"k{k}s{st}", b=b, h=h, w=w0, ci=ci, co=co,
                k=k, padding=p, stride=st, sparsity=sp,
                occupancy=round(occ, 4),
                us={r: round(v, 1) for r, v in us.items()}))

            def run_conv(occ=occ, cfg=cfg, wgt=wgt, st=st, p=p,
                         strip_ok=strip_ok, strip=strip, pixel=pixel):
                acfg = cfg.replace(route="adaptive", occupancy_hint=occ)
                s = strip if strip_ok else pixel
                cfgs = {"adaptive": acfg}
                for r in (("strip", "dense") if strip_ok
                          else ("pixel", "dense")):
                    cfgs[r] = cfg.replace(route=r)
                mk = {name: (lambda ss, rc=rc: engine.conv2d(
                    ss, wgt, cfg=rc, stride=st, padding=p))
                    for name, rc in cfgs.items()}
                return _adaptive_case(mk, s, op="conv2d", reps=reps)
            adaptive_cases.append(dict(
                boundary="conv", backend="block", shape_class=f"k{k}s{st}",
                sparsity=sp, occupancy=occ, us=us, run=run_conv,
                achievable=(("strip", "dense") if strip_ok
                            else ("pixel", "dense"))))

    # -- pool boundaries: window vs pixel vs dense-by-choice ----------------
    # (B, H, W, C, k, stride); the wide-channel row is the raw-time contest:
    # reduce_window reads k²·C floats per output pixel while the
    # capacity-clamped window grid touches only live blocks.
    pool_shapes = [(2, 16, 16, 128, 2, 2)]
    if not smoke:
        pool_shapes.append((2, 16, 16, 16, 2, 2))
    for (b, h, w0, c, k, st) in pool_shapes:
        cfg = engine.EngineConfig(backend="block", blk_m=engine.STRIP_W,
                                  blk_k=8)
        for sp in sparsities:
            x = _sweep_input(rng, (b, h, w0, c), sp)
            probe = engine.fire_conv(x, cfg, blk_m=engine.STRIP_W,
                                     keep_dense=False)
            cap = max(int(jnp.max(probe.events.counts)), 1)
            ccfg = cfg.replace(capacity=cap)
            stream = engine.fire_conv(x, ccfg, blk_m=engine.STRIP_W,
                                      keep_dense=True)
            occ = float(stream.occupancy())
            fns = {}
            for route in ("window", "pixel", "dense"):
                rcfg = ccfg.replace(route=route)
                fn = jax.jit(lambda s, rcfg=rcfg: engine.maxpool2d(
                    s, k, st, cfg=rcfg))
                fns[route] = (lambda f=fn: f(stream))
            us = _interleaved_best(fns, reps=reps)
            # Raw kernel vs raw reduce_window: no re-emission on either
            # side — the kernel-rework claim, separated from boundary cost.
            raw_w = jax.jit(lambda s: engine.get_backend(
                "maxpool2d_events_window", "block")(s, k, st, ccfg))
            raw_d = jax.jit(lambda xx: engine.maxpool2d(xx, k, st, cfg=ccfg))
            no_twin = stream.without_dense()
            raw_us = _interleaved_best(
                dict(window=lambda: raw_w(no_twin), dense=lambda: raw_d(x)),
                reps=reps)
            us_rw, us_rd = raw_us["window"], raw_us["dense"]
            yw, yd = raw_w(no_twin), raw_d(x)
            plan = pool_window_plan((b, h, w0, c), k, st,
                                    nkb=stream.events.num_k_blocks,
                                    capacity=cap)
            entries.append(dict(
                kind="crossover", boundary="pool", backend="block",
                shape_class=f"k{k}s{st}c{c}", b=b, h=h, w=w0, c=c, k=k,
                stride=st, sparsity=sp, occupancy=round(occ, 4),
                capacity=cap,
                us={r: round(v, 1) for r, v in us.items()},
                raw_window_us=round(us_rw, 1), raw_dense_us=round(us_rd, 1),
                raw_speedup=round(us_rd / max(us_rw, 1e-9), 3),
                raw_bit_exact=bool(jnp.all(
                    yw.reshape(yd.shape) == yd)),
                grid_reduction=round(plan["grid_reduction"], 2),
                parts=plan["parts"]))

            def run_pool(occ=occ, ccfg=ccfg, stream=stream, k=k, st=st):
                acfg = ccfg.replace(route="adaptive", occupancy_hint=occ)
                mk = {name: (lambda s, rc=rc: engine.maxpool2d(
                    s, k, st, cfg=rc))
                    for name, rc in (("adaptive", acfg),
                                     ("window", ccfg.replace(
                                         route="window")),
                                     ("dense", ccfg.replace(
                                         route="dense")))}
                return _adaptive_case(mk, stream, op="maxpool2d", reps=reps)
            adaptive_cases.append(dict(
                boundary="pool", backend="block",
                shape_class=f"k{k}s{st}c{c}",
                sparsity=sp, occupancy=occ, us=us, run=run_pool,
                achievable=("window", "dense")))

    # -- linear boundaries: event vs dense ----------------------------------
    # The pallas chained linear is the other measured losing case (0.87x at
    # full occupancy) the adaptive router must route dense.
    m, kd, n = 32, 256, 128
    wl = jnp.asarray(rng.normal(size=(kd, n)).astype(np.float32))
    for backend in (("block",) if smoke else ("block", "pallas")):
        cfg = engine.EngineConfig(backend=backend, blk_m=8, blk_k=32,
                                  blk_n=32)
        for sp in sparsities:
            a = _sweep_input(rng, (m, kd), sp)
            stream = engine.fire(a, cfg)       # twin kept, like dispatch
            occ = float(stream.occupancy())
            ecfg2 = cfg.replace(route="event")
            fn_e = jax.jit(lambda s: engine.linear(s, wl, cfg=ecfg2))
            dcfg2 = cfg.replace(route="dense")
            fn_d = jax.jit(lambda s: engine.linear(s, wl, cfg=dcfg2))
            us = _interleaved_best(
                dict(event=lambda: fn_e(stream),
                     dense=lambda: fn_d(stream)), reps=reps)
            entries.append(dict(
                kind="crossover", boundary="linear", backend=backend,
                shape_class=f"n{n}", m=m, k=kd, n=n, sparsity=sp,
                occupancy=round(occ, 4),
                us={r: round(v, 1) for r, v in us.items()}))

            def run_linear(occ=occ, cfg=cfg, stream=stream, wl=wl):
                acfg = cfg.replace(route="adaptive", occupancy_hint=occ)
                mk = {name: (lambda s, rc=rc: engine.linear(s, wl, cfg=rc))
                      for name, rc in (("adaptive", acfg),
                                       ("event", cfg.replace(
                                           route="event")),
                                       ("dense", cfg.replace(
                                           route="dense")))}
                return _adaptive_case(mk, stream, op="linear", reps=reps)
            adaptive_cases.append(dict(
                boundary="linear", backend=backend, shape_class=f"n{n}",
                sparsity=sp, occupancy=occ, us=us, run=run_linear,
                achievable=("event", "dense")))

    # -- adaptive pass: route with the just-measured table installed --------
    table = xover.CrossoverTable(entries)
    prev = xover.set_active_table(table)
    try:
        for case in adaptive_cases:
            # Paired interleaved timings of the adaptive dispatch and the
            # routes *achievable from this stream's granularity* — the
            # flavor is producer-bound (a strip stream cannot
            # retroactively ride the per-tap path), so those are the
            # static choices the router actually arbitrates.
            paired, route, exec_identical = case["run"]()
            adaptive_us = paired.pop("adaptive")
            # The calibration pass timed these exact executables on these
            # exact streams (same cfg, same input — same jit graph): its
            # minima are more samples of the same program, so pool them.
            # This keeps the published table and the adaptive judgment one
            # consistent measurement set — two phases disagreeing inside
            # the noise floor about a near-crossover point must not read
            # as a routing error.
            for r, v in case["us"].items():
                if r in paired:
                    paired[r] = min(paired[r], v)
            best_route = min(paired, key=paired.get)
            best_us = paired[best_route]
            ev_us = [v for r, v in paired.items()
                     if r in xover.EVENT_ROUTES]
            static_event_us = min(ev_us) if ev_us else None
            # When the router picked the paired-best route, the adaptive
            # executable IS that route's executable (jaxpr-identical) —
            # overhead 1.0 by construction, not by a second noisy
            # measurement.  Only a divergent pick is judged on wall time.
            # Judging a divergent pick: the adaptive executable is jaxpr-
            # identical to its chosen static route's, so their timings
            # sample the *same program* — pool the minima.  A pick still
            # over the acceptance bar after pooling gets bounded
            # confirmation rounds (all-route re-timings, minima pooled):
            # near-crossover boundaries sit inside the harness noise floor
            # and a single calibration-vs-judgment disagreement there is
            # not a routing error.  The hysteresis raise below still
            # catches unambiguous misses — pooling sharpens both sides.
            rounds = 0
            while True:
                if exec_identical and route in paired:
                    pooled = min(adaptive_us, paired[route])
                    adaptive_us = paired[route] = pooled
                best_route = min(paired, key=paired.get)
                best_us = paired[best_route]
                if route == best_route and exec_identical:
                    overhead = 1.0
                    break
                overhead = adaptive_us / max(best_us, 1e-9)
                if overhead <= 1.05 or rounds >= 3:
                    break
                rounds += 1
                paired2, _, exec2 = case["run"]()
                adaptive_us = min(adaptive_us, paired2.pop("adaptive"))
                for r, v in paired2.items():
                    paired[r] = min(paired[r], v)
                exec_identical = exec_identical or exec2
            entries.append(dict(
                kind="adaptive", boundary=case["boundary"],
                backend=case["backend"], shape_class=case["shape_class"],
                sparsity=case["sparsity"],
                occupancy=round(case["occupancy"], 4), route=route,
                exec_identical=exec_identical,
                adaptive_us=round(adaptive_us, 1),
                achievable=list(case["achievable"]),
                best_route=best_route, best_us=round(best_us, 1),
                static_event_us=(round(static_event_us, 1)
                                 if static_event_us is not None else None),
                vs_static_event=(round(static_event_us
                                       / max(adaptive_us, 1e-9), 3)
                                 if static_event_us is not None else None),
                overhead_vs_best=round(overhead, 3)))
            if overhead > 1.0 + xover.ROUTE_HYSTERESIS:
                raise RuntimeError(
                    f"sweep[{case['boundary']}/{case['shape_class']}@occ="
                    f"{case['occupancy']:.2f}]: adaptive picked {route} at "
                    f"{adaptive_us:.1f}us, {overhead:.2f}x the best static "
                    f"route {best_route} ({best_us:.1f}us) — beyond the "
                    f"hysteresis band, an unambiguously wrong decision")
    finally:
        xover.set_active_table(prev)
    _merge_bench(out_path, entries, {"crossover", "adaptive"})
    return entries


def route_gate(out_path: str = "BENCH_engine.json"):
    """CI smoke gate (DESIGN.md §11): re-derive every routing decision of
    the smoke nets in adaptive mode across occupancy hints and **fail** if
    any decision contradicts the committed crossover table by more than
    ROUTE_HYSTERESIS (``route_conflicts``), or if adaptive mode ever
    yields a fallback_decode on an eligible net — dense by *choice* is
    ``routed_dense``, never a fallback.  No numeric work: decisions are
    trace-time static, so ``jax.eval_shape`` under the dispatch tracer
    sees exactly what a compiled graph would do."""
    from repro.costmodel import crossover as xover
    from repro.models.cnn import MINI_S4, init_cnn_params, make_cnn_forward

    table = xover.load_crossover_table(out_path)
    if not len(table):
        print(json.dumps(dict(kind="route_gate",
                              skipped=f"no crossover entries in "
                                      f"{out_path} — run --sweep first")))
        return
    prev = xover.set_active_table(table)
    try:
        records = []
        for spec, size in ((_smoke_spec(), 8), (_smoke_ds_spec(), 16),
                           (MINI_S4, 32)):
            spec = spec.scaled(size)
            params = init_cnn_params(jax.random.PRNGKey(0), spec,
                                     weight_sparsity=0.5)
            x = jax.ShapeDtypeStruct(
                (2, spec.input_size, spec.input_size, spec.in_ch),
                jnp.float32)
            for occ in (0.05, 0.5, 1.0):
                cfg = engine.EngineConfig(backend="auto", route="adaptive",
                                          occupancy_hint=occ)
                fwd = make_cnn_forward(spec, mnf=True, engine_cfg=cfg)
                with engine.trace_dispatch() as recs:
                    jax.eval_shape(fwd, params, x)
                records.extend(recs)
        conflicts = xover.route_conflicts(records, table)
        if conflicts:
            raise RuntimeError(
                f"route gate: {len(conflicts)} decision(s) contradict the "
                f"crossover table beyond the {xover.ROUTE_HYSTERESIS:.0%} "
                f"hysteresis band: {conflicts}")
        fallbacks = [r for r in records if r.get("fallback_decode")]
        if fallbacks:
            raise RuntimeError(
                f"route gate: adaptive mode produced fallback_decode on an "
                f"eligible net (dense-by-choice must be routed_dense): "
                f"{fallbacks}")
        decided = [r for r in records if r.get("route") is not None]
        print(json.dumps(dict(
            kind="route_gate", decisions=len(decided), conflicts=0,
            fallback_decodes=0,
            routes={r: sum(1 for d in decided if d["route"] == r)
                    for r in sorted({d["route"] for d in decided})})))
    finally:
        xover.set_active_table(prev)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--engine", action="store_true",
                    help="sweep EngineConfig.backend and write "
                         "BENCH_engine.json")
    ap.add_argument("--cnn-chain", action="store_true",
                    help="time the event-resident CNN pipeline vs the "
                         "per-layer round-trip (cnn_chain entries)")
    ap.add_argument("--conv-fused", action="store_true",
                    help="time the fused strip-tiled conv kernel (one "
                         "launch/layer) vs the per-tap chained path "
                         "(conv_fused entries)")
    ap.add_argument("--pool", action="store_true",
                    help="time the event-native max-pool (events in -> "
                         "events out) vs the dense pool + re-encode "
                         "round-trip (pool entries)")
    ap.add_argument("--serve", action="store_true",
                    help="benchmark the bucketed AOT-warmed serving "
                         "replica: requests/s + p50/p99 per bucket, cold "
                         "compile vs snapshot restore and replica "
                         "TTFR (serve_bench entries)")
    ap.add_argument("--mlp", action="store_true",
                    help="benchmark the event-native MLP chain (mlp_chain "
                         "entries): events/token at swept input sparsity, "
                         "int8 vs f32 steady-state, and the per-layer "
                         "exactness-contract flags; fails on any eligible "
                         "FC boundary reporting fallback_decode")
    ap.add_argument("--lm-decode", action="store_true",
                    help="benchmark the fire-gated recurrent decode "
                         "(lm_decode entries): events/token across a "
                         "threshold sweep, gated vs dense-step "
                         "steady-state, output drift, and the exactness "
                         "flags (block bitwise at threshold 0; pallas "
                         "bitwise within-backend); fails on any "
                         "eligible recurrent boundary reporting "
                         "fallback_decode in the mini recurrent net")
    ap.add_argument("--sweep", action="store_true",
                    help="occupancy sweep 0-1 over conv/pool/linear "
                         "boundaries: per-route microseconds at each point "
                         "(crossover entries — the adaptive routing "
                         "table) plus the adaptive router re-timed "
                         "end-to-end against the best static route "
                         "(adaptive entries); combine with --smoke for "
                         "the fast CI subset")
    ap.add_argument("--smoke", action="store_true",
                    help="fast CI subset: 1-rep kernel microbench + engine "
                         "sweep + mini-net cnn chains (incl. a stride-4 "
                         "net whose mid-layer must ride the fused straddle "
                         "plan) + stride-1/2/4 conv_fused shapes and "
                         "one pool shape + the MLP mini-net chain + a "
                         "mini serving replica — keeps "
                         "every benchmark path from rotting and fails on "
                         "strip-layer or pool-boundary fallback_decode, "
                         "steady-state recompiles, or padding drift")
    ap.add_argument("--out", default="BENCH_engine.json")
    args = ap.parse_args()
    if args.smoke:
        if args.sweep:
            # Slow-lane CI subset: 3 sparsity points, one shape per
            # boundary kind, block backend — exercises the whole sweep +
            # adaptive machinery without the full calibration cost.
            for e in sweep_rows(args.out, smoke=True, reps=2):
                print(json.dumps(e))
            return
        for name, us, compile_us, derived in rows(reps=1):
            print(f"{name},{us:.1f},compile={compile_us:.1f},{derived}")
        for e in engine_rows(args.out, reps=1):
            print(json.dumps(e))
        for e in cnn_chain_rows(args.out, smoke=True, reps=1):
            print(json.dumps(e))
        for e in conv_fused_rows(args.out, smoke=True, reps=1):
            print(json.dumps(e))
        for e in pool_rows(args.out, smoke=True, reps=1):
            print(json.dumps(e))
        for e in mlp_rows(args.out, smoke=True, reps=1):
            print(json.dumps(e))
        for e in lm_decode_rows(args.out, smoke=True, reps=1):
            print(json.dumps(e))
        for e in serve_rows(args.out, smoke=True, reps=1):
            print(json.dumps(e))
        route_gate(args.out)
        return
    if args.engine:
        for e in engine_rows(args.out):
            print(json.dumps(e))
    if args.cnn_chain:
        for e in cnn_chain_rows(args.out):
            print(json.dumps(e))
    if args.conv_fused:
        for e in conv_fused_rows(args.out):
            print(json.dumps(e))
    if args.pool:
        for e in pool_rows(args.out):
            print(json.dumps(e))
    if args.serve:
        for e in serve_rows(args.out):
            print(json.dumps(e))
    if args.mlp:
        for e in mlp_rows(args.out):
            print(json.dumps(e))
    if args.lm_decode:
        for e in lm_decode_rows(args.out):
            print(json.dumps(e))
    if args.sweep:
        for e in sweep_rows(args.out):
            print(json.dumps(e))
    if (args.engine or args.cnn_chain or args.conv_fused or args.pool
            or args.serve or args.mlp or args.sweep or args.lm_decode):
        return
    for name, us, compile_us, derived in rows():
        print(f"{name},{us:.1f},compile={compile_us:.1f},{derived}")


if __name__ == "__main__":
    main()
