"""ServeEngine — the production serving tier over the event-resident CNN.

One engine = one replica: a (data, model) mesh with weights replicated and
the batch axis sharded on ``data``, one AOT-compiled pipeline per batch
bucket, and a continuous batcher routing the FIFO request queue into the
smallest admissible bucket each tick (DESIGN.md §10).

The three invariants the tier is built around, each enforced or measured:

  * **No steady-state compilation.**  Every bucket executable is built at
    startup (``serving.aot``); ``recompiles`` counts every lower+compile
    the engine ever performs, and a flat counter after warmup proves no
    tick traced or compiled anything (CI asserts this — ``serve --smoke``).
  * **Padding is bitwise-free.**  Short batches are zero-padded to the
    bucket shape; zero rows ride the pipeline as event-free streams and
    their logits are sliced off, so a real request's logits are bitwise
    the unpadded forward's (tests/test_serving.py asserts per bucket).
  * **No silent event-path degradation.**  ``boundary_report`` abstract-
    traces every bucket's pipeline under ``engine.trace_dispatch``; an
    eligible boundary reporting ``fallback_decode`` is a serving bug, not
    a slow path (CI-fatal in the smoke loop).

The tier names its work to the profiler (``engine.trace.span``), per tick
and per batch, never per request: ``mnf.serve.tick`` (stats ``tick``,
``queued``) holds one ``mnf.serve.batch`` per bucket batch (``bucket``,
``rows``, ``rid0``/``rid1``, ``wait_ms``: how long its oldest row queued),
which holds ``pad``, ``place``, ``dispatch``, ``wait`` (the device),
``fetch`` and ``complete``; ``mnf.serve.compile`` marks every executable
load or compile.  ``op_layers`` maps a bucket's compiled instructions to
the network layer scopes (``mnf.L03.conv``) that the device time in a
trace is attributed by.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any

import jax
import numpy as np

from repro import engine as mnf_engine
from repro.core.fire import FireConfig
from repro.engine.trace import op_scopes, span
from repro.launch.steps import make_cnn_serve_step
from repro.serving.aot import (aot_compile, load_executable,
                               save_executable, snapshot_key)
from repro.serving.batcher import (DEFAULT_BUCKETS, ContinuousBatcher,
                                   Request, pad_bucket)

__all__ = ["ServeEngineConfig", "ServeEngine", "percentile"]


@dataclasses.dataclass(frozen=True)
class ServeEngineConfig:
    """Replica-level knobs of the serving tier (the CLI maps onto this).

    buckets:      compiled batch shapes, ascending (requests are padded up
                  to the smallest admissible one).
    mnf:          event-resident pipeline (False = dense oracle serving).
    backend/threshold: forwarded into the per-bucket EngineConfig.
    cache_dir:    warm-start directory (None = off) for per-bucket
                  executable snapshots, so a restarted replica restores
                  finished executables from disk without tracing or
                  compiling.  Bucket compiles then bypass the JAX
                  persistent compilation cache (``aot_compile(fresh=True)``).
    aot_warmup:   compile every bucket at startup (False defers each bucket
                  to its first request — only for tests/latency studies).
    max_batches_per_tick: tick batch budget (None = drain the queue).
    """

    buckets: tuple = DEFAULT_BUCKETS
    mnf: bool = True
    backend: str = "auto"
    threshold: float = 0.0
    cache_dir: str | None = None
    aot_warmup: bool = True
    max_batches_per_tick: int | None = None


def _memory_plan(compiled) -> dict:
    """An executable's device memory plan, bytes per device."""
    mem = compiled.memory_analysis()
    if mem is None:
        return {}
    return dict(argument_bytes=int(mem.argument_size_in_bytes),
                output_bytes=int(mem.output_size_in_bytes),
                temp_bytes=int(mem.temp_size_in_bytes))


def percentile(values: list, q: float) -> float:
    """p-th percentile of a latency list (0 for an empty window)."""
    if not values:
        return 0.0
    return float(np.percentile(np.asarray(values, np.float64), q))


class ServeEngine:
    """One serving replica: sharded, continuously batched, AOT-warmed."""

    def __init__(self, spec, params, cfg: ServeEngineConfig | None = None, *,
                 mesh=None, engine_cfg=None, fire_cfg=None):
        self.cfg = cfg or ServeEngineConfig()
        self.spec = spec
        if mesh is None:
            from repro.launch.mesh import make_serve_mesh
            mesh = make_serve_mesh()
        self.mesh = mesh
        ecfg = (engine_cfg or mnf_engine.EngineConfig(
            backend=self.cfg.backend,
            threshold=self.cfg.threshold)).resolved()
        fire_cfg = fire_cfg or FireConfig(threshold=self.cfg.threshold)
        self.fire_cfg = fire_cfg
        # donate=False: logits cannot alias the image buffer, so donation
        # buys nothing here and XLA warns per bucket; the padded buffer is
        # engine-owned and reused across ticks anyway.
        self.plans = {
            b: make_cnn_serve_step(spec, b, mnf=self.cfg.mnf,
                                   engine_cfg=ecfg, fire_cfg=fire_cfg,
                                   mesh=mesh, donate=False)
            for b in self.cfg.buckets}
        self.engine_cfg = ecfg
        self.batcher = ContinuousBatcher(
            self.cfg.buckets,
            max_batches_per_tick=self.cfg.max_batches_per_tick)
        # Params are placed once, replicated over the mesh (weights
        # replicated, batch sharded — ROADMAP item 1's layout).
        self.params = self._replicate(params)
        self._exec: dict[int, Any] = {}
        #: Every lower+compile this engine ever ran.  Flat after warmup ==
        #: no steady-state tick compiled anything (the CI smoke invariant).
        self.recompiles = 0
        #: Buckets whose executable was restored from a cache_dir snapshot
        #: (no trace, no lower, no compile — the restarted-replica path).
        self.snapshot_hits = 0
        self.warmup_s: dict[int, dict] = {}
        self.completed: list[Request] = []
        self.ttfr_s: float | None = None   # time to first response
        self._born = time.perf_counter()
        self._serve_window = 0.0
        if self.cfg.aot_warmup:
            self.warm()

    # -- placement -----------------------------------------------------------

    def _replicate(self, params):
        from jax.sharding import NamedSharding, PartitionSpec as P
        sh = NamedSharding(self.mesh, P())
        return jax.tree.map(lambda p: jax.device_put(p, sh), params)

    def _place(self, bucket: int, x: np.ndarray):
        sh = self.plans[bucket].input_sharding
        return jax.device_put(x, sh) if sh is not None else jax.numpy.asarray(x)

    # -- compilation ---------------------------------------------------------

    def _snapshot_key(self, bucket: int) -> str:
        return snapshot_key(self.spec, bucket, self.cfg.mnf,
                            self.engine_cfg, self.fire_cfg,
                            tuple(self.mesh.axis_names),
                            tuple(self.mesh.devices.shape))

    def _compiled(self, bucket: int):
        """The bucket's AOT executable.

        Resolution order: in-memory → ``cache_dir`` executable snapshot
        (restored without any trace/lower/compile — the restarted-replica
        fast path) → lower+compile (counted in ``recompiles``, snapshotted
        for the next replica)."""
        if bucket not in self._exec:
            plan = self.plans[bucket]
            key = self._snapshot_key(bucket)
            if self.cfg.cache_dir:
                t0 = time.perf_counter()
                with span("serve.compile", bucket=bucket, source="snapshot"):
                    restored = load_executable(self.cfg.cache_dir, key)
                if restored is not None:
                    self._exec[bucket] = restored
                    self.snapshot_hits += 1
                    self.warmup_s[bucket] = dict(
                        load_s=round(time.perf_counter() - t0, 4),
                        **_memory_plan(restored))
                    return restored
            self.recompiles += 1
            with span("serve.compile", bucket=bucket, source="compile"):
                compiled, lower_s, compile_s = aot_compile(
                    plan.fn, plan.arg_specs, fresh=bool(self.cfg.cache_dir))
            self._exec[bucket] = compiled
            self.warmup_s[bucket] = dict(lower_s=round(lower_s, 4),
                                         compile_s=round(compile_s, 4),
                                         **_memory_plan(compiled))
            if self.cfg.cache_dir:
                save_executable(compiled, self.cfg.cache_dir, key)
        return self._exec[bucket]

    def warm(self) -> dict:
        """AOT-compile every bucket (startup warmup; persistent-cache hits
        make a restarted replica's warmup a disk read).  Returns per-bucket
        lower/compile (or snapshot load) seconds and the executable's
        memory plan: ``argument_bytes``, ``output_bytes``, ``temp_bytes``
        per device."""
        for b in self.cfg.buckets:
            self._compiled(b)
        return self.warmup_s

    def op_layers(self, bucket: int) -> dict[str, str]:
        """Instruction name (``mnf_event_matmul.349``, ``fusion.6``) ->
        network layer scope (``mnf.L03.conv``, ``mnf.out``) of the bucket's
        compiled step, read off its HLO text: the names a profiler trace
        gives each device op.  Built on demand, never on the serving path.
        """
        layers = op_scopes(self._compiled(bucket).as_text())
        if not layers:
            # JAX's persistent compile cache keys a module without its
            # debug info, so the executable may have been read back from an
            # entry that a build without layer scopes wrote: compile the
            # same module afresh to read them.
            plan = self.plans[bucket]
            self.recompiles += 1
            compiled, _, _ = aot_compile(plan.fn, plan.arg_specs, fresh=True)
            layers = op_scopes(compiled.as_text())
        return layers

    def boundary_report(self, bucket: int | None = None) -> dict:
        """Abstract-trace one bucket's pipeline: chained/pool/fallback
        counts plus the per-boundary routing decisions (no numeric work —
        ``jax.eval_shape`` under the dispatch tracer).
        ``fallback_decodes`` must be 0 on an eligible network, and because
        routes are trace-time static (DESIGN.md §11) ``routes`` states
        exactly what each compiled boundary does — a snapshot-restored
        executable must report the same list it was compiled with (the
        serve smoke checks restart drift)."""
        from repro.models.cnn import make_cnn_forward
        from repro.models.mlp import MLPSpec, make_mlp_forward
        bucket = self.cfg.buckets[0] if bucket is None else bucket
        plan = self.plans[bucket]
        make_fwd = make_mlp_forward if isinstance(self.spec, MLPSpec) \
            else make_cnn_forward
        fwd = make_fwd(self.spec, mnf=self.cfg.mnf,
                       engine_cfg=self.engine_cfg)
        with mnf_engine.trace_dispatch() as recs:
            jax.eval_shape(fwd, plan.arg_specs[0], plan.arg_specs[1])
        routes = [dict(op=r.get("op"), route=r.get("route"),
                       occupancy=r.get("occupancy"),
                       source=r.get("route_source"),
                       shape_class=r.get("shape_class"))
                  for r in recs if r.get("route") is not None]
        route_counts: dict[str, int] = {}
        for r in routes:
            route_counts[r["route"]] = route_counts.get(r["route"], 0) + 1
        return dict(
            bucket=bucket,
            chained=sum(1 for r in recs if r.get("chained")),
            pool_events=sum(1 for r in recs if r.get("pool_events")),
            fallback_decodes=sum(
                1 for r in recs if r.get("fallback_decode")),
            routed_dense=sum(1 for r in recs if r.get("routed_dense")),
            routes=routes, route_counts=route_counts,
            boundaries=plan.boundaries)

    # -- request path --------------------------------------------------------

    def submit(self, image) -> Request:
        """Enqueue one request (a (H, W, C) image)."""
        return self.batcher.submit(image, submit_time=time.perf_counter())

    def run_tick(self) -> list[Request]:
        """Drain this tick's queue through the compiled buckets.

        Routing, padding, execution, unpadding; completions carry
        per-request latency (submit → logits on the host).  Returns the
        requests completed this tick, in FIFO order.
        """
        t_tick0 = time.perf_counter()
        done: list[Request] = []
        budget = self.batcher.max_batches_per_tick
        batches = 0
        with span("serve.tick", tick=self.batcher.tick,
                  queued=self.batcher.pending()):
            while budget is None or batches < budget:
                batch = self.batcher.next_batch()
                if batch is None:
                    break
                bucket, reqs = batch
                batches += 1
                self._run_batch(bucket, reqs)
                done.extend(reqs)
        self.batcher.end_tick()
        self._serve_window += time.perf_counter() - t_tick0
        self.completed.extend(done)
        return done

    def _run_batch(self, bucket: int, reqs: list[Request]) -> None:
        """Serve one bucket batch and complete its requests."""
        wait_ms = (time.perf_counter() - reqs[0].submit_time) * 1e3
        with span("serve.batch", bucket=bucket, rows=len(reqs),
                  rid0=reqs[0].rid, rid1=reqs[-1].rid, wait_ms=wait_ms):
            with span("serve.pad"):
                x = pad_bucket([r.image for r in reqs], bucket)
            step = self._compiled(bucket)
            with span("serve.place"):
                x = self._place(bucket, x)
            with span("serve.dispatch"):
                y = step(self.params, x)
            with span("serve.wait"):
                y = jax.block_until_ready(y)
            with span("serve.fetch"):
                logits = np.asarray(y)[:len(reqs)]  # mask padded rows off
            now = time.perf_counter()
            with span("serve.complete"):
                for i, r in enumerate(reqs):
                    r.result = logits[i]
                    r.latency_s = now - r.submit_time
            if self.ttfr_s is None:
                self.ttfr_s = now - self._born

    # -- reporting -----------------------------------------------------------

    def stats(self) -> dict:
        """requests/s + p50/p99 latency, overall and per bucket."""
        lats = [r.latency_s for r in self.completed]
        per_bucket = {}
        for b in self.cfg.buckets:
            bl = [r.latency_s for r in self.completed if r.bucket == b]
            per_bucket[b] = dict(
                requests=len(bl),
                p50_ms=round(percentile(bl, 50) * 1e3, 3),
                p99_ms=round(percentile(bl, 99) * 1e3, 3))
        return dict(
            requests=len(lats),
            requests_s=round(len(lats) / max(self._serve_window, 1e-9), 2),
            p50_ms=round(percentile(lats, 50) * 1e3, 3),
            p99_ms=round(percentile(lats, 99) * 1e3, 3),
            per_bucket=per_bucket,
            recompiles=self.recompiles,
            snapshot_hits=self.snapshot_hits,
            warmup_s=self.warmup_s,
            ttfr_s=round(self.ttfr_s, 4) if self.ttfr_s is not None
            else None,
            devices=len(self.mesh.devices.flat),
            data_shards={b: p.data_shards for b, p in self.plans.items()})
