"""Run one cell of BENCHMARK.json on the chip and print its result.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process: it makes the weights and an input pool on the device from the
seed, builds and warms a ``ServeEngine`` replica with the traffic's buckets,
drives the measured window through ``submit``/``run_tick``, reads the peak
device memory, frees the replica, then checks a sample of the served
logits against the plain reference. With ``--trace 1`` it profiles the
first ``trace_seconds`` of the window and reports the per-layer metrics
instead of the end-to-end ones.

The last line of standard output is the result as one JSON object; the
numbers compared for ``correct`` close standard error. Without a TPU, with
fewer chips than the cell asks for, or outside a checkout that holds the
program, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import catalog, compare, system, trace_reduce, traffic  # noqa: E402
from bench import reference as ref  # noqa: E402
from bench.metrics import Run  # noqa: E402

OUT = os.path.join(ROOT, "bench", "out")
#: Weights take keys ``fold_in(seed, layer)``; the input pool this one.
POOL_STREAM = 1 << 30


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def _log(name: str, value) -> None:
    print(f"{name}: {json.dumps(value, default=str)}", file=sys.stderr,
          flush=True)


def _span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(f"bench.{name}")


def _devices(chips: int, require_tpu: bool) -> list:
    import jax
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"no TPU chip found: JAX sees {len(devices)} "
                     f"{devices[0].platform!r} device(s) "
                     f"({devices[0].device_kind}); this benchmark runs only "
                     "on a TPU")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chip(s), JAX sees "
                     f"{len(devices)}")
    return devices[:chips]


def _peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


class _Tracer:
    """Profiles from the window's start for ``seconds``, then stops at the
    next tick boundary."""

    def __init__(self, log_dir: str, seconds: float):
        import jax
        self.jax = jax
        self.log_dir = log_dir
        self.seconds = seconds
        self.until = None
        shutil.rmtree(log_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        self.window = _span(trace_reduce.WINDOW_SPAN)
        self.window.__enter__()
        self.start = time.perf_counter()

    def after_tick(self, now: float) -> None:
        if self.until is None and now - self.start >= self.seconds:
            self.stop()

    def stop(self) -> None:
        if self.until is None:
            self.window.__exit__(None, None, None)
            self.until = time.perf_counter()
            self.jax.profiler.stop_trace()


def make_inputs(cfg: dict, tr: dict, seed: int):
    """The seed's weights, as a function that makes them on the device in
    one compiled call, and its input pool, on the host."""
    import jax
    import numpy as np
    key = ref.seed_key(seed)
    init = jax.jit(lambda kd: ref.init_params(cfg, kd))
    with _span("generate"):
        pool = np.asarray(jax.jit(lambda kd: traffic.make_pool(
            cfg, tr["input"], tr["pool"],
            jax.random.fold_in(kd, POOL_STREAM)))(key))
    return lambda: jax.block_until_ready(init(key)), pool


def warm_replica(cfg: dict, tr: dict, params, pool, require_tpu: bool):
    """A replica with the traffic's buckets compiled, each run once."""
    replica = system.Replica(cfg, params, tr["buckets"],
                             require_tpu=require_tpu)
    for b in tr["buckets"]:
        for i in range(b):
            replica.submit(pool[i % len(pool)])
        replica.run_tick()
    return replica


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True) -> dict:
    """Run one cell and return its result object (also printed)."""
    cell = catalog.load_cell(workload)
    cfg, tr = cell.config, cell.traffic
    system.require_program()
    import jax
    import numpy as np
    devices = _devices(cell.chips, require_tpu)
    peaks = catalog.peaks_for(devices[0].device_kind) if trace else None
    _log("compile_cache_dir", system.configure_cache())
    t_devices = time.perf_counter()

    weights, pool = make_inputs(cfg, tr, seed)
    params = weights()
    t_inputs = time.perf_counter()
    replica = warm_replica(cfg, tr, params, pool, require_tpu)
    del params
    # Collect what tracing and compiling left behind now, and keep the
    # survivors out of later collections, so that no collection of set-up's
    # garbage lands inside the window.
    t_gc = time.perf_counter()
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - T_START
    _log("engine", replica.engine_config)
    _log("warmup_s", replica.engine.warmup_s)
    _log("setup_phases_s", {
        "start_to_chip": t_devices - T_START, "inputs": t_inputs - t_devices,
        "replica": t_gc - t_inputs, "gc": T_START + setup_s - t_gc})
    compiles = replica.compiles

    tracer = None
    if trace:
        tracer = _Tracer(os.path.join(OUT, "trace", workload),
                         tr.get("trace_seconds") or seconds)
    win = traffic.drive(replica, pool, tr, seed, seconds, _span,
                        after_tick=tracer.after_tick if tracer else None)
    if tracer:
        tracer.stop()
    compiles_in_window = replica.compiles - compiles
    memory_peak = _peak_bytes(devices)
    del replica
    gc.collect()

    t_ref = time.perf_counter()
    with _span("reference"):
        params = weights()
        errs = compare.check_sample(cfg, params, pool, win.sample_idx,
                                    win.sample_logits,
                                    cfg["reference_block"])
    ref_s = time.perf_counter() - t_ref
    limit = cfg["check"]["logit_err"]
    worst = float(errs.max()) if len(errs) else float("inf")
    unanswered = win.attempted - len(win.done)
    check = {
        "logit_err": {"value": worst, "limit": limit},
        "unanswered": {"value": int(unanswered), "limit": 0},
        "compiles_in_window": {"value": int(compiles_in_window), "limit": 0},
    }
    correct = (worst <= limit and unanswered == 0
               and compiles_in_window == 0)

    run = Run(setup_s=setup_s, window=win,
              buckets=tuple(tr["buckets"]))
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": memory_peak}
    result = {"correct": bool(correct), "attempted": int(win.attempted),
              "failed": int(unanswered + int((errs > limit).sum()))}
    if trace:
        from bench import counts
        summary = trace_reduce.reduce(trace_reduce.load(
            trace_reduce.find_xplane(tracer.log_dir)))
        run.trace, run.traced_until, run.peaks = summary, tracer.until, peaks
        run.counts = counts.pool_counts(cfg, params, pool,
                                        cfg["reference_block"])
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        metrics_of = cell.per_layer
    else:
        metrics_of = cell.end_to_end
    metrics = {}
    for m in metrics_of:
        value = catalog.metric_reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    result.update(metrics=metrics, device=device)
    if trace:
        result["breakdown"] = run.trace.breakdown()
    result["check"] = check

    lat = win.lateness_s
    ends = win.done[np.cumsum(win.tick_sizes, dtype=np.int64) - 1]
    ticks = np.diff(ends, prepend=win.start)
    _log("window", {"seconds": win.seconds, "requests": len(win.done),
                    "ticks": len(ticks),
                    "tick_s_median": float(np.median(ticks)),
                    "tick_s_max": float(ticks.max()),
                    "generator_lateness_p95_ms":
                        float(np.percentile(lat, 95) * 1e3)})
    _log("peak_bytes_in_use", memory_peak)
    _log("reference_s", ref_s)
    _log("checked", int(len(errs)))
    for name, c in check.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    except (NoChip, system.MissingProgram, catalog.UnknownDevice) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())
