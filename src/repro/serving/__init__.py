"""repro.serving — the production CNN serving tier (DESIGN.md §10).

Turns the single-jit event-resident pipeline into a replica that serves
heavy traffic: a FIFO request queue continuously batched into padded
bucket shapes {1, 8, 32, 128}, batch-parallel ``shard_map`` over the
(data, model) mesh with weights replicated, and AOT warmup + per-bucket
executable snapshots so a restarted replica answers in seconds instead
of re-paying the 17–36 s chained-pipeline JIT per bucket.

    from repro import serving
    eng = serving.ServeEngine(spec, params,
                              serving.ServeEngineConfig(cache_dir="snaps"))
    eng.submit(image)
    done = eng.run_tick()          # -> completed Requests with latencies
    print(eng.stats())             # requests/s, p50/p99 per bucket
"""
from repro.serving.aot import (aot_compile, compile_cache_dir,
                               configure_persistent_cache)
from repro.serving.batcher import (DEFAULT_BUCKETS, ContinuousBatcher,
                                   Request, pad_bucket, smallest_bucket)
from repro.serving.server import ServeEngine, ServeEngineConfig, percentile

__all__ = [
    "DEFAULT_BUCKETS", "ContinuousBatcher", "Request", "pad_bucket",
    "smallest_bucket",
    "ServeEngine", "ServeEngineConfig", "percentile",
    "aot_compile", "compile_cache_dir", "configure_persistent_cache",
]
