"""AOT warmup + persistent compilation cache + executable snapshots.

The chained CNN pipeline pays 17–36 s of JIT per network (BENCH
``cnn_chain`` compile_us) — per *bucket shape* in the serving tier.
Three mechanisms take that off the request path, each cutting deeper:

  * **AOT warmup** — every bucket's pipeline is ``jit(...).lower(...)
    .compile()``'d at engine startup, so the first request of any bucket
    hits a finished executable, never a trace.
  * **JAX persistent compilation cache** — XLA compile outputs are
    cached under :func:`compile_cache_dir` (``JAX_COMPILATION_CACHE_DIR``,
    else one fixed directory in the checkout); a restarted process skips
    the XLA compile (measured ~6× on AlexNet@64).  But tracing + lowering
    is pure Python work repaid every process, and at ~3–4 s per AlexNet
    bucket it dominates the re-warm.
  * **Executable snapshots** — the compiled executable itself is
    serialized per bucket (``jax.experimental.serialize_executable``)
    under ``cache_dir``; a restarted replica ``pickle.load``s finished
    executables and never traces, lowers, or compiles at all.  This is
    what makes warmed-replica TTFR an order of magnitude under the cold
    compile (BENCH ``serve_bench_summary``).

The entry points turn the compile cache on with
:func:`configure_persistent_cache`.  Snapshots live under the operator's
``ServeEngineConfig.cache_dir`` / ``launch.serve --cache-dir``, and a
replica with a ``cache_dir`` compiles its buckets around the compile
cache (``aot_compile(fresh=True)``) so that every snapshot it writes
restores.  Snapshots are keyed by jax version,
device kind, mesh layout, network spec and engine config; a key miss or
an unpicklable payload falls back to the compile path, never fails.
"""
from __future__ import annotations

import contextlib
import hashlib
import os
import pickle
import time

import jax

__all__ = ["DEFAULT_CACHE_DIR", "compile_cache_dir",
           "configure_persistent_cache", "persistent_cache_off",
           "aot_compile", "snapshot_key",
           "save_executable", "load_executable"]


#: The XLA compile cache's home when ``JAX_COMPILATION_CACHE_DIR`` is
#: unset: one fixed, gitignored directory inside the checkout.  The path is
#: part of the cache key, so a directory that moves between runs never hits.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def compile_cache_dir() -> str:
    """Where the XLA compile cache lives: ``JAX_COMPILATION_CACHE_DIR`` when
    it is set, :data:`DEFAULT_CACHE_DIR` otherwise."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_CACHE_DIR


def configure_persistent_cache() -> str:
    """Turn on JAX's persistent compilation cache at
    :func:`compile_cache_dir` and return that directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it, and no
    directory is set here.  Thresholds drop to zero so every bucket
    executable is cached — serving warmup wants *all* compiles persisted,
    including the small buckets XLA compiles quickly.
    """
    cache_dir = compile_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # JAX latches cache state at the first compile of the process: if any
    # jit ran before the dir was set (param init counts), the cache object
    # initialized as "no cache" and every later lookup silently misses.
    # A reset re-initializes it from the dir just configured.
    from jax.experimental.compilation_cache import compilation_cache
    compilation_cache.reset_cache()
    return cache_dir


@contextlib.contextmanager
def persistent_cache_off():
    """Compile inside without reading or writing JAX's persistent cache.

    The switch is latched per process like the directory, so the cache
    module is reset on the way in and on the way out."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


def aot_compile(jitted, arg_specs, *, fresh: bool = False) -> tuple:
    """``jitted.lower(*arg_specs).compile()`` with the wall time split out.

    Returns ``(compiled, lower_s, compile_s)``.  ``compile_s`` is where the
    persistent cache bites: a warm replica's XLA compile is a disk
    deserialize.  ``fresh=True`` compiles around that cache — what an
    executable to be snapshotted needs: one read back from the persistent
    cache does not survive serialize → deserialize on XLA:CPU (the restored
    executable fails at run time with missing functions).  The compiled
    executable is shape-strict — calling it can never retrace, which is
    what makes the steady-state recompile counter a meaningful invariant
    (a flat counter proves no tick compiled).
    """
    t0 = time.perf_counter()
    lowered = jitted.lower(*arg_specs)
    t1 = time.perf_counter()
    with persistent_cache_off() if fresh else contextlib.nullcontext():
        compiled = lowered.compile()
    t2 = time.perf_counter()
    return compiled, t1 - t0, t2 - t1


def snapshot_key(*parts) -> str:
    """Stable snapshot filename for an executable: every input that could
    change the compiled artifact goes into the hash — jax version, device
    kind, and whatever the caller passes (spec, bucket, engine config,
    mesh layout)."""
    dev = jax.devices()[0]
    tag = repr((jax.__version__, dev.platform, dev.device_kind) + parts)
    return hashlib.sha256(tag.encode()).hexdigest()[:24]


def save_executable(compiled, cache_dir: str, key: str) -> bool:
    """Snapshot a compiled executable under ``cache_dir`` (best-effort:
    an unserializable executable just means the next replica recompiles)."""
    from jax.experimental import serialize_executable as se
    try:
        blob = pickle.dumps(se.serialize(compiled))
    except Exception:  # pragma: no cover - backend-dependent
        return False
    os.makedirs(cache_dir, exist_ok=True)
    path = os.path.join(cache_dir, f"exec-{key}.pkl")
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "wb") as f:
        f.write(blob)
    os.replace(tmp, path)               # atomic: a reader never sees half
    return True


def load_executable(cache_dir: str, key: str):
    """Load a snapshot, or None (missing / stale / different build — the
    caller falls back to compiling).  Only ever reads the operator's own
    ``cache_dir``."""
    from jax.experimental import serialize_executable as se
    path = os.path.join(cache_dir, f"exec-{key}.pkl")
    if not os.path.exists(path):
        return None
    try:
        with open(path, "rb") as f:
            payload, in_tree, out_tree = pickle.load(f)
        return se.deserialize_and_load(payload, in_tree, out_tree)
    except Exception:  # pragma: no cover - stale or foreign snapshot
        return None
