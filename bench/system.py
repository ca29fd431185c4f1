"""The system under test, and the only module here that imports it.

Builds the program's network spec from a configuration file and serves it
through ``repro.serving.ServeEngine``: AOT-compiled buckets, the continuous
batcher, padding and the Pallas event kernels.
"""
from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(ROOT, "src")

#: Prefix of every Pallas kernel of the event path; the trace is reduced by it.
KERNEL_PREFIX = "mnf_"


class MissingProgram(RuntimeError):
    """The checkout holds no program to measure."""


def require_program() -> None:
    """Put the checkout's ``src`` on the path, or raise MissingProgram."""
    if not os.path.isdir(os.path.join(SRC, "repro")):
        raise MissingProgram(f"no repro package under {SRC}: the benchmark "
                             "runs from a checkout of the repository")
    if SRC not in sys.path:
        sys.path.insert(0, SRC)


def configure_cache() -> str:
    """JAX's persistent compilation cache at the program's fixed path
    (``JAX_COMPILATION_CACHE_DIR`` if set, else ``.jax_cache/`` in the
    checkout)."""
    require_program()
    from repro import serving
    return serving.configure_persistent_cache()


def build_spec(cfg: dict):
    require_program()
    from repro.models import cnn, mlp
    layers = cfg["layers"]
    if cfg["family"] == "mlp":
        if any(layer["kind"] != "fc" for layer in layers):
            raise ValueError(f"{cfg['name']}: an mlp has only fc layers")
        return mlp.MLPSpec(cfg["name"], int(cfg["input_shape"][0]),
                           tuple(layer["out"] for layer in layers))
    make = dict(
        conv=lambda l: cnn.ConvSpec(l["out_ch"], l["k"], l["stride"],
                                    l["padding"]),
        pool=lambda l: cnn.PoolSpec(l["k"], l["stride"]),
        fc=lambda l: cnn.FCSpec(l["out"]))
    h, w, c = cfg["input_shape"]
    if h != w:
        raise ValueError(f"{cfg['name']}: the program serves square images")
    return cnn.CNNSpec(cfg["name"], h, c,
                       tuple(make[l["kind"]](l) for l in layers),
                       num_classes=cfg["num_classes"])


class Replica:
    """One ``ServeEngine`` with the traffic's buckets, warmed at start."""

    def __init__(self, cfg: dict, params, buckets, *, require_tpu: bool):
        require_program()
        from repro import serving
        self.engine = serving.ServeEngine(
            build_spec(cfg), params,
            serving.ServeEngineConfig(buckets=tuple(buckets)))
        ecfg = self.engine.engine_cfg
        self.engine_config = dict(backend=ecfg.backend,
                                  interpret=ecfg.interpret, route=ecfg.route)
        if require_tpu and (ecfg.backend != "pallas" or ecfg.interpret):
            raise RuntimeError(f"the engine resolved to {self.engine_config},"
                               " not the Pallas kernels on the chip")

    @property
    def compiles(self) -> int:
        return self.engine.recompiles

    def submit(self, x):
        return self.engine.submit(x)

    def run_tick(self):
        done = self.engine.run_tick()
        self.engine.completed.clear()   # the window keeps its own record
        return done
