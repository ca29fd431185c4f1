"""The route the served program gives each network layer, by layer index.

``ServeEngine.boundary_report(bucket)`` traces a bucket's step abstractly
under the program's dispatch tracer and lists one route per boundary that
consumes an event stream; each names its ``layer``, the index of the
``mnf.L<i>`` scope it was traced under. A program whose routes carry no
``layer`` gives nothing to read here.
"""
from __future__ import annotations

import numpy as np


def layers_on(workload: str, op: str, route: str) -> list[int] | None:
    """Sorted indices of the layers whose ``op`` boundary takes ``route`` in
    some bucket of ``workload``, from a replica built like the served one
    with zero weights and nothing compiled. None where the routes name no
    layer."""
    from bench import catalog, system
    from bench import reference as ref
    system.require_program()
    from repro import serving
    cell = catalog.load_cell(workload)
    cfg = cell.config
    params = [None if s is None else np.zeros(s, np.float32)
              for s in (ref.weight_shape(layer, shape) for layer, shape in
                        zip(cfg["layers"], ref.layer_inputs(cfg)))]
    buckets = tuple(cell.traffic["buckets"])
    engine = serving.ServeEngine(
        system.build_spec(cfg), params,
        serving.ServeEngineConfig(buckets=buckets, aot_warmup=False))
    routes = [r for b in buckets for r in engine.boundary_report(b)["routes"]]
    if not routes or any(r.get("layer") is None for r in routes):
        return None
    return sorted({r["layer"] for r in routes
                   if r["op"] == op and r["route"] == route})
