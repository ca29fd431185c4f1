"""repro.engine — the unified MNF event-pipeline engine (DESIGN.md §3–§5).

One config, one registry, one inter-layer currency:

  * :class:`EngineConfig` consolidates every tiling/capacity/threshold/
    backend knob that used to be scattered across four divergent entry
    points (``mnf_linear``, ``tap_event_conv2d``, ``event_matmul``,
    ``fire_and_encode``).
  * The backend registry maps ``(op, backend)`` to implementations; the
    built-in dense/scalar/block/pallas paths register at import, and new
    backends (sharded, quantized) are one :func:`register_backend` away
    from every model in the repo.
  * :class:`EventStream` makes ``BlockEvents`` the currency between layers:
    ``fire`` emits it, ``linear`` consumes it directly — activations stay
    compressed end to end, the paper's core claim.

Typical use::

    from repro import engine
    cfg = engine.EngineConfig(backend="auto")
    s = engine.fire(engine.linear(x, w1, cfg=cfg), cfg)   # layer 1
    y = engine.linear(s, w2, cfg=cfg)                     # layer 2, chained
"""
from repro.core.events import (STRIP_CO_MIN, STRIP_STRIDES, STRIP_W,
                               pool_window_ineligible_reason,
                               retile_ineligible_reason, strip_eligible,
                               strip_ineligible_reason)
from repro.costmodel.crossover import linear_shape_class
from repro.engine.api import (conv2d, describe, fire, fire_conv, fire_delta,
                              linear, matmul, maxpool2d,
                              pool_ineligible_reason,
                              recurrent_ineligible_reason, recurrent_step,
                              route_conv, route_linear, route_pool,
                              route_recurrent, sparsify)
from repro.engine.config import BACKENDS, RECURRENT_BLK_K, EngineConfig
from repro.engine.registry import (dispatch, get_backend, list_backends,
                                   register_backend, registered_ops)
from repro.engine.stream import EventStream
from repro.engine.trace import scope, trace_dispatch

import repro.engine.backends  # noqa: F401  (registers built-in backends)

__all__ = [
    "BACKENDS", "RECURRENT_BLK_K", "EngineConfig", "EventStream",
    "STRIP_CO_MIN", "STRIP_STRIDES", "STRIP_W", "strip_eligible",
    "strip_ineligible_reason", "pool_window_ineligible_reason",
    "retile_ineligible_reason", "linear_shape_class",
    "register_backend", "get_backend", "dispatch", "list_backends",
    "registered_ops",
    "matmul", "linear", "conv2d", "maxpool2d", "pool_ineligible_reason",
    "route_conv", "route_pool", "route_linear", "route_recurrent",
    "recurrent_ineligible_reason", "recurrent_step",
    "fire", "fire_conv", "fire_delta", "sparsify", "describe",
    "trace_dispatch", "scope",
]
