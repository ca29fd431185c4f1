"""Production meshes.

Never touches jax device state at import time: meshes are built by FUNCTION
call only.  Dry-run processes must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before any jax
import* (launch/dryrun.py does this in its first two lines).

All mesh construction in the repo funnels through :func:`checked_mesh`,
which is capacity-checked: requesting more mesh slots than the runtime has
devices used to surface as a raw XLA/``make_mesh`` assertion deep in jax
internals.  ``checked_mesh`` raises :class:`MeshCapacityError` — a named,
actionable error that says how many devices exist, how many the shape
needs, and how to get them (``XLA_FLAGS`` host-device forcing, or a
smaller shape).  No mesh ever degrades to a smaller one: a replica that
asked for four devices and silently got one would hide the device it was
meant to use.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["MeshCapacityError", "checked_mesh", "make_production_mesh",
           "make_serve_mesh", "make_small_mesh"]


class MeshCapacityError(RuntimeError):
    """Requested mesh shape needs more devices than the runtime has."""


def checked_mesh(shape, axes):
    """``jax.make_mesh`` (every axis ``AxisType.Auto``) with a capacity
    check: raises :class:`MeshCapacityError` (named, actionable) when
    ``shape`` needs more devices than ``jax.devices()`` provides."""
    need = 1
    for s in shape:
        need *= int(s)
    have = len(jax.devices())
    if need > have:
        raise MeshCapacityError(
            f"mesh shape {tuple(shape)} over axes {tuple(axes)} needs "
            f"{need} devices but only {have} exist. Either request a "
            f"smaller mesh, or force host devices before any jax import "
            f"(XLA_FLAGS=--xla_force_host_platform_device_count={need}).")
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 (data, model) single pod; 2×16×16 (pod, data, model) for two."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return checked_mesh(shape, axes)


def make_small_mesh(shape=(2, 4), axes=("data", "model")):
    """Test-scale mesh (requires a forced host device count >= prod(shape))."""
    return checked_mesh(shape, axes)


def make_serve_mesh(data: int | None = None, model: int = 1):
    """The serving tier's (data, model) mesh: batch axis over every device.

    ``data=None`` spans all visible devices (the replica default: weights
    replicated, batch sharded on ``data``).  An explicit shape that exceeds
    the device count raises :class:`MeshCapacityError`.
    """
    if data is None:
        data = max(len(jax.devices()) // model, 1)
    return checked_mesh((data, model), ("data", "model"))
