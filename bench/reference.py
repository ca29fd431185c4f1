"""Plain float32 forward of a benchmark configuration, and its weights.

Built from the configuration file alone (``configs/<name>.json``) with
``jax.numpy`` and ``lax``: convolutions with symmetric zero padding, VALID
max pools, dense layers, ReLU after every layer but the last, NHWC
flattening before the first dense layer of a CNN. It imports nothing of the
program. Every product runs at ``Precision.HIGHEST`` (full float32).

``precision="high"`` is the control: the same forward at XLA's ``high``
precision, three bfloat16 passes per product (``hi*hi + hi*lo + lo*hi``),
spelled out so that it computes the same on every backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST
PRECISIONS = ("highest", "high")


def seed_key(seed: int) -> np.ndarray:
    """Raw threefry key data (two uint32 words) for any whole-number seed,
    including seeds past 32 bits."""
    s = int(seed) % (1 << 64)
    return np.array([s >> 32, s & 0xFFFFFFFF], np.uint32)


def layer_inputs(cfg: dict) -> list[tuple]:
    """Per-sample shape entering each layer, and the output shape last."""
    shape = tuple(cfg["input_shape"])
    shapes = [shape]
    for layer in cfg["layers"]:
        if layer["kind"] == "conv":
            h, w, _ = shape
            k, s, p = layer["k"], layer["stride"], layer["padding"]
            shape = ((h + 2 * p - k) // s + 1, (w + 2 * p - k) // s + 1,
                     layer["out_ch"])
        elif layer["kind"] == "pool":
            h, w, c = shape
            k, s = layer["k"], layer["stride"]
            shape = ((h - k) // s + 1, (w - k) // s + 1, c)
        elif layer["kind"] == "fc":
            shape = (layer["out"],)
        else:
            raise ValueError(f"unknown layer kind {layer['kind']!r}")
        shapes.append(shape)
    return shapes


def weight_shape(layer: dict, in_shape: tuple) -> tuple | None:
    if layer["kind"] == "conv":
        return (layer["k"], layer["k"], in_shape[-1], layer["out_ch"])
    if layer["kind"] == "fc":
        return (int(np.prod(in_shape)), layer["out"])
    return None


def init_params(cfg: dict, key_data) -> list:
    """He-normal weights, pruned to ``cfg["weight_density"]`` by an
    independent uniform draw per weight; ``None`` for a pool. Call it under
    ``jax.jit`` so that the weights are made on the device in one call."""
    density = cfg["weight_density"]
    params = []
    for i, (layer, in_shape) in enumerate(zip(cfg["layers"],
                                              layer_inputs(cfg))):
        shape = weight_shape(layer, in_shape)
        if shape is None:
            params.append(None)
            continue
        k = jax.random.fold_in(key_data, i)
        fan_in = int(np.prod(shape[:-1]))
        w = jax.random.normal(k, shape, jnp.float32) * (2.0 / fan_in) ** 0.5
        if density < 1.0:
            keep = jax.random.uniform(jax.random.fold_in(k, 1), shape)
            w = jnp.where(keep < density, w, 0.0)
        params.append(w)
    return params


def _bf16(a):
    # An explicit rounding: XLA may drop a float32 -> bfloat16 -> float32
    # round trip as excess precision, and then ``a - hi`` reads 0.
    return lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def _split(a):
    hi = _bf16(a)
    return hi, _bf16(a - hi)


def _product(op, x, w, precision: str):
    if precision == "highest":
        return op(x, w)
    if precision == "high":
        xh, xl = _split(x)
        wh, wl = _split(w)
        return op(xh, wh) + op(xh, wl) + op(xl, wh)
    raise ValueError(f"precision must be one of {PRECISIONS}")


def conv(x, w, stride: int, padding: int):
    return lax.conv_general_dilated(
        x, w, (stride, stride), [(padding, padding)] * 2,
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)


def dense(x, w):
    return jnp.dot(x, w, precision=HIGHEST)


def max_pool(x, k: int, stride: int):
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, k, k, 1),
                             (1, stride, stride, 1), "VALID")


def layer_forward(layer: dict, w, x, precision: str = "highest"):
    """One layer, ReLU included where the network has it."""
    kind = layer["kind"]
    if kind == "pool":
        return max_pool(x, layer["k"], layer["stride"])
    if kind == "conv":
        op = functools.partial(conv, stride=layer["stride"],
                               padding=layer["padding"])
    else:
        if x.ndim > 2:
            x = x.reshape(x.shape[0], -1)
        op = dense
    y = _product(op, x, w, precision)
    return y if layer.get("last") else jnp.maximum(y, 0.0)


def mark_last(cfg: dict) -> list[dict]:
    layers = [dict(layer) for layer in cfg["layers"]]
    layers[-1]["last"] = True
    return layers


def forward(cfg: dict, params, x, precision: str = "highest"):
    """Logits of a batch ``x`` of shape (B, *cfg["input_shape"])."""
    for layer, w in zip(mark_last(cfg), params):
        x = layer_forward(layer, w, x, precision)
    return x
