"""Useful operations and least bytes of each layer, from the plain reference.

For every input of the pool, and every layer, on the activations the plain
reference computes:

* useful MACs: the products whose operands are both nonzero, i.e.
  ``sum(conv(1[x != 0], 1[w != 0]))`` for a convolution and
  ``1[x != 0] @ 1[w != 0]`` summed for a dense layer; 0 for a pool;
* activation elements: ``nnz(input) + numel(output)``.

A step that serves requests ``R`` then needs at least, per layer,
``2 * sum_R macs`` operations and ``4 * (sum_R elements + nnz(weights))``
bytes of float32. Any implementation, dense or event-driven, does that
much, so a share of a roofline built on them cannot pass 100%.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference as ref

BYTES_PER_ELEMENT = 4


def _nz(a):
    return (a != 0).astype(jnp.float32)


def layer_counts(layer: dict, w, x):
    """(useful MACs, nnz(x) + numel(out), out) per row of ``x``, int32."""
    y = ref.layer_forward(layer, w, x)
    rows = x.shape[0]
    act = (jnp.sum(x != 0, axis=tuple(range(1, x.ndim)), dtype=jnp.int32)
           + np.int32(np.prod(y.shape[1:])))
    if layer["kind"] == "pool":
        macs = jnp.zeros((rows,), jnp.int32)
    elif layer["kind"] == "conv":
        # Each count is at most k*k*C (exact in float32); sum as int32.
        cnt = ref.conv(_nz(x), _nz(w), layer["stride"], layer["padding"])
        macs = jnp.sum(jnp.round(cnt).astype(jnp.int32), axis=(1, 2, 3))
    else:
        flat = x.reshape(rows, -1)
        per_row = jnp.sum(w != 0, axis=1, dtype=jnp.int32)
        macs = jnp.dot((flat != 0).astype(jnp.int32), per_row,
                       preferred_element_type=jnp.int32)
    return macs, act, y


@functools.partial(jax.jit, static_argnums=0)
def _block_counts(layers: tuple, params, x):
    macs, acts = [], []
    for layer, w in zip(layers, params):
        m, a, x = layer_counts(dict(layer), w, x)
        macs.append(m)
        acts.append(a)
    return jnp.stack(macs), jnp.stack(acts)


def _frozen(cfg: dict) -> tuple:
    return tuple(tuple(sorted(layer.items())) for layer in ref.mark_last(cfg))


def pool_counts(cfg: dict, params, pool: np.ndarray, block: int) -> dict:
    """Counts of every pool input: ``macs`` and ``elements`` (layers x
    inputs, int64), ``weight_nnz`` (layers), layer ``kinds``."""
    layers = _frozen(cfg)
    macs, acts = [], []
    for lo in range(0, len(pool), block):
        x = pool[lo:lo + block]
        pad = block - len(x)
        if pad:
            x = np.concatenate([x, np.zeros((pad,) + x.shape[1:], x.dtype)])
        m, a = _block_counts(layers, params, jnp.asarray(x))
        macs.append(np.asarray(m, np.int64)[:, :block - pad])
        acts.append(np.asarray(a, np.int64)[:, :block - pad])
    wnnz = np.array([0 if w is None else int(jnp.sum(w != 0))
                     for w in params], np.int64)
    return dict(macs=np.concatenate(macs, axis=1),
                elements=np.concatenate(acts, axis=1), weight_nnz=wnnz,
                kinds=[layer["kind"] for layer in cfg["layers"]])


def step_bound_s(counts: dict, pool_idx: np.ndarray, peaks: dict) -> float:
    """Least device seconds for one step that serves the pool inputs
    ``pool_idx``: per layer the larger of operations over peak and bytes
    over bandwidth, summed over layers."""
    flops = 2.0 * counts["macs"][:, pool_idx].sum(axis=1)
    nbytes = BYTES_PER_ELEMENT * (counts["elements"][:, pool_idx].sum(axis=1)
                                  + counts["weight_nnz"])
    return float(np.maximum(flops / peaks["bf16_flops_per_s"],
                            nbytes / peaks["hbm_bytes_per_s"]).sum())


def useful_flops(counts: dict, pool_idx: np.ndarray) -> float:
    return 2.0 * float(counts["macs"][:, pool_idx].sum())
