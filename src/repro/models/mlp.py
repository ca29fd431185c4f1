"""Event-native MLP workloads: the paper's FC/MNIST-class networks.

The MNF paper evaluates FC networks (MNIST MLPs) alongside the CNNs; this
module is the FC twin of ``models/cnn.py``, riding the exact same engine
seams (DESIGN.md §12):

  * dense  — the engine's dense backend + ReLU (the oracle),
  * mnf    — event-resident: ``engine.fire`` emits an ``EventStream`` after
             every hidden layer and the next ``engine.linear`` consumes it
             directly.  Every boundary is FC→FC, which is always
             re-tile-free (the stream already lives in the flattened view),
             so the chained forward has **zero densify points** by
             construction — input encode to logits.  With
             ``cfg.int8_events`` the fire phase emits int8 event values
             carrying ``QParams`` and every boundary requantizes; the
             round-trip twin is then the fake-quant forward, and the chain
             matches it bitwise within a backend (DESIGN.md §12).

``make_mlp_pipeline`` is the single-jit whole-network closure the serving
tier buckets (``launch/serve.py --mlp``); ``mlp_boundary_summary`` is the
static per-boundary accounting serving's boundary report states, with the
same record schema as ``chain_boundary_summary`` so CNN and MLP cells
report through one code path.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro import engine
from repro.core.fire import FireConfig, fire
from repro.models.cnn import FCSpec, fc_in_events, layer_scope

__all__ = ["MLPSpec", "LENET_300_100", "MLP_MINI", "init_mlp_params",
           "mlp_forward", "make_mlp_forward", "make_mlp_pipeline",
           "mlp_boundary_summary", "mlp_layer_dense_macs",
           "run_mlp_with_stats"]


@dataclasses.dataclass(frozen=True)
class MLPSpec:
    """A fully-connected network: ``in_features -> widths[0] -> ... ->
    widths[-1]`` with a fire (ReLU-family) boundary between layers and raw
    logits out of the last.  ``widths[-1]`` is the class count."""

    name: str
    in_features: int
    widths: tuple

    @property
    def num_classes(self) -> int:
        return self.widths[-1]

    @property
    def layers(self) -> tuple:
        """FCSpec view of the stack — the same layer vocabulary the CNN
        models use, so spec-polymorphic code (serving, benchmarks) can walk
        ``spec.layers`` without caring which family it holds."""
        return tuple(FCSpec(w) for w in self.widths)

    def feature_sizes(self) -> tuple:
        """Input width entering each layer."""
        return (self.in_features,) + self.widths[:-1]


#: The paper's MNIST-class workload: LeNet-300-100 (784 -> 300 -> 100 -> 10),
#: the standard FC benchmark of sparse-accelerator papers.
LENET_300_100 = MLPSpec("lenet_300_100", 784, (300, 100, 10))

#: Seconds-scale smoke MLP exercising both FC→FC chain boundaries — the
#: serving smoke and ``kernel_bench --smoke`` bucket-serve this net.
MLP_MINI = MLPSpec("mlp_mini", 64, (32, 16, 10))


def init_mlp_params(key: jax.Array, spec: MLPSpec,
                    weight_sparsity: float = 0.0):
    """He-initialized FC params; optional unstructured pruning (the paper
    prunes MNIST MLPs to ~10% weight density)."""
    params = []
    for i, (fan_in, out) in enumerate(zip(spec.feature_sizes(), spec.widths)):
        k = jax.random.fold_in(key, i)
        wgt = jax.random.normal(k, (fan_in, out), jnp.float32)
        wgt = wgt * (2.0 / fan_in) ** 0.5
        if weight_sparsity > 0.0:
            keep = jax.random.uniform(jax.random.fold_in(k, 1), wgt.shape)
            wgt = jnp.where(keep >= weight_sparsity, wgt, 0.0)
        params.append(wgt)
    return params


def mlp_layer_dense_macs(spec: MLPSpec):
    """Per-layer dense MAC counts (what a dense accelerator does)."""
    return [fan_in * out
            for fan_in, out in zip(spec.feature_sizes(), spec.widths)]


def mlp_boundary_summary(spec: MLPSpec, *, batch: int = 1,
                         fire_cfg: FireConfig = FireConfig(),
                         engine_cfg: engine.EngineConfig | None = None
                         ) -> dict:
    """Static per-boundary accounting of the chained MLP (no tracing).

    Same schema as ``models.cnn.chain_boundary_summary`` so serving's
    boundary report handles both families through one code path.  Every
    boundary past the input is FC→FC — always eligible, never re-tiled —
    so ``densify`` and ``retile`` are structurally 0; ``routes`` lists the
    ``engine.route_linear`` decision of each stream-consuming boundary
    (DESIGN.md §11/§12).
    """
    cfg = _mlp_cfg(engine_cfg, mnf=True, fire_cfg=fire_cfg)
    out = dict(conv=0, fc=len(spec.widths), pool=0, pool_events=0,
               densify=0, input_encode=0, retile=0, routes=[])
    for i, (fan_in, width) in list(enumerate(zip(spec.feature_sizes(),
                                                 spec.widths)))[1:]:
        dec = engine.route_linear(batch, fan_in, width, cfg)
        out["routes"].append(dict(
            op="linear", layer=i, route=dec.route, occupancy=dec.occupancy,
            est_event_cost=dec.est_event_cost,
            est_dense_cost=dec.est_dense_cost, source=dec.source,
            shape_class=engine.linear_shape_class(batch, fan_in, width)))
    return out


def _mlp_cfg(base: engine.EngineConfig | None, *, mnf: bool,
             fire_cfg: FireConfig) -> engine.EngineConfig:
    cfg = base or engine.EngineConfig()
    if not mnf:
        cfg = cfg.replace(backend="dense")
    return cfg.replace(threshold=fire_cfg.threshold,
                       magnitude=fire_cfg.magnitude,
                       int8_events=cfg.int8_events
                       or fire_cfg.quantize_to_int8)


def _forward(params, x, spec: MLPSpec, *, fire_cfg: FireConfig,
             cfg: engine.EngineConfig, chain: bool,
             stats: list | None = None):
    """The one traced forward body behind ``mlp_forward`` /
    ``make_mlp_pipeline``.

    ``chain=True`` threads one EventStream through fire→linear→fire→…:
    every hidden boundary stays event-only (the fired twin is dropped).
    The chain head passes the dense input straight into ``engine.linear``
    — event backends encode it losslessly at threshold 0, the same encode
    the round-trip twin's first layer performs, so the two paths multiply
    identical tiles from the first layer on and agree bitwise within a
    backend (DESIGN.md §12).  ``chain=False`` is that per-layer round-trip
    twin (dense at every boundary, identical compute geometry).
    """
    # Dispatch at threshold 0: the fire phase already zeroed sub-threshold
    # activations, so the boundary encode must be lossless (DESIGN.md §5).
    fcfg = cfg.replace(threshold=0.0)
    layers = spec.layers
    for i, (layer, wgt) in enumerate(zip(layers, params)):
        with engine.scope(layer_scope(i, layer)):
            if stats is not None:
                in_ev = fc_in_events(x, fire_cfg.threshold)
                stats.append(dict(event_macs=in_ev * layer.out,  # Algorithm 2
                                  in_events=in_ev))
            acc = engine.linear(x, wgt, cfg=fcfg)
            last = i == len(layers) - 1
            if last:
                x = acc
            elif chain:
                x = engine.fire(acc, cfg, keep_dense=False)
            else:
                x = fire(acc, fire_cfg)
    return x


def mlp_forward(params, x: jax.Array, spec: MLPSpec, *, mnf: bool = True,
                fire_cfg: FireConfig = FireConfig(),
                engine_cfg: engine.EngineConfig | None = None,
                chain: bool | None = None):
    """x: (B, in_features) -> logits (B, classes).  mnf=False is the oracle.

    ``chain`` selects the event-resident path (default: on for MNF; int8
    requantization chains too); ``chain=False`` forces the per-layer dense
    round-trip twin the chained path is bitwise-measured against.
    """
    cfg = _mlp_cfg(engine_cfg, mnf=mnf, fire_cfg=fire_cfg)
    if chain is None:
        chain = mnf
    return _forward(params, x, spec, fire_cfg=fire_cfg, cfg=cfg,
                    chain=chain and mnf)


def make_mlp_forward(spec: MLPSpec, *, mnf: bool = True,
                     fire_cfg: FireConfig = FireConfig(),
                     engine_cfg: engine.EngineConfig | None = None,
                     chain: bool | None = None):
    """The un-jitted whole-network closure: ``fwd(params, x) -> logits`` —
    the seam the serving tier wraps (bucket-shaped jit or batch-parallel
    ``shard_map`` body, same as ``make_cnn_forward``)."""
    cfg = _mlp_cfg(engine_cfg, mnf=mnf, fire_cfg=fire_cfg)
    if chain is None:
        chain = mnf
    chain = chain and mnf

    def fwd(params, x):
        return _forward(params, x, spec, fire_cfg=fire_cfg, cfg=cfg,
                        chain=chain)

    return fwd


def make_mlp_pipeline(spec: MLPSpec, *, mnf: bool = True,
                      fire_cfg: FireConfig = FireConfig(),
                      engine_cfg: engine.EngineConfig | None = None,
                      chain: bool | None = None, donate: bool = True):
    """One jitted forward per network: ``fn(params, x) -> logits``."""
    fwd = make_mlp_forward(spec, mnf=mnf, fire_cfg=fire_cfg,
                           engine_cfg=engine_cfg, chain=chain)
    return jax.jit(fwd, donate_argnums=(1,) if donate else ())


def run_mlp_with_stats(params, x: jax.Array, spec: MLPSpec,
                       fire_cfg: FireConfig = FireConfig(),
                       engine_cfg: engine.EngineConfig | None = None):
    """Chained MNF forward + per-layer event accounting.

    Returns (logits, stats list); each layer's stats carry ``dense_macs``
    (static), ``event_macs`` (Algorithm 2: in_events × out) and
    ``in_events`` — the events/token quantity ``kernel_bench --mlp``
    sweeps over input sparsity.
    """
    cfg = _mlp_cfg(engine_cfg, mnf=True, fire_cfg=fire_cfg)

    def fwd(p, xx):
        stats: list = []
        logits = _forward(p, xx, spec, fire_cfg=fire_cfg, cfg=cfg,
                          chain=True, stats=stats)
        return logits, tuple(stats)

    logits, traced = jax.jit(fwd)(params, x)
    stats = []
    for macs, tr in zip(mlp_layer_dense_macs(spec), traced):
        d = dict(kind="fc", dense_macs=float(x.shape[0] * macs))
        d.update({k: float(v) for k, v in tr.items()})
        stats.append(d)
    return logits, stats
