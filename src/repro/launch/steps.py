"""Sharded step factories: train_step / prefill_step / serve_step per cell.

Each factory resolves param/cache/batch shardings from logical axis specs
under the given mesh and returns a jitted function plus the sharding trees
(the dry-run lowers these functions with ShapeDtypeStruct inputs).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.engine import EngineConfig
from repro.models import transformer as tfm
from repro.optim import AdamWConfig, OptState, adamw_init, adamw_update
from repro.parallel.sharding import (ShardingRules, data_axis_size,
                                     make_rules, make_sharder,
                                     named_sharding_tree, serve_batch_pspec)

__all__ = ["CellPlan", "CNNCellPlan", "plan_cell", "make_train_step",
           "make_prefill_step", "make_serve_step", "make_cnn_serve_step",
           "cell_engine_config"]


def cell_engine_config(cfg: ModelConfig) -> EngineConfig:
    """Resolve the MNF engine configuration a cell runs under.

    One seam for every step factory: the model-level MNFConfig maps onto an
    EngineConfig with backend/interpret pinned per device (DESIGN.md §4), so
    dry-run reports and serving logs state exactly which multiply-phase
    implementation the cell uses.
    """
    return EngineConfig.from_mnf(cfg.mnf).resolved()


@dataclasses.dataclass
class CellPlan:
    """Everything the dry-run/launcher needs for one (arch × shape × mesh)."""

    cfg: ModelConfig
    shape: ShapeConfig
    mesh: Mesh
    rules: ShardingRules
    param_shapes: Any
    param_shardings: Any
    fn: Any                     # jitted step function
    arg_specs: tuple            # ShapeDtypeStructs to lower with
    donate: tuple = ()
    engine: EngineConfig = dataclasses.field(default_factory=EngineConfig)


def _dp_spec(mesh: Mesh) -> P:
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    return P(dp if len(dp) > 1 else dp[0])


def _batch_shardings(inputs: dict, mesh: Mesh, rules: ShardingRules) -> dict:
    """Divisibility-aware batch sharding per input (batch=1 cells stay
    replicated instead of tripping pjit's divisibility check)."""
    from repro.parallel.sharding import logical_to_pspec
    out = {}
    for k, sds in inputs.items():
        axes = ("batch",) + (None,) * (len(sds.shape) - 1)
        out[k] = NamedSharding(mesh, logical_to_pspec(axes, sds.shape, mesh,
                                                      rules))
    return out


def _param_shapes_and_shardings(cfg: ModelConfig, mesh: Mesh,
                                rules: ShardingRules):
    # Specs are static python data built during tracing — capture them via a
    # side channel so eval_shape only sees array outputs.
    box = {}

    def initf(k):
        p, s = tfm.init_params(k, cfg)
        box["specs"] = s
        return p

    shapes = jax.eval_shape(initf, jax.ShapeDtypeStruct((2,), jnp.uint32))
    specs = box["specs"]
    shardings = named_sharding_tree(specs, shapes, mesh, rules)
    return shapes, specs, shardings


def make_train_step(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh, *,
                    opt: AdamWConfig | None = None,
                    rules: ShardingRules | None = None,
                    accum_steps: int = 1) -> CellPlan:
    """accum_steps > 1 runs gradient accumulation: the global batch splits
    into microbatches scanned sequentially (grads averaged, one optimizer
    step) — the standard lever when a cell exceeds HBM at the target
    batch."""
    opt = opt or AdamWConfig()
    rules = rules or make_rules(mesh, fsdp=cfg.fsdp, seq_shard=cfg.seq_shard)
    sc = make_sharder(mesh, rules)

    pshapes, pspecs, pshard = _param_shapes_and_shardings(cfg, mesh, rules)
    oshard = OptState(mu=pshard, nu=pshard,
                      count=NamedSharding(mesh, P()))
    inputs = tfm.input_specs(cfg, shape)
    bshard = _batch_shardings(inputs, mesh, rules)
    assert shape.global_batch % accum_steps == 0, (shape.global_batch,
                                                   accum_steps)

    def train_step(params, opt_state, batch):
        if accum_steps == 1:
            loss, grads = jax.value_and_grad(
                lambda p: tfm.lm_loss(p, batch, cfg, sc=sc))(params)
        else:
            micro = jax.tree.map(
                lambda x: x.reshape((accum_steps, x.shape[0] // accum_steps)
                                    + x.shape[1:]), batch)

            def acc_body(carry, mb):
                loss_acc, grad_acc = carry
                l, g = jax.value_and_grad(
                    lambda p: tfm.lm_loss(p, mb, cfg, sc=sc))(params)
                g = jax.tree.map(lambda a, b_: a + b_.astype(jnp.float32),
                                 grad_acc, g)
                return (loss_acc + l, g), None

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (loss, grads), _ = jax.lax.scan(
                acc_body, (jnp.zeros((), jnp.float32), zeros), micro)
            loss = loss / accum_steps
            grads = jax.tree.map(lambda g: g / accum_steps, grads)
        new_p, new_o, metrics = adamw_update(grads, opt_state, params, opt)
        return new_p, new_o, dict(loss=loss, **metrics)

    fn = jax.jit(train_step,
                 in_shardings=(pshard, oshard, bshard),
                 out_shardings=(pshard, oshard, None),
                 donate_argnums=(0, 1))
    oshapes = jax.eval_shape(adamw_init, pshapes)
    return CellPlan(cfg=cfg, shape=shape, mesh=mesh, rules=rules,
                    param_shapes=pshapes, param_shardings=pshard, fn=fn,
                    arg_specs=(pshapes, oshapes, inputs), donate=(0, 1),
                    engine=cell_engine_config(cfg))


def _cache_shardings(cfg: ModelConfig, bsz: int, max_len: int, mesh: Mesh,
                     rules: ShardingRules):
    cshapes = tfm.cache_specs(cfg, bsz, max_len)
    caxes = tfm.cache_axes(cfg)
    return cshapes, named_sharding_tree(caxes, cshapes, mesh, rules)


def make_prefill_step(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh, *,
                      rules: ShardingRules | None = None) -> CellPlan:
    rules = rules or make_rules(mesh, fsdp=cfg.fsdp, seq_shard=cfg.seq_shard)
    sc = make_sharder(mesh, rules)
    pshapes, pspecs, pshard = _param_shapes_and_shardings(cfg, mesh, rules)
    inputs = tfm.input_specs(cfg, shape)
    bshard = _batch_shardings(inputs, mesh, rules)
    _, cshard = _cache_shardings(cfg, shape.global_batch, shape.seq_len,
                                 mesh, rules)

    def prefill_step(params, batch):
        logits, cache = tfm.prefill(
            params, batch["tokens"], cfg,
            vision_embeds=batch.get("vision_embeds"),
            audio_frames=batch.get("audio_frames"),
            max_len=shape.seq_len, sc=sc)
        return logits, cache

    fn = jax.jit(prefill_step, in_shardings=(pshard, bshard),
                 out_shardings=(None, cshard))
    return CellPlan(cfg=cfg, shape=shape, mesh=mesh, rules=rules,
                    param_shapes=pshapes, param_shardings=pshard, fn=fn,
                    arg_specs=(pshapes, inputs),
                    engine=cell_engine_config(cfg))


def make_serve_step(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh, *,
                    rules: ShardingRules | None = None) -> CellPlan:
    """decode: one new token against a seq_len-long cache."""
    rules = rules or make_rules(mesh, fsdp=cfg.fsdp, seq_shard=cfg.seq_shard)
    sc = make_sharder(mesh, rules)
    pshapes, pspecs, pshard = _param_shapes_and_shardings(cfg, mesh, rules)
    inputs = tfm.input_specs(cfg, shape)
    bshard = _batch_shardings(inputs, mesh, rules)
    cshapes, cshard = _cache_shardings(cfg, shape.global_batch,
                                       shape.seq_len, mesh, rules)
    pos_spec = jax.ShapeDtypeStruct((), jnp.int32)

    def serve_step(params, cache, batch, decode_pos):
        logits, new_cache = tfm.decode_step(
            params, cache, batch["tokens"], decode_pos, cfg, sc=sc)
        return logits, new_cache

    fn = jax.jit(serve_step,
                 in_shardings=(pshard, cshard, bshard, None),
                 out_shardings=(None, cshard),
                 donate_argnums=(1,))
    return CellPlan(cfg=cfg, shape=shape, mesh=mesh, rules=rules,
                    param_shapes=pshapes, param_shardings=pshard, fn=fn,
                    arg_specs=(pshapes, cshapes, inputs, pos_spec),
                    donate=(1,), engine=cell_engine_config(cfg))


@dataclasses.dataclass
class CNNCellPlan:
    """Serving plan for a CNN workload (the paper's inference driver).

    The whole network is one compiled pipeline (models/cnn.make_cnn_pipeline,
    DESIGN.md §5.1): ``fn(params, images) -> logits`` with the image buffer
    donated — batched requests ride a single jit per (network, batch shape).
    """

    spec: Any                   # models.cnn.CNNSpec
    batch: int
    fn: Any                     # jitted whole-network pipeline
    arg_specs: tuple            # (param ShapeDtypeStructs, image SDS)
    donate: tuple = (1,)
    engine: EngineConfig = dataclasses.field(default_factory=EngineConfig)
    #: Static chain accounting (models/cnn.chain_boundary_summary): how many
    #: pool boundaries ride the event-native segment max and how many
    #: densify points remain — serving logs report the DESIGN.md §7
    #: zero-densify invariant per cell.
    boundaries: dict = dataclasses.field(default_factory=dict)
    #: Serving-tier mesh placement (DESIGN.md §10): the (data, model) mesh
    #: the pipeline is placed on, how many ways the batch axis shards over
    #: it (1 = replicated single-device execution), and the NamedSharding
    #: the image buffer must arrive under (None off-mesh).
    mesh: Any = None
    data_shards: int = 1
    input_sharding: Any = None


def make_cnn_serve_step(spec, batch: int, *, mnf: bool = True,
                        engine_cfg: EngineConfig | None = None,
                        fire_cfg=None, donate: bool = True,
                        mesh: Mesh | None = None) -> CNNCellPlan:
    """Compile the event-resident CNN/MLP pipeline for batched serving.

    ``spec`` is a ``models.cnn.CNNSpec`` (already ``.scaled(...)`` to the
    serving resolution) or a ``models.mlp.MLPSpec`` — the FC family rides
    the same plan with a flat ``(batch, in_features)`` input buffer.  One
    jit covers conv→fire→…→FC; the MNF path keeps activations
    event-resident between conv layers (DESIGN.md §5).

    With a ``mesh``, the pipeline goes **batch-parallel**: the forward is
    wrapped in a ``shard_map`` over the mesh's data axes — weights
    replicated (in_spec ``P()``), the batch axis sharded — so each device
    runs the identical per-sample event pipeline over its batch shard
    (near-linear device scaling, and bitwise-identical logits, since the
    forward is per-sample independent).  A batch that does not divide the
    data axes (bucket 1 on a multi-device replica) stays replicated
    instead of tripping the divisibility check — same policy as
    ``parallel.sharding.serve_batch_pspec``.
    """
    from repro.core.fire import FireConfig
    from repro.models import cnn as cnn_mod
    from repro.models import mlp as mlp_mod

    fire_cfg = fire_cfg or FireConfig()
    ecfg = (engine_cfg or EngineConfig(backend="auto")).resolved()
    is_mlp = isinstance(spec, mlp_mod.MLPSpec)
    make_fwd = mlp_mod.make_mlp_forward if is_mlp \
        else cnn_mod.make_cnn_forward
    fwd = make_fwd(spec, mnf=mnf, fire_cfg=fire_cfg, engine_cfg=ecfg)
    data = data_axis_size(mesh) if mesh is not None else 1
    shards = data if (data > 1 and batch % data == 0) else 1
    in_shard = None
    if shards > 1:
        dp = _dp_spec(mesh)
        # check_vma off: the out_specs claim no replication the body does
        # not establish, and the event kernels carry no vma rules.
        fwd = jax.shard_map(fwd, mesh=mesh, in_specs=(P(), dp), out_specs=dp,
                            check_vma=False)
        in_shard = NamedSharding(mesh, serve_batch_pspec(mesh, batch))
    elif mesh is not None:
        in_shard = NamedSharding(mesh, P())
    fn = jax.jit(fwd, donate_argnums=(1,) if donate else ())
    init = mlp_mod.init_mlp_params if is_mlp else cnn_mod.init_cnn_params
    pshapes = jax.eval_shape(lambda k: init(k, spec),
                             jax.ShapeDtypeStruct((2,), jnp.uint32))
    if is_mlp:
        x_spec = jax.ShapeDtypeStruct((batch, spec.in_features), jnp.float32)
        boundaries = mlp_mod.mlp_boundary_summary(
            spec, batch=batch, fire_cfg=fire_cfg,
            engine_cfg=ecfg) if mnf else {}
    else:
        x_spec = jax.ShapeDtypeStruct(
            (batch, spec.input_size, spec.input_size, spec.in_ch),
            jnp.float32)
        boundaries = cnn_mod.chain_boundary_summary(
            spec, batch=batch, fire_cfg=fire_cfg,
            engine_cfg=ecfg) if mnf else {}
    return CNNCellPlan(spec=spec, batch=batch, fn=fn,
                       arg_specs=(pshapes, x_spec),
                       donate=(1,) if donate else (), engine=ecfg,
                       boundaries=boundaries, mesh=mesh, data_shards=shards,
                       input_sharding=in_shard)


def plan_cell(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh,
              **kw) -> CellPlan:
    if shape.kind == "train":
        return make_train_step(cfg, shape, mesh, **kw)
    if shape.kind == "prefill":
        return make_prefill_step(cfg, shape, mesh, **kw)
    return make_serve_step(cfg, shape, mesh, **kw)
