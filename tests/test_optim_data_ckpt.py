"""Optimizer, schedules, compression, data pipeline, checkpointing."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import checkpoint as ckpt
from repro.data import TokenStreamConfig, cnn_batch, lm_batch, markov_lm_batch
from repro.optim import (AdamWConfig, adamw_init, adamw_update, constant,
                         event_psum, global_norm, quantized_psum,
                         topk_threshold, warmup_cosine)


def test_adamw_minimizes_quadratic():
    params = {"w": jnp.asarray([5.0, -3.0])}
    opt = AdamWConfig(lr=0.1, weight_decay=0.0, grad_clip=1e9)
    state = adamw_init(params)
    for _ in range(300):
        grads = jax.grad(lambda p: jnp.sum(p["w"] ** 2))(params)
        params, state, _ = adamw_update(grads, state, params, opt)
    assert float(jnp.abs(params["w"]).max()) < 1e-2


def test_grad_clip_metric():
    params = {"w": jnp.ones(4)}
    opt = AdamWConfig(grad_clip=1.0)
    state = adamw_init(params)
    grads = {"w": jnp.full((4,), 100.0)}
    _, _, metrics = adamw_update(grads, state, params, opt)
    assert float(metrics["grad_norm"]) == pytest.approx(200.0)


def test_schedules():
    s = warmup_cosine(1.0, 10, 100)
    assert float(s(jnp.asarray(0))) == 0.0
    assert float(s(jnp.asarray(10))) == pytest.approx(1.0)
    assert float(s(jnp.asarray(100))) == pytest.approx(0.1, abs=1e-3)
    assert float(constant(0.5)(jnp.asarray(7))) == 0.5


def test_quantized_psum_single_device():
    from repro.launch.mesh import checked_mesh
    x = jnp.linspace(-1, 1, 64)
    out = jax.shard_map(
        lambda v: quantized_psum(v, "i"),
        mesh=checked_mesh((1,), ("i",)),
        in_specs=jax.sharding.PartitionSpec(),
        out_specs=jax.sharding.PartitionSpec(), check_vma=False)(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x), atol=1e-2)


def test_event_psum_error_feedback():
    """Fired + residual always reconstructs the running gradient sum."""
    from repro.launch.mesh import checked_mesh
    mesh = checked_mesh((1,), ("i",))
    P = jax.sharding.PartitionSpec
    residual = jnp.zeros(32)
    total_sent = jnp.zeros(32)
    total_true = jnp.zeros(32)
    rng = np.random.default_rng(0)
    for step in range(6):
        g = jnp.asarray(rng.normal(size=32).astype(np.float32))
        fired, residual = jax.shard_map(
            lambda gv, rv: event_psum(gv, rv, "i", k_frac=0.25),
            mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()),
            check_vma=False)(g, residual)
        total_sent = total_sent + fired
        total_true = total_true + g
        np.testing.assert_allclose(np.asarray(total_sent + residual),
                                   np.asarray(total_true), atol=1e-5)
        # communication is sparse
        assert (np.asarray(fired) != 0).mean() <= 0.6


def test_topk_threshold():
    x = jnp.arange(100.0)
    th = topk_threshold(x, 0.1)
    assert float(th) == 90.0


def test_lm_batch_determinism_and_resume():
    cfg = TokenStreamConfig(vocab_size=64, seq_len=16, global_batch=4)
    b1 = lm_batch(cfg, 7)
    b2 = lm_batch(cfg, 7)
    np.testing.assert_array_equal(np.asarray(b1["tokens"]),
                                  np.asarray(b2["tokens"]))
    b3 = lm_batch(cfg, 8)
    assert not np.array_equal(np.asarray(b1["tokens"]),
                              np.asarray(b3["tokens"]))
    # host sharding partitions the batch deterministically
    h0 = lm_batch(cfg, 7, host_index=0, host_count=2)
    assert h0["tokens"].shape[0] == 2


def test_markov_batch_has_structure():
    cfg = TokenStreamConfig(vocab_size=32, seq_len=64, global_batch=4)
    b = markov_lm_batch(cfg, 0)
    toks = np.asarray(b["tokens"])
    # with 8 successors per token, bigram entropy is far below uniform
    assert b["labels"].shape == (4, 64)
    assert toks.min() >= 0 and toks.max() < 32


def test_cnn_batch_sparsity():
    x = np.asarray(cnn_batch(2, 16, 3, 0, activation_sparsity=0.7))
    assert abs((x == 0).mean() - 0.7) < 0.1
    assert (x >= 0).all()


def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(6).reshape(2, 3).astype(jnp.float32),
            "b": {"c": jnp.ones((4,), jnp.bfloat16)},
            "count": jnp.asarray(3)}
    d = str(tmp_path / "ck")
    ckpt.save(tree, d, 10)
    ckpt.save(tree, d, 20)
    assert ckpt.latest_step(d) == 20
    assert ckpt.all_steps(d) == [10, 20]
    like = jax.tree.map(jnp.zeros_like, tree)
    restored, step = ckpt.restore(like, d)
    assert step == 20
    np.testing.assert_array_equal(np.asarray(restored["a"]),
                                  np.asarray(tree["a"]))
    assert restored["b"]["c"].dtype == jnp.bfloat16


def test_checkpoint_async_and_atomicity(tmp_path):
    d = str(tmp_path / "ck")
    t = ckpt.save_async({"x": jnp.ones(8)}, d, 5)
    t.join()
    assert ckpt.latest_step(d) == 5
    # a leftover tmp dir never shadows a completed step
    assert not any(p.endswith(".tmp") for p in os.listdir(d))
