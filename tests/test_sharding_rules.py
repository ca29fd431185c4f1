"""Logical-axis resolver: priority, divisibility, reuse (no multi-device)."""
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.parallel.sharding import logical_to_pspec, make_rules


@pytest.fixture(scope="module")
def mesh16():
    # abstract mesh: shape arithmetic only, no devices needed
    return AbstractMesh((16, 16), ("data", "model"))


def test_divisibility_drops_heads(mesh16):
    rules = make_rules(mesh16)
    # 12 heads don't divide 16 -> heads replicated, attn_seq takes model
    ps = logical_to_pspec(("batch", "attn_seq", "heads", None),
                          (256, 4096, 12, 128), mesh16, rules)
    assert ps == P("data", "model")


def test_priority_prefers_heads(mesh16):
    rules = make_rules(mesh16)
    ps = logical_to_pspec(("batch", "attn_seq", "heads", None),
                          (256, 4096, 32, 128), mesh16, rules)
    assert ps == P("data", None, "model")


def test_axis_reuse_blocked(mesh16):
    rules = make_rules(mesh16)
    # experts take model; ff_expert must not reuse it
    ps = logical_to_pspec(("experts", "embed", "ff"), (64, 2048, 1408),
                          mesh16, rules)
    assert ps == P("model")


def test_vocab_beats_cache_seq(mesh16):
    rules = make_rules(mesh16)
    ps = logical_to_pspec(("cache_seq", "vocab"), (32768, 256000), mesh16,
                          rules)
    assert ps == P(None, "model")


def test_fsdp_rule(mesh16):
    rules = make_rules(mesh16, fsdp=True)
    ps = logical_to_pspec(("vocab", "embed"), (256000, 4608), mesh16, rules)
    assert ps == P("model", "data")
    rules2 = make_rules(mesh16, fsdp=False)
    ps2 = logical_to_pspec(("vocab", "embed"), (256000, 4608), mesh16, rules2)
    assert ps2 == P("model")


def test_batch_over_pod_and_data():
    mesh = AbstractMesh((2, 16, 16), ("pod", "data", "model"))
    rules = make_rules(mesh)
    ps = logical_to_pspec(("batch", None), (256, 4096), mesh, rules)
    assert ps == P(("pod", "data"))
    # batch=1 (long_500k): replicated
    ps1 = logical_to_pspec(("batch", None), (1, 4096), mesh, rules)
    assert ps1 == P()


def test_overrides():
    mesh = AbstractMesh((16, 16), ("data", "model"))
    rules = make_rules(mesh, overrides={"ff": None})
    ps = logical_to_pspec(("embed", "ff"), (1024, 4096), mesh, rules)
    assert ps == P()
