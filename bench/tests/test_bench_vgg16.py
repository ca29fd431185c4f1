"""The vgg16_224 configuration: the program's VGG16, served on the CPU
against the plain reference, and the strip kernel's roofline reader."""
import os
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import catalog, compare, routes, system, traffic  # noqa: E402
from bench import program_trace as pt  # noqa: E402
from bench import reference as ref  # noqa: E402
from bench.metrics import Run, strip_roofline  # noqa: E402
from bench.trace_reduce import Summary  # noqa: E402
from bench.traffic import Window  # noqa: E402

CELL = "vgg16_224-b1-stream"
#: The conv layers of VGG16@224 that ride the fused strip kernel (224, 112
#: and 56 wide); the 28- and 14-wide ones take the per-tap pixel path.
STRIP_LAYERS = [0, 1, 3, 4, 6, 7, 8]


def _config():
    return catalog.load_json(os.path.join(ROOT, "bench", "configs",
                                          "vgg16_224.json"))


def test_config_builds_the_programs_vgg16():
    sys.path.insert(0, system.SRC)
    from repro.models import cnn
    cfg = _config()
    got = system.build_spec(cfg)
    assert got.layers == cnn.VGG16.layers
    assert (got.input_size, got.in_ch, got.num_classes) == (224, 3, 1000)
    assert ref.layer_inputs(cfg)[18] == (7, 7, 512)   # into fc6: K 25088
    n = sum(int(np.prod(s)) for s in (
        ref.weight_shape(layer, shape) for layer, shape in
        zip(cfg["layers"], ref.layer_inputs(cfg))) if s is not None)
    assert n == 138_344_128          # 138.4 M weights, no bias


def test_layer_list_at_32_served_matches_the_reference():
    """The configuration's 21 layers at a 32x32 input (fc6 then takes a
    1x1x512 map), served through a replica on the CPU, answer within the
    configuration's own limit of the plain reference."""
    cfg = dict(_config(), input_shape=[32, 32, 3], image_shape=[32, 32, 3])
    tr = catalog.load_json(os.path.join(ROOT, "bench", "traffic",
                                        "b1-stream.json"))
    key = ref.seed_key(3000000041)
    params = jax.jit(lambda kd: ref.init_params(cfg, kd))(key)
    pool = np.asarray(jax.jit(lambda kd: traffic.make_pool(
        cfg, tr["input"], 3, kd))(key))
    replica = system.Replica(cfg, params, tr["buckets"], require_tpu=False)
    served = []
    for x in pool:
        replica.submit(x)
        served += replica.run_tick()
    assert replica.compiles == len(tr["buckets"])
    got = np.stack([np.asarray(r.result) for r in served])
    err = compare.check_sample(cfg, params, pool, np.arange(len(pool)), got,
                               len(pool))
    assert err.max() <= cfg["check"]["logit_err"], err


def test_routes_name_the_strip_layers_of_the_cell():
    assert routes.layers_on(CELL, "conv2d", "strip") == STRIP_LAYERS
    assert routes.layers_on(CELL, "maxpool2d", "window") == [2, 5]
    assert routes.layers_on("lenet300-b128-closed", "conv2d", "strip") == []


def _run():
    """One frame served inside the trace; strip layers 0 and 1 bound at
    1 s and 2 s, the pixel conv layer 2 at 4 s."""
    win = Window(start=0.0, end=10.0, done=np.array([5.0]),
                 pool_idx=np.array([0]), tick_sizes=[1])
    cnt = dict(macs=np.array([[500], [1000], [2000]], np.int64),
               elements=np.zeros((3, 1), np.int64),
               weight_nnz=np.zeros(3, np.int64),
               kinds=["conv", "conv", "conv"])
    return Run(setup_s=1.0, window=win, buckets=(1,), traced_until=10.0,
               trace=Summary(window_s=10.0, busy_s=9.0, op_s={}, idle_s={}),
               counts=cnt, peaks=dict(bf16_flops_per_s=1e3,
                                      hbm_bytes_per_s=1e2))


def _program():
    s = 1_000_000_000
    return pt.Program(
        workload=CELL, window=(0, 10 * s),
        spans=[("mnf.serve.tick", 0, 10 * s, {})],
        device_ops=[[("mnf_event_conv_fused.1", 0, 2 * s),
                     ("mnf_event_conv_fused.2", 2 * s, 6 * s),
                     ("mnf_event_matmul.3", 6 * s, 9 * s)]],
        layers={"mnf_event_conv_fused.1": "mnf.L00.conv",
                "mnf_event_conv_fused.2": "mnf.L01.conv",
                "mnf_event_matmul.3": "mnf.L02.conv"})


def test_strip_roofline_reads_the_strip_layers_alone(monkeypatch):
    monkeypatch.setattr(pt, "for_run", lambda run: _program())
    monkeypatch.setattr(routes, "layers_on", lambda w, op, route: [0, 1])
    assert strip_roofline.read(_run()) == pytest.approx(100 * 3.0 / 6.0)


@pytest.mark.parametrize("strip", [None, []],
                         ids=["routes-name-no-layer", "no-strip-layer"])
def test_strip_roofline_reads_nothing_without_strip_layers(monkeypatch,
                                                           strip):
    monkeypatch.setattr(pt, "for_run", lambda run: _program())
    monkeypatch.setattr(routes, "layers_on", lambda w, op, route: strip)
    assert strip_roofline.read(_run()) is None
    run = _run()
    run.counts = None
    assert strip_roofline.read(run) is None
