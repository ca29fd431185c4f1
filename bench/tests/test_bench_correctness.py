"""What decides ``correct``: a sound run passes; the control and a planted
fault fail.

The control is the plain reference at the next precision below the one the
configurations state (XLA's ``high``: three bfloat16 passes) put in the
program's place. The fault alters the answers where the compiled step
produces them. Both run here on the CPU, at the configurations' own widths.
"""
import os
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import catalog, compare, run, system, traffic  # noqa: E402
from bench import reference as ref  # noqa: E402

SEEDS = (3000000019, 3000000021, 3000000023)


def _load(config, mix):
    return (catalog.load_json(os.path.join(ROOT, "bench", "configs",
                                       config + ".json")),
            catalog.load_json(os.path.join(ROOT, "bench", "traffic",
                                       mix + ".json")))


@pytest.mark.parametrize("config,mix,n", [("lenet300", "b128-closed", 256),
                                          ("alexnet224", "b8-closed", 4)])
def test_control_at_high_precision_fails_the_limit(config, mix, n):
    cfg, tr = _load(config, mix)
    init = jax.jit(lambda kd: ref.init_params(cfg, kd))
    for seed in SEEDS:
        key = ref.seed_key(seed)
        params = init(key)
        pool = np.asarray(jax.jit(lambda kd: traffic.make_pool(
            cfg, tr["input"], n, kd))(key))
        idx = np.arange(n)
        control = compare.reference_logits(cfg, params, pool, idx, n, "high")
        err = compare.check_sample(cfg, params, pool, idx, control, n)
        assert err.max() > cfg["check"]["logit_err"], seed


@pytest.fixture
def cpu_cell(monkeypatch):
    """Drive ``run.run_cell`` here: no look for a chip, and the test
    process's compile cache left as it is."""
    monkeypatch.setattr(system, "configure_cache", lambda: "unchanged")

    def cell(seed):
        return run.run_cell("lenet300-b128-closed", seed, 0.5, False,
                            require_tpu=False)
    return cell


def test_a_sound_run_is_correct(cpu_cell, capsys):
    res = cpu_cell(SEEDS[0])
    assert res["correct"] and res["failed"] == 0
    assert res["attempted"] > 0
    assert set(res["metrics"]) == {"setup_s", "digits_per_s"}
    assert list(res)[-1] == "check"
    assert capsys.readouterr().err.rstrip().splitlines()[-1].startswith(
        "check ")


def test_an_answer_altered_where_it_is_produced_is_caught(cpu_cell,
                                                          monkeypatch):
    from repro.serving import server
    compiled = server.ServeEngine._compiled

    def altered(self, bucket):
        step = compiled(self, bucket)

        def run_step(params, x):
            y = step(params, x)
            return y.at[:, 0].add(1e-3 * jax.numpy.abs(y).max())
        return run_step

    monkeypatch.setattr(server.ServeEngine, "_compiled", altered)
    res = cpu_cell(SEEDS[1])
    assert not res["correct"]
    assert res["failed"] > 0
    assert res["check"]["logit_err"]["value"] > \
        res["check"]["logit_err"]["limit"]
