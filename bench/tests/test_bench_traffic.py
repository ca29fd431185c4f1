"""The traffic generator against a fake server: loops, schedule, sample."""
import contextlib
import os
import sys
import time
from types import SimpleNamespace

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import catalog  # noqa: E402
from bench import reference as ref  # noqa: E402
from bench import traffic  # noqa: E402


class FakeServer:
    """Answers every queued request at each tick with ``[sum(x)]``."""

    def __init__(self):
        self.queue, self.rid = [], 0

    def submit(self, x):
        r = SimpleNamespace(rid=self.rid, submit_time=time.perf_counter(),
                            x=x, latency_s=0.0, result=None)
        self.rid += 1
        self.queue.append(r)
        return r

    def run_tick(self):
        done, self.queue = self.queue, []
        now = time.perf_counter()
        for r in done:
            r.latency_s = now - r.submit_time
            r.result = np.array([float(r.x.sum())], np.float32)
        return done


def _span(name):
    return contextlib.nullcontext()


POOL = np.arange(5 * 3, dtype=np.float32).reshape(5, 3)


def _check_record(win):
    assert len(win.done) == win.attempted
    assert np.all(win.latency_s >= 0) and np.all(win.lateness_s >= 0)
    assert sum(win.tick_sizes) == len(win.done)
    assert np.array_equal(win.pool_idx, np.arange(len(win.done)) % len(POOL))
    # every sampled logit belongs to the pool input it names
    np.testing.assert_array_equal(win.sample_logits[:, 0],
                                  POOL[win.sample_idx].sum(axis=1))


def test_closed_loop_keeps_every_client_busy():
    tr = dict(loop="closed", clients=3, check_sample=7)
    win = traffic.drive(FakeServer(), POOL, tr, seed=11, seconds=0.05,
                        span=_span)
    assert set(win.tick_sizes) == {3}
    assert len(win.sample_idx) == 7
    _check_record(win)


def test_open_loop_sends_on_schedule_and_answers_everything():
    tr = dict(loop="open", rate=2000.0, check_sample=50)
    ticks = []
    win = traffic.drive(FakeServer(), POOL, tr, seed=2**33 + 1,
                        seconds=0.2, span=_span, after_tick=ticks.append)
    assert win.attempted == len(traffic.open_schedule(2000.0, 0.2,
                                                      2**33 + 1))
    assert win.seconds == pytest.approx(0.2)
    assert len(ticks) == len(win.tick_sizes)
    assert np.all(win.done >= win.due)
    _check_record(win)


def test_every_seed_gets_the_same_gaps_in_another_order():
    a = np.diff(traffic.open_schedule(1000.0, 1.0, 1))
    b = np.diff(traffic.open_schedule(1000.0, 1.0, 2))
    assert not np.array_equal(a, b)
    assert len(a) == pytest.approx(1000, rel=0.05)
    assert len(b) == pytest.approx(1000, rel=0.05)


def test_input_pool_follows_its_parameters():
    cfg = catalog.load_json(os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "configs", "lenet300.json"))
    inputs = dict(low=0.0, high=1.0, field=20, density=0.375)
    x = np.asarray(jax.jit(lambda k: traffic.make_pool(
        cfg, inputs, 64, k))(ref.seed_key(2**40 + 3)))
    assert x.shape == (64, 784)
    img = x.reshape(64, 28, 28)
    outside = img.copy()
    outside[:, 4:24, 4:24] = 0
    assert not outside.any()
    assert x.min() >= 0 and x.max() <= 1
    assert (x > 0).mean() == pytest.approx(0.375 * 400 / 784, rel=0.1)
    dense = dict(low=0.0, high=1.0, field=None, density=1.0)
    y = np.asarray(traffic.make_pool(cfg, dense, 4, ref.seed_key(5)))
    assert (y > 0).all()
