"""The one traffic generator: an input pool and a closed or open loop.

A traffic file (``traffic/<name>.json``) holds only parameters:

``loop``           ``"closed"``: ``clients`` callers, each sending its next
                   request when the last is answered. ``"open"``: Poisson
                   arrivals at ``rate`` requests/s, sent on schedule whatever
                   the server does.
``buckets``        the batch shapes the replica compiles and warms.
``input``          pixel values uniform in (``low``, ``high``], nonzero with
                   probability ``density`` inside a centred ``field`` x
                   ``field`` window (``null``: the whole image), zero outside.
``pool``           distinct inputs made from the seed; request ``i`` sends
                   input ``i % pool``.
``check_sample``   how many served requests the reference checks.
``trace_seconds``  how much of the window a traced run profiles.

The open loop's gaps are one fixed set of exponential quantiles, shuffled by
the seed: every seed offers the same arrivals in another order. Latency runs
from the time a request was due to the time its logits were on the host.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

LOOPS = ("closed", "open")
#: Spin instead of sleeping for the last stretch before a due time.
SPIN_S = 2e-4


def make_pool(cfg: dict, inputs: dict, n: int, key_data) -> jax.Array:
    """``n`` inputs of ``cfg["input_shape"]``; jit it with ``cfg``,
    ``inputs`` and ``n`` static so that the pool is made in one call."""
    h, w, c = cfg["image_shape"]
    kv, km = jax.random.split(jnp.asarray(key_data, jnp.uint32))
    u = jax.random.uniform(kv, (n, h, w, c))
    vals = inputs["low"] + (inputs["high"] - inputs["low"]) * (1.0 - u)
    live = jax.random.uniform(km, (n, h, w, c)) < inputs["density"]
    field = inputs.get("field")
    if field is not None:
        y0, x0 = (h - field) // 2, (w - field) // 2
        rows = (jnp.arange(h) >= y0) & (jnp.arange(h) < y0 + field)
        cols = (jnp.arange(w) >= x0) & (jnp.arange(w) < x0 + field)
        live = live & (rows[:, None] & cols[None, :])[None, :, :, None]
    x = jnp.where(live, vals, 0.0).astype(jnp.float32)
    return x.reshape((n,) + tuple(cfg["input_shape"]))


def open_schedule(rate: float, seconds: float, seed: int) -> np.ndarray:
    """Due offsets (s from the window start) of the open loop's requests
    within ``seconds``: exponential gaps at their quantile midpoints, in an
    order drawn from the seed."""
    n = int(np.ceil(rate * seconds * 1.25)) + 16
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u) / rate
    np.random.default_rng(seed).shuffle(gaps)
    due = np.cumsum(gaps) - gaps[0]
    return due[due < seconds]


@dataclasses.dataclass
class Window:
    """What the measured window did, one entry per completed request."""

    start: float = 0.0          # host clock at the window's start
    end: float = 0.0            # window's end for rates (see ``drive``)
    due: np.ndarray = None      # host clock the request was due
    submit: np.ndarray = None   # host clock it was handed to the server
    done: np.ndarray = None     # host clock its logits were on the host
    pool_idx: np.ndarray = None
    tick_sizes: list = dataclasses.field(default_factory=list)
    sample_idx: np.ndarray = None   # pool index of each checked request
    sample_logits: np.ndarray = None
    attempted: int = 0

    @property
    def seconds(self) -> float:
        return self.end - self.start

    @property
    def latency_s(self) -> np.ndarray:
        return self.done - self.due

    @property
    def lateness_s(self) -> np.ndarray:
        return self.submit - self.due


class _Reservoir:
    """A uniform sample of ``size`` completed requests (algorithm R), with
    its own generator from the seed."""

    def __init__(self, size: int, seed: int):
        self.size = size
        self.rng = np.random.default_rng([seed, 1])
        self.idx: list[int] = []
        self.rows: list = []
        self.seen = 0

    def offer(self, pool_idx: np.ndarray, logits: np.ndarray) -> None:
        n = len(pool_idx)
        take = max(0, min(n, self.size - self.seen))
        for i in range(take):
            self.idx.append(int(pool_idx[i]))
            self.rows.append(logits[i])
        if take < n:
            ords = self.seen + np.arange(take, n)
            slots = self.rng.integers(0, ords + 1)
            for i, slot in zip(range(take, n), slots):
                if slot < self.size:
                    self.idx[slot] = int(pool_idx[i])
                    self.rows[slot] = logits[i]
        self.seen += n


def drive(server, pool: np.ndarray, traffic: dict, seed: int,
          seconds: float, span: Callable,
          after_tick: Callable[[float], None] | None = None,
          clock: Callable[[], float] = time.perf_counter) -> Window:
    """Run the measured window against ``server`` and return its record.

    ``server`` has ``submit(x) -> request`` and ``run_tick() -> [request]``
    (each completed request carries ``rid``, ``submit_time``, ``latency_s``
    and ``result``). ``span(name)`` is a context manager around each call;
    ``after_tick(now)`` runs after each tick.
    """
    loop = traffic["loop"]
    if loop not in LOOPS:
        raise ValueError(f"traffic loop must be one of {LOOPS}, not {loop!r}")
    n_pool = len(pool)
    res = _Reservoir(traffic["check_sample"], seed)
    due_of: dict[int, float] = {}
    rec = dict(due=[], submit=[], done=[], pool=[])
    sent = 0

    def submit(due: float) -> None:
        nonlocal sent
        req = server.submit(pool[sent % n_pool])
        due_of[req.rid] = (due if due is not None else req.submit_time,
                           sent % n_pool)
        sent += 1

    def tick() -> None:
        with span("run_tick"):
            done = server.run_tick()
        if after_tick is not None:
            after_tick(clock())
        if not done:
            return
        meta = [due_of.pop(r.rid) for r in done]
        pidx = np.fromiter((m[1] for m in meta), np.int64, len(done))
        rec["due"].append(np.fromiter((m[0] for m in meta), np.float64,
                                      len(done)))
        rec["submit"].append(np.fromiter((r.submit_time for r in done),
                                         np.float64, len(done)))
        rec["done"].append(np.fromiter((r.submit_time + r.latency_s
                                        for r in done), np.float64,
                                       len(done)))
        rec["pool"].append(pidx)
        win.tick_sizes.append(len(done))
        res.offer(pidx, np.stack([np.asarray(r.result) for r in done]))

    win = Window()
    win.start = clock()
    stop = win.start + seconds
    if loop == "closed":
        clients = traffic["clients"]
        while clock() < stop:
            with span("submit"):
                for _ in range(clients - len(due_of)):
                    submit(None)
            tick()
        win.end = clock()
    else:
        due = win.start + open_schedule(traffic["rate"], seconds, seed)
        i = 0
        while i < len(due):
            now = clock()
            if due[i] > now:
                with span("wait"):
                    if due[i] - now > SPIN_S:
                        time.sleep(due[i] - now - SPIN_S)
                    while clock() < due[i]:
                        pass
                now = clock()
            with span("submit"):
                while i < len(due) and due[i] <= now:
                    submit(float(due[i]))
                    i += 1
            tick()
        while due_of:
            tick()
        win.end = stop
    win.attempted = sent
    for k, v in rec.items():
        arr = np.concatenate(v) if v else np.zeros(0)
        setattr(win, {"pool": "pool_idx"}.get(k, k), arr)
    win.sample_idx = np.asarray(res.idx, np.int64)
    win.sample_logits = (np.stack(res.rows) if res.rows
                         else np.zeros((0, 0), np.float32))
    return win
