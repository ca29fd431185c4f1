"""Median latency of every request of the window, from when it was due."""
from bench.metrics import latency_ms


def read(run):
    return latency_ms(run, 50)
