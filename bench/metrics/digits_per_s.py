"""Digits scored in the window over the window's seconds (the MLP cells'
throughput, kept apart from the CNN's so that each has its own bound)."""
from bench.metrics.images_per_s import read  # noqa: F401
