"""Useful operations served in the traced window over what the chip's bf16
peak does in that time, in %. Useful operations are the products with two
nonzero operands (``counts.py``), so no implementation can pass 100%."""
from bench import counts
from bench.metrics import batches


def read(run):
    if run.trace is None or run.counts is None:
        return None
    flops = sum(counts.useful_flops(run.counts, idx)
                for _, idx in batches(run))
    if flops <= 0:
        return None
    return 100.0 * flops / (run.trace.window_s
                            * run.peaks["bf16_flops_per_s"])
