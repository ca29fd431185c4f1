"""The served steps' least time over the device's busy time, in %.

Each step's least time sums, over the network's layers, the larger of its
useful operations over the bf16 peak and its least bytes over the HBM
bandwidth (``counts.step_bound_s``)."""
from bench import counts
from bench.metrics import batches


def read(run):
    if run.trace is None or run.counts is None or run.trace.busy_s <= 0:
        return None
    bound = sum(counts.step_bound_s(run.counts, idx, run.peaks)
                for _, idx in batches(run))
    return 100.0 * bound / run.trace.busy_s if bound > 0 else None
