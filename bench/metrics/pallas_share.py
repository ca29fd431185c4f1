"""Share of the device's busy time spent in the event path's Pallas
kernels (ops named ``mnf_*``), in %. The rest is XLA's own ops: gathers,
encodes, pads and copies around them. Nothing to read where no such kernel
ran."""
from bench.system import KERNEL_PREFIX


def read(run):
    if run.trace is None or run.trace.busy_s <= 0:
        return None
    kernel_s = run.trace.kernel_s(KERNEL_PREFIX)
    return 100.0 * kernel_s / run.trace.busy_s if kernel_s > 0 else None
