"""The host's own time in a serving tick, in ms: the median over the
traced window's ticks of ``mnf.serve.tick`` less the ``mnf.serve.wait``
(the device's step) inside it. Nothing to read where the program names no
ticks."""
import numpy as np

from bench import program_trace


def read(run):
    prog = program_trace.for_run(run)
    ticks = prog.tick_host_ms() if prog is not None else []
    return float(np.median(ticks)) if ticks else None
