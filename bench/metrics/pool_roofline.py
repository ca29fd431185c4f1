"""The pool layers' least time over their device time, in %: the least seconds
of the network's pool layers (their least bytes over the bandwidth, since a
pool does no useful operation; ``program_trace.layer_bound_s``) summed over
the batches served in the trace, over the device op time under those layers'
scopes (``mnf.L<i>.pool``). Nothing to read where the program has no layer
scopes or the network no pool layer."""
from bench.program_trace import kind_roofline


def read(run):
    return kind_roofline(run, "pool")
