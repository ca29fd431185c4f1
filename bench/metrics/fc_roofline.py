"""The fc layers' least time over their device time, in %: the least seconds of
the network's fc layers (the larger of their useful operations over the peak
and their least bytes over the bandwidth; ``program_trace.layer_bound_s``)
summed over the batches served in the trace, over the device op time under
those layers' scopes (``mnf.L<i>.fc``). Nothing to read where the program
has no layer scopes or the network no fc layer."""
from bench.program_trace import kind_roofline


def read(run):
    return kind_roofline(run, "fc")
