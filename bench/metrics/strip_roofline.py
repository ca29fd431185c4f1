"""The fused strip conv kernel's least time over its device time, in %: the
least seconds of the conv layers whose dispatch route is ``strip``
(``routes.layers_on``; ``program_trace.layer_bound_s``), summed over the
batches served in the trace, over the device op time under those layers'
scopes (``mnf.L<i>.conv``). Nothing to read where the program's routes name
no layer, or no conv layer rides the strip kernel."""
from bench import program_trace, routes
from bench.metrics import batches


def read(run):
    if run.counts is None or run.peaks is None:
        return None
    prog = program_trace.with_layers(program_trace.for_run(run))
    if prog is None:
        return None
    idx = routes.layers_on(prog.workload, "conv2d", "strip")
    if not idx:
        return None
    layer_s = prog.layer_op_s()
    op_s = sum(layer_s.get(f"{program_trace.PROGRAM}L{i:02d}.conv", 0.0)
               for i in idx)
    bound = sum(float(program_trace.layer_bound_s(run.counts, served,
                                                  run.peaks)[idx].sum())
                for _, served in batches(run))
    return 100.0 * bound / op_s if op_s > 0 and bound > 0 else None
