"""Dispatch tracing — make the engine's fallbacks visible (DESIGN.md §5.1).

The engine API silently degrades in two places: a backend without a
registered ``linear_events`` / ``conv2d_events`` op decodes the incoming
``EventStream`` (the round-trip the chained path exists to avoid), and
``EventStream.dense()`` on a twin-less stream is a real decode.  Both used to
be invisible.  ``trace_dispatch()`` collects a record per dispatch so tests
and benchmarks can assert *where* densification happens::

    with engine.trace_dispatch() as records:
        y = engine.linear(stream, w, cfg=cfg)
    assert not any(r.get("fallback_decode") for r in records)

Records are appended at Python dispatch time, which under ``jax.jit`` means
trace time: the counts describe the compiled graph's structure (how many
decode ops it contains), which is exactly the per-boundary accounting the
benchmarks report.  Every record carries ``layer``: the index ``i`` of
the innermost ``mnf.L<i>.<kind>`` scope it was traced under (None outside
every layer scope), so a record names the network layer whose device time
the profiler attributes to that scope.  Conv dispatches additionally mark
the tiling they rode — ``strip=True, launches=1`` for the fused strip
kernel vs ``launches=k*k`` for the per-tap path — so grid/launch
accounting and the strip-degradation CI guard read straight off the
records.  Nesting is
supported; each context sees every record emitted while it is active.

The same module names the program to the profiler.  ``span`` marks a
stretch of host time (``mnf.serve.tick``, ``mnf.serve.batch``, ...) with
its counters as event stats; ``scope`` names the ops traced inside it
(``mnf.L03.conv``), and the name reaches every compiled instruction as
``op_name`` metadata, so device time can be attributed to a network layer
(``serving.ServeEngine.op_layers``).  A span costs about a microsecond
when no profiler session is active and records only inside one; a scope
costs nothing at run time.
"""
from __future__ import annotations

import contextlib
import re

import jax

__all__ = ["record", "trace_dispatch", "span", "scope", "op_scopes"]

#: Prefix of every span and scope the program names.
PREFIX = "mnf."

_SINKS: list[list] = []
#: Indices of the layer scopes open now, innermost last.
_LAYERS: list[int] = []
_LAYER_NAME = re.compile(r"L(\d+)\.")


def record(**fields) -> None:
    """Append one dispatch record to every active ``trace_dispatch`` context,
    with ``layer`` the innermost open layer scope's index.

    No-op (and allocation-free) when no context is active — safe to call on
    every hot-path dispatch.
    """
    if _SINKS:
        rec = dict(fields, layer=_LAYERS[-1] if _LAYERS else None)
        for sink in _SINKS:
            sink.append(rec)


@contextlib.contextmanager
def trace_dispatch():
    """Context manager yielding the list of dispatch records."""
    sink: list = []
    _SINKS.append(sink)
    try:
        yield sink
    finally:
        _SINKS.remove(sink)


def span(name: str, **stats):
    """A profiler span ``mnf.<name>`` on the host; ``stats`` (numbers or
    strings) become the event's stats in the trace."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **stats)


@contextlib.contextmanager
def scope(name: str):
    """A named scope ``mnf.<name>`` over the ops traced inside it; a layer
    scope (``L03.conv``) also sets the ``layer`` of the records made in it."""
    m = _LAYER_NAME.match(name)
    with jax.named_scope(PREFIX + name):
        if m:
            _LAYERS.append(int(m.group(1)))
        try:
            yield
        finally:
            if m:
                _LAYERS.pop()


# One HLO instruction line: ``[ROOT] %name = <shape> <opcode>(<operands>)...``.
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_LAYER_SCOPE = re.compile(r"(?:^|/)(mnf\.(?:L\d+\.[a-z]+|out))(?=/|$)")
_REF = re.compile(r"%([\w.\-]+)")


def op_scopes(hlo_text: str) -> dict[str, str]:
    """Instruction name -> the layer scope (``mnf.L03.conv``, ``mnf.out``)
    it was traced under, from the ``op_name`` metadata of a compiled
    module's text (``compiled.as_text()``).

    An instruction outside every layer scope (a parameter's layout copy, a
    weight slice, the output copy) takes the scope of its first user that
    has one, else of its first operand that has one, so that the work done
    to feed a layer counts as that layer's.
    """
    names, refs, scopes = [], {}, {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            continue
        name, rest = m.groups()
        names.append(name)
        refs[name] = _REF.findall(rest)
        op = _OP_NAME.search(rest)
        layer = _LAYER_SCOPE.search(op.group(1)) if op else None
        if layer:
            scopes[name] = layer.group(1)
    users: dict[str, list] = {n: [] for n in names}
    operands: dict[str, list] = {n: [] for n in names}
    for n in names:
        for r in refs[n]:
            if r in users and r != n:
                users[r].append(n)
                operands[n].append(r)
    # Users come after their operands in the text: walk back for users,
    # forward for operands, until nothing changes.
    for neighbours, order in ((users, names[::-1]), (operands, names)):
        changed = True
        while changed:
            changed = False
            for n in order:
                if n not in scopes:
                    near = next((scopes[u] for u in neighbours[n]
                                 if u in scopes), None)
                    if near is not None:
                        scopes[n] = near
                        changed = True
    return scopes
