"""The serving tier's profiler spans and the compiled step's layer scopes.

``run_tick`` names its work to the profiler per tick and per batch, never
per request; each network layer's ops carry a named scope that reaches the
compiled instructions, and ``ServeEngine.op_layers`` reads it back.  The
spans are read from a real profiler trace of the CPU backend.
"""
import glob
import os
import re

import jax
import numpy as np
import pytest

from repro.engine.trace import op_scopes
from repro.models.mlp import LENET_300_100, MLP_MINI, init_mlp_params
from repro.serving import ServeEngine, ServeEngineConfig

BATCH_CHILDREN = ["mnf.serve.pad", "mnf.serve.place", "mnf.serve.dispatch",
                  "mnf.serve.wait", "mnf.serve.fetch", "mnf.serve.complete"]


def _program_spans(log_dir) -> list:
    """[(name, start_ns, end_ns, stats)] of every ``mnf.*`` host event,
    sorted by start."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(str(log_dir), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    data = ProfileData.from_file(path)
    out = [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
           for plane in data.planes if plane.name == "/host:CPU"
           for line in plane.lines for e in line.events
           if e.name.startswith("mnf.")]
    return sorted(out, key=lambda s: (s[1], -s[2]))


def _inside(outer, spans):
    return [s for s in spans if s is not outer
            and outer[1] <= s[1] and s[2] <= outer[2]]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """An engine that compiles on its first tick, and a trace of two ticks:
    the first (3 requests, bucket 4, compiles) and the second (4 requests,
    nothing compiled)."""
    params = init_mlp_params(jax.random.PRNGKey(0), MLP_MINI, 0.5)
    eng = ServeEngine(MLP_MINI, params,
                      ServeEngineConfig(buckets=(4,), aot_warmup=False))
    x = np.random.default_rng(0).random((7, MLP_MINI.in_features),
                                        dtype=np.float32)
    log_dir = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(log_dir))
    try:
        for i in range(3):
            eng.submit(x[i])
        first = eng.run_tick()
        for i in range(3, 7):
            eng.submit(x[i])
        second = eng.run_tick()
    finally:
        jax.profiler.stop_trace()
    return eng, first, second, _program_spans(log_dir)


def test_one_tick_span_per_tick_with_its_counters(traced):
    _, first, second, spans = traced
    ticks = [s for s in spans if s[0] == "mnf.serve.tick"]
    assert [t[3]["tick"] for t in ticks] == [0, 1]
    assert [t[3]["queued"] for t in ticks] == [3, 4]
    assert len(first) == 3 and len(second) == 4


def test_batch_span_holds_the_six_children_in_order(traced):
    _, _, _, spans = traced
    for tick in (s for s in spans if s[0] == "mnf.serve.tick"):
        batch, = [s for s in _inside(tick, spans)
                  if s[0] == "mnf.serve.batch"]
        children = [s for s in _inside(batch, spans)
                    if s[0] in BATCH_CHILDREN]
        assert [c[0] for c in children] == BATCH_CHILDREN
        ends = [c[2] for c in children]
        starts = [c[1] for c in children]
        assert all(e <= s for e, s in zip(ends, starts[1:]))


def test_batch_span_stats(traced):
    _, first, second, spans = traced
    batches = [s[3] for s in spans if s[0] == "mnf.serve.batch"]
    assert [(b["bucket"], b["rows"]) for b in batches] == [(4, 3), (4, 4)]
    assert [(b["rid0"], b["rid1"]) for b in batches] == [
        (first[0].rid, first[-1].rid), (second[0].rid, second[-1].rid)]
    assert [(b["rid0"], b["rid1"]) for b in batches] == [(0, 2), (3, 6)]
    assert all(b["wait_ms"] >= 0 for b in batches)


def test_compile_span_where_the_compile_happened(traced):
    eng, _, _, spans = traced
    compiles = [s for s in spans if s[0] == "mnf.serve.compile"]
    assert len(compiles) == eng.recompiles == 1
    assert compiles[0][3]["bucket"] == 4
    assert compiles[0][3]["source"] == "compile"
    first_batch = next(s for s in spans if s[0] == "mnf.serve.batch")
    assert compiles[0] in _inside(first_batch, spans)


def test_no_span_per_request(traced):
    _, first, second, spans = traced
    # tick + batch + six children per tick, and one compile: 7 requests
    # add nothing.
    assert len(spans) == 2 * (2 + len(BATCH_CHILDREN)) + 1
    assert {s[0] for s in spans} == (
        {"mnf.serve.tick", "mnf.serve.batch", "mnf.serve.compile"}
        | set(BATCH_CHILDREN))


def test_latency_runs_to_the_logits_on_the_host(traced):
    _, first, second, spans = traced
    fetches = [s for s in spans if s[0] == "mnf.serve.fetch"]
    for reqs, fetch in zip((first, second), fetches):
        assert all(r.result is not None and r.latency_s > 0 for r in reqs)
        # Every request's latency covers its batch's fetch.
        fetch_s = (fetch[2] - fetch[1]) * 1e-9
        assert min(r.latency_s for r in reqs) >= fetch_s


def test_op_layers_maps_lenet_in_config_order():
    params = init_mlp_params(jax.random.PRNGKey(1), LENET_300_100, 0.5)
    eng = ServeEngine(LENET_300_100, params,
                      ServeEngineConfig(buckets=(8,)))
    layers = eng.op_layers(8)
    assert set(layers.values()) == {"mnf.L00.fc", "mnf.L01.fc",
                                    "mnf.L02.fc"}
    text = eng._compiled(8).as_text()
    heavy = re.findall(r"%([\w.\-]+) = [^\n]*? (?:dot|custom-call)\(", text)
    assert heavy
    assert all(layers.get(name, "").startswith("mnf.L") for name in heavy)
    # Layer i's weights feed the ops of scope L<i>.
    params_of = re.findall(r'%([\w.\-]+) = [^\n]*parameter\((\d)\)'
                           r'[^\n]*op_name="params\[\d\]"', text)
    assert len(params_of) == 3
    for name, i in params_of:
        assert layers[name] == f"mnf.L{int(i):02d}.fc"


def test_op_scopes_gives_unscoped_instructions_their_users_scope():
    text = "\n".join([
        "HloModule jit_fwd",
        "ENTRY %main (w: f32[4,4], x: f32[2,4]) -> f32[2,4] {",
        '  %w = f32[4,4] parameter(0), metadata={op_name="params[0]"}',
        '  %x = f32[2,4] parameter(1), metadata={op_name="x"}',
        "  %copy.1 = f32[4,4] copy(%w)",
        '  %dot.2 = f32[2,4] dot(%x, %copy.1), metadata={op_name='
        '"jit(fwd)/mnf.L00.fc/jit(event_matmul)/dot_general"}',
        '  %mnf_event_matmul.3 = f32[2,4] custom-call(%dot.2), metadata={'
        'op_name="jit(fwd)/shmap_body/mnf.L01.fc/mnf_event_matmul"}',
        "  ROOT %copy.4 = f32[2,4] copy(%mnf_event_matmul.3)",
        "}",
    ])
    assert op_scopes(text) == {
        "w": "mnf.L00.fc", "x": "mnf.L00.fc", "copy.1": "mnf.L00.fc",
        "dot.2": "mnf.L00.fc", "mnf_event_matmul.3": "mnf.L01.fc",
        "copy.4": "mnf.L01.fc"}


def test_op_layers_compiles_afresh_when_the_executable_lost_its_scopes():
    """An executable read back from a compile cache that a build without
    layer scopes wrote (the cache key ignores debug info) carries no
    scopes: op_layers compiles the same module afresh to read them."""
    params = init_mlp_params(jax.random.PRNGKey(2), MLP_MINI, 0.5)
    eng = ServeEngine(MLP_MINI, params, ServeEngineConfig(buckets=(2,)))
    want = eng.op_layers(2)
    compiles = eng.recompiles
    text = re.sub(r"mnf\.(L\d+\.[a-z]+|out)/", "",
                  eng._compiled(2).as_text())

    class _Unscoped:
        def as_text(self):
            return text

    eng._exec[2] = _Unscoped()
    assert op_scopes(eng._exec[2].as_text()) == {}
    assert eng.op_layers(2) == want
    assert eng.recompiles == compiles + 1
