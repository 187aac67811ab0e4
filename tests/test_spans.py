"""Profiler spans and device scopes of fit() and serve(): the scope map of
the program fit() runs (one device, and a stream mesh of four virtual
devices in a subprocess), the serving spans in a profiler capture, and the
serving counters beside them."""
import json
import math
import os
import pathlib
import re
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro import spans
from repro.api import BigMeansConfig, ServeConfig, fit, lower_fit, serve
from repro.core.bigmeans import LANES

REPO = pathlib.Path(__file__).resolve().parents[1]

_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*\w+"
                          r"\[([\d,]*)\]\S*\s+([\w-]+)\(")


def _instructions(hlo_text: str, opcode: str) -> dict:
    """Instruction name -> element count of each array-valued ``opcode``."""
    out = {}
    for line in hlo_text.splitlines():
        g = _INSTRUCTION.match(line)
        if g and g.group(3) == opcode:
            out[g.group(1)] = math.prod(
                int(d) for d in g.group(2).split(",") if d)
    return out


def _check_scope_map(hlo_text: str, chunk_rows: int) -> dict:
    """Every scope is mapped, and the gather of a whole chunk batch's rows
    (from the packed copy of the 8-wide dataset: 128 lanes a row) is
    sampling's."""
    scopes = spans.op_scopes(hlo_text)
    assert set(spans.SCOPES) <= set(scopes.values())
    chunk = [n for n, size in _instructions(hlo_text, "gather").items()
             if size == chunk_rows * LANES]
    assert chunk, "no gather of a whole chunk batch in the program"
    assert {scopes[n] for n in chunk} == {spans.FIT_SAMPLE}
    return scopes


def test_scope_of_takes_the_first_repro_component():
    assert spans.scope_of("jit(f)/while/body/repro.fit.seed/cond/"
                          "repro.fit.lloyd/add") == spans.FIT_SEED
    assert spans.scope_of("jit(f)/vmap(repro.fit.sample)/gather") \
        == spans.FIT_SAMPLE
    assert spans.scope_of("jit(f)/while/body/add") == spans.UNSCOPED


def test_op_scopes_reads_instruction_names_and_metadata():
    text = "\n".join([
        "HloModule jit_f, entry_computation_layout={()->f32[]}",
        "ENTRY %main.1 () -> f32[8] {",
        '  %fusion.149 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fc, '
        'metadata={op_type="gather" op_name="jit(f)/repro.fit.sample/'
        'gather" source_file="x.py" source_line=3}',
        "  %copy.7 = f32[8]{0} copy(%fusion.149)",
        '  ROOT %while.2 = f32[8]{0} while(%copy.7), condition=%c, body=%b, '
        'metadata={op_name="jit(f)/while"}',
        "}"])
    assert spans.op_scopes(text) == {"fusion.149": spans.FIT_SAMPLE,
                                     "copy.7": spans.UNSCOPED,
                                     "while.2": spans.UNSCOPED}


def test_lower_fit_is_the_program_fit_runs_and_maps_every_scope():
    X = jax.random.normal(jax.random.PRNGKey(0), (4000, 8))
    cfg = BigMeansConfig(k=5, s=500, n_chunks=8, batch=4, sync_every=1,
                         impl="ref", max_iters=10)
    key = jax.random.PRNGKey(3)
    compiled = lower_fit(X, cfg, method="batched", key=key).compile()
    _check_scope_map(compiled.as_text(), chunk_rows=4 * 500)
    state, _ = compiled(X, key)
    assert float(state.f_best) == fit(X, cfg, method="batched",
                                      key=key).objective


def test_lower_fit_refuses_what_runs_as_more_than_one_program():
    X = np.zeros((100, 2), np.float32)
    with pytest.raises(ValueError):
        lower_fit(X, k=2, s=10, n_chunks=2, method="streaming")
    with pytest.raises(ValueError):
        lower_fit(X, k=2, s=10, method="kmeanspp")


_MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax
from repro import spans
from repro.api import BigMeansConfig, TopologySpec, lower_fit

X = jax.random.normal(jax.random.PRNGKey(0), (4000, 8))
cfg = BigMeansConfig(k=5, s=500, n_chunks=16, batch=8, sync_every=1,
                     impl="ref", max_iters=10,
                     topology=TopologySpec(kind="stream_mesh", devices=4))
text = lower_fit(X, cfg, method="batched", key=jax.random.PRNGKey(3)) \
    .compile().as_text()
print("RESULT " + json.dumps({"text": text}))
"""


def test_lower_fit_maps_every_scope_on_a_four_device_stream_mesh():
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"), JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", _MESH_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    text = json.loads(line[len("RESULT "):])["text"]
    # each device gathers its 2 of the 8 streams' chunks
    scopes = _check_scope_map(text, chunk_rows=2 * 500)
    exchange = [*_instructions(text, "all-gather"),
                *_instructions(text, "all-reduce")]
    assert exchange and {scopes[n] for n in exchange} == {spans.FIT_KEEP}


def _host_events(xplane: pathlib.Path) -> list:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(xplane))
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith("repro.")]


def _points(m, n, seed):
    return np.random.default_rng(seed).normal(size=(m, n)).astype(np.float32)


def test_a_profile_of_serve_holds_the_launch_spans_and_their_children(
        tmp_path):
    C = _points(5, 4, 0)
    with serve({"m": C}, ServeConfig(max_linger_ms=1.0, impl="ref")) as srv:
        srv.assign("m", _points(3, 4, 1))                    # warm
        with jax.profiler.trace(str(tmp_path)):
            for i in range(3):
                srv.assign("m", _points(3, 4, 2 + i))
    events = _host_events(sorted(tmp_path.rglob("*.xplane.pb"))[-1])
    names = {n for n, *_ in events}
    assert names >= set(spans.SPANS) - {spans.FIT_DISPATCH,
                                        spans.FIT_COLLECT}
    launches = [e for e in events if e[0] == spans.SERVE_LAUNCH]
    assert len(launches) >= 3
    children = (spans.SERVE_PACK, spans.SERVE_DISPATCH, spans.SERVE_FETCH,
                spans.SERVE_SCATTER)
    for _, a, b, args in launches:
        assert args["requests"] == 1 and args["rows"] == 3
        assert args["bucket"] == 64
        inside = [e for e in events if e[0] in children
                  and e[3].get("launch") == args["launch"]]
        assert sorted(e[0] for e in inside) == sorted(children)
        assert all(a <= lo and hi <= b for _, lo, hi, _ in inside)


def test_queue_wait_lies_within_latency_and_each_launch_is_timed():
    C = _points(5, 4, 0)
    with serve({"m": C}, ServeConfig(max_linger_ms=2.0, impl="ref")) as srv:
        futures = [srv.submit("m", _points(1 + i % 7, 4, i))
                   for i in range(40)]
        responses = [f.result(timeout=60) for f in futures]
        stats = srv.stats("m")
        series = srv.batcher_stats("m")
    assert len(series.launch_ms) == stats["n_batches"]
    assert all(t > 0 for t in series.launch_ms)
    assert len(series.queue_ms) == len(series.latencies_ms) == 40
    for r in responses:
        assert 0 <= r.queue_ms <= r.latency_ms
    # every request of a launch carries its id; launches are numbered 1..n
    assert sorted({r.launch for r in responses}) \
        == list(range(1, stats["n_batches"] + 1))
    assert 0 < stats["launch_p50_ms"] <= stats["launch_p99_ms"]
    assert stats["queue_p99_ms"] <= stats["p99_ms"]


def _dispatch_and_collect(tmp_path, X, method):
    cfg = BigMeansConfig(k=3, s=200, n_chunks=4, batch=2, impl="ref",
                         max_iters=5)
    fit(X, cfg, method=method)                          # compile outside
    with jax.profiler.trace(str(tmp_path)):
        fit(X, cfg, method=method)
    events = _host_events(sorted(tmp_path.rglob("*.xplane.pb"))[-1])
    (dispatch,) = [e for e in events if e[0] == spans.FIT_DISPATCH]
    (collect,) = [e for e in events if e[0] == spans.FIT_COLLECT]
    assert dispatch[2] <= collect[1]                    # disjoint, in order
    return dispatch[3]


def test_a_profile_of_fit_holds_its_dispatch_and_collect_spans(tmp_path):
    X = jax.random.normal(jax.random.PRNGKey(0), (2000, 4))
    # 4 features take 4 lanes of a packed row: 32 points to a row
    assert _dispatch_and_collect(tmp_path, X, "batched") == {
        "strategy": "batched", "n_chunks": 4, "gather": "packed", "g": 32,
        "lloyd": "two_pass", "k_tiles": 0}


@pytest.mark.parametrize("n, gather, g", [(28, "packed", 4),
                                          (100, "packed", 1),
                                          (130, "rows", 1)])
def test_the_dispatch_span_says_how_chunk_rows_are_gathered(tmp_path, n,
                                                             gather, g):
    X = jax.random.normal(jax.random.PRNGKey(1), (1000, n))
    args = _dispatch_and_collect(tmp_path, X, "sequential")
    assert (args["gather"], args["g"]) == (gather, g)


@pytest.mark.parametrize("method", ["batched", "sequential"])
@pytest.mark.parametrize("impl, k, n, lloyd, k_tiles", [
    ("ref", 25, 28, "two_pass", 0),
    ("pallas_interpret", 25, 28, "fused", 1),
    ("pallas_interpret", 25, 768, "fused", 1),
    ("pallas_interpret", 4096, 128, "fused", 8),
    ("pallas_interpret", 8320, 128, "two_pass", 0),    # envelope miss
])
def test_the_dispatch_span_says_which_lloyd_path_runs(method, impl, k, n,
                                                      lloyd, k_tiles):
    from repro.api import as_source, strategies

    X = np.zeros((16384, n), np.float32)
    cfg = BigMeansConfig(k=k, s=8320, n_chunks=4, batch=2, impl=impl)
    program = strategies.plan(method, cfg, as_source(X),
                              jax.random.PRNGKey(0))
    args = program.dispatch_args()
    assert (args["lloyd"], args["k_tiles"]) == (lloyd, k_tiles)
