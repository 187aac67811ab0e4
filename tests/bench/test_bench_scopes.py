"""Device time by program scope and idle gaps by program span
(``bench.scopes``), on a hand-built trace and on a traced hepmass.fit
window recorded on a TPU v5e with the compiled HLO of its program."""
import gzip
import pathlib

import pytest

from bench import scopes, trace

HERE = pathlib.Path(__file__).resolve().parent

HLO = "\n".join([
    "HloModule jit_prog, entry_computation_layout={()->f32[8]}",
    "ENTRY %main () -> f32[8] {",
    '  %fusion.2 = f32[8,28]{1,0} fusion(%x), kind=kLoop, calls=%f, '
    'metadata={op_name="jit(prog)/while/body/repro.fit.sample/gather"}',
    '  %while.1 = (f32[8]{0}) while(%x), condition=%c, body=%b, '
    'metadata={op_name="jit(prog)/while"}',
    '  %custom-call.3 = f32[8]{0} custom-call(%x), '
    'metadata={op_name="jit(prog)/repro.fit.lloyd/repro.fit.keep/pallas"}',
    "}"])


def _hand_trace(tmp_path):
    """One chip; times in microseconds.  The window runs 100..1100 us."""
    from jax.profiler import ProfileData

    def ev(meta, start_us, dur_us):
        return (f"events {{ metadata_id: {meta} offset_ps: "
                f"{int(start_us * 1e6)} duration_ps: {int(dur_us * 1e6)} }}")

    def meta(i, name):
        return f'event_metadata {{ key: {i} value {{ id: {i} name: "{name}" }} }}'

    host = "\n".join([
        'planes { id: 1 name: "/host:CPU"',
        'lines { id: 1 name: "main" timestamp_ns: 0',
        ev(1, 100, 1000),                     # bench.window
        ev(2, 100, 900),                      # bench.fit_job: never read here
        ev(3, 100, 150),                      # repro.fit.dispatch 100..250
        ev(4, 520, 230),                      # repro.fit.collect 520..750
        "}",
        'lines { id: 2 name: "serve" timestamp_ns: 0',
        ev(5, 900, 100),                      # repro.serve.take 900..1000
        "}",
        meta(1, "bench.window"), meta(2, "bench.fit_job"),
        meta(3, "repro.fit.dispatch"), meta(4, "repro.fit.collect"),
        meta(5, "repro.serve.take"), "}"])
    device = "\n".join([
        'planes { id: 2 name: "/device:TPU:0"',
        'lines { id: 1 name: "XLA Modules" timestamp_ns: 0',
        ev(1, 200, 300),                      # jit_prog 200..500
        ev(2, 700, 50),                       # jit_other 700..750
        "}",
        'lines { id: 2 name: "XLA Ops" timestamp_ns: 0',
        ev(3, 200, 300),                      # while 200..500, holding
        ev(4, 250, 100),                      # the fusion 250..350 and
        ev(5, 400, 50),                       # the kernel 400..450
        ev(6, 700, 50),                       # an op of jit_other
        "}",
        meta(1, "jit_prog(123)"), meta(2, "jit_other(9)"),
        meta(3, "%while.1 = (f32[8]{0}) while(x)"),
        meta(4, "%fusion.2 = f32[8,28]{1,0} fusion(x)"),
        meta(5, "%custom-call.3 = f32[8]{0} custom-call(x)"),
        meta(6, "%fusion.2 = f32[4]{0} fusion(y)"), "}"])
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(
        host + "\n" + device))
    return path


def test_scope_seconds_on_a_hand_built_trace(tmp_path):
    path = _hand_trace(tmp_path)
    (chip,) = scopes.scope_seconds(path, 1, HLO)
    assert chip == pytest.approx({
        "repro.fit.sample": 100e-6,
        "repro.fit.lloyd": 50e-6,       # the first repro.* component
        "unscoped": 150e-6,             # the while's self time
        "unmapped": 50e-6,              # the same name in another program
    })
    assert sum(chip.values()) == pytest.approx(
        trace.reduce(path, chips=1).busy_s[0])
    # without the program's text every operation is unmapped
    (bare,) = scopes.scope_seconds(path, 1, None)
    assert bare == pytest.approx({"unmapped": 350e-6})
    assert scopes.per_job_ms([chip], jobs=2)["repro.fit.sample"] == \
        pytest.approx(0.05)


def test_program_gaps_on_a_hand_built_trace(tmp_path):
    # gaps: 100..200 (dispatch), 500..700 (collect from 520 overlaps most),
    # 750..1100 (take 900..1000 overlaps 100 us, more than nothing)
    gaps = scopes.program_gaps(_hand_trace(tmp_path))
    assert gaps == pytest.approx({"repro.fit.dispatch": 100e-6,
                                  "repro.fit.collect": 200e-6,
                                  "repro.serve.take": 350e-6})
    assert scopes.NO_PROGRAM_SPAN not in gaps


def _recorded(tmp_path):
    """One hepmass.fit job traced by ``python3 -m bench.scopes --keep`` on
    a TPU v5e, with the compiled HLO text of the program it ran."""
    path = tmp_path / "hepmass_fit_scoped.xplane.pb"
    path.write_bytes(gzip.decompress(
        (HERE / "data" / "hepmass_fit_scoped.xplane.pb.gz").read_bytes()))
    hlo = gzip.decompress((HERE / "data" / "hepmass_fit_scoped.hlo.txt.gz")
                          .read_bytes()).decode()
    return path, hlo


def test_a_recorded_chip_window_is_reduced_by_scope(tmp_path):
    from repro import spans

    path, hlo = _recorded(tmp_path)
    red = trace.reduce(path, chips=1)
    busy = red.busy_s[0]
    (chip,) = scopes.scope_seconds(path, 1, hlo)
    assert sum(chip.values()) == pytest.approx(busy)
    assert set(spans.SCOPES) <= set(chip)
    assert chip.get("unscoped", 0) + chip.get("unmapped", 0) < 0.05 * busy
    # the gather of the 8 chunks' 512000 rows of 28 is sampling's
    gather = dict(red.ops)["fusion.149 f32[512000,28]"]
    assert spans.op_scopes(hlo)["fusion.149"] == spans.FIT_SAMPLE
    assert chip[spans.FIT_SAMPLE] >= gather > 0.5 * busy
    # the idle time between the operations falls in the fit's own spans
    gaps = scopes.program_gaps(path)
    assert set(gaps) <= {spans.FIT_COLLECT, spans.FIT_DISPATCH,
                         scopes.NO_PROGRAM_SPAN}
    assert gaps[spans.FIT_COLLECT] > gaps.get(scopes.NO_PROGRAM_SPAN, 0)
    assert sum(gaps.values()) == pytest.approx(red.window_s - busy)
