"""The benchmark's yardstick on the CPU: order statistics, the work model,
the peaks table, the trace reduction and the float64 references."""
import gzip
import math
import pathlib

import numpy as np
import pytest

from bench import peaks, reference, stats, trace, work

HERE = pathlib.Path(__file__).resolve().parent


def test_tail_counts_a_failed_request_above_every_limit():
    lat = [1.0] * 98 + [2.0]
    assert stats.tail(lat, 0.99) == 2.0
    # one failure among 100: p99 is the largest finite latency, p100 is not
    assert stats.tail(lat, 0.99, n_failed=1) == 2.0
    assert stats.tail(lat, 1.0, n_failed=1) == math.inf
    # two failures: the rank falls among them, never clamped to a time
    assert stats.tail(lat, 0.99, n_failed=2) == math.inf
    assert stats.tail(lat, 0.5, n_failed=2) == 1.0


def test_tail_is_nearest_rank():
    vals = list(range(1, 201))              # 1..200
    assert stats.tail(vals, 0.5) == 100
    assert stats.tail(vals, 0.99) == 198
    assert stats.tail(vals[::-1], 0.99) == 198
    with pytest.raises(ValueError):
        stats.tail([], 0.5)


def test_per_job_divides_the_whole_window_by_the_jobs():
    assert stats.per_job(20.4, 51) == pytest.approx(0.4)
    with pytest.raises(ValueError):
        stats.per_job(20.0, 0)


def test_chunk_traffic_against_a_hand_count():
    s, n, k = 1000, 28, 25
    w = work.chunk_traffic(s, n, k, "f32", passes=3)
    # per pass: distance 2*s*k*n + update 2*s*k*n + assembly 3*s*k
    assert w["flops"] == 3 * (2 * 1000 * 25 * 28 * 2 + 3 * 1000 * 25)
    # per pass: the chunk (s*n*4) + centroids in and sums out (2*k*n*4)
    # + counts (k*4)
    assert w["bytes"] == 3 * (1000 * 28 * 4 + 2 * 25 * 28 * 4 + 25 * 4)
    assert work.chunk_bytes(s, n, "bf16") == s * n * 2
    assert work.chunk_bytes(s, n, "int8") == s * n + 4 * n
    assert work.job_passes(n_iterations=40, n_chunks=16) == 72


def test_roofline_takes_the_larger_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.roofline_seconds(1000.0, 50.0, peak) == (10.0, "flops")
    assert work.roofline_seconds(100.0, 50.0, peak) == (5.0, "bytes")


def test_peaks_know_v5e_and_refuse_an_unknown_device():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks("cpu")


def _hand_trace(tmp_path):
    """Two chips; times in microseconds from the window's start (100 us)."""
    from jax.profiler import ProfileData

    def ev(meta, start_us, dur_us):
        return (f"events {{ metadata_id: {meta} offset_ps: "
                f"{int(start_us * 1e6)} duration_ps: {int(dur_us * 1e6)} }}")

    def meta(i, name):
        return f'event_metadata {{ key: {i} value {{ id: {i} name: "{name}" }} }}'

    host = "\n".join([
        'planes { id: 1 name: "/host:CPU"',
        'lines { id: 1 name: "python" timestamp_ns: 0',
        ev(1, 100, 1000),                     # the window: 100..1100 us
        ev(2, 100, 300),                      # bench.fit_job 100..400
        ev(3, 600, 300),                      # bench.wait 600..900
        "}", meta(1, "bench.window"), meta(2, "bench.fit_job"),
        meta(3, "bench.wait"), "}"])

    def device(pid, index, events):
        return "\n".join([
            f'planes {{ id: {pid} name: "/device:TPU:{index}"',
            'lines { id: 1 name: "XLA Ops" timestamp_ns: 0',
            *[ev(m, a, d) for m, a, d in events], "}",
            meta(1, "%while.1 = (f32[8]{0}) while(x)"),
            meta(2, "%fusion.2 = f32[8,28]{1,0} fusion(x)"),
            meta(3, "%all-gather.3 = f32[4,25]{1,0} all-gather(x)"), "}"])

    # chip 0: while 200..500 holding fusion 250..350; all-gather 700..750;
    #         an op before the window (50..90) is left out
    # chip 1: fusion 100..200, all-gather 700..800
    text = "\n".join([
        host,
        device(2, 0, [(1, 200, 300), (2, 250, 100), (3, 700, 50),
                      (2, 50, 40)]),
        device(3, 1, [(2, 100, 100), (3, 700, 100)])])
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return path


def test_trace_reduction_on_a_hand_built_trace(tmp_path):
    red = trace.reduce(_hand_trace(tmp_path), chips=2)
    assert red.window_s == pytest.approx(1000e-6)
    # chip 0 busy: 200..500 and 700..750 = 350 us; chip 1: 100 + 100 us
    assert red.busy_s == pytest.approx([350e-6, 200e-6])
    assert red.idle_share == pytest.approx(1 - 275e-6 / 1000e-6)
    assert red.collective_s == pytest.approx([50e-6, 100e-6])
    ops = dict(red.ops)
    # self time: the while op's 300 us less the fusion nested in it
    assert ops["while.1 f32[8]"] == pytest.approx(200e-6 / 2)
    assert ops["fusion.2 f32[8,28]"] == pytest.approx(200e-6 / 2)
    assert ops["all-gather.3 f32[4,25]"] == pytest.approx(150e-6 / 2)
    # chip 0's gaps: 100..200 (in fit_job), 500..700 (fit_job 400 ends,
    # wait from 600: wait overlaps most), 750..1100 (wait, then no span)
    gaps = dict(red.idle_gaps)
    assert gaps["bench.fit_job"] == pytest.approx(100e-6)
    assert gaps["bench.wait"] == pytest.approx(200e-6 + 350e-6)
    with pytest.raises(ValueError):
        trace.reduce(_hand_trace(tmp_path), chips=3)


def _recorded(name, tmp_path):
    """A trace of a ``bench.run --trace 1`` window on a TPU v5e, copied
    from the run's ``.bench_out/trace`` before the reduction removed it."""
    path = tmp_path / name
    path.write_bytes(gzip.decompress((HERE / "data" / f"{name}.gz")
                                     .read_bytes()))
    return path


def test_trace_reduction_on_a_recorded_chip_trace(tmp_path):
    red = trace.reduce(_recorded("hepmass_fit.xplane.pb", tmp_path), chips=1)
    assert 0 < red.busy_s[0] <= red.window_s
    assert red.collective_s == [0.0]
    assert red.ops and all(t > 0 for _, t in red.ops)
    assert {g for g, _ in red.idle_gaps} <= {"bench.fit_job",
                                             trace.NO_SPAN}


def test_trace_reduction_on_a_recorded_four_chip_trace(tmp_path):
    """One stream-mesh job on a 2x2 v5e host: four chips, each with its
    share of the all-gathers of the incumbent exchange."""
    red = trace.reduce(_recorded("hepmass_fit_mesh4.xplane.pb", tmp_path),
                       chips=4)
    assert len(red.busy_s) == 4
    assert all(0 < b <= red.window_s for b in red.busy_s)
    assert all(c > 0 for c in red.collective_s)
    assert max(red.collective_s) < min(red.busy_s)


def test_key_index_follows_the_trace_order():
    # one device: round-major
    assert [reference.key_index(i, batch=4, rounds=3, devices=1)
            for i in range(12)] == list(range(12))
    # two devices, two streams each: device 0's rounds, then device 1's
    got = [reference.key_index(i, batch=4, rounds=3, devices=2)
           for i in range(12)]
    assert got == [0, 1, 4, 5, 8, 9, 2, 3, 6, 7, 10, 11]


def test_lloyd_drop_reads_nothing_at_a_fixed_point():
    x = np.array([[0.0, 0.0], [2.0, 0.0], [10.0, 0.0], [12.0, 0.0]])
    means = np.array([[1.0, 0.0], [11.0, 0.0]])
    assert reference.lloyd_drop64(x, means) == 0.0
    # one centroid 1 off its rows' mean: the objective falls from 2+2+2 to 4
    off = np.array([[1.0, 1.0], [11.0, 0.0]])
    assert reference.lloyd_drop64(x, off) == pytest.approx((6.0 - 4.0) / 6.0)
    # a centroid with no rows stays where it is
    empty = np.array([[1.0, 0.0], [11.0, 0.0], [100.0, 0.0]])
    assert reference.lloyd_drop64(x, empty) == 0.0


def test_replay_accepts_by_the_stream_then_by_the_fleet():
    # 2 streams, 4 rounds, sync every 2 rounds; rows are rounds
    f = np.array([[5.0, 3.0],       # both accepted (from infinity)
                  [4.0, 4.0],       # stream 0 improves; 4 is not < 3
                  [3.5, 2.0],       # after the sync both hold 3: 3.5 not
                  [2.5, 2.5]])      # both hold their own: 2.5 < 3, not < 2
    want = np.array([[1, 1], [1, 0], [0, 1], [1, 0]], bool)
    got = reference.replay_accepted(f.reshape(-1), batch=2, rounds=4,
                                    devices=1, sync_every=2)
    assert (got == want.reshape(-1)).all()
    # the same chunks in a two-device mesh's trace order (one stream each)
    order = [reference.key_index(i, batch=2, rounds=4, devices=2)
             for i in range(8)]
    got = reference.replay_accepted(f.reshape(-1)[order], batch=2, rounds=4,
                                    devices=2, sync_every=2)
    assert (got == want.reshape(-1)[order]).all()
    # without the sync stream 0 would have accepted 3.5 in round 2
    nosync = reference.replay_accepted(f.reshape(-1), batch=2, rounds=4,
                                       devices=1, sync_every=4)
    assert nosync.reshape(4, 2)[2, 0]


def test_assignment_gaps_allow_exact_ties_only():
    x = np.array([[1.0, 1.0], [2.0, 1.0], [4.0, 1.0]])
    c = np.array([[1.0, 1.0], [3.0, 1.0]])
    d_true = np.array([0.0, 1.0, 1.0])
    # row 1 is an exact tie: either id is right
    for ids in ([0, 0, 1], [0, 1, 1]):
        assert reference.assignment_gaps(x, c, ids, d_true) == (0.0, 0.0)
    gap, err = reference.assignment_gaps(x, c, [1, 0, 1], d_true)
    assert gap == pytest.approx(4.0 / 4.0) and err == 0.0
    _, err = reference.assignment_gaps(x, c, [0, 0, 1], d_true + 0.5)
    assert err > 0
    assert reference.assignment_gaps(x, c, [0, 0, 2], d_true)[0] == math.inf


def test_a_collective_is_named_by_the_op_not_by_its_operands():
    assert trace.is_collective("%all-gather.3 = f32[4,25]{1,0} all-gather(x)")
    assert trace.is_collective("%all-reduce-start.1 = f32[] all-reduce-start(y)")
    assert not trace.is_collective(
        "%fusion.7 = f32[25,28]{1,0} fusion(%all-gather.3), kind=kLoop")
