"""Profiler capture of the measured window and its reduction to numbers.

The window runs under the host span ``bench.window``; every phase of the
harness inside it runs under a ``bench.*`` span of its own.  From the trace
this module takes, per chip used: the union of the intervals in which an
operation ran (busy time), the time of the collective operations, the
operations that took most time, and each idle gap of the first chip,
attributed to the ``bench.*`` span that overlaps it most.
"""
from __future__ import annotations

import bisect
import collections
import contextlib
import dataclasses
import pathlib
import shutil

WINDOW_SPAN = "bench.window"
NO_SPAN = "no_bench_span"
OPS_LINE = "XLA Ops"
COLLECTIVE_MARKS = ("all-gather", "all-reduce", "all-to-all",
                    "collective-permute", "reduce-scatter")


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: list            # per chip, seconds of op-interval union
    collective_s: list      # per chip, seconds of collective ops
    ops: list               # [(name, seconds averaged over chips)], by time
    idle_gaps: list         # [(span name, seconds)] on the first chip

    @property
    def busy_mean_s(self) -> float:
        return sum(self.busy_s) / len(self.busy_s)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_mean_s / self.window_s


@contextlib.contextmanager
def capture(out_dir: pathlib.Path):
    """Trace the enclosed block into ``out_dir`` (emptied first)."""
    import jax

    shutil.rmtree(out_dir, ignore_errors=True)
    out_dir.mkdir(parents=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0       # the bench.* spans are enough
    options.enable_hlo_proto = False
    jax.profiler.start_trace(str(out_dir), profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def xplane_file(out_dir: pathlib.Path) -> pathlib.Path:
    found = sorted(out_dir.rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {out_dir}")
    return found[-1]


def _union(intervals) -> list:
    out = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return out


def short_name(hlo: str) -> str:
    """``%fusion.149 = f32[512000,28]{0,1:...} fusion(...)`` ->
    ``fusion.149 f32[512000,28]``."""
    head, _, rest = hlo.partition(" = ")
    shape = rest.lstrip("(").split("{", 1)[0].split(" ", 1)[0][:60]
    return f"{head.lstrip('%')} {shape}".strip()


def is_collective(hlo: str) -> bool:
    """Whether the op itself (its name, not its operands) is a collective."""
    head = hlo.partition(" = ")[0].lower()
    return any(m in head for m in COLLECTIVE_MARKS)


def _self_times(events) -> collections.Counter:
    """Nanoseconds of each op (by :func:`short_name`) not covered by the ops
    nested inside it: a ``while`` op's line also holds its body's ops."""
    out = collections.Counter()
    stack = []                               # (end, name) of open parents
    for n, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= a:
            stack.pop()
        name = short_name(n)
        out[name] += b - a
        if stack:
            out[stack[-1][1]] -= b - a
        stack.append((b, name))
    return out


def _device_index(plane_name: str) -> int:
    tail = plane_name.rsplit(":", 1)[-1]
    return int(tail) if tail.isdigit() else 1 << 30


def _attribute_gaps(union, w0, w1, spans) -> collections.Counter:
    """Seconds of each gap between ``union``'s intervals inside the window,
    by the span (start, end, name) that overlaps the gap most."""
    spans.sort()
    starts = [a for a, _, _ in spans]
    reach, top = [], float("-inf")           # running max of span ends
    for _, b, _ in spans:
        top = max(top, b)
        reach.append(top)
    gaps = collections.Counter()
    cursor = w0
    for lo, hi in list(union) + [[w1, w1]]:
        if lo > cursor:
            best, best_overlap = NO_SPAN, 0
            j = bisect.bisect_left(starts, lo) - 1
            while j >= 0 and reach[j] > cursor:
                a, b, n = spans[j]
                overlap = min(b, lo) - max(a, cursor)
                if overlap > best_overlap:
                    best, best_overlap = n, overlap
                j -= 1
            gaps[best] += (lo - cursor) / 1e9
        cursor = max(cursor, hi)
    return gaps


def reduce(path: pathlib.Path, chips: int, top: int = 10) -> Reduction:
    """Reduce one ``.xplane.pb`` file; ``chips`` device planes are read."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    host_spans = []              # (name, start, end) of bench.* spans
    devices = []                 # (index, [(name, start, end)])
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        host_spans.append(
                            (e.name, e.start_ns, e.start_ns + e.duration_ns))
        elif plane.name.startswith("/device:") and \
                plane.name.split(":")[-1].isdigit():
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices.append((_device_index(plane.name), [
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events if e.duration_ns > 0]))
    windows = [(a, b) for n, a, b in host_spans if n == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found "
                         f"{len(windows)}")
    w0, w1 = windows[0]
    devices.sort(key=lambda d: d[0])
    if len(devices) < chips:
        raise ValueError(f"trace holds {len(devices)} device op lines, "
                         f"the cell uses {chips}")
    busy, coll, per_op, first_union = [], [], collections.Counter(), None
    for _, events in devices[:chips]:
        clipped = [(n, max(a, w0), min(b, w1)) for n, a, b in events
                   if b > w0 and a < w1]
        union = _union([(a, b) for _, a, b in clipped])
        busy.append(sum(b - a for a, b in union) / 1e9)
        coll.append(sum(b - a for n, a, b in clipped if is_collective(n))
                    / 1e9)
        for n, t in _self_times(clipped).items():
            per_op[n] += t / 1e9 / chips
        if first_union is None:
            first_union = union
    gaps = _attribute_gaps(first_union, w0, w1, [
        (a, b, n) for n, a, b in host_spans if n != WINDOW_SPAN])
    return Reduction(
        window_s=(w1 - w0) / 1e9, busy_s=busy, collective_s=coll,
        ops=per_op.most_common(top), idle_gaps=gaps.most_common(top))
