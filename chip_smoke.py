#!/usr/bin/env python3
"""Bring-up smoke test: the main path of Big-means on a TPU, at the paper's
HEPMASS scale, through the entry points a user calls.

    python3 chip_smoke.py             # one chip (the default)
    python3 chip_smoke.py --chips 4   # the multi-device paths only

One process drives the chip(s).  Every phase prints one line; any failed
check prints ``FAIL <phase>: <reason>`` and exits 1.  Without a TPU the
script exits 2 before any phase.  On success the last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

One chip:

* ``kernels`` — ``ops.fused_step`` (f32, bf16, int8) and
  ``ops.fused_step_batched`` (B=8) on s=64000 rows of the HEPMASS
  surrogate (n=28, k=25) against a host float64 evaluation of the same
  precision policy (its operand rounding and quantization reproduced).
* ``fit`` — ``fit(X, cfg, method="batched")`` on the 10.5M x 28 surrogate
  held in HBM, with the paper config (k=25, s=64000, batch=8); ``evaluate``
  checked against float64 on a fixed row sample.
* ``streaming`` — the ``repro.launch.train`` path: ``fit(provider, cfg,
  method="streaming")`` with its prefetch thread and per-chunk transfer.
* ``serve`` — the fitted centroids behind ``serve()``, concurrent requests
  over several bucket sizes, ids checked against float64 nearest centroids.

``--chips 4``: ``stream_mesh`` streaming at devices=4 against the
single-device run (bitwise), and ``worker_mesh`` sharded ``fit`` against the
one-chip ``fit`` (f(C, X) within ``SHARDED_RTOL``), printing where every
shard of the sharded dataset sits.

Walls are host-clock smoke numbers (compile = first call minus a repeat of
the same call), not benchmark results.  Any Pallas demotion, envelope miss,
ref retry, demoted serving bucket or post-warmup recompile fails the run.
"""
from __future__ import annotations

import argparse
import concurrent.futures
import contextlib
import dataclasses
import io
import json
import pathlib
import sys
import time

import numpy as np

SRC = pathlib.Path(__file__).resolve().parent / "src"

# Tolerances.  The float64 references evaluate the same precision policy
# (bf16 operand rounding, int8 codes and scales), so what remains is f32
# accumulation order, plus rows whose nearest centroid is a near-tie.
# f32 at 1e-5 cannot pass if a contraction runs as one bf16 pass, which
# moves the objective by ~1e-3 at this shape.
OBJ_RTOL = {"f32": 1e-5, "bf16": 1e-4, "int8": 1e-4}
# Rows whose best and second-best float64 scores are closer than this
# (relative to ||x||^2 + ||c||^2) may legitimately flip assignment.
TIE_RTOL = {"f32": 1e-6, "bf16": 1e-4, "int8": 1e-4}
FIT_RTOL = 1e-5          # f(C, X) on the device vs float64, row sample
DIST_RTOL = 1e-5         # served distances vs float64, scaled as above
SHARDED_RTOL = 1e-2      # worker_mesh f(C, X) vs the one-chip fit


@dataclasses.dataclass(frozen=True)
class Sizes:
    m: int = 10_500_000      # HEPMASS rows (PAPER_DATASETS)
    n: int = 28
    k: int = 25
    s: int = 64_000
    batch: int = 8
    rounds: int = 4          # in-core rounds of `batch` chunks
    stream_chunks: int = 16
    sample_every: int = 50   # f(C, X) float64 check on every 50th row
    requests: int = 48
    request_sizes: tuple = (1, 7, 64, 100, 300, 1000, 2500, 4096)


class SmokeFailure(Exception):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def _timed(fn):
    t0 = time.monotonic()
    out = fn()
    return out, time.monotonic() - t0


def _block(tree):
    import jax

    return jax.block_until_ready(tree)


def _no_demotions(phase: str) -> None:
    from repro.kernels import ops

    demos = ops.kernel_demotions()
    check(not demos, f"{phase}: kernels left the Pallas path: " + "; ".join(
        f"{d['op']} {d['shape']} {d['precision']}: {d['error']}"
        for d in demos))


def _bf16_round(a):
    import ml_dtypes

    return np.asarray(a, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float64)


def _policy_operands(x, c, precision: str):
    """(xo, co, t, scale) as the kernel's contraction sees them, float64.

    ``t`` / ``scale`` are the int8 per-centroid and per-feature scales
    (None otherwise); the dot product the kernel computes is
    ``(xo @ co.T) * t``.
    """
    from repro.kernels import precision as px

    if precision == "f32":
        return (np.asarray(x, np.float64), np.asarray(c, np.float64),
                None, None)
    if precision == "bf16":
        return _bf16_round(x), _bf16_round(c), None, None
    q, scale = px.host_quantize(np.asarray(x))
    cq, t = px.quantize_centroids(c, scale)
    return (q.astype(np.float64), np.asarray(cq, np.float64),
            np.asarray(t, np.float64), np.asarray(scale, np.float64))


def _step_ref(x, c, precision: str) -> dict:
    """Float64 (sums, counts, obj) of one Lloyd step under ``precision``,
    plus the number of near-tie rows and the largest row norm."""
    xo, co, t, scale = _policy_operands(x, c, precision)
    c64 = np.asarray(c, np.float64)
    csq = (c64 * c64).sum(1)                          # full-width ||c||^2
    dots = xo @ co.T
    if t is not None:
        dots = dots * t[None, :]
        xd = xo * scale[None, :]                      # dequantized rows
    else:
        xd = xo
    xsq = (xd * xd).sum(1)
    score = csq[None, :] - 2.0 * dots
    ids = np.argmin(score, axis=1)
    top2 = np.partition(score, 1, axis=1)[:, :2]
    scale_row = xsq + csq[ids]
    near_tie = (top2[:, 1] - top2[:, 0]) <= TIE_RTOL[precision] * scale_row
    mind = np.maximum(score[np.arange(len(ids)), ids] + xsq, 0.0)
    onehot = np.zeros((len(ids), c64.shape[0]))
    onehot[np.arange(len(ids)), ids] = 1.0
    return {"sums": onehot.T @ xd, "counts": onehot.sum(0),
            "obj": mind.sum(), "ties": int(near_tie.sum()),
            "row_max": float(np.abs(xd).max())}


def _compare_step(got, want: dict, precision: str, what: str) -> float:
    sums, counts, obj = (np.asarray(a, np.float64) for a in got)
    ties = want["ties"]
    dc = float(np.abs(counts - want["counts"]).sum())
    check(dc <= 2 * ties,
          f"{what}: counts differ by {dc} with {ties} near-tie rows")
    ds = float(np.abs(sums - want["sums"]).max())
    tol_s = (2 * ties * want["row_max"]
             + OBJ_RTOL[precision] * np.abs(want["sums"]).max())
    check(ds <= tol_s, f"{what}: sums differ by {ds} > {tol_s}")
    rel = abs(float(obj) - want["obj"]) / want["obj"]
    check(rel <= OBJ_RTOL[precision],
          f"{what}: objective {float(obj)!r} vs float64 {want['obj']!r}, "
          f"rel {rel:.3e} > {OBJ_RTOL[precision]}")
    return rel


def phase_kernels(X, sz: Sizes) -> str:
    from repro.kernels import ops
    from repro.kernels import precision as px

    s, k, b = sz.s, sz.k, sz.batch
    x = X[:s]
    c = X[b * s:b * s + k]
    xb = X[:b * s].reshape(b, s, sz.n)
    cb = X[b * s:b * s + b * k].reshape(b, k, sz.n)
    xh, ch = np.asarray(x), np.asarray(c)
    xbh, cbh = np.asarray(xb), np.asarray(cb)
    parts, walls = [], []
    for prec in ("f32", "bf16", "int8"):
        xin = px.cast_storage(x, prec)
        first = lambda: _block(ops.fused_step(xin, c, precision=prec))
        got, t_first = _timed(first)
        _, t_steady = _timed(first)
        rel = _compare_step(got, _step_ref(xh, ch, prec), prec,
                            f"fused_step {prec} [{s},{sz.n}] k={k}")
        xbin = px.cast_storage(xb, prec)
        gotb = _block(ops.fused_step_batched(xbin, cb, precision=prec))
        relb = 0.0
        for i in range(b):
            one = tuple(a[i] for a in gotb)
            relb = max(relb, _compare_step(
                one, _step_ref(xbh[i], cbh[i], prec), prec,
                f"fused_step_batched {prec} [{b},{s},{sz.n}] k={k} "
                f"stream {i}"))
        parts.append(f"{prec} obj_rel={rel:.2e} batched_rel={relb:.2e}")
        walls.append(f"{prec} {t_first - t_steady:.2f}/{t_steady * 1e3:.2f}ms")
    _no_demotions("kernels")
    return (f"s={s} n={sz.n} k={k} B={b}: " + ", ".join(parts)
            + " | compile/steady " + ", ".join(walls))


def _paper_cfg(sz: Sizes, n_chunks: int):
    """The paper config (configs/bigmeans_paper.py) at ``sz``'s k, s and
    batch, with a budget of ``n_chunks``."""
    from repro.configs.bigmeans_paper import CONFIG

    return CONFIG.algo.replace(k=sz.k, s=sz.s, batch=sz.batch,
                               n_chunks=n_chunks, seed=0)


def _f64_objective(x, c) -> float:
    x = np.asarray(x, np.float64)
    c = np.asarray(c, np.float64)
    total = 0.0
    for lo in range(0, len(x), 16384):
        xs = x[lo:lo + 16384]
        d = ((xs[:, None, :] - c[None, :, :]) ** 2).sum(-1)
        total += d.min(1).sum()
    return total


def phase_fit(X, sz: Sizes, expect_impl: str):
    from repro.api import evaluate, fit

    cfg = _paper_cfg(sz, sz.rounds * sz.batch)
    r1, t_first = _timed(lambda: fit(X, cfg, method="batched"))
    r2, t_steady = _timed(lambda: fit(X, cfg, method="batched"))
    impl = r1.extras["fit"]["impl"]
    check(impl == expect_impl, f"fit: impl {impl!r}, expected {expect_impl}")
    _no_demotions("fit")
    check(np.isfinite(r1.objective), f"fit: objective {r1.objective}")
    check(float(r1.objective) == float(r2.objective),
          f"fit: repeat gave {r2.objective!r}, first {r1.objective!r}")
    (_, f_full), t_eval_first = _timed(lambda: evaluate(r1, X))
    _, t_eval = _timed(lambda: evaluate(r1, X))
    check(np.isfinite(f_full), f"fit: f(C, X) = {f_full}")
    idx = np.arange(0, sz.m, sz.sample_every)
    xs = X[idx]
    _, f_dev = evaluate(r1.centroids, xs)
    f64 = _f64_objective(np.asarray(xs), np.asarray(r1.centroids))
    rel = abs(f_dev - f64) / f64
    check(rel <= FIT_RTOL,
          f"fit: f(C, X[::{sz.sample_every}]) {f_dev!r} vs float64 {f64!r}"
          f", rel {rel:.3e} > {FIT_RTOL}")
    line = (f"X=[{sz.m},{sz.n}] in HBM, k={cfg.k} s={cfg.s} "
            f"batch={cfg.batch} chunks={cfg.n_chunks} impl={impl}: "
            f"f(C,X)={f_full:.7e} sample_rel={rel:.2e} "
            f"accepted={r1.n_accepted} | compile={t_first - t_steady:.2f}s "
            f"steady={t_steady:.3f}s | evaluate compile="
            f"{t_eval_first - t_eval:.2f}s steady={t_eval:.2f}s")
    return r1, line


def phase_streaming(sz: Sizes, expect_impl: str) -> str:
    from repro.launch import train

    argv = ["--chunks", str(sz.stream_chunks), "--scale", "1.0"]

    def run():
        with contextlib.redirect_stdout(io.StringIO()):   # its own log
            return train.main(argv)

    r1, t_first = _timed(run)
    r2, t_steady = _timed(run)
    impl = r1.extras["fit"]["impl"]
    check(impl == expect_impl,
          f"streaming: impl {impl!r}, expected {expect_impl}")
    _no_demotions("streaming")
    check(np.isfinite(r1.objective), f"streaming: f_best {r1.objective}")
    check(r1.n_chunks == sz.stream_chunks
          and not r1.extras.get("chunks_failed"),
          f"streaming: ran {r1.n_chunks} chunks, failed "
          f"{r1.extras.get('chunks_failed')}")
    check(float(r1.objective) == float(r2.objective),
          f"streaming: repeat gave {r2.objective!r}, first {r1.objective!r}")
    return (f"chunks={r1.n_chunks} f_best={r1.objective:.7e} "
            f"accepted={r1.n_accepted} impl={impl} | "
            f"compile={t_first - t_steady:.2f}s steady={t_steady:.3f}s")


def phase_serve(X, result, sz: Sizes, expect_impl: str) -> str:
    from repro.api import ServeConfig, serve

    rng = np.random.default_rng(0)
    reqs = []
    for i in range(sz.requests):
        size = sz.request_sizes[i % len(sz.request_sizes)]
        lo = int(rng.integers(0, sz.m - size))
        reqs.append(np.asarray(X[lo:lo + size]))
    cents = np.asarray(result.centroids, np.float64)
    cfg = ServeConfig()
    srv, t_warm = _timed(lambda: serve({"hepmass": result}, cfg))
    with srv:
        warm_recompiles = srv.recompiles("hepmass")
        with concurrent.futures.ThreadPoolExecutor(16) as pool:
            (resps, t_traffic) = _timed(lambda: list(pool.map(
                lambda q: srv.assign("hepmass", q), reqs)))
        stats = srv.stats("hepmass")
        health = srv.health()
        recompiles = srv.recompiles("hepmass")
    mismatched = 0
    for q, resp in zip(reqs, resps):
        d64 = ((q.astype(np.float64)[:, None, :] - cents[None]) ** 2).sum(-1)
        want = d64.argmin(1)
        rows = np.arange(len(q))
        bad = (resp.ids != want) & (d64[rows, resp.ids] != d64[rows, want])
        mismatched += int(bad.sum())
        mag = (q.astype(np.float64) ** 2).sum(1) + (cents ** 2).sum(1)[want]
        derr = np.abs(resp.dists - d64[rows, want])
        check((derr <= DIST_RTOL * mag).all(),
              f"serve: distance error {derr.max()} at request size {len(q)}")
    check(mismatched == 0,
          f"serve: {mismatched} ids differ from float64 without a tie")
    model = health["models"]["hepmass"]
    check(health["ok"], f"serve: health {health}")
    check(stats["n_ref_retries"] == 0,
          f"serve: {stats['n_ref_retries']} launches retried on ref")
    check(not model["demoted_buckets"],
          f"serve: buckets {model['demoted_buckets']} demoted to ref")
    check(stats["n_failed"] == 0, f"serve: {stats['n_failed']} failed")
    check(recompiles == warm_recompiles,
          f"serve: {recompiles - warm_recompiles} recompiles after warmup")
    check(stats["impl"] == expect_impl,
          f"serve: impl {stats['impl']!r}, expected {expect_impl}")
    _no_demotions("serve")
    return (f"{sz.requests} requests, sizes {sorted(set(sz.request_sizes))}"
            f", buckets {list(cfg.buckets())}: ids match float64, "
            f"impl={stats['impl']} batches={stats['n_batches']} "
            f"ref_retries=0 demoted=0 recompiles_after_warmup=0 | "
            f"warmup={t_warm:.2f}s traffic={t_traffic:.3f}s "
            f"p50={stats['p50_ms']:.2f}ms p99={stats['p99_ms']:.2f}ms")


def _shard_devices(arr) -> str:
    return ", ".join(
        f"{sh.index[0].start or 0}:{sh.index[0].stop}@{sh.device.id}"
        for sh in sorted(arr.addressable_shards,
                         key=lambda sh: sh.index[0].start or 0))


def phase_stream_mesh(sz: Sizes, devices: int) -> str:
    from repro.api import TopologySpec, fit
    from repro.data.synthetic import GMMSpec, gmm_chunk
    from repro.engine import topology as topo_lib

    spec = GMMSpec(m=sz.m, n=sz.n, components=sz.k, spread=4.0, seed=0)
    cfg = _paper_cfg(sz, sz.stream_chunks)

    def provider(cid):
        return np.asarray(gmm_chunk(spec, cid, cfg.s))

    mesh_spec = TopologySpec(kind="stream_mesh", devices=devices)
    mesh = topo_lib.resolve(mesh_spec).mesh
    one, t_one = _timed(lambda: fit(provider, cfg.replace(topology="single"),
                                    method="streaming", n_features=sz.n))
    many, t_many = _timed(lambda: fit(provider,
                                      cfg.replace(topology=mesh_spec),
                                      method="streaming", n_features=sz.n))
    _no_demotions("stream_mesh")
    ids = sorted(d.id for d in mesh.devices.flat)
    check(len(set(ids)) == devices, f"stream_mesh: mesh devices {ids}")
    check(float(one.objective) == float(many.objective)
          and np.array_equal(np.asarray(one.centroids),
                             np.asarray(many.centroids)),
          f"stream_mesh: f_best {many.objective!r} vs single-device "
          f"{one.objective!r} (not bitwise)")
    return (f"devices={ids} streams={cfg.batch} chunks={cfg.n_chunks}: "
            f"f_best={many.objective!r} == single-device bitwise | "
            f"single={t_one:.2f}s mesh={t_many:.2f}s (compile included)")


def phase_worker_mesh(X, sz: Sizes, devices: int) -> str:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.api import TopologySpec, evaluate, fit
    from repro.engine import topology as topo_lib

    cfg = _paper_cfg(sz, sz.rounds * sz.batch)
    one, t_one = _timed(lambda: fit(X, cfg.replace(topology="single"),
                                    method="batched"))
    _, f_one = evaluate(one, X)
    spec = TopologySpec(kind="worker_mesh", devices=devices)
    mesh = topo_lib.resolve(spec, role="worker").mesh
    Xs = jax.device_put(X, NamedSharding(mesh, P(mesh.axis_names[0])))
    where = _shard_devices(Xs)
    check(len({sh.device.id for sh in Xs.addressable_shards}) == devices,
          f"worker_mesh: dataset shards on {where}")
    sh, t_sh = _timed(lambda: fit(Xs, cfg.replace(topology=spec),
                                  method="sharded"))
    _no_demotions("worker_mesh")
    _, f_sh = evaluate(sh, Xs)
    rel = abs(f_sh - f_one) / f_one
    check(np.isfinite(f_sh) and rel <= SHARDED_RTOL,
          f"worker_mesh: f(C,X) {f_sh!r} vs one-chip {f_one!r}, rel "
          f"{rel:.3e} > {SHARDED_RTOL}")
    return (f"X rows by device {where}: f(C,X)={f_sh:.7e} vs one-chip "
            f"{f_one:.7e} rel={rel:.2e} | one-chip={t_one:.2f}s "
            f"sharded={t_sh:.2f}s (compile included)")


def run(chips: int, sz: Sizes = Sizes(), expect_impl: str = "pallas",
        platform: str = "tpu") -> dict:
    """All phases for ``chips``; returns the device record.  Raises
    :class:`SmokeFailure` on the first failed check."""
    import jax

    from repro.data.synthetic import GMMSpec, gmm_dataset
    from repro.kernels import ops

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    check(device["platform"] == platform,
          f"device: platform {device['platform']!r}, need {platform!r}")
    check(len(devs) >= chips, f"device: {len(devs)} devices, need {chips}")
    print(f"[device] platform={device['platform']} kind={device['kind']} "
          f"count={device['count']}", flush=True)
    ops.reset_kernel_demotions()

    def report(name, fn):
        try:
            out, wall = _timed(fn)
        except SmokeFailure:
            raise
        except Exception as exc:
            raise SmokeFailure(f"{name}: {type(exc).__name__}: {exc}") from exc
        line = out[1] if isinstance(out, tuple) else out
        print(f"[{name}] {line} ({wall:.1f}s)", flush=True)
        return out

    def data():
        spec = GMMSpec(m=sz.m, n=sz.n, components=sz.k, seed=0)
        X = _block(gmm_dataset(spec))
        return X, f"GMM surrogate [{sz.m},{sz.n}] f32 on {X.devices()}"

    X = report("data", data)[0]
    if chips == 1:
        report("kernels", lambda: phase_kernels(X, sz))
        result = report("fit", lambda: phase_fit(X, sz, expect_impl))[0]
        report("streaming", lambda: phase_streaming(sz, expect_impl))
        report("serve", lambda: phase_serve(X, result, sz, expect_impl))
    else:
        report("stream_mesh", lambda: phase_stream_mesh(sz, chips))
        report("worker_mesh", lambda: phase_worker_mesh(X, sz, chips))
    _no_demotions("end")
    return device


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the single-chip main path; 4: only the "
                         "multi-device paths and their comparisons")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package under {SRC}; run from a "
              "checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    from repro.launch import compile_cache

    cache = pathlib.Path(compile_cache.configure())
    warm = sum(1 for _ in cache.iterdir()) if cache.is_dir() else 0
    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (default device platform "
              f"{platform!r}); nothing was run", file=sys.stderr)
        return 2
    from repro.configs.bigmeans_paper import CONFIG
    from repro.data.synthetic import PAPER_DATASETS

    sz = Sizes()
    paper = (CONFIG.m, CONFIG.n_features, CONFIG.k, CONFIG.s, CONFIG.batch)
    if paper != (sz.m, sz.n, sz.k, sz.s, sz.batch) \
            or PAPER_DATASETS["hepmass"] != (sz.m, sz.n):
        print(f"FAIL sizes: paper config {paper} is not {sz}", flush=True)
        return 1
    print(f"[cache] {cache}: {warm} entries at start "
          f"({'warm' if warm else 'cold'})", flush=True)
    try:
        device = run(args.chips, sz)
    except SmokeFailure as exc:
        print(f"FAIL {exc}", flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
