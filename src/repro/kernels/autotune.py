"""Block-size autotuner for the Pallas Lloyd-hot-path kernels.

The kernels historically ran with hardcoded tilings (``block_m=256``,
``_BLOCK_K=128``, ``_BLOCK_N=512``) — guesses that cannot be right for every
``(backend, batch, m, k, n, precision)`` point.  This module times a small
candidate set of tilings ONCE per shape key and caches the winner:

* **in-process** — a dict keyed by ``(kind, backend, B, m, k, n, precision)``;
* **on disk (optional)** — a JSON cache (``REPRO_AUTOTUNE_CACHE=/path.json``
  or :func:`set_cache_path`), so the one-time timing cost survives restarts
  and winners can be pinned/shipped per host type.

Tile choice is strictly perf-only: every candidate computes identical
(sums, counts, obj) — the accumulators are f32 and padding is masked — so
the tuner can never change results (asserted by tests/test_precision.py).

``repro.kernels.ops`` consults :func:`get_blocks` instead of the module
constants.  When tuning is disabled (the default — enable with
``REPRO_AUTOTUNE=1``, :func:`enable`, or ``BigMeansConfig(autotune=True)``)
the lookup falls through to cached winners if present, else the historical
defaults, without ever timing anything.

Caveat — tuning vs jit caches: block sizes are read at *trace* time and are
not part of any jit cache key, so winners only reach launches whose
enclosing jit entry point (``lloyd``, the drivers) is traced *after* the
cache is populated.  ``repro.api.fit(autotune=True)`` pre-tunes before its
strategy compiles, which covers the normal path; a shape that was already
compiled untuned earlier in the process keeps its existing (default-tiled)
executable until the trace cache is invalidated.
"""
from __future__ import annotations

import json
import os
import threading
import time
from typing import Callable

_DEFAULTS: dict[str, dict] = {
    "assign": {"block_m": 256, "block_k": 128, "block_f": 256},
    # None -> the kernel's shape-derived tile (see fused_step._batched_tiles)
    "fused": {"block_m": 256, "block_k": None, "block_n": None,
              "pipeline": "blocks"},
    "fused_batched": {"block_m": 256, "block_k": None, "block_n": None},
}

_lock = threading.RLock()
_cache: dict[str, dict] = {}          # key -> winning blocks
_loaded_paths: set[str] = set()
_enabled: bool = os.environ.get("REPRO_AUTOTUNE", "") not in ("", "0")
_cache_path: str | None = os.environ.get("REPRO_AUTOTUNE_CACHE") or None

_WARMUP, _REPS = 1, 3

# Observability: cache files that failed to load (corrupt JSON, stale or
# unknown schema) are *ignored*, never fatal — but each ignore is recorded
# here so drivers can surface it as a trace event instead of the cache
# silently reverting to defaults.
_events: list[tuple] = []


def events() -> list[tuple]:
    """Every cache-load anomaly this process has recorded, in order.

    Entries are ``("autotune_cache_ignored", path, reason)`` for whole-file
    rejects and ``("autotune_cache_entry_ignored", path, key)`` for
    malformed individual entries.  ``repro.api.fit`` drains new entries into
    the run trace.
    """
    return list(_events)


def _record_event(kind: str, *info) -> None:
    _events.append((kind,) + info)


def enable(on: bool = True) -> None:
    """Turn timing-based tuning on/off process-wide (lookups always work)."""
    global _enabled
    _enabled = bool(on)


def enabled() -> bool:
    return _enabled


def set_cache_path(path: str | os.PathLike | None) -> None:
    """Point the on-disk JSON cache at ``path`` (``None`` disables disk)."""
    global _cache_path
    _cache_path = None if path is None else os.fspath(path)


def cache_path() -> str | None:
    return _cache_path


def clear(disk: bool = False) -> None:
    """Drop every cached winner (and the disk cache file when ``disk``)."""
    with _lock:
        _cache.clear()
        _loaded_paths.clear()
        if disk and _cache_path and os.path.exists(_cache_path):
            os.remove(_cache_path)


def cache_key(kind: str, *, backend: str, b: int, m: int, k: int, n: int,
              precision: str) -> str:
    return f"{kind}|{backend}|b{b}|m{m}|k{k}|n{n}|{precision}"


def _valid_entry(blocks) -> bool:
    """A disk-cache entry ops can splat into a kernel call as kwargs."""
    if not isinstance(blocks, dict):
        return False
    return all(
        isinstance(name, str)
        and (val is None or isinstance(val, (int, str))
             and not isinstance(val, bool))
        for name, val in blocks.items())


def _load_disk() -> None:
    if not _cache_path or _cache_path in _loaded_paths:
        return
    _loaded_paths.add(_cache_path)
    try:
        with open(_cache_path) as f:
            data = json.load(f)
    except FileNotFoundError:
        return                          # no cache yet: the normal first run
    except (OSError, ValueError) as exc:
        _record_event("autotune_cache_ignored", _cache_path,
                      f"unreadable: {type(exc).__name__}: {exc}")
        return
    if not isinstance(data, dict) or not isinstance(data.get("entries"), dict):
        _record_event("autotune_cache_ignored", _cache_path,
                      "not a cache object")
        return
    if data.get("version") != 1:
        _record_event("autotune_cache_ignored", _cache_path,
                      f"stale schema version {data.get('version')!r}")
        return
    for key, blocks in data["entries"].items():
        if not _valid_entry(blocks):
            _record_event("autotune_cache_entry_ignored", _cache_path, key)
            continue
        _cache.setdefault(key, blocks)


def _save_disk() -> None:
    if not _cache_path:
        return
    # Merge-on-write: re-read the file so concurrent processes sharing one
    # cache path keep each other's entries (this process's winners take
    # precedence); os.replace keeps each write atomic.
    merged: dict[str, dict] = {}
    try:
        with open(_cache_path) as f:
            merged.update(json.load(f).get("entries", {}))
    except (OSError, ValueError):
        pass
    merged.update(_cache)
    tmp = f"{_cache_path}.tmp.{os.getpid()}"
    payload = {"version": 1, "entries": dict(sorted(merged.items()))}
    d = os.path.dirname(_cache_path)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(tmp, "w") as f:
        json.dump(payload, f, indent=1)
    os.replace(tmp, _cache_path)


def candidates(kind: str, *, b: int, m: int, k: int, n: int,
               precision: str) -> list[dict]:
    """The handful of tilings worth timing for this kernel kind + shape."""
    from repro.kernels import fused_step as fused

    out: list[dict] = []
    if kind == "fused":
        # Shape-derived default tiling first (see fused_batched below), then
        # lane/contraction tile variants x the two pipelines: 'blocks' (grid
        # streaming) vs 'dma' (double-buffered explicit copies) — the tuner
        # decides per backend whether compute/DMA overlap pays.
        _, _, bk0, bn0 = fused._batched_tiles(k, n)
        out.append({"block_m": 256, "block_k": bk0, "block_n": bn0,
                    "pipeline": "blocks"})
        for pipe in ("blocks", "dma"):
            for bm in (128, 256, 512):
                for bk, bn in ((bk0, bn0), (128, 256), (256, 512)):
                    cand = {"block_m": bm, "block_k": bk, "block_n": bn,
                            "pipeline": pipe}
                    if cand in out:
                        continue
                    if not fused.fits_tiling(k, n, bk, bn):
                        continue
                    out.append(cand)
    elif kind == "fused_batched":
        # The shape-derived default tiling is candidate #0, so tuning can
        # never cache something slower than not tuning at all.
        _, _, bk0, bn0 = fused._batched_tiles(k, n)
        out.append({"block_m": 256, "block_k": bk0, "block_n": bn0})
        for bm in (128, 256, 512):
            for bk in (bk0, 128, 256):
                for bn in (256, 512):
                    cand = {"block_m": bm, "block_k": bk, "block_n": bn}
                    if cand in out:
                        continue
                    if not fused.fits_tiling(k, n, bk, bn):
                        continue
                    out.append(cand)
    elif kind == "assign":
        # Serving-shaped calls (small m, large k) need different tilings
        # from the training hot path: block_m candidates above the actual
        # point count collapse to one launch shape (assign_pallas clamps
        # to max(8, m), so they are deduped here), and once k exceeds one
        # centroid tile the [bm, bk] reduce amortizes over wider block_k.
        bms = sorted({min(bm, max(8, m)) for bm in (128, 256, 512)})
        bks = [bk for bk in (128, 256, 512) if bk == 128 or k > bk // 2]
        for bm in bms:
            for bk in bks:
                for bf in (256, 512):
                    out.append({"block_m": bm, "block_k": bk,
                                "block_f": bf})
    else:
        raise ValueError(f"unknown autotune kind {kind!r}")
    # Defaults first, so ties keep historic behaviour.  For the fused kinds
    # the "default" that must be timed first is the shape-derived tiling
    # prepended above (the _DEFAULTS entry holds unresolved Nones).
    head = (out[0],) if kind in ("fused", "fused_batched") \
        else (_DEFAULTS[kind],)
    out.sort(key=lambda blk: blk not in head)
    return out


def _time(run: Callable[[], object]) -> float:
    for _ in range(_WARMUP):
        run()                                  # compile + warm caches
    best = float("inf")
    for _ in range(_REPS):
        t0 = time.perf_counter()
        run()
        best = min(best, time.perf_counter() - t0)
    return best


def get_blocks(
    kind: str,
    bench_factory: Callable[[dict], Callable[[], object]] | None = None,
    *,
    backend: str,
    b: int,
    m: int,
    k: int,
    n: int,
    precision: str,
) -> dict:
    """The tiling ``ops`` should launch with for this kernel kind + shape.

    Resolution order: in-process cache -> on-disk cache -> (when tuning is
    enabled and a ``bench_factory`` is given) time the candidates once and
    cache the winner -> the historical defaults.  ``bench_factory(blocks)``
    must return a zero-arg callable that runs the kernel to completion
    (``jax.block_until_ready``); a candidate whose build or run raises is
    skipped, so an over-aggressive tiling can never take down the fit.
    """
    key = cache_key(kind, backend=backend, b=b, m=m, k=k, n=n,
                    precision=precision)
    with _lock:
        _load_disk()
        hit = _cache.get(key)
    if hit is not None:
        return dict(hit)
    if not _enabled or bench_factory is None:
        return dict(_DEFAULTS[kind])

    best_blocks, best_t = dict(_DEFAULTS[kind]), float("inf")
    for blocks in candidates(kind, b=b, m=m, k=k, n=n, precision=precision):
        try:
            t = _time(bench_factory(blocks))
        except Exception:
            continue
        if t < best_t:
            best_blocks, best_t = blocks, t
    with _lock:
        _cache[key] = dict(best_blocks)
        _save_disk()
    return dict(best_blocks)
