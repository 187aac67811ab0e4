"""Operations and bytes that one Big-means fit job requires.

A copy of ``chunk_traffic`` from ``repro/launch/roofline.py`` (kept here so
the yardstick does not move when the program does), plus the roofline time
of that work against a chip's peaks.
"""
from __future__ import annotations

# Storage bytes per chunk element.
_ITEMSIZE = {"f32": 4, "bf16": 2, "bf16x3": 4, "int8": 1}


def chunk_bytes(s: int, n: int, precision: str) -> int:
    """Bytes to stream one ``[s, n]`` chunk once under ``precision`` (int8
    adds one f32 scale per feature)."""
    b = s * n * _ITEMSIZE[precision]
    if precision == "int8":
        b += 4 * n
    return b


def chunk_traffic(s: int, n: int, k: int, precision: str,
                  passes: float) -> dict:
    """FLOPs and bytes of ``passes`` passes of the Lloyd loop over one chunk.

    Per pass: the distance contraction (2*s*k*n), the norm/argmin assembly
    (3*s*k) and the one-hot update contraction (2*s*k*n); bytes are the
    chunk stream plus the centroid read and the sums/counts write-back.
    """
    flops_pass = 4.0 * s * k * n + 3.0 * s * k
    bytes_pass = chunk_bytes(s, n, precision) + 2 * (4 * k * n) + 4 * k
    return {"flops": flops_pass * passes, "bytes": bytes_pass * passes}


def job_passes(n_iterations: int, n_chunks: int) -> int:
    """Chunk passes of one fit job: every Lloyd iteration reads its chunk
    once, and each chunk adds two (the acceptance assign and update)."""
    return int(n_iterations) + 2 * int(n_chunks)


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> tuple:
    """Least time the chip could take for the work, and which peak bounds
    it (``'flops'`` or ``'bytes'``)."""
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")
