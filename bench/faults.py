"""Faults planted in the timed path underneath the harness.

Used to read the upper ends of the limits of ``correct`` on the chip
(``python3 -m bench.calibrate --control fault=<name>:<seeds>``) and by the
tests that see ``correct`` come out false.  Each patch is seen by programs
traced after it: entering and leaving one clears JAX's caches.

* ``kernel_unchanged`` — the fused Lloyd kernel returns sums that leave
  every centroid where it was (a step that returns its state unchanged);
* ``kernel_half_rows`` — the kernel sums only the first half of each
  chunk's rows, so each centroid is the mean over half its rows;
* ``no_periodic_sync`` — the incumbent exchange every ``sync_every``
  rounds is left out, between the streams of a chip and between chips.
"""
from __future__ import annotations

import contextlib


def _kernel_unchanged(real):
    def fused_step_batched(x, c, **kw):
        sums, counts, f = real(x, c, **kw)
        return c * counts[..., None], counts, f
    return fused_step_batched


def _kernel_half_rows(real):
    import jax.numpy as jnp

    def fused_step_batched(x, c, **kw):
        half = x[:, : x.shape[1] // 2]
        return real(jnp.concatenate([half, half], axis=1), c, **kw)
    return fused_step_batched


def _no_periodic_sync(real):
    def stream_scan(*a, sync_fn, **kw):
        return real(*a, sync_fn=lambda states: states, **kw)
    return stream_scan


def _targets():
    from repro.engine import incore
    from repro.kernels import ops

    return {
        "kernel_unchanged": (ops, "fused_step_batched", _kernel_unchanged),
        "kernel_half_rows": (ops, "fused_step_batched", _kernel_half_rows),
        "no_periodic_sync": (incore, "stream_scan", _no_periodic_sync),
    }


NAMES = ("kernel_unchanged", "kernel_half_rows", "no_periodic_sync")


@contextlib.contextmanager
def planted(name: str):
    """Run the body with fault ``name`` planted in the program."""
    import jax

    module, attr, wrap = _targets()[name]
    real = getattr(module, attr)
    setattr(module, attr, wrap(real))
    jax.clear_caches()
    try:
        yield
    finally:
        setattr(module, attr, real)
        jax.clear_caches()
