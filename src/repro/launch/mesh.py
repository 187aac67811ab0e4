"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state.  Single pod: 16x16 = 256 chips (TPU v5e pod
slice); multi-pod: 2 pods x 256 = 512 chips with a leading "pod" axis.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """`jax.make_mesh` with Auto axis types — the entry point for tests and
    benchmark subprocesses."""
    kwargs = {"axis_types": (AxisType.Auto,) * len(axes)}
    if devices is not None:
        kwargs["devices"] = devices
    return jax.make_mesh(tuple(shape), tuple(axes), **kwargs)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, found {len(devices)} — set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=512 before "
            "importing jax (dryrun.py does this)."
        )
    return make_mesh(shape, axes, devices=devices[:n])


def make_host_mesh(shape=None, axes=("data", "model")):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    if shape is None:
        shape = (n, 1)
    return make_mesh(shape, axes)
