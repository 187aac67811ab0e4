"""Per-chip peaks, keyed by ``device_kind`` as JAX reports it.

The table is ``peaks.json`` beside this file, with its source.  A device
that is not in the table is an error, never a default.
"""
from __future__ import annotations

import json
import pathlib

TABLE = pathlib.Path(__file__).resolve().parent / "peaks.json"


class UnknownDevice(KeyError):
    pass


def peaks(device_kind: str, table: pathlib.Path = TABLE) -> dict:
    """The peaks of one chip of ``device_kind``; raises :class:`UnknownDevice`."""
    devices = json.loads(table.read_text())["devices"]
    try:
        return dict(devices[device_kind])
    except KeyError:
        raise UnknownDevice(
            f"no peaks for device kind {device_kind!r} in {table.name}; "
            f"known: {sorted(devices)}") from None
