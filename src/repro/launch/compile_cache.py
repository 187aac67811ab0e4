"""Where entry points keep JAX's persistent compilation cache.

Command-line entry points (``chip_smoke.py``, ``repro.launch.train`` and
the ``benchmarks`` CLIs) call :func:`configure` once, before their first
compile.  The library never calls it on import, and neither do the tests.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads that directory itself and
  this leaves it alone — no other path is set in code.
* Unset: the cache goes to ``<checkout>/.jax_cache`` (gitignored).  The
  path is fixed — never a temp name, a pid or a time — so a later process
  in the same checkout finds what an earlier one compiled.
"""
from __future__ import annotations

import os
import pathlib

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def configure() -> str:
    """Point JAX's persistent compile cache at its directory; returns it."""
    env = os.environ.get(ENV)
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_DIR))
    return str(CHECKOUT_DIR)
