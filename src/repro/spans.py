"""The program's profiler spans and device scopes, by name.

Host spans are ``jax.profiler.TraceAnnotation`` events on the thread that
runs the work; device scopes are ``jax.named_scope`` names, which reach
the compiled HLO as the ``op_name`` metadata of every instruction traced
under them.  A profiler capture (``jax.profiler.trace``) holds both on the
clock of the device op line.  With no capture running a span costs well
under two microseconds and a scope nothing at run time.

Host spans:

* ``repro.fit.dispatch`` — ``fit()`` entry until its jitted call returns
  (args ``strategy``, ``n_chunks``; and where the fit runs as one jitted
  program, ``gather``: ``packed`` or ``rows``, ``g``, the points one
  gathered row holds, ``lloyd``: ``fused`` where the fused Pallas Lloyd
  kernel runs or ``two_pass``, and ``k_tiles``, the lane tiles of k that
  kernel walks, 0 on the two-pass path);
* ``repro.fit.collect`` — reading the fit's result to the host;
* ``repro.serve.take`` — the batcher waiting and lingering for requests;
* ``repro.serve.launch`` — one launch, pack to scatter (args ``launch``,
  ``requests``, ``rows``, ``bucket``), parent of ``repro.serve.pack``
  (copying requests into the bucket buffer), ``repro.serve.dispatch``
  (``device_put`` and the jitted assign call), ``repro.serve.fetch``
  (reading ids and distances to the host) and ``repro.serve.scatter``
  (resolving each request's future), which carry its ``launch``.

Program counter: ``FitResult.seed_steps``, the D^2 steps the K-means++
seeding loops ran over the fit's chunks (k for each chunk whose step
seeded; per chunk in ``ChunkInfo.seed_steps``).

Device scopes: ``repro.fit.sample`` (drawing a chunk's rows and gathering
them, and the dataset's packed copy they are gathered from),
``repro.fit.seed`` (K-means++ re-seeding of degenerate slots),
``repro.fit.lloyd`` (the Lloyd search: lane pad, kernel, epilogue) and
``repro.fit.keep`` (keep-the-best and the incumbent exchange).
"""
from __future__ import annotations

import re

FIT_DISPATCH = "repro.fit.dispatch"
FIT_COLLECT = "repro.fit.collect"
FIT_SAMPLE = "repro.fit.sample"
FIT_SEED = "repro.fit.seed"
FIT_LLOYD = "repro.fit.lloyd"
FIT_KEEP = "repro.fit.keep"
SERVE_TAKE = "repro.serve.take"
SERVE_LAUNCH = "repro.serve.launch"
SERVE_PACK = "repro.serve.pack"
SERVE_DISPATCH = "repro.serve.dispatch"
SERVE_FETCH = "repro.serve.fetch"
SERVE_SCATTER = "repro.serve.scatter"

SPANS = (FIT_DISPATCH, FIT_COLLECT, SERVE_TAKE, SERVE_LAUNCH, SERVE_PACK,
         SERVE_DISPATCH, SERVE_FETCH, SERVE_SCATTER)
SCOPES = (FIT_SAMPLE, FIT_SEED, FIT_LLOYD, FIT_KEEP)
UNSCOPED = "unscoped"

_SCOPE = re.compile(r"repro\.[\w.]+")
# `%name = <shape> <opcode>(...), ..., metadata={... op_name="..." ...}`
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_OP_NAME = re.compile(r'metadata=\{[^}]*?\bop_name="([^"]*)"')


def scope_of(op_name: str) -> str:
    """The first ``repro.*`` component of an HLO ``op_name``, else
    :data:`UNSCOPED`."""
    found = _SCOPE.search(op_name)
    return found.group(0) if found else UNSCOPED


def op_scopes(hlo_text: str) -> dict[str, str]:
    """Instruction name to scope for every instruction of an HLO module's
    text (``jax.stages.Compiled.as_text()``).  The device op line of a
    profile names each operation by its instruction; an instruction
    without ``op_name`` metadata maps to :data:`UNSCOPED`."""
    out = {}
    for line in hlo_text.splitlines():
        head = _INSTRUCTION.match(line)
        if head:
            meta = _OP_NAME.search(line)
            out[head.group(1)] = scope_of(meta.group(1)) if meta else UNSCOPED
    return out
