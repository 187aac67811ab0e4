"""The fused Lloyd kernel's share of its roofline, in percent.

The work is what the window's jobs required of the kernel: the counters
``kernel_flops`` and ``kernel_bytes`` (``bench.work.chunk_traffic`` at
passes = each job's Lloyd iterations).  Its roofline time is the larger
of its operations over the chip's peak rate and its bytes over the chip's
memory bandwidth (``bench/peaks.json``); the share divides that by the
device seconds of the operations whose name begins
``fused_step_batched_pallas``, averaged over the chips used.  The peak is
the bf16 rate: under f32 the distance contraction (``Precision.HIGHEST``)
takes six bf16 passes and the one-hot update three, so an f32
configuration reads at most about 2/9 of 100.  None
where the counters or the kernel's operations are absent (a program whose
Lloyd step runs the two-pass path).
"""
from bench import work

KERNEL = "fused_step_batched_pallas"


def read(ctx):
    red, c = ctx["reduction"], ctx["counters"]
    if red is None or not c.get("kernel_flops"):
        return None
    seconds = sum(t for name, t in red.ops if name.startswith(KERNEL))
    if not seconds:
        return None
    t_roof, _ = work.roofline_seconds(c["kernel_flops"], c["kernel_bytes"],
                                      ctx["peaks"])
    return 100.0 * t_roof / seconds
