"""Dense frontend kernel: 7x7 blur, FAST-9/16 and 3x3 NMS over every
pixel of every pyramid level of every camera, in one launch."""

from __future__ import annotations

from benchmarks.chip.kernels import shapes

#: Substrings of the kernel's device-op names in a profiler trace.
NAMES = ("frontend_fused",)


def work(config: dict) -> dict:
    """Algorithmic HBM bytes and VPU operations of one program call:
    each level pixel read once as uint8, its blur written as uint8 and
    its score as int16; per pixel 14 blur multiply-adds (2 ops each),
    16 tap differences, 16 arcs x 8 compares for each of the bright
    and dark arcs, 32 arc maxima, 8 NMS compares."""
    px = shapes.cameras(config) * sum(h * w for h, w in
                                      shapes.level_shapes(config))
    return {"bytes": px * (1 + 1 + 2),
            "vpu_ops": px * (28 + 16 + 2 * 16 * 8 + 32 + 8)}
