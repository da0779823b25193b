"""Sparse describe kernel: intensity-centroid moments and steered rBRIEF
for every keypoint of every level of every camera, in one launch."""

from __future__ import annotations

from benchmarks.chip.kernels import shapes

NAMES = ("describe_fused",)

PATCH = 31 * 31


def work(config: dict) -> dict:
    """Per keypoint: its 31x31 raw and smoothed uint8 patches and its
    int32 coordinates read; theta (f32), two moments (f32) and one
    256-bit descriptor written.  Operations: two moment sums (2 per
    pixel each) and 256 compares with their bit packing."""
    kp = shapes.cameras(config) * config["orb"]["max_features"]
    return {"bytes": kp * (2 * PATCH + 8 + 4 + 8 + 32),
            "vpu_ops": kp * (4 * PATCH + 2 * 256)}
