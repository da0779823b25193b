"""Temporal matcher kernel: the match-only Hamming argmin between the
previous and the current frame's left features, for every pair."""

from __future__ import annotations

from benchmarks.chip.kernels import shapes

NAMES = ("match_fused",)

DESC, META = 32, 16


def work(config: dict) -> dict:
    """Per pair: both frames' descriptors and meta rows read, distance
    and index written.  Operations per candidate as in ``fm``."""
    k = config["orb"]["max_features"]
    n = shapes.pairs(config)
    return {"bytes": n * (2 * k * (DESC + META) + k * 8),
            "vpu_ops": n * k * k * (8 + 8 * 12 + 16)}
