"""Feature-matcher kernel: epipolar-band Hamming argmin and the SAD
rectification sweep for every stereo pair, in one launch."""

from __future__ import annotations

from benchmarks.chip.kernels import shapes

NAMES = ("match_rectify_fused",)

DESC, META = 32, 16        # 256-bit descriptor; (x, y, level, valid) f32


def work(config: dict) -> dict:
    """Per pair: both sides' descriptors and meta rows read, each left
    feature's 11x11 window and its matched right 11x(11+2r) strip read
    as uint8; distance, index, right coordinates and SAD argmin written.
    Operations: per left-right candidate 8 xors, 8 popcounts (12 ops
    each) and the sum and band tests (16); per left feature
    (2r+1) x 11 x 11 absolute differences and sums (3 ops)."""
    orb = config["orb"]
    k, p, r = orb["max_features"], orb["sad_window"], orb["sad_range"]
    n = shapes.pairs(config)
    window = p * p + p * (p + 2 * r)
    return {"bytes": n * (2 * k * (DESC + META) + k * window + k * 20),
            "vpu_ops": n * (k * k * (8 + 8 * 12 + 16)
                            + k * (2 * r + 1) * p * p * 3)}
