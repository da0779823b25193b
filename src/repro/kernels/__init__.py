"""Pallas TPU kernels for the paper's compute hot-spots (FE + FM).

frontend_fused — batched blur + FAST + NMS megakernel (one VMEM pass
                 per tile for all cameras x levels — the DENSE stage,
                 paper's frame-multiplexed FE analog)
describe_fused — batched moments + LUT-steered rBRIEF per keypoint
                 block (the SPARSE stage; gather-free taps via one-hot
                 matmuls, 30-degree-binned steering ROM; theta is taken
                 by ``ops`` with XLA's atan2, which Mosaic lacks)
matcher_fused  — the FM megakernel (Hamming argmin + in-kernel SAD) and
                 its match-only and SAD-only variants
pattern        — BRIEF sampling pattern + STEER_LUT ROM (numpy-only)
fast_detect    — FAST-9/16 corner score map (standalone, halo'd tiles)
gaussian_blur  — fused separable 7x7 Gaussian (line-buffer analog)
hamming_match  — fused search-region + Hamming argmin (FM front half)
sad_rectify    — 11x11 SAD sweep (FM rectifier)

ops.py dispatches kernels vs. the pure-jnp oracles in ref.py and owns
all padding and layout; the whole-frame entry points are
``ops.fast_blur_nms_pyramid`` (dense), ``ops.orient_describe_pyramid``
(sparse) and ``ops.match_rectify_fused`` (FM) — three launches per
frame for the whole camera batch.  On the TPU the kernels compile with
Mosaic; on the CPU they run in interpret mode (tests).
"""

from repro.kernels import ops, ref  # noqa: F401
