"""Share of the temporal_match kernel's HBM roofline: its algorithmic bytes per
program call (kernels/temporal_match.py) at the chip's published HBM bandwidth,
over its measured device time per call.  Bounded by bytes: the
kernel's work is VPU compares, popcounts and integer sums, for which
no public peak exists."""

from benchmarks.chip.trace import roofline


def read(ctx):
    return roofline(ctx, "temporal_match")
