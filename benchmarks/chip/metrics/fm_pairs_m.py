"""Millions of left x right candidate slots the stereo FM kernel's grid
walks per rig frame: the window's sum of the ``fm_pairs`` stat of the
program's ``repro.frame_call`` spans over their number.  It grows as
the square of the feature budget."""

from benchmarks.chip.span_stats import for_ctx


def read(ctx):
    got = for_ctx(ctx)
    if got is None or "fm_pairs" not in got["sums"]:
        return None
    return got["sums"]["fm_pairs"] / got["calls"] / 1e6
