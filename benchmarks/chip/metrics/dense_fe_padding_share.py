"""Share of the dense FE grid's 128x128 tiles that hold no level pixel:
100 x (1 - the window's sum of ``fe_tiles_real`` over its sum of
``fe_tiles``, stats of the program's ``repro.frame_call`` spans).  Every
camera x pyramid level slab is padded to the level-0 canvas, so the
smaller the levels, the more of the grid is padding."""

from benchmarks.chip.span_stats import for_ctx


def read(ctx):
    got = for_ctx(ctx)
    if got is None or not got["sums"].get("fe_tiles"):
        return None
    sums = got["sums"]
    return 100.0 * (1.0 - sums["fe_tiles_real"] / sums["fe_tiles"])
