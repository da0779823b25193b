"""Megabytes (10**6 B) of host frames the program handed to its frame
program per ``process_frame`` call: the ``h2d_bytes`` stat of each
``repro.frame_call`` span, summed over the window's
``repro.process_frame`` calls."""

from benchmarks.chip.program_trace import for_ctx


def read(ctx):
    got = for_ctx(ctx)
    if got is None:
        return None
    return got["h2d_bytes"] / got["entry_calls"] / 1e6
