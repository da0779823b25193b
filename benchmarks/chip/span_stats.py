"""Sums of the stats the program puts on its ``repro.frame_call`` spans.

``VisualSystem.process_frame`` records each frame call's H2D bytes and,
where the program has them, the kernel grids of the frame from static
shapes (``fe_tiles``, ``fe_tiles_real``, ``fm_pairs``) as stats of its
``repro.frame_call`` span.  ``program_trace.reduce`` sums only
``h2d_bytes``; this module sums every numeric stat of the window's
frame calls, reading the run's trace file once for all its readers.  A
program without a stat leaves it out of the sums, and its reader then
returns None.
"""

from __future__ import annotations

import collections

from benchmarks.chip import program_trace

_CACHE: dict = {}


def frame_calls(ev: dict) -> dict:
    """The ``repro.frame_call`` spans that start inside the window of
    ``program_trace.events``' output: ``window_s``, their number
    (``calls``) and, per numeric stat, its sum (``sums``)."""
    lo, hi = program_trace._window(ev)
    sums: collections.Counter = collections.Counter()
    calls = 0
    for name, start, _end, _thread, stats in ev["host"]:
        if name != program_trace.FRAME_CALL_SPAN or not lo <= start < hi:
            continue
        calls += 1
        for key, value in stats.items():
            if isinstance(value, int):
                sums[key] += value
    return {"window_s": (hi - lo) / 1e9, "calls": calls, "sums": dict(sums)}


def for_ctx(ctx: dict) -> dict | None:
    """``frame_calls`` of the run's trace, where its window is the one
    the harness reduced and it holds a frame call; else None."""
    path = program_trace.trace_file()
    if path is None:
        return None
    if path not in _CACHE:
        try:
            _CACHE[path] = frame_calls(program_trace.events(path))
        except (OSError, ValueError):
            _CACHE[path] = None
    got = _CACHE[path]
    if (got is None or not got["calls"]
            or abs(got["window_s"] - ctx["reduced"]["window_s"]) > 1e-9):
        return None
    return got
