"""The program's own instrumentation in a profiler trace.

``VisualSystem.process_frame`` records ``repro.*`` host spans (the
whole call, carrying ``call``, around ``repro.validate``,
``repro.frame_call``, carrying ``h2d_bytes``, and
``repro.localize_call``), and each stage of its device programs runs
under a ``jax.named_scope`` (``pyramid``, ``dense_fe``,
``select_topk``, ``describe``, ``stereo``, ``localize`` with
``temporal_match`` and ``pose_solve`` inside).  A TPU op event carries
no scope: its name is the HLO instruction without metadata, and the
``XLA Modules`` line of its chip says which program ran it.  The scope
is the instruction's ``op_name`` in that program's optimized HLO, which
``compiled_scopes`` compiles again from the configuration (the run
left every program in the compile cache).

This module reads both beside ``trace.py``: ``events`` keeps
``trace.events``' records and adds each op's program and every host
span's thread and stats; ``reduce`` gives what the program's per-layer
metrics read.  A trace of a program without the instrumentation holds
no ``repro.*`` span, and every reader then returns None.

The harness hands a metric reader the reduced trace, not the trace's
file, so ``for_ctx`` finds the run's own file where ``harness.profiled``
writes it (a ``chipbench-trace-*`` directory under the temporary
directory, removed after the readers ran) and checks that its window is
the one the harness reduced.
"""

from __future__ import annotations

import bisect
import collections
import re
import tempfile
from pathlib import Path

from benchmarks.chip import trace

PROGRAM_PREFIX = "repro."
ENTRY_SPAN = "repro.process_frame"
FRAME_CALL_SPAN = "repro.frame_call"
MODULES_LINE = "XLA Modules"
_MODULE = re.compile(r"^HloModule (\S+?),? ")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?(%\S+ = .*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def events(path: str) -> dict:
    """``trace.events`` of the trace file, plus ``op_module``, per chip
    the program of each op in the order of ``ops`` ("" for an op outside
    every program), and ``host``, every ``bench.*`` and ``repro.*`` host
    span as ``[name, start, end, thread, stats]``."""
    from jax.profiler import ProfileData
    ops: dict = collections.defaultdict(list)
    modules: dict = collections.defaultdict(list)
    spans, host = [], []
    for plane in ProfileData.from_file(path).planes:
        m = trace.DEVICE_PLANE.match(plane.name)
        if m:
            chip = int(m.group(1))
            lines = {line.name: line for line in plane.lines}
            runs = sorted((e.start_ns, e.start_ns + e.duration_ns,
                           e.name.split("(")[0])
                          for e in (lines[MODULES_LINE].events
                                    if MODULES_LINE in lines else ()))
            starts = [r[0] for r in runs]
            for e in (lines[trace.OPS_LINE].events
                      if trace.OPS_LINE in lines else ()):
                end = e.start_ns + e.duration_ns
                ops[chip].append([*trace.parse_op(e.name), e.start_ns, end])
                k = bisect.bisect_right(starts, e.start_ns) - 1
                modules[chip].append(runs[k][2] if k >= 0
                                     and runs[k][1] >= end else "")
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if not e.name.startswith((trace.SPAN_PREFIX,
                                              PROGRAM_PREFIX)):
                        continue
                    end = e.start_ns + e.duration_ns
                    if e.name.startswith(trace.SPAN_PREFIX):
                        spans.append([e.name, e.start_ns, end])
                    host.append([e.name, e.start_ns, end, line.name,
                                 {k: _number(v) for k, v in e.stats}])
    return {"ops": dict(ops), "spans": spans, "op_module": dict(modules),
            "host": host}


def _number(value):
    try:
        return int(value)
    except (TypeError, ValueError):
        return value


def hlo_scopes(text: str) -> tuple[str, dict]:
    """The module name of one optimized HLO text and, per instruction
    name, its ``(display, op_name)`` (display as ``trace.parse_op``
    gives it; op_name "" where the instruction has no metadata)."""
    m = _MODULE.match(text)
    if not m:
        raise ValueError("no HloModule line in the HLO text")
    out = {}
    for line in text.splitlines():
        ins = _INSTRUCTION.match(line)
        if not ins:
            continue
        display = trace.parse_op(ins.group(1))[0]
        op_name = _OP_NAME.search(line)
        out[display.split(" ")[0]] = (display,
                                      op_name.group(1) if op_name else "")
    return m.group(1), out


def op_scopes(ev: dict, scopes: dict) -> dict:
    """Per chip, the scope path of each op of ``ev``: its instruction's
    op_name in ``scopes`` (module -> ``hlo_scopes``), "" for an op of a
    program not in ``scopes``.  An op of a known program whose
    instruction is missing or has another shape means another program
    ran: ValueError."""
    out = {}
    for chip, ops in ev["ops"].items():
        paths = []
        for (display, *_), module in zip(ops, ev["op_module"][chip]):
            table = scopes.get(module)
            if table is None:
                paths.append("")
                continue
            name, _opcode, shape = display.split(" ", 2)
            want = table.get(name)
            # async ops print as async-start in the trace
            if want is None or want[0].split(" ", 2)[2] != shape:
                raise ValueError(f"{module}: traced op {display!r} is not "
                                 "the compiled program's")
            paths.append(want[1])
        out[chip] = paths
    return out


def compiled_scopes(config: dict) -> dict:
    """``hlo_scopes`` of the stream driver's programs for the
    configuration, compiled for the local device as the run compiled
    them: a fresh session's ``process_frame`` runs once on a blank frame
    to build its programs, and each is lowered and compiled again, which
    the compile cache answers."""
    import numpy as np
    from benchmarks.chip import program
    vs = program.session(config)
    from repro import localization
    orb = config["orb"]
    frame = np.zeros((config["rig"]["n_cameras"], orb["height"],
                      orb["width"]),
                     np.uint8 if config["precision"] == "uint8"
                     else np.float32)
    out = vs.process_frame(frame)
    args = {"process_frame": (frame,)}
    if config["localize"]:
        args["localize_frame"] = (out.stereo, localization.state_from(out))
    return dict(hlo_scopes(vs.program(key).lower(*a).compile().as_text())
                for key, a in args.items())


def in_scope(path: str, scope: str) -> bool:
    """Whether an op's scope path lies under ``scope``: one of its
    components is the scope, or a transform of it (``vmap(scope)``)."""
    return any(part == scope or part.endswith(f"({scope})")
               for part in path.split("/"))


def innermost(spans) -> list:
    """The host timeline cut where any span starts or ends, each piece
    ``[name, start, end]`` under the innermost span that covers it (the
    latest started; spans of one thread nest).  ``bench.window`` covers
    everything and names no piece.  The pieces are disjoint, as
    ``trace.idle_by_span`` wants its spans."""
    spans = sorted((s for s in spans if s[0] != trace.WINDOW_SPAN),
                   key=lambda s: (s[1], -s[2]))
    edges = sorted({t for s in spans for t in (s[1], s[2])})
    out, active, j = [], [], 0
    for a, b in zip(edges, edges[1:]):
        while j < len(spans) and spans[j][1] <= a:
            active.append(spans[j])
            j += 1
        active = [s for s in active if s[2] > a]
        if active:
            name = max(active, key=lambda s: (s[1], -s[2]))[0]
            if out and out[-1][0] == name and out[-1][2] == a:
                out[-1][2] = b
            else:
                out.append([name, a, b])
    return out


def _window(ev: dict) -> tuple[int, int]:
    windows = [(s, e) for n, s, e in ev["spans"] if n == trace.WINDOW_SPAN]
    if not windows or not ev["ops"]:
        raise ValueError("trace has no bench.window span or no device ops")
    return windows[0]


def reduce(ev: dict, top: int = 10) -> dict:
    """Per-window totals of the program's instrumentation: host seconds
    per ``repro.*`` span name, the idle gaps by innermost span, device
    seconds per scope path, the ``h2d_bytes`` the frame calls handed
    over, and the number of ``repro.process_frame`` calls, each counted
    for what starts inside the window.  Device seconds are averaged
    over the chips that ran anything, as ``trace.reduce`` does."""
    lo, hi = _window(ev)
    chips = sorted(ev["ops"])
    scope_s: dict = collections.defaultdict(float)
    gaps = []
    for chip in chips:
        live = []
        paths = ev.get("op_scope", {}).get(chip) or [""] * len(
            ev["ops"][chip])
        for (n, op, tg, s, e), scope in zip(ev["ops"][chip], paths):
            if e <= lo or s >= hi:
                continue
            s, e = max(s, lo), min(e, hi)
            live.append((s, e))
            if op not in trace.CONTAINERS:
                scope_s[scope] += (e - s) / 1e9 / len(chips)
        busy = trace.union(live)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    host = [h for h in ev.get("host", []) if lo <= h[1] < hi]
    program = [h for h in host if h[0].startswith(PROGRAM_PREFIX)]
    span_s: dict = collections.defaultdict(float)
    for name, s, e, *_ in program:
        span_s[name] += (e - s) / 1e9
    idle = trace.idle_by_span(gaps, innermost(h[:3] for h in host),
                              len(chips))
    return {
        "window_s": (hi - lo) / 1e9,
        "span_s": dict(span_s),
        "entry_calls": sum(1 for h in program if h[0] == ENTRY_SPAN),
        "h2d_bytes": sum(int(h[4].get("h2d_bytes", 0)) for h in program
                         if h[0] == FRAME_CALL_SPAN),
        "program_idle_s": sum(s for n, s in idle.items()
                              if n.startswith(PROGRAM_PREFIX)),
        "scope_s": dict(scope_s),
        "idle_gaps": sorted(([n, s] for n, s in idle.items()),
                            key=lambda x: -x[1])[:top],
    }


def scope_seconds(reduced: dict, scope: str) -> float:
    """Device seconds of every op under ``scope``."""
    return sum(s for path, s in reduced["scope_s"].items()
               if in_scope(path, scope))


# --------------------------------------------------------------------------
# The run's trace, for the metric readers

_CACHE: dict = {}


def trace_file() -> str | None:
    """The newest trace file ``harness.profiled`` left under the
    temporary directory; None where there is none."""
    found = Path(tempfile.gettempdir()).glob(
        "chipbench-trace-*/**/*.xplane.pb")
    newest = max(found, key=lambda p: p.stat().st_mtime, default=None)
    return str(newest) if newest else None


def read_run(path: str, config: dict) -> dict | None:
    """``reduce`` of one run's trace file, with each op's scope where
    the programs' compiled HLO can be had (``scoped``; else the reason
    in ``scope_error``); None where the trace has no
    ``repro.process_frame`` span."""
    ev = events(path)
    if not any(h[0] == ENTRY_SPAN for h in ev["host"]):
        return None
    error = ""
    try:
        ev["op_scope"] = op_scopes(ev, compiled_scopes(config))
    except Exception as e:      # noqa: BLE001 — a reader reports, never raises
        error = f"{type(e).__name__}: {e}"
    got = reduce(ev)
    got["scoped"], got["scope_error"] = not error, error
    return got


def for_ctx(ctx: dict) -> dict | None:
    """``read_run`` of the run's trace, where its window is the one the
    harness reduced; else None."""
    path = trace_file()
    if path is None:
        return None
    if path not in _CACHE:
        try:
            _CACHE[path] = read_run(path, ctx["cell"].config)
        except (OSError, ValueError):
            _CACHE[path] = None
    got = _CACHE[path]
    if (got is None
            or abs(got["window_s"] - ctx["reduced"]["window_s"]) > 1e-9):
        return None
    return got


def span_ms(ctx: dict, name: str):
    """Mean host milliseconds per frame of the ``name`` spans."""
    got = for_ctx(ctx)
    if got is None or name not in got["span_s"]:
        return None
    return trace.per_call_ms(ctx, got["span_s"][name])


def scope_ms(ctx: dict, scope: str):
    """Device milliseconds per frame of the ops under ``scope``."""
    got = for_ctx(ctx)
    if got is None or not got["scoped"]:
        return None
    return trace.per_call_ms(ctx, scope_seconds(got, scope))
