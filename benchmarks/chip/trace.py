"""Reduction of a profiler trace to what the per-layer metrics read.

Input: the ``.xplane.pb`` file JAX's profiler writes.  Device planes
(``/device:TPU:<n>``) carry one event per executed operation on their
``XLA Ops`` line; the host plane carries the benchmark's own spans
(``bench.*`` trace annotations), among them ``bench.window`` around the
measured window.  Output: per operation name its count and device
seconds inside the window, whether it is a Mosaic kernel or a sort, the
union of the device's busy intervals, and a breakdown of the largest
device operations and of the idle gaps by what the host was doing.
"""

from __future__ import annotations

import collections
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
# An op event's name is its HLO instruction: "%name = type opcode(...".
_HLO = re.compile(r"^%?(\S+) = (.*?) ([a-z][a-z0-9-]*)\(")
_TYPE = re.compile(r"[a-z][a-z0-9]*\[[0-9,]*\]")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
#: Ops whose events enclose the events of the ops they run.
CONTAINERS = ("while", "conditional", "call")


def parse_op(text: str) -> tuple[str, str, str]:
    """(display name, opcode, custom-call target) of one op event: the
    display name is the instruction's name, opcode and first shape."""
    m = _HLO.match(text)
    if not m:
        return text[:80], "", ""
    name, typ, opcode = m.groups()
    shape = _TYPE.search(typ)
    target = _TARGET.search(text) if opcode == "custom-call" else None
    return (f"{name} {opcode} {shape.group(0) if shape else typ[:40]}",
            opcode, target.group(1) if target else "")


def events(path: str) -> dict:
    """The trace's device operations and host spans, times in ns:
    ``{"ops": {chip: [[display, opcode, target, start, end]]}, "spans":
    [[name, start, end]]}`` (see ``parse_op``)."""
    from jax.profiler import ProfileData
    ops: dict = collections.defaultdict(list)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    ops[int(m.group(1))].append(
                        [*parse_op(e.name), e.start_ns,
                         e.start_ns + e.duration_ns])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append([e.name, e.start_ns,
                                      e.start_ns + e.duration_ns])
    return {"ops": dict(ops), "spans": spans}


def is_kernel(opcode: str, target: str) -> bool:
    """A Mosaic (Pallas) kernel: a TPU custom call."""
    return opcode == "custom-call" and target == "tpu_custom_call"


def union(intervals) -> list[tuple[int, int]]:
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out



def idle_by_span(gaps, spans, n_chips: int) -> dict:
    """Seconds of the idle gaps under each host span (spans are disjoint
    apart from the window's); the rest under "no bench span"."""
    spans = sorted(spans, key=lambda sp: sp[1])
    host: dict = collections.defaultdict(float)
    j = 0
    for gs, ge in sorted(gaps):
        while j < len(spans) and spans[j][2] <= gs:
            j += 1
        left = ge - gs
        k = j
        while k < len(spans) and spans[k][1] < ge:
            ov = min(spans[k][2], ge) - max(spans[k][1], gs)
            if ov > 0:
                host[spans[k][0]] += ov / 1e9 / n_chips
                left -= ov
            k += 1
        if left > 0:
            host["no bench span"] += left / 1e9 / n_chips
    return host


def reduce(ev: dict, top: int = 10) -> dict:
    """Per-window totals.  Device seconds are averaged over the chips
    that ran anything; the window is the ``bench.window`` span."""
    windows = [(s, e) for n, s, e in ev["spans"] if n == WINDOW_SPAN]
    if not windows or not ev["ops"]:
        raise ValueError("trace has no bench.window span or no device ops")
    lo, hi = windows[0]
    chips = sorted(ev["ops"])
    per_op: dict = collections.defaultdict(lambda: [0, 0.0, False, False])
    busy_ns = 0
    gaps = []
    for chip in chips:
        live = [(n, op, tg, max(s, lo), min(e, hi))
                for n, op, tg, s, e in ev["ops"][chip] if e > lo and s < hi]
        for n, op, tg, s, e in live:
            if op in CONTAINERS:
                continue
            rec = per_op[n]
            rec[0] += 1
            rec[1] += (e - s) / 1e9 / len(chips)
            rec[2] = is_kernel(op, tg)
            rec[3] = op == "sort"
        busy = union([(s, e) for *_, s, e in live])
        busy_ns += sum(e - s for s, e in busy)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    host = idle_by_span(gaps, [sp for sp in ev["spans"]
                               if sp[0] != WINDOW_SPAN], len(chips))
    ranked = sorted(per_op.items(), key=lambda kv: -kv[1][1])
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / 1e9 / len(chips),
        "ops": {n: {"count": r[0], "seconds": r[1], "kernel": r[2],
                    "sort": r[3]} for n, r in per_op.items()},
        "breakdown": {
            "device_ops": [[n, r[1]] for n, r in ranked[:top]],
            "idle_gaps": sorted(([n, s] for n, s in host.items()),
                                key=lambda x: -x[1])[:top]},
    }


def reduce_file(path: str) -> dict:
    return reduce(events(path))


def seconds(reduced: dict, keep) -> float:
    """Device seconds of the ops for which ``keep(name, op)`` holds."""
    return sum(op["seconds"] for n, op in reduced["ops"].items()
               if keep(n, op))


def kernel(name: str):
    """The module ``kernels/<name>.py``: the kernel's trace names and
    its work per program call."""
    import importlib
    from pathlib import Path
    if not (Path(__file__).parent / "kernels" / f"{name}.py").is_file():
        raise ValueError(f"no kernels/{name}.py")
    return importlib.import_module(f"benchmarks.chip.kernels.{name}")


def kernel_seconds(reduced: dict, name: str) -> float:
    names = kernel(name).NAMES
    return seconds(reduced, lambda n, op: op["kernel"]
                   and any(k in n for k in names))


def per_call_ms(ctx: dict, secs: float):
    """Milliseconds per program call of the traced window; None where
    the trace holds no such time."""
    calls = ctx["window"].calls
    return 1e3 * secs / calls if secs > 0 and calls else None


def roofline(ctx: dict, name: str):
    """Percent of the kernel's bytes-bound roofline; None where the
    kernel did not run."""
    ms = per_call_ms(ctx, kernel_seconds(ctx["reduced"], name))
    if ms is None:
        return None
    least = kernel(name).work(ctx["cell"].config)["bytes"] / \
        ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (ms / 1e3)
