"""The general harness: finds a cell's parts by name and runs it.

``BENCHMARK.json`` names each cell's configuration and traffic mix.
The harness reads the configuration from its file, the mix from
``traffic/<traffic>.json``, the driver named by the mix from
``drivers/<driver>.py``, each per-layer metric's reader from
``metrics/<metric>.py`` and each kernel's name map and byte count from
``kernels/<kernel>.py``.  A name with no file is an error, never a
default.  A run: set-up (traffic rendered on the device, the system
built and every shape of the window warmed), the measured window, the
device's peak memory, then the reference over every answer of the
window, then the metrics.
"""

from __future__ import annotations

import contextlib
import importlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
import typing
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


class SpecError(Exception):
    """A name in BENCHMARK.json or a mix that has no file of its own."""


class NoChip(Exception):
    """JAX sees no TPU, or fewer chips than the cell asks for."""


def load_json(path: Path) -> dict:
    if not path.is_file():
        raise SpecError(f"no file {path}")
    return json.loads(path.read_text())


def load_module(path: Path, name: str):
    if not path.is_file():
        raise SpecError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell(typing.NamedTuple):
    name: str
    entry: dict               # the cell's entry in BENCHMARK.json
    config: dict              # the configuration's file
    traffic: dict             # the traffic mix's file
    end_to_end: list          # BENCHMARK.json metrics this cell reports
    per_layer: list

    @property
    def chips(self) -> int:
        return int(self.entry["chips"])

    @property
    def driver(self):
        name = self.traffic["driver"]
        if not (HERE / "drivers" / f"{name}.py").is_file():
            raise SpecError(f"no driver {name!r} for traffic "
                            f"{self.entry['traffic']!r}")
        return importlib.import_module(f"benchmarks.chip.drivers.{name}")


def _reports(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(name: str, bench: dict | None = None) -> Cell:
    bench = bench if bench is not None else load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json")
    entry = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if entry["config"] not in configs:
        raise SpecError(f"workload {name!r} names no known config "
                        f"{entry['config']!r}")
    config = load_json(ROOT / configs[entry["config"]]["file"])
    traffic = load_json(HERE / "traffic" / f"{entry['traffic']}.json")
    e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
    moved = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if _reports(m, name) and m["moves"] in moved]
    return Cell(name, entry, config, traffic, e2e, layer)


# --------------------------------------------------------------------------
# Device, peaks and the compile cache

def device_info(chips: int, require_tpu: bool = True) -> dict:
    import jax
    devices = jax.devices()
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        raise NoChip(f"JAX sees no TPU (platform {dev.platform!r})")
    if require_tpu and len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees "
                     f"{len(devices)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def peaks(device_kind: str) -> dict:
    table = load_json(HERE / "peaks.json")["devices"]
    if device_kind not in table:
        raise SpecError(f"no peaks for device_kind {device_kind!r} in "
                        "peaks.json")
    return table[device_kind]


def memory_peak_bytes(count: int) -> int:
    import jax
    stats = [d.memory_stats() or {} for d in jax.devices()[:count]]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at ``JAX_COMPILATION_CACHE_DIR``
    where it is set, else at the fixed ``<checkout>/.jax_cache``; every
    program is kept, however fast it compiled."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileCounter:
    """Counts JAX's tracing and compilation events while armed, so a
    window that compiles anything is seen.  One per process."""

    def __init__(self) -> None:
        import jax
        global COMPILES
        self.armed = False
        self.events = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        COMPILES = self

    def _on(self, event: str, duration: float, **_) -> None:
        if self.armed and event.startswith("/jax/core/compile"):
            self.events += 1


COMPILES: CompileCounter | None = None


# --------------------------------------------------------------------------
# One run

class Window(typing.NamedTuple):
    """What a driver's measured window gives back.  ``latency_s`` has one
    entry per answered frame; ``answers`` pairs each answer's reference
    key with its host output; ``spans`` holds the host-clock spans the
    driver measured around the program's calls (name -> seconds per
    program call); ``calls`` counts program calls."""

    t0: float
    t1: float
    attempted: int
    failed: int
    latency_s: list
    answers: list
    spans: dict
    calls: int
    counters: dict


def span(name: str, traced: bool):
    """A host span in the profiler's trace when the run is traced."""
    if not traced:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


def mean_ms(values):
    """Mean of host-clock spans in milliseconds; None where none."""
    return 1e3 * sum(values) / len(values) if values else None


def percentile(values, q: float) -> float:
    import numpy as np
    return float(np.percentile(np.asarray(values, np.float64), q))


def end_to_end(win: Window, setup_s: float) -> dict:
    answered = len(win.latency_s)
    return {
        "rig_frames_per_s": answered / (win.t1 - win.t0),
        "frame_ms_p50": 1e3 * percentile(win.latency_s, 50),
        "frame_ms_p95": 1e3 * percentile(win.latency_s, 95),
        "setup_s": setup_s,
    }


@contextlib.contextmanager
def profiled(enabled: bool):
    """A profiler trace around the window, into a fresh directory under
    TMPDIR that is removed once read; yields a list that receives the
    path of the trace file."""
    if not enabled:
        yield None
        return
    import jax
    tmp = tempfile.mkdtemp(prefix="chipbench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    found: list = []
    jax.profiler.start_trace(tmp, profiler_options=opts)
    try:
        yield found
    finally:
        jax.profiler.stop_trace()
        found.extend(str(p) for p in Path(tmp).rglob("*.xplane.pb"))
        found.append(tmp)


def per_layer(cell: Cell, ctx: dict) -> dict:
    out = {}
    for m in cell.per_layer:
        reader = load_module(HERE / "metrics" / f"{m['name']}.py",
                             f"chipbench_metric_{m['name']}")
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, require_tpu: bool = True,
             session_factory=None, log=print) -> dict:
    """One run of a cell; returns the result line as a dict.

    ``session_factory`` replaces the system under test (tests plant
    faults through it); ``require_tpu=False`` lets a test drive the rest
    of a run on the CPU, where no metric is ever reported."""
    from benchmarks.chip import check, program, trace as trace_mod
    device = device_info(cell.chips, require_tpu)
    on_tpu = device["platform"] == "tpu"
    pk = peaks(device["kind"]) if on_tpu else None
    if require_tpu:
        log(f"compile cache: {enable_compile_cache()}")
    counter = COMPILES or CompileCounter()
    t_device = time.perf_counter() - t_start
    driver = cell.driver.Driver(
        cell, seed, session_factory or program.session)
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.2f} s: {t_device:.2f} s to reach the device, "
        f"{setup_s - t_device:.2f} s traffic, system and warm-up")
    counter.events = 0
    counter.armed = True
    with profiled(trace) as found:
        win = driver.window(seconds, traced=trace)
    counter.armed = False
    log(f"window from {win.t0:.3f} to {win.t1:.3f} s on the monotonic "
        f"clock; slowest frame {1e3 * max(win.latency_s, default=0.0):.1f} "
        f"ms from {win.counters.get('slowest_frame_at_s', 0.0):.3f} s in")
    device["memory_peak_bytes"] = memory_peak_bytes(device["count"])
    retraces = driver.retraces()
    driver.close()
    checks = check.check(cell, driver, win, log=log)
    result = {
        "correct": all(c["value"] <= c["limit"] for c in checks.values()),
        "attempted": win.attempted, "failed": win.failed}
    metrics, breakdown = {}, None
    if trace:
        if on_tpu:
            reduced = trace_mod.reduce_file(found[0])
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            breakdown = reduced["breakdown"]
            metrics = per_layer(cell, {"cell": cell, "reduced": reduced,
                                       "window": win, "peaks": pk})
        shutil.rmtree(found[-1], ignore_errors=True)
    elif on_tpu:
        values = end_to_end(win, setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    result["metrics"] = metrics
    result["device"] = device
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["window"] = {"compile_events": counter.events,
                        "retraces": retraces,
                        "latency_max_ms": 1e3 * max(win.latency_s, default=0.0),
                        **win.counters}
    result["checks"] = checks
    return result


def write_result(result: dict, err=sys.stderr, out=sys.stdout) -> None:
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=err)
    err.flush()
    print(json.dumps(result), file=out, flush=True)
