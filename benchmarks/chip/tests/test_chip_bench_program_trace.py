"""The reduction of the program's own spans and scopes
(``program_trace.py``) and the readers of its metrics: on hand-made
events whose answers are worked out below, on the recorded trace of a
program without them, and on a small trace recorded on the chip."""

from __future__ import annotations

import collections
import gzip
import json
import sys
import types
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[3]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.chip import harness, program_trace, trace  # noqa: E402

MS = 1_000_000          # ns
HERE = ROOT / "benchmarks" / "chip"
READERS = ("frame_call_ms", "localize_call_ms", "program_idle_ms",
           "h2d_mb_per_frame", "scope_ms.select_topk", "scope_ms.pyramid",
           "scope_ms.localize")


def _events():
    """Window 0-100 ms, two frames.  Host (one thread): dispatch 0-10
    holding process_frame 1-9 (validate 1-2, frame_call 2-5, localize_call
    5-8), fetch 10-50, dispatch 50-60 holding process_frame 51-59
    (validate 51-52, frame_call 52-56, localize_call 56-58), fetch
    60-100.  Device: busy 4-48, 57-72 and 95-100 (one op runs past the
    window's end)."""
    f, lo = "jit(process_frame)", "jit(localize_frame)"
    ops = [  # display, opcode, target, start, end, module, scope
        ["fusion.1 fusion u8[4]", "fusion", "", 4, 20, f, f"{f}/pyramid/mul"],
        ["sort.9 sort s32[4]", "sort", "", 20, 30, f,
         f"{f}/vmap(select_topk)/top_k"],
        ["describe_fused_pyramid_pallas.1 custom-call f32[4]", "custom-call",
         "tpu_custom_call", 30, 35, f,
         f"{f}/describe/jit(describe_fused_pyramid_pallas)/pallas_call"],
        ["copy.3 copy s32[4]", "copy", "", 35, 40, f, ""],
        ["match_fused_pallas.1 custom-call s32[2]", "custom-call",
         "tpu_custom_call", 40, 45, lo,
         f"{lo}/localize/temporal_match/pallas_call"],
        ["sort.42 sort f32[1,2000]", "sort", "", 45, 48, lo,
         f"{lo}/localize/pose_solve/sort"],
        ["fusion.1 fusion u8[4]", "fusion", "", 57, 70, f, f"{f}/pyramid/mul"],
        ["gather.5 gather s32[4]", "gather", "", 70, 72, f,
         f"{f}/select_topk/gather"],
        ["while.6 while s32[]", "while", "", 70, 72, f, f"{f}/select_topk/while"],
        ["fusion.7 fusion f32[3]", "fusion", "", 95, 110, lo,
         f"{lo}/localize/add"],
    ]
    module = {f: "jit_process_frame", lo: "jit_localize_frame"}
    ev = {"ops": {0: [[*o[:3], o[3] * MS, o[4] * MS] for o in ops]},
          "op_module": {0: [module[o[5]] for o in ops]}}
    scopes = collections.defaultdict(dict)
    for o in ops:
        scopes[module[o[5]]][o[0].split(" ")[0]] = (o[0], o[6])
    host = [("bench.window", 0, 100, {}),
            ("bench.dispatch", 0, 10, {}),
            ("repro.process_frame", 1, 9, {"call": 1}),
            ("repro.validate", 1, 2, {}),
            ("repro.frame_call", 2, 5, {"h2d_bytes": 1000}),
            ("repro.localize_call", 5, 8, {}),
            ("bench.fetch", 10, 50, {}),
            ("bench.dispatch", 50, 60, {}),
            ("repro.process_frame", 51, 59, {"call": 2}),
            ("repro.validate", 51, 52, {}),
            ("repro.frame_call", 52, 56, {"h2d_bytes": 1000}),
            ("repro.localize_call", 56, 58, {}),
            ("bench.fetch", 60, 100, {})]
    ev["host"] = [[n, s * MS, e * MS, "main", st] for n, s, e, st in host]
    ev["spans"] = [h[:3] for h in ev["host"] if h[0].startswith("bench.")]
    return ev, dict(scopes)


def _scoped():
    ev, scopes = _events()
    ev["op_scope"] = program_trace.op_scopes(ev, scopes)
    return ev


def test_idle_goes_to_the_innermost_span():
    r = program_trace.reduce(_scoped())
    gaps = dict(r["idle_gaps"])
    # Gaps 0-4, 48-57 and 72-95 ms.  0-1 dispatch, 1-2 validate, 2-4
    # frame_call; 48-50 fetch, 50-51 dispatch, 51-52 validate, 52-56
    # frame_call, 56-57 localize_call; 72-95 fetch.
    want = {"bench.dispatch": 2, "repro.validate": 2, "repro.frame_call": 6,
            "repro.localize_call": 1, "bench.fetch": 25}
    assert set(gaps) == set(want)
    for name, ms in want.items():
        assert gaps[name] == pytest.approx(ms / 1e3), name
    assert r["program_idle_s"] == pytest.approx(0.009)
    # The bench.dispatch share of trace.reduce is the same time, now
    # split between the dispatch and the program's spans inside it.
    old = dict(trace.reduce(_scoped())["breakdown"]["idle_gaps"])
    inside = sum(s for n, s in gaps.items()
                 if n == "bench.dispatch" or n.startswith("repro."))
    assert inside == pytest.approx(old["bench.dispatch"])
    assert gaps["bench.fetch"] == pytest.approx(old["bench.fetch"])


def test_spans_calls_and_bytes():
    r = program_trace.reduce(_scoped())
    assert r["window_s"] == pytest.approx(0.100)
    assert r["entry_calls"] == 2
    assert r["h2d_bytes"] == 2000
    assert r["span_s"]["repro.frame_call"] == pytest.approx(0.007)
    assert r["span_s"]["repro.localize_call"] == pytest.approx(0.005)
    assert r["span_s"]["repro.process_frame"] == pytest.approx(0.016)


def test_scopes_hold_their_ops_and_nothing_else():
    r = program_trace.reduce(_scoped())
    secs = lambda s: program_trace.scope_seconds(r, s)  # noqa: E731
    # pyramid 4-20 and 57-70; select_topk 20-30 and 70-72 (the while
    # op encloses what it runs and is not counted); localize 40-48 and
    # 95-100 (clipped at the window), its kernel included.
    assert secs("pyramid") == pytest.approx(0.029)
    assert secs("select_topk") == pytest.approx(0.012)
    assert secs("localize") == pytest.approx(0.013)
    assert secs("temporal_match") == pytest.approx(0.005)
    assert secs("pose_solve") == pytest.approx(0.003)
    assert secs("describe") == pytest.approx(0.005)
    assert r["scope_s"][""] == pytest.approx(0.005)
    # no stage is a substring match of another's program name
    assert not program_trace.in_scope("jit(localize_frame)/add", "localize")


def test_scopes_come_from_the_compiled_program():
    ev, scopes = _events()
    paths = program_trace.op_scopes(ev, scopes)[0]
    assert paths[1] == "jit(process_frame)/vmap(select_topk)/top_k"
    # an op of a program the table does not hold has no scope
    del scopes["jit_localize_frame"]
    paths = program_trace.op_scopes(ev, scopes)[0]
    assert paths[4] == "" and paths[1].endswith("top_k")
    # an op the compiled program does not hold: another program ran
    scopes["jit_process_frame"]["sort.9"] = ("sort.9 sort s32[8]", "x")
    with pytest.raises(ValueError):
        program_trace.op_scopes(ev, scopes)


def test_hlo_scopes_reads_names_and_op_names():
    import jax
    import jax.numpy as jnp

    def f(x):
        with jax.named_scope("stage"):
            return jnp.sort(x * 2.0)

    text = jax.jit(f).lower(jnp.ones((64,))).compile().as_text()
    module, table = program_trace.hlo_scopes(text)
    assert module == "jit_f"
    sorts = {n: v for n, v in table.items() if " sort " in v[0]}
    assert sorts and all(program_trace.in_scope(v[1], "stage")
                         for v in sorts.values())
    with pytest.raises(ValueError):
        program_trace.hlo_scopes("no module here")


def _ctx(ev, calls=2):
    return {"reduced": trace.reduce(ev),
            "cell": types.SimpleNamespace(config={}),
            "window": types.SimpleNamespace(calls=calls)}


def _read(name, ctx):
    reader = harness.load_module(HERE / "metrics" / f"{name}.py",
                                 f"test_program_metric_{name}")
    return reader.read(ctx)


@pytest.fixture
def run_trace(monkeypatch):
    """Points the readers at hand-made events as if they were the run's
    trace file, and at their scope table as the compiled programs'."""
    def use(ev, scopes):
        program_trace._CACHE.clear()
        monkeypatch.setattr(program_trace, "trace_file", lambda: "run.pb")
        monkeypatch.setattr(program_trace, "events", lambda path: ev)
        monkeypatch.setattr(program_trace, "compiled_scopes",
                            lambda config: scopes)
    yield use
    program_trace._CACHE.clear()


def test_readers_on_hand_made_events(run_trace):
    ev, scopes = _events()
    run_trace(ev, scopes)
    got = {n: _read(n, _ctx(ev)) for n in READERS}
    assert got == pytest.approx({
        "frame_call_ms": 3.5, "localize_call_ms": 2.5,
        "program_idle_ms": 4.5, "h2d_mb_per_frame": 0.001,
        "scope_ms.select_topk": 6.0, "scope_ms.pyramid": 14.5,
        "scope_ms.localize": 6.5})


def test_readers_are_silent_without_the_program_instrumentation(run_trace):
    """A program without the instrumentation: no ``repro.*`` span (and
    so no compile of its programs)."""
    ev, _ = _events()
    ev["host"] = [h for h in ev["host"] if not h[0].startswith("repro.")]
    run_trace(ev, {})
    assert all(_read(n, _ctx(ev)) is None for n in READERS)


def test_scope_readers_are_silent_where_no_program_compiles(
        run_trace, monkeypatch):
    ev, _ = _events()
    run_trace(ev, {})

    def fail(config):
        raise AttributeError("'VisualSystem' object has no attribute "
                             "'program'")
    monkeypatch.setattr(program_trace, "compiled_scopes", fail)
    got = {n: _read(n, _ctx(ev)) for n in READERS}
    assert got["frame_call_ms"] == pytest.approx(3.5)
    assert all(got[n] is None for n in READERS if n.startswith("scope_ms."))
    assert "AttributeError" in program_trace._CACHE["run.pb"]["scope_error"]


def test_readers_are_silent_on_another_window(run_trace):
    ev, scopes = _events()
    run_trace(ev, scopes)
    other, _ = _events()
    other["spans"][0][2] += MS            # a window 1 ms longer
    assert all(_read(n, _ctx(other)) is None for n in READERS)


def test_readers_are_silent_with_no_trace_file(monkeypatch):
    program_trace._CACHE.clear()
    monkeypatch.setattr(program_trace, "trace_file", lambda: None)
    assert all(_read(n, _ctx(_events()[0])) is None for n in READERS)


def test_trace_file_is_the_newest_profiled_run(tmp_path, monkeypatch):
    import os
    monkeypatch.setattr("tempfile.gettempdir", lambda: str(tmp_path))
    assert program_trace.trace_file() is None
    paths = []
    for i, run in enumerate(("chipbench-trace-a", "chipbench-trace-b")):
        p = tmp_path / run / "plugins" / "profile" / "x" / "h.xplane.pb"
        p.parent.mkdir(parents=True)
        p.write_bytes(b"")
        os.utime(p, (1000 + i, 1000 + i))
        paths.append(p)
    (tmp_path / "other" / "h.xplane.pb").parent.mkdir()
    (tmp_path / "other" / "h.xplane.pb").write_bytes(b"")
    assert program_trace.trace_file() == str(paths[1])


def _load(name):
    with gzip.open(HERE / "testdata" / name, "rt") as f:
        ev = json.load(f)
    for key in ("ops", "op_module", "op_scope"):
        if key in ev:
            ev[key] = {int(k): v for k, v in ev[key].items()}
    return ev


def test_recorded_program_trace(run_trace):
    """Six frames of quad720.stream traced on a TPU v5 lite chip with the
    program's spans, and the scope of each traced instruction of its two
    programs as their HLO compiled on that chip gives it."""
    ev = _load("stream_program_trace.json.gz")
    scopes = {m: {n: tuple(v) for n, v in t.items()}
              for m, t in ev.pop("scopes").items()}
    assert sum(1 for s in ev["spans"] if s[0] == "bench.dispatch") == 6
    run_trace(ev, scopes)
    got = {n: _read(n, _ctx(ev, calls=6)) for n in READERS}
    assert got == pytest.approx({
        "frame_call_ms": 1.531215, "localize_call_ms": 5.3752365,
        "program_idle_ms": 0.63040283, "h2d_mb_per_frame": 3.6864,
        "scope_ms.select_topk": 37.787006, "scope_ms.pyramid": 0.335705,
        "scope_ms.localize": 0.27929617})
    r = program_trace._CACHE["run.pb"]
    old = trace.reduce(ev)
    # the top-K's scope holds every sort of the frame program
    sorts = trace.seconds(old, lambda n, op: op["sort"])
    assert program_trace.scope_seconds(r, "select_topk") >= sorts - 1e-3
    # ops under no scope: under 5% of the device's busy time
    assert r["scope_s"][""] / old["busy_s"] == pytest.approx(0.026144,
                                                             rel=1e-3)
    # the idle under bench.dispatch now lies under the program's spans
    gaps = dict(r["idle_gaps"])
    dispatch = dict(old["breakdown"]["idle_gaps"])["bench.dispatch"]
    assert r["program_idle_s"] / dispatch > 0.9
    assert gaps["repro.frame_call"] == pytest.approx(0.00357081, rel=1e-6)


def test_without_program_spans_the_attribution_is_the_old_one():
    """On the recorded trace of a program with no ``repro.*`` span, the
    innermost span of every gap is the ``bench.*`` span trace.py names."""
    ev = _load("stream_trace.json.gz")
    old = dict(trace.reduce(ev)["breakdown"]["idle_gaps"])
    ev["host"] = [[*s, "main", {}] for s in ev["spans"]]
    new = dict(program_trace.reduce(ev)["idle_gaps"])
    assert new == pytest.approx(old)
    assert program_trace.reduce(ev)["entry_calls"] == 0


def test_compiled_scopes_of_the_stream_programs():
    """The stream cell's two programs at a tiny frame, compiled on the
    CPU: every stage scope names some instruction."""
    sys.path.insert(0, str(HERE / "tests"))
    import chip_bench_tiny
    scopes = program_trace.compiled_scopes(
        chip_bench_tiny.tiny("quad720.stream").config)
    assert set(scopes) == {"jit_process_frame", "jit_localize_frame"}
    paths = {m: [v[1] for v in t.values()] for m, t in scopes.items()}
    for stage in ("pyramid", "dense_fe", "select_topk", "describe",
                  "stereo"):
        assert any(program_trace.in_scope(p, stage)
                   for p in paths["jit_process_frame"]), stage
    for stage in ("localize", "temporal_match", "pose_solve"):
        assert any(program_trace.in_scope(p, stage)
                   for p in paths["jit_localize_frame"]), stage


#: The trace names the program gives its four frame-path kernels
#: (``pallas_call(name=...)``, pinned by ``tests/test_tpu_compile.py``).
KERNEL_TRACE_NAMES = {"dense_fe": "frontend_fused_pyramid_pallas",
                      "describe": "describe_fused_pyramid_pallas",
                      "fm": "match_rectify_fused_pallas",
                      "temporal_match": "match_fused_pallas"}


@pytest.mark.parametrize("kernel", sorted(KERNEL_TRACE_NAMES))
def test_each_kernel_name_matches_its_own_names_only(kernel):
    name = KERNEL_TRACE_NAMES[kernel] + ".1"
    for other in KERNEL_TRACE_NAMES:
        hit = any(k in name for k in trace.kernel(other).NAMES)
        assert hit == (other == kernel), (kernel, other)
