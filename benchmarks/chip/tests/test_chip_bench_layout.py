"""The chip benchmark's files: BENCHMARK.json against its contract, every
name it gives has a file of its own, and the kernels' byte counts."""

from __future__ import annotations

import json
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[3]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.chip import harness, trace  # noqa: E402
from benchmarks.chip.kernels import shapes  # noqa: E402

HERE = ROOT / "benchmarks" / "chip"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.fixture(scope="module")
def bench():
    return json.loads(json.dumps(BENCH))


def _one_line(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_command(bench):
    assert set(bench) == KEYS
    assert bench["paths"] == ["benchmarks/chip"]
    assert len(bench["command"]) <= 32
    assert all(_one_line(w) for w in bench["command"])
    assert 1 <= bench["run_seconds"] <= 51
    # A full check of 24 cells fits its 43,200 s.
    t = bench["run_seconds"]
    assert (2 + 14 * 24) * (t + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_names_units_and_text_fields(bench):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in bench[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append(entry["name"])
            for key in ("why", "layer", "source"):
                if key in entry:
                    assert _one_line(entry[key]), (entry["name"], key)
            if "unit" in entry:
                assert UNIT.match(entry["unit"]), entry["unit"]
                assert entry["better"] in ("lower", "higher")
    assert len(names) == len(set(names))


def test_every_config_loads_and_is_used(bench):
    used = {w["config"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["name"] in used
        assert c["file"].startswith("benchmarks/chip/configs/")
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        for key in ("rig", "camera", "orb", "precision", "impl", "localize"):
            assert key in cfg, (c["name"], key)
        assert isinstance(c["reduced"], list) and len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key) and key in cfg, (c["name"], key)


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves(bench, cell):
    entry = next(w for w in bench["workloads"] if w["name"] == cell)
    assert set(entry) == {"name", "config", "traffic", "chips", "why"}
    assert entry["chips"] in (1, 4)
    loaded = harness.load_cell(cell, bench)
    assert loaded.driver.Driver
    names = {m["name"] for m in loaded.end_to_end}
    assert {"setup_s", "rig_frames_per_s"} <= names
    assert loaded.per_layer
    for m in loaded.per_layer:
        reader = harness.load_module(HERE / "metrics" / f"{m['name']}.py",
                                     f"test_metric_{m['name']}")
        assert callable(reader.read)


def test_each_configuration_traffic_pair_once(bench):
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_metrics_declare_moves_layer_and_cells(bench):
    e2e = {m["name"] for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    assert "setup_s" in e2e
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= cells
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        if m["name"].endswith("_roofline"):
            trace.kernel(m["name"][:-len("_roofline")])
        if m["name"].startswith("kernel_ms."):
            trace.kernel(m["name"][len("kernel_ms."):])


def test_missing_names_are_errors(bench):
    with pytest.raises(harness.SpecError):
        harness.load_cell("no_such_cell", bench)
    broken = json.loads(json.dumps(bench))
    broken["workloads"][0]["traffic"] = "no_such_traffic"
    with pytest.raises(harness.SpecError):
        harness.load_cell(broken["workloads"][0]["name"], broken)
    with pytest.raises(ValueError):
        trace.kernel("no_such_kernel")


def test_unknown_device_kind_is_an_error():
    with pytest.raises(harness.SpecError):
        harness.peaks("TPU v99 imaginary")
    assert harness.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def _config(name):
    return json.loads((HERE / "configs" / f"{name}.json").read_text())


def test_720p_quad_shapes():
    cfg = _config("quad720")
    assert shapes.level_shapes(cfg) == [(720, 1280), (600, 1067)]
    assert shapes.cameras(cfg) == 4 and shapes.pairs(cfg) == 2


@pytest.mark.parametrize("kernel,want_bytes", [
    # 4 cameras x (921,600 + 640,200) px x (1 image + 1 blur + 2 score) B
    ("dense_fe", 4 * (720 * 1280 + 600 * 1067) * 4),
    # 4,000 keypoints x (2 x 961 patch bytes + 8 + 4 + 8 + 32)
    ("describe", 4 * 1000 * (2 * 961 + 52)),
    # 2 pairs x (2 x 1,000 x 48 + 1,000 x (121 + 231) + 1,000 x 20)
    ("fm", 2 * (2 * 1000 * 48 + 1000 * (121 + 231) + 1000 * 20)),
    # 2 pairs x (2 x 1,000 x 48 + 1,000 x 8)
    ("temporal_match", 2 * (2 * 1000 * 48 + 1000 * 8)),
])
def test_720p_quad_kernel_bytes(kernel, want_bytes):
    work = trace.kernel(kernel).work(_config("quad720"))
    assert work["bytes"] == want_bytes
    assert work["vpu_ops"] > 0
