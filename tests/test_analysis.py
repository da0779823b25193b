"""`repro.analysis` acceptance: the static auditor proves the real
tree's invariants (launch budgets, VMEM residency, dtype contracts,
index-map bounds, serving hostlint) AND each checker demonstrably FAILS
on a deliberately broken fixture — an auditor that cannot fail proves
nothing."""

import json

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import analysis

H, W, K = 96, 128, 64
SMALL = dict(height=H, width=W, max_features=K)


def _spec(name):
    return next(s for s in analysis.MATRIX if s.name == name)


def _trace(name):
    return analysis.trace_entry(_spec(name), **SMALL)


# ---------------------------------------------------------------------------
# fixture kernels (traced only — interpret mode, never executed)

def _copy_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...]


def _pallas_copy(x, in_spec=None, out_spec=None, grid=(2,)):
    spec = pl.BlockSpec((4,), lambda i: (i,))
    return pl.pallas_call(
        _copy_kernel,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        grid=grid,
        in_specs=[in_spec if in_spec is not None else spec],
        out_specs=out_spec if out_spec is not None else spec,
        interpret=True)(x)


def _sites_of(fn, *avals):
    closed = jax.make_jaxpr(fn)(*avals)
    return closed, analysis.pallas_sites(closed)


_VEC = jax.ShapeDtypeStruct((8,), jnp.float32)


# ---------------------------------------------------------------------------
# launch auditor — green on the tree, red on extra launches

def test_frame_entry_proves_three_launch_budget():
    te = _trace("frame_f32")
    assert te.count.bounded
    assert te.count.total == 3 == len(te.sites)
    assert te.audit_count == 3
    assert all(s.mult == 1 for s in te.sites)


def test_localized_frame_is_four_launches():
    te = _trace("frame_loc")
    assert te.count.total == 4 <= _spec("frame_loc").launch_budget


def test_scan_applies_trip_multiplier():
    """run (T=2 sequential) is a scan over the 3-launch frame core:
    3 traced sites, each with multiplier 2, static total 6."""
    te = _trace("run_f32")
    assert len(te.sites) == 3 and te.audit_count == 3
    assert all(s.mult == 2 for s in te.sites)
    assert te.count.total == 6


def test_extra_launch_breaks_the_budget():
    closed, sites = _sites_of(lambda x: _pallas_copy(_pallas_copy(x)),
                              _VEC)
    count = analysis.count_launches(closed)
    assert count.total == 2 == len(sites)
    assert count.total > 1  # vs a 1-launch budget: the gate trips


def test_nested_scan_multiplies():
    def f(x):
        def outer(c, _):
            def inner(c2, _):
                return _pallas_copy(c2), None
            c, _ = jax.lax.scan(inner, c, None, length=4)
            return c, None
        c, _ = jax.lax.scan(outer, x, None, length=3)
        return c

    closed, sites = _sites_of(f, _VEC)
    assert [s.mult for s in sites] == [12]
    assert analysis.count_launches(closed).total == 12


def test_while_body_launch_is_unbounded():
    def f(x):
        def body(carry):
            i, v = carry
            return i + 1, _pallas_copy(v)
        _, out = jax.lax.while_loop(lambda c: c[0] < 3, body, (0, x))
        return out

    closed, _ = _sites_of(f, _VEC)
    count = analysis.count_launches(closed)
    assert not count.bounded
    assert count.unbounded_sites
    assert "while" in count.unbounded_sites[0].path


def test_cond_counts_worst_case_branch():
    def f(p, x):
        return jax.lax.cond(p,
                            lambda v: _pallas_copy(_pallas_copy(v)),
                            lambda v: _pallas_copy(v), x)

    closed, sites = _sites_of(f, jax.ShapeDtypeStruct((), jnp.bool_),
                              _VEC)
    assert len(sites) == 3          # every branch's kernels reported
    assert analysis.count_launches(closed).total == 2  # max, not sum


# ---------------------------------------------------------------------------
# VMEM residency — documented number on the tree, red on a fat block

def test_fm_resident_bytes_match_documented_720p_number():
    """The fused FM launch at 720p f32 must account to the documented
    7.91 MiB/pair residency (the PR 7 class of regression this catches
    before runtime)."""
    te = analysis.trace_entry(_spec("match_f32"), height=720,
                              width=1280, max_features=1000)
    (site,) = te.sites
    v = analysis.launch_vmem(site)
    assert v.ok
    assert round(v.resident_bytes / 2 ** 20, 2) == 7.91


def test_all_matrix_launches_fit_default_budget():
    for name in ("frame_f32", "frame_u8", "frame_loc"):
        for site in _trace(name).sites:
            v = analysis.launch_vmem(site)
            assert v.ok, (name, v.kernel, v.resident_bytes)


def test_uint8_cuts_resident_bytes_3x():
    f32 = {v.kernel: v for v in
           (analysis.launch_vmem(s) for s in _trace("frame_f32").sites)}
    u8 = {v.kernel: v for v in
          (analysis.launch_vmem(s) for s in _trace("frame_u8").sites)}
    total_f32 = sum(v.resident_bytes for v in f32.values())
    total_u8 = sum(v.resident_bytes for v in u8.values())
    # Image slabs shrink 4x; int32 score/descriptor blocks are shared
    # by both datapaths, so the aggregate saving lands a bit above 3x.
    assert total_u8 * 3 <= total_f32


def test_oversized_block_fails_the_budget():
    big = jax.ShapeDtypeStruct((2048, 2048), jnp.float32)
    spec = pl.BlockSpec((2048, 2048), lambda: (0, 0))
    closed, (site,) = _sites_of(
        lambda x: pl.pallas_call(
            _copy_kernel, out_shape=big, in_specs=[spec],
            out_specs=spec, interpret=True)(x), big)
    v = analysis.launch_vmem(site)
    assert not v.ok                   # 2 x 16 MiB blocks vs 16 MiB core
    assert v.resident_bytes == 2 * 2048 * 2048 * 4
    assert analysis.launch_vmem(site, budget=64 * 2 ** 20).ok


def test_unblocked_halo_counted_in_block_bytes():
    """frontend_fused loads (1, T+8, W+8) halo'd row bands via Element
    blocks — residency must charge the halo'd band, not the 128x128
    tile."""
    te = _trace("frame_f32")
    halo = [b for s in te.sites
            for b in analysis.launch_vmem(s).blocks
            if b.mode == "Element"]
    assert halo
    assert any(b.block_shape[-2] == 136 and b.block_shape[-1] == W + 8
               for b in halo)


# ---------------------------------------------------------------------------
# dtype flow — clean on the tree, red on a float leak

def test_uint8_matrix_has_zero_dtype_violations():
    for name in ("frame_u8", "fleet_u8"):
        te = _trace(name)
        for site in te.sites:
            assert analysis.check_kernel_dtypes(site) == [], site.name


def test_integer_contract_applies_to_dense_u8_frontend():
    from repro.analysis import dtype_flow
    te = _trace("frame_u8")
    assert any(dtype_flow._integer_contract(s) for s in te.sites)


def test_f32_leak_in_integer_kernel_is_flagged():
    def leaky(x_ref, o_ref):
        o_ref[...] = (x_ref[...].astype(jnp.float32)
                      * jnp.float32(1.5)).astype(jnp.uint8)

    spec = pl.BlockSpec((4,), lambda i: (i,))
    u8 = jax.ShapeDtypeStruct((8,), jnp.uint8)
    closed, (site,) = _sites_of(
        lambda x: pl.pallas_call(
            leaky, out_shape=u8, grid=(2,), in_specs=[spec],
            out_specs=spec, interpret=True)(x), u8)
    violations = analysis.check_kernel_dtypes(site)
    assert violations
    assert {v.rule for v in violations} == {"float-in-integer-kernel"}


def test_weak_float_promotion_is_its_own_rule():
    def promoted(x_ref, o_ref):
        o_ref[...] = (x_ref[...] + 0.5).astype(jnp.uint8)

    spec = pl.BlockSpec((4,), lambda i: (i,))
    u8 = jax.ShapeDtypeStruct((8,), jnp.uint8)
    closed, (site,) = _sites_of(
        lambda x: pl.pallas_call(
            promoted, out_shape=u8, grid=(2,), in_specs=[spec],
            out_specs=spec, interpret=True)(x), u8)
    rules = {v.rule for v in analysis.check_kernel_dtypes(site)}
    assert "weak-float-promotion" in rules


def test_float_kernel_is_exempt_from_integer_contract():
    closed, (site,) = _sites_of(_pallas_copy, _VEC)
    assert analysis.check_kernel_dtypes(site) == []


# ---------------------------------------------------------------------------
# bounds — proven on the tree, red on an off-by-one index map

def test_real_kernels_prove_in_bounds():
    for name in ("frame_f32", "frame_u8", "frame_loc"):
        for site in _trace(name).sites:
            assert analysis.check_bounds(site) == [], site.name


def test_blocked_index_map_off_by_one_is_caught():
    bad = pl.BlockSpec((4,), lambda i: (i + 1,))
    closed, (site,) = _sites_of(
        lambda x: _pallas_copy(x, in_spec=bad), _VEC)
    violations = analysis.check_bounds(site)
    assert violations
    assert violations[0].grid_point == (1,)
    assert "escapes" in violations[0].message


def test_unblocked_window_escaping_slab_is_caught():
    bad = pl.BlockSpec((pl.Element(6),), lambda i: (i * 4,))
    out = pl.BlockSpec((pl.Element(6),), lambda i: (0,))
    out_shape = jax.ShapeDtypeStruct((6,), jnp.float32)
    closed, (site,) = _sites_of(
        lambda x: pl.pallas_call(
            _copy_kernel, out_shape=out_shape, grid=(2,),
            in_specs=[bad], out_specs=out, interpret=True)(x), _VEC)
    violations = analysis.check_bounds(site)
    assert violations
    assert "[4, 10)" in violations[0].message


# ---------------------------------------------------------------------------
# hostlint — clean tree, red fixtures

def test_serving_tree_is_hostlint_clean():
    assert analysis.lint_serving() == []


_WATCHDOG_BAD = """
import threading

class Guard:
    def _attempt(self, fn):
        box = {}
        def worker():
            self.stats["calls"] += 1
            box["value"] = fn()
        t = threading.Thread(target=worker, daemon=True)
        t.start()
        return box
"""

_WATCHDOG_LOCKED = _WATCHDOG_BAD.replace(
    '            self.stats["calls"] += 1\n',
    '            with self._lock:\n'
    '                self.stats["calls"] += 1\n')


def test_lock_free_watchdog_mutation_is_flagged():
    findings = analysis.lint_source(_WATCHDOG_BAD, "failover.py")
    assert [f.rule for f in findings] == ["watchdog-unlocked"]
    assert findings[0].symbol == "self.stats"


def test_locked_watchdog_mutation_passes():
    assert analysis.lint_source(_WATCHDOG_LOCKED, "failover.py") == []


_HOT_BLOCKING = """
import time
import numpy as np

class Service:
    def step(self, now):
        out = self.vs.process_fleet(self.batch)
        out.depth.block_until_ready()
        host = np.asarray(out.depth)
        time.sleep(0.01)
        return host

    def submit(self, images):
        return np.asarray(images)
"""


def test_blocking_and_transfer_calls_flagged_only_in_hot_paths():
    findings = analysis.lint_source(_HOT_BLOCKING, "service.py")
    rules = sorted(f.rule for f in findings)
    assert rules == ["blocking-call", "blocking-call", "host-transfer"]
    # submit (intake) is not a hot path: its np.asarray is allowed.
    assert all(f.line < _HOT_BLOCKING.count("\n") for f in findings)


def test_pragma_suppresses_a_deliberate_call():
    src = _HOT_BLOCKING.replace(
        "host = np.asarray(out.depth)",
        "host = np.asarray(out.depth)  # audit: host-ok")
    rules = sorted(f.rule for f in
                   analysis.lint_source(src, "service.py"))
    assert rules == ["blocking-call", "blocking-call"]


def test_per_call_jit_in_hot_path_is_retrace_risk():
    src = """
import jax

class Service:
    def step(self, now):
        fn = jax.jit(lambda x: x + 1)
        return fn(self.batch)
"""
    findings = analysis.lint_source(src, "service.py")
    assert [f.rule for f in findings] == ["retrace-risk"]


# ---------------------------------------------------------------------------
# report + CI gate plumbing

def test_run_audit_green_on_current_tree():
    rep = analysis.run_audit(**SMALL)
    assert rep["ok"], rep["checks"]
    assert all(rep["checks"].values())
    names = {e["name"] for e in rep["entries"]}
    assert {"frame_f32", "frame_u8", "frame_loc", "fleet_loc",
            "match_f32"} <= names


def test_matrix_covers_every_required_runtime_gate():
    from benchmarks.check_launches import REQUIRED_GATES
    claimed = {g for s in analysis.MATRIX for g in s.gates}
    assert set(REQUIRED_GATES) <= claimed


def test_check_audit_reconciles_and_catches_drift(tmp_path):
    from benchmarks import check_audit
    from benchmarks.check_launches import REQUIRED_GATES

    entries = [{"name": f"e{i}", "gates": [g],
                "launches": {"static": 4 if "loc" in g else 3}}
               for i, g in enumerate(REQUIRED_GATES)]
    audit = {"checks": {"launch_budget": True}, "entries": entries}
    rows = [{"table": "launch_gate", "name": g,
             "value": 4 if "loc" in g else 3, "unit": "kernels",
             "note": ""} for g in REQUIRED_GATES]
    bench = {"rows": rows}

    a, b = tmp_path / "AUDIT.json", tmp_path / "BENCH.json"
    a.write_text(json.dumps(audit))
    b.write_text(json.dumps(bench))
    assert check_audit.check(str(a), str(b)) == 0

    # Runtime drifts by one launch -> the gate trips.
    rows[0]["value"] += 1
    b.write_text(json.dumps(bench))
    assert check_audit.check(str(a), str(b)) == 1

    # Non-numeric runtime value -> clear failure, not a crash.
    rows[0]["value"] = "n/a"
    b.write_text(json.dumps(bench))
    assert check_audit.check(str(a), str(b)) == 1


def test_check_launches_rejects_non_numeric_and_nan(capsys):
    from benchmarks.check_launches import _numeric
    assert _numeric({"value": 3}, "t", "n") == 3.0
    assert _numeric({"value": "3.5"}, "t", "n") == 3.5
    assert _numeric({"value": "oops"}, "t", "n") is None
    assert _numeric({"value": float("nan")}, "t", "n") is None
    assert _numeric({"value": None}, "t", "n") is None
    out = capsys.readouterr().out
    assert "not numeric" in out and "not finite" in out
