"""Trace-time static analysis of the visual system (PR 10).

The paper proves its resource claims at synthesis time — BRAM/DSP
budgets hold before the bitstream ever runs.  This package is that
discipline for the jax_pallas repro: every ``VisualSystem`` entry point
is traced ABSTRACTLY (``jax.make_jaxpr`` over shape/dtype structs — no
data, no kernel execution, no TPU) and the traced program is audited:

  ``jaxpr_walk``   find every ``pallas_call`` with its static trip
                   multiplier (scan × length, cond worst-case branch,
                   while = unbounded) — the launch-budget proof
  ``vmem``         per-launch resident bytes from the BlockSpecs/grid
                   (Element halo windows included) vs a per-core budget
  ``dtype_flow``   silent-widening lint over kernel-body jaxprs
                   (float in an all-integer kernel, float64 anywhere,
                   weak-type promotions)
  ``bounds``       every BlockSpec index_map evaluated over its FULL
                   grid — blocks proven inside the padded slab
  ``hostlint``     AST lint over ``repro.serving`` hot paths (blocking
                   calls, per-call jax.jit retrace risk, watchdog
                   thread touching shared state without a lock)
  ``matrix``       the audited entry × precision × masked × localize ×
                   fleet matrix, reconciled 1:1 with the runtime
                   ``launch_gate/*`` benchmark rows
  ``report``       assembles ``AUDIT.json`` for the CI gate
                   (``benchmarks/check_audit.py``)

Run: ``PYTHONPATH=src python -m repro.analysis [--quick]``.
"""

from repro.analysis.bounds import BoundsViolation, check_bounds
from repro.analysis.dtype_flow import DtypeViolation, check_kernel_dtypes
from repro.analysis.hostlint import (HostLintFinding, lint_serving,
                                     lint_source)
from repro.analysis.jaxpr_walk import (LaunchCount, PallasSite,
                                       count_launches, pallas_sites)
from repro.analysis.matrix import (MATRIX, EntrySpec, TracedEntry,
                                   trace_entry, trace_matrix)
from repro.analysis.report import audit_entry, run_audit, write_report
from repro.analysis.vmem import (DEFAULT_VMEM_BUDGET, LaunchVmem,
                                 launch_vmem)

__all__ = [
    "BoundsViolation", "check_bounds",
    "DtypeViolation", "check_kernel_dtypes",
    "HostLintFinding", "lint_serving", "lint_source",
    "LaunchCount", "PallasSite", "count_launches", "pallas_sites",
    "MATRIX", "EntrySpec", "TracedEntry", "trace_entry", "trace_matrix",
    "audit_entry", "run_audit", "write_report",
    "DEFAULT_VMEM_BUDGET", "LaunchVmem", "launch_vmem",
]
