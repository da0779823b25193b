"""Dtype-flow lint over kernel-body jaxprs: silent widenings.

The ``precision="uint8"`` datapath's whole claim is that slabs stay in
integer fixed-point end-to-end (uint8 pyramid, int32 blur accumulation,
int16 FAST scores, int8 descriptor selection) — a float32 intermediate
silently re-widening the working set would void the 4x VMEM cut while
every launch-count gate still passes.  This lint walks each traced
kernel BODY (the ``jaxpr`` param of the ``pallas_call`` eqn, including
nested ``pjit`` sub-jaxprs) and flags:

  * ``float64-leak`` — any float64 value anywhere, every precision:
    nothing in the pipeline is specified in double, so an f64 aval is
    always an accidental promotion (x64 mode would silently double
    every buffer);
  * ``float-in-integer-kernel`` — a floating-point intermediate inside
    a kernel whose operands (all input AND output blocks) are integer.
    Integer-in/integer-out is exactly where the fixed-point contract
    holds: any float aval between them is a silent widening (the class
    of bug where a literal ``0.5 * x`` sneaks into the int32 blur).
    Kernels with a legitimate float operand (descriptor theta/meta,
    depth) are exempt by construction — the contract is derived from
    the traced operand dtypes, not from a name list;
  * ``weak-float-promotion`` — the float intermediate is weakly typed
    (a bare python float literal promoted the lattice), reported as its
    own class because the fix is different: annotate the constant, not
    the op.
"""

from __future__ import annotations

import dataclasses

import jax.extend.core as jcore
import jax.numpy as jnp

from repro.analysis.jaxpr_walk import PallasSite

__all__ = ["DtypeViolation", "check_kernel_dtypes"]


@dataclasses.dataclass(frozen=True)
class DtypeViolation:
    kernel: str
    rule: str                 # 'float64-leak' | 'float-in-integer-kernel'
    #                         | 'weak-float-promotion'
    dtype: str
    primitive: str            # eqn that produced the value ('invar' for
    #                         kernel inputs)
    detail: str


def _avals(jaxpr: jcore.Jaxpr):
    """Yield (aval, primitive_name) for every value produced in the
    kernel body, recursing into sub-jaxprs (pjit etc.)."""
    for var in jaxpr.invars + jaxpr.constvars:
        yield var.aval, "invar"
    stack = [jaxpr]
    while stack:
        j = stack.pop()
        for eqn in j.eqns:
            for var in eqn.outvars:
                yield var.aval, eqn.primitive.name
            for val in eqn.params.values():
                vals = val if isinstance(val, (tuple, list)) else (val,)
                for v in vals:
                    if isinstance(v, jcore.ClosedJaxpr):
                        stack.append(v.jaxpr)
                    elif isinstance(v, jcore.Jaxpr):
                        stack.append(v)


def _dtype_of(aval):
    # Works for ShapedArray and pallas MemRef avals alike; anything
    # without a dtype (tokens) is skipped.
    return getattr(aval, "dtype", None)


def _integer_contract(site: PallasSite) -> bool:
    """True when EVERY traced operand block (inputs and outputs) of the
    launch is integer/bool — the fixed-point contract then holds for
    the whole kernel body."""
    dtypes = [bm.array_aval.dtype
              for bm in site.grid_mapping.block_mappings]
    return bool(dtypes) and not any(
        jnp.issubdtype(d, jnp.floating) for d in dtypes)


def check_kernel_dtypes(site: PallasSite) -> list[DtypeViolation]:
    """All dtype-flow violations in one launch's kernel body."""
    out: list[DtypeViolation] = []
    int_only = _integer_contract(site)
    for aval, prim in _avals(site.kernel_jaxpr):
        dt = _dtype_of(aval)
        if dt is None:
            continue
        if dt == jnp.float64:
            out.append(DtypeViolation(
                site.name, "float64-leak", str(dt), prim,
                "float64 value traced inside a kernel — nothing in the "
                "pipeline is specified in double precision"))
            continue
        if int_only and jnp.issubdtype(dt, jnp.floating):
            weak = bool(getattr(aval, "weak_type", False))
            rule = ("weak-float-promotion" if weak
                    else "float-in-integer-kernel")
            detail = (
                "weakly-typed float (bare python literal) promoted "
                "inside an all-integer kernel — annotate the constant"
                if weak else
                "float intermediate in a kernel whose operands are all "
                "integer: the fixed-point contract is silently widened")
            out.append(DtypeViolation(site.name, rule, str(dt), prim,
                                      detail))
    return out
