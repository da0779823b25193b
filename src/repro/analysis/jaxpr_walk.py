"""Closed-jaxpr walking: find every ``pallas_call`` with its trip count.

This is the trace-time analog of ``launch/hlo_stats.py``'s HLO call
graph: instead of parsing compiled HLO text, we walk the CLOSED jaxpr of
an entry point (``jax.make_jaxpr`` over abstract shapes — no data, no
execution) and enumerate every ``pallas_call`` equation together with a
static execution multiplier:

  * ``pjit`` / ``custom_jvp`` / ``custom_vjp`` / other call-like
    primitives are transparent (multiplier unchanged),
  * ``scan`` multiplies by its static ``length`` (nested scans multiply,
    exactly like nested while bodies in ``hlo_stats.analyze``),
  * ``while`` has no static trip count: launches inside its body are
    UNBOUNDED — recorded as such so a budget check can refuse to prove
    anything rather than silently under-count,
  * ``cond`` branches are alternatives, not a sequence: the launch count
    of a cond is the MAX over its branches (the budget must hold on the
    worst-case path), while ``sites`` still reports every branch's
    kernels so resource checks cover all of them.

The result is the number the runtime ``ops.launch_audit`` counter
observes while tracing — proven from the program structure instead of
observed from a counter, so CI can require the two to agree exactly
(``benchmarks/check_audit.py``).
"""

from __future__ import annotations

import dataclasses

import jax.extend.core as jcore

__all__ = ["PallasSite", "LaunchCount", "pallas_sites", "count_launches"]


@dataclasses.dataclass(frozen=True)
class PallasSite:
    """One ``pallas_call`` equation found in a traced program.

    ``mult`` is the static number of times the launch executes per call
    of the traced entry (scan trip counts multiplied along the path);
    ``None`` means the site sits inside a ``while`` body and has no
    static bound.  ``path`` is the chain of enclosing control-flow
    primitives, for error messages."""

    eqn: jcore.JaxprEqn
    mult: int | None
    path: tuple[str, ...]

    @property
    def name(self) -> str:
        info = self.eqn.params.get("name_and_src_info")
        return getattr(info, "name", None) or "<pallas_call>"

    @property
    def src(self) -> str:
        return str(self.eqn.params.get("name_and_src_info", ""))

    @property
    def grid_mapping(self):
        return self.eqn.params["grid_mapping"]

    @property
    def kernel_jaxpr(self) -> jcore.Jaxpr:
        body = self.eqn.params["jaxpr"]
        return body.jaxpr if isinstance(body, jcore.ClosedJaxpr) else body


@dataclasses.dataclass(frozen=True)
class LaunchCount:
    """Static launch count of a traced program: ``total`` bounded
    launches plus the sites that could not be bounded (inside ``while``
    bodies).  ``bounded`` is False when any unbounded site exists — a
    budget can then not be proven."""

    total: int
    unbounded_sites: tuple[PallasSite, ...] = ()

    @property
    def bounded(self) -> bool:
        return not self.unbounded_sites


def _sub_jaxprs(val):
    """Yield every (Closed)Jaxpr living in one eqn param value."""
    vals = val if isinstance(val, (tuple, list)) else (val,)
    for v in vals:
        if isinstance(v, jcore.ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, jcore.Jaxpr):
            yield v


def _mul(mult: int | None, k: int) -> int | None:
    return None if mult is None else mult * k


def pallas_sites(closed: jcore.ClosedJaxpr) -> list[PallasSite]:
    """Every ``pallas_call`` in ``closed`` (recursively), with trip
    multipliers.  Sites on all ``cond`` branches are reported (resource
    checks must hold on every path)."""
    out: list[PallasSite] = []

    def walk(jaxpr: jcore.Jaxpr, mult: int | None,
             path: tuple[str, ...]) -> None:
        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            if prim == "pallas_call":
                out.append(PallasSite(eqn, mult, path))
                continue
            if prim == "scan":
                k = int(eqn.params.get("length", 1))
                for sub in _sub_jaxprs(eqn.params.get("jaxpr")):
                    walk(sub, _mul(mult, k), path + (f"scan[{k}]",))
                continue
            if prim == "while":
                for key in ("body_jaxpr", "cond_jaxpr"):
                    for sub in _sub_jaxprs(eqn.params.get(key)):
                        walk(sub, None, path + ("while",))
                continue
            if prim == "cond":
                branches = eqn.params.get("branches", ())
                for b, branch in enumerate(branches):
                    for sub in _sub_jaxprs(branch):
                        walk(sub, mult, path + (f"cond.{b}",))
                continue
            # Generic call-like primitive (pjit, custom_jvp_call, ...):
            # descend into every jaxpr-valued param, multiplier unchanged.
            for val in eqn.params.values():
                for sub in _sub_jaxprs(val):
                    walk(sub, mult, path + (prim,))

    walk(closed.jaxpr, 1, ())
    return out


def count_launches(closed: jcore.ClosedJaxpr) -> LaunchCount:
    """Static launch count of ``closed``: scan bodies multiply by their
    trip count, cond takes the worst-case branch, while bodies are
    unbounded.  Matches what ``ops.launch_audit`` observes at trace time
    for bounded programs."""

    def walk(jaxpr: jcore.Jaxpr, mult: int | None,
             path: tuple[str, ...]):
        total = 0
        unbounded: list[PallasSite] = []
        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            if prim == "pallas_call":
                if mult is None:
                    unbounded.append(PallasSite(eqn, None, path))
                else:
                    total += mult
                continue
            if prim == "scan":
                k = int(eqn.params.get("length", 1))
                for sub in _sub_jaxprs(eqn.params.get("jaxpr")):
                    t, u = walk(sub, _mul(mult, k), path + (f"scan[{k}]",))
                    total += t
                    unbounded.extend(u)
                continue
            if prim == "while":
                for key in ("body_jaxpr", "cond_jaxpr"):
                    for sub in _sub_jaxprs(eqn.params.get(key)):
                        _, u = walk(sub, None, path + ("while",))
                        unbounded.extend(u)
                continue
            if prim == "cond":
                worst = 0
                for b, branch in enumerate(eqn.params.get("branches", ())):
                    bt = 0
                    for sub in _sub_jaxprs(branch):
                        t, u = walk(sub, mult, path + (f"cond.{b}",))
                        bt += t
                        unbounded.extend(u)
                    worst = max(worst, bt)
                total += worst
                continue
            for val in eqn.params.values():
                for sub in _sub_jaxprs(val):
                    t, u = walk(sub, mult, path + (prim,))
                    total += t
                    unbounded.extend(u)
        return total, unbounded

    total, unbounded = walk(closed.jaxpr, 1, ())
    return LaunchCount(total, tuple(unbounded))
