"""Grid / index-map bounds proof: every block stays inside its slab.

Each BlockSpec's ``index_map`` is a tiny traced function from grid
indices to a block position; pallas trusts it.  A map that walks a
block past the padded slab edge (an off-by-one in the halo arithmetic,
a slab index that ignores the shape table) reads garbage — silently on
interpret-mode CPU.  This checker closes that gap abstractly: it
evaluates every ``index_map_jaxpr`` over its ENTIRE grid with
``jax.core.eval_jaxpr`` (pure python, no compilation — grids here are a
few hundred points; scalar-prefetch operands, which these index maps do
not read, are fed zeros) and proves, per dimension:

  * a blocked dim — the returned BLOCK index ``b`` satisfies
    ``0 <= b`` and ``b * block < dim`` (the block's first element is
    inside the array; pallas pads the tail block);
  * a ``pl.Element`` dim — the returned ELEMENT start ``s`` satisfies
    ``-lo <= s`` and ``s + block <= dim + hi`` where ``(lo, hi)`` is
    the dim's declared padding (none by default) — halo windows must
    sit entirely inside the pre-padded slab.
"""

from __future__ import annotations

import dataclasses
import itertools

import jax.core as jcore
import numpy as np

from repro.analysis.jaxpr_walk import PallasSite
from repro.analysis.vmem import block_dim

__all__ = ["BoundsViolation", "check_bounds"]

# Violations are truncated per block-mapping: one broken index map can
# fail at thousands of grid points and they all say the same thing.
_MAX_VIOLATIONS = 5


@dataclasses.dataclass(frozen=True)
class BoundsViolation:
    kernel: str
    origin: str
    grid_point: tuple
    dim: int
    message: str


def _operand_zeros(avals) -> list:
    """Zero stand-ins for the scalar-prefetch operands of an index map."""
    out = []
    for aval in avals:
        inner = getattr(aval, "inner_aval", aval)
        out.append(np.zeros(inner.shape, inner.dtype))
    return out


def _check_mapping(site: PallasSite, bm, grid) -> list[BoundsViolation]:
    closed = bm.index_map_jaxpr
    dims = tuple(int(d) for d in bm.array_aval.shape)
    origin = str(getattr(bm, "origin", "?"))
    invars = closed.jaxpr.invars
    if len(invars) < len(grid):
        return [BoundsViolation(
            site.name, origin, (), -1,
            f"index_map takes {len(invars)} args but the "
            f"grid has rank {len(grid)} — cannot evaluate")]
    operands = _operand_zeros(v.aval for v in invars[len(grid):])
    out: list[BoundsViolation] = []
    for point in itertools.product(*(range(g) for g in grid)):
        idx = jcore.eval_jaxpr(closed.jaxpr, closed.consts,
                               *(np.int32(p) for p in point), *operands)
        for d, raw in enumerate(idx):
            v = int(raw)
            bd = bm.block_shape[d]
            bs = block_dim(bd)
            dim = dims[d] if d < len(dims) else 1
            if type(bd).__name__ == "Element":
                lo, hi = (int(p) for p in bd.padding)
                if v < -lo or v + bs > dim + hi:
                    out.append(BoundsViolation(
                        site.name, origin, point, d,
                        f"element window [{v}, {v + bs}) escapes "
                        f"dim {d} of extent {dim} "
                        f"(padding ({lo}, {hi}))"))
            elif v < 0 or v * bs >= dim:
                out.append(BoundsViolation(
                    site.name, origin, point, d,
                    f"block index {v} (block {bs}) escapes dim "
                    f"{d} of extent {dim}"))
            if len(out) >= _MAX_VIOLATIONS:
                return out
    return out


def check_bounds(site: PallasSite) -> list[BoundsViolation]:
    """Prove every BlockSpec of one launch in-bounds over its full
    grid; returns the (truncated) list of violations, empty = proven."""
    grid = tuple(int(g) for g in site.grid_mapping.grid)
    out: list[BoundsViolation] = []
    for bm in site.grid_mapping.block_mappings:
        out.extend(_check_mapping(site, bm, grid))
        if len(out) >= _MAX_VIOLATIONS:
            break
    return out[:_MAX_VIOLATIONS]
