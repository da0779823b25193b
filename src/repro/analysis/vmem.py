"""Static VMEM residency accounting per ``pallas_call`` launch.

The paper's FPGA flow proves BRAM fit at synthesis; the TPU analog is
the per-core VMEM a launch keeps resident: one block per operand per
grid step (input AND output BlockSpecs), with ``pl.Element`` windows
(the halo'd row bands of the dense stencils) counted at their full
block shape — halos included, exactly the bytes the kernel touches.
Scalar-prefetch operands live in SMEM and are not counted.  ``launch_vmem`` reads the traced
``grid_mapping.block_mappings`` of a :class:`~.jaxpr_walk.PallasSite`
and reports:

  * ``resident_bytes`` — Σ blocks × itemsize with ONE buffer per
    operand: the floor any schedule must hold resident (this is the
    accounting behind the repro's 7.91 MiB/pair @720p f32 / 1.98 MiB
    uint8 numbers), and the number the budget gates;
  * ``pipelined_bytes`` — the same with each operand's pipeline
    buffer count (2 by default, 1 for ``pl.Buffered(1)`` resident
    slabs), the steady-state working set of the pipelined schedule,
    reported for context but NOT gated.

The default budget is 16 MiB — one TPU core's VMEM.  A 1080p float32
FM slab pair (≈17.1 MiB) correctly fails it; the 720p matrix passes.
"""

from __future__ import annotations

import dataclasses
import math

from repro.analysis.jaxpr_walk import PallasSite

__all__ = ["DEFAULT_VMEM_BUDGET", "BlockUsage", "LaunchVmem",
           "launch_vmem"]

# One TPU core's vector memory.  Configurable per call — the CLI
# exposes --vmem-budget-mib.
DEFAULT_VMEM_BUDGET = 16 * 1024 * 1024


@dataclasses.dataclass(frozen=True)
class BlockUsage:
    """One operand's per-grid-step resident block."""

    origin: str               # 'args[i]' / 'outputs[i]' per the trace
    block_shape: tuple        # as written in the BlockSpec (halos incl.)
    dtype: str
    mode: str                 # 'Blocked' | 'Element'
    nbytes: int
    buffers: int = 2          # pipeline buffers the schedule allocates


@dataclasses.dataclass(frozen=True)
class LaunchVmem:
    """Residency verdict for one launch site."""

    kernel: str
    grid: tuple
    blocks: tuple[BlockUsage, ...]
    resident_bytes: int       # 1 buffer per operand (gated)
    pipelined_bytes: int      # pipeline buffers per operand (reported)
    budget: int

    @property
    def ok(self) -> bool:
        return self.resident_bytes <= self.budget


def block_dim(d) -> int:
    """Extent of one block dim: an int, a ``pl.Blocked`` /
    ``pl.Element`` (its ``block_size``), or squeezed (one row)."""
    if isinstance(d, int):
        return d
    return int(getattr(d, "block_size", 1) or 1)


def block_mode(bm) -> str:
    """'Element' when the block is indexed by element offsets (Pallas
    requires all dims or none to be), else 'Blocked'."""
    return ("Element" if any(type(d).__name__ == "Element"
                             for d in bm.block_shape) else "Blocked")


def _usage(bm) -> BlockUsage:
    dtype = bm.array_aval.dtype
    shape = tuple(block_dim(d) for d in bm.block_shape)
    buffers = getattr(bm.pipeline_mode, "buffer_count", None) or 2
    return BlockUsage(
        origin=str(getattr(bm, "origin", "?")),
        block_shape=shape,
        dtype=str(dtype),
        mode=block_mode(bm),
        nbytes=math.prod(shape) * dtype.itemsize,
        buffers=int(buffers))


def launch_vmem(site: PallasSite,
                budget: int = DEFAULT_VMEM_BUDGET) -> LaunchVmem:
    """Resident-bytes accounting for one ``pallas_call``: every input
    and output BlockSpec contributes one block per grid step."""
    gm = site.grid_mapping
    blocks = tuple(_usage(bm) for bm in gm.block_mappings)
    resident = sum(b.nbytes for b in blocks)
    return LaunchVmem(
        kernel=site.name,
        grid=tuple(int(g) for g in gm.grid),
        blocks=blocks,
        resident_bytes=resident,
        pipelined_bytes=sum(b.nbytes * b.buffers for b in blocks),
        budget=int(budget))
