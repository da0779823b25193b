"""Device time per frame of every op under the program's ``canvas``
named scope: the per-level pad and concatenate that put every camera x
level slab on the common canvas of the dense FE and describe kernels."""

from benchmarks.chip.program_trace import scope_ms


def read(ctx):
    return scope_ms(ctx, "canvas")
