"""Device time per frame of every op under the program's ``localize``
named scope."""

from benchmarks.chip.program_trace import scope_ms


def read(ctx):
    return scope_ms(ctx, "localize")
