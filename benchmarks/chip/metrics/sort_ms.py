"""Device time per program call of the sort operations (the top-K of the
FAST score maps, the pose solve's residual ranking)."""

from benchmarks.chip.trace import per_call_ms, seconds


def read(ctx):
    return per_call_ms(ctx, seconds(ctx["reduced"],
                                    lambda n, op: op["sort"]))
