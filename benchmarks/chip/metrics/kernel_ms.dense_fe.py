"""Device time per program call of the dense_fe kernel."""

from benchmarks.chip.trace import kernel_seconds, per_call_ms


def read(ctx):
    return per_call_ms(ctx, kernel_seconds(ctx["reduced"], "dense_fe"))
