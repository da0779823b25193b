"""Device time per program call of the fm kernel."""

from benchmarks.chip.trace import kernel_seconds, per_call_ms


def read(ctx):
    return per_call_ms(ctx, kernel_seconds(ctx["reduced"], "fm"))
