"""Device time per program call of every operation that is not a Mosaic
kernel: the jnp glue (pyramid, top-K, gathers, depth, pose solve)."""

from benchmarks.chip.trace import per_call_ms, seconds


def read(ctx):
    s = seconds(ctx["reduced"], lambda n, op: not op["kernel"])
    return per_call_ms(ctx, s)
