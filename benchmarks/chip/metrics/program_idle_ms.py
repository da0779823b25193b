"""Device-idle time per frame while the host is inside one of the
program's ``repro.*`` spans: the part of the idle the program owns, as
against the caller's fetch."""

from benchmarks.chip.program_trace import for_ctx


def read(ctx):
    got, calls = for_ctx(ctx), ctx["window"].calls
    return None if got is None or not calls else \
        1e3 * got["program_idle_s"] / calls
