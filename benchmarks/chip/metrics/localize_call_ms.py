"""Host time per frame inside the program's ``repro.localize_call``
span: the previous state's lookup, the localize program's call and the
carry of the next state (``localization.state_from``)."""

from benchmarks.chip.program_trace import span_ms


def read(ctx):
    return span_ms(ctx, "repro.localize_call")
