"""Host time per frame inside the program's ``repro.frame_call`` span:
the frame program's jitted call, its argument H2D and its enqueue."""

from benchmarks.chip.program_trace import span_ms


def read(ctx):
    return span_ms(ctx, "repro.frame_call")
