"""Host time per frame from the ``process_frame`` call until it returns,
before the output is fetched: tracing-cache lookups, H2D of the frame,
and the dispatch of the frame and localization programs."""

from benchmarks.chip.harness import mean_ms


def read(ctx):
    return mean_ms(ctx["window"].spans.get("dispatch"))
