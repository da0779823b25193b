"""Driver ``stream``: one rig, closed loop, frame by frame.

The robot's own frontend (and backend, where the configuration
localizes) at batch 1: each frame's host uint8 array goes to
``VisualSystem.process_frame``, and the rig's next frame is handed over
once the whole output is on the host (``jax.device_get``).  The rig
replays a ring of ``ring_frames`` rendered frames; the pose chain is
reset where the ring wraps, so every lap asks for the same answers.
Latency runs from the ``process_frame`` call to the end of the fetch.
"""

from __future__ import annotations

import time

import jax

from benchmarks.chip import check, traffic
from benchmarks.chip.harness import Window, span

ENTRIES = ("process_frame", "localize_frame")


class Driver:
    def __init__(self, cell, seed: int, make_session) -> None:
        self.cell = cell
        self.ring = int(cell.traffic["ring_frames"])
        self.frames = traffic.render(cell.config, cell.traffic["scene"],
                                     self.ring, seed)
        self.vs = make_session(cell.config)
        self.localize = bool(cell.config["localize"])
        # Warm-up: two laps compile (or load) every program of the
        # window and settle the host path; the window starts a new lap.
        for i in range(2 * self.ring):
            self._frame(i)

    def _frame(self, i: int):
        out = self.vs.process_frame(self.frames.images[i % self.ring])
        host = jax.device_get(out)
        if self.localize and i % self.ring == self.ring - 1:
            self.vs.reset_localization()
        return host

    def window(self, seconds: float, traced: bool) -> Window:
        images, ring, vs = self.frames.images, self.ring, self.vs
        lat, starts, answers, dispatch, fetch = [], [], [], [], []
        self._traces = self._trace_total()
        i = 0
        t0 = time.perf_counter()
        with span("bench.window", traced):
            while True:
                a = time.perf_counter()
                if a - t0 >= seconds and i % ring == 0:
                    break
                with span("bench.dispatch", traced):
                    out = vs.process_frame(images[i % ring])
                b = time.perf_counter()
                with span("bench.fetch", traced):
                    host = jax.device_get(out)
                c = time.perf_counter()
                if self.localize and i % ring == ring - 1:
                    vs.reset_localization()
                lat.append(c - a)
                starts.append(a - t0)
                dispatch.append(b - a)
                fetch.append(c - b)
                answers.append((i % ring, host))
                i += 1
        t1 = time.perf_counter()
        slowest = max(range(i), key=lat.__getitem__, default=0)
        return Window(t0=t0, t1=t1, attempted=i, failed=0, latency_s=lat,
                      answers=answers,
                      spans={"dispatch": dispatch, "fetch": fetch},
                      calls=i, counters={
                          "frames": i,
                          "slowest_frame_at_s": starts[slowest] if i else 0.0})

    def _trace_total(self) -> int:
        return sum(self.vs.trace_count(k) for k in ENTRIES)

    def retraces(self) -> int:
        return self._trace_total() - self._traces

    def close(self) -> None:
        del self.vs

    def reference(self) -> dict:
        """Ring position -> the plain reference's answer, the pose chain
        reset at position 0 as the window resets it."""
        return check.reference_answers(self.cell.config, self.frames.images,
                                       chain=self.localize)
