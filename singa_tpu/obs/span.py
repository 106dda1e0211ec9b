"""``obs.span``: the one way host code names a stretch of its work.

    with obs.span("sched.admit", tick=7, rid=3, slot=0):
        ...

A span is written to two places. Always to the profiler: it enters
``jax.profiler.TraceAnnotation("singa/" + name, **attrs)``, so that in
a trace (``profile@K:steps=N`` on a job, ``--trace 1`` on a benchmark
cell) the host's work lies on the same clock as the device's operations
and an idle gap of the device can be named by what the host did in it.
Outside a profiler session that is one flag test; the attributes go in
as keywords, so nothing is formatted while no trace runs. And, where the
caller has a ``FlightRecorder``, ``Span.record`` writes the finished
span into the operator's per-rank log with the two clocks that log
carries (``time.time()`` for merging ranks, ``time.perf_counter()`` for
the duration).

``attrs`` must be host scalars: the recorder's rule (obs/recorder.py),
and a device array would make the annotation wait for the device.
"""

from __future__ import annotations

import time

import jax


class Span:
    """One named stretch of host work. ``t0_wall`` and ``dur`` (seconds)
    are set once the ``with`` block is left.

    ``nested=False`` is for a stretch that does not nest in its thread's
    other spans (a request's life from admission to retirement crosses
    ticks): the profiler's thread lines hold properly nested events, so
    such a span gets no annotation and lives in the recorder's log
    alone."""

    __slots__ = ("name", "attrs", "nested", "t0_wall", "t0", "dur", "_ann")

    def __init__(self, name: str, nested: bool = True, **attrs):
        self.name, self.attrs, self.nested = name, attrs, nested
        self.t0_wall = self.t0 = self.dur = 0.0
        self._ann = None

    def start(self) -> "Span":
        if self.nested:
            self._ann = jax.profiler.TraceAnnotation(
                "singa/" + self.name, **self.attrs
            )
            self._ann.__enter__()
        self.t0_wall, self.t0 = time.time(), time.perf_counter()
        return self

    def stop(self) -> None:
        self.dur = time.perf_counter() - self.t0
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None

    def note(self, **attrs) -> None:
        """Attributes known only once the work is done (tokens emitted),
        added before the span is left."""
        self.attrs.update(attrs)
        if self._ann is not None:
            self._ann.set_metadata(**attrs)

    __enter__ = start

    def __exit__(self, *exc) -> None:
        self.stop()

    def record(self, recorder, name: str, *, track: str = "phases",
               steps: int | None = None) -> None:
        """The finished span into ``recorder``'s log under the log's own
        vocabulary (``name`` on ``track``, covering ``steps`` steps or
        tokens). No recorder, no record."""
        if recorder is not None:
            recorder.record_span(
                name, self.t0_wall, self.dur, track=track, steps=steps
            )


#: ``with obs.span("sched.tick", tick=3):``
span = Span
