"""Observability: the flight recorder + trace plane (see recorder.py).

The reference's only observability was the Worker display line —
``Performance`` metric averages plus ``TimerInfo`` phase accumulators
printed every display interval (src/worker/worker.cc:350-386). This
package is the fleet-grade replacement: a per-rank structured event log
(every lifecycle event of the resilience runtime, buffered and flushed
at cadence boundaries), span-mode phase timers exported as Chrome-trace
tracks, and the ``profile@K`` trigger bracketing steps with
``jax.profiler`` traces. ``singa_tpu/tools/trace.py`` merges the
per-rank logs into one Perfetto-loadable ``trace.json``.

``obs.span`` (span.py) is the one call host code names its work with:
a ``singa/<name>`` annotation on the profiler's clock, beside the
device's operations, and a record in the flight recorder where one is
attached.
"""

from .recorder import FlightRecorder, config_hash, recorder_for_job
from .span import Span, span

__all__ = ["FlightRecorder", "Span", "config_hash", "recorder_for_job", "span"]
