"""Unified observability substrate: spans + metrics, dependency-free.

Two pillars, both pure stdlib (no jax, no numpy) so every layer of the
stack — compiler, executor, scheduler, serving front ends — can depend
on them without import cycles or accelerator-backend coupling:

  * ``trace`` — a thread-safe span tracer with an injectable monotonic
    clock and a bounded ring buffer, exporting Chrome trace-event JSON
    (complete/instant/async/counter events) loadable in Perfetto or
    ``chrome://tracing``.  One :class:`~repro.obs.trace.Tracer` threaded
    through ``compile_network`` -> ``InferenceService`` puts compile
    phases, serving steps and request lifecycles on a single shared
    timeline.  Its spans also land on ``jax.profiler``'s host timeline
    whenever a profiler records, beside the device's operations, which
    the jitted forward names by ``jax.named_scope`` (``<layer>/<stage>``).
  * ``metrics`` — counters, gauges, and fixed-bucket histograms with
    exact sample-backed percentiles, grouped in a process-global but
    resettable :class:`~repro.obs.metrics.MetricsRegistry`, with JSON
    snapshot and Prometheus text exposition.

The ring buffer is opt-in: a ``tracer=None`` default everywhere resolves
to the shared no-op :data:`~repro.obs.trace.NULL_TRACER`, which records
nothing and reads no clock; a tracer never reaches the jitted forward.
"""

from repro.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    Meter,
    MetricsRegistry,
    get_registry,
)
from repro.obs.trace import NULL_TRACER, Tracer, get_tracer, set_tracer

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "Meter",
    "MetricsRegistry",
    "get_registry",
    "NULL_TRACER",
    "Tracer",
    "get_tracer",
    "set_tracer",
]
