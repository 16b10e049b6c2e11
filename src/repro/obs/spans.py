"""Pipeline span tracing: per-stage host time for the serving path.

The serving pipeline is a fixed sequence of stages (DESIGN.md §12):

  * ``admit`` — one ``submit`` into the router;
  * ``take``, ``coalesce``, ``h2d``, ``dispatch`` — per dispatched span:
    pop its rows from the router, pack the micro-batches, copy them to the
    device, enqueue the jitted step (jax dispatch is asynchronous: the
    device runs after this returns);
  * ``device_wait``, ``d2h`` — on the drain copy thread, per dispatched
    span: wait until the step's outputs are ready, then copy them to host
    memory;
  * ``flush_wait`` — the caller blocked on the copy thread's results;
  * ``emit`` — the per-tenant grouping of the drained rows and the
    observation of their latency;
  * ``group`` — the service's local-id mapping and union-find.

Each stage's seconds accumulate into the shared
:class:`~repro.obs.registry.MetricsRegistry` under ``span/<stage>/time_s``
(a float counter) and ``span/<stage>/calls``.  The caller thread's stages
of one ``flush`` tile it; the copy thread's two overlap ``flush_wait``.

Every span is also a ``jax.profiler.TraceAnnotation`` named
``sssj.<stage>`` whose ``dispatch`` argument is the ordinal of the
dispatched span the work belongs to (the value ``runtime/spans_dispatched``
takes for it), so under a profiler trace the host stages sit on the device
operations' timeline and a copy-thread span names the dispatch it drains.
With no trace active an annotation costs one check.  Time is taken inside
the annotation, so recorded seconds leave out the annotation's own cost.
"""

from __future__ import annotations

import time
from typing import Optional, Tuple

from jax.profiler import TraceAnnotation

from .registry import Counter, MetricsRegistry

__all__ = ["PIPELINE_STAGES", "Span", "SpanTracer"]

# canonical serving-pipeline stage names, in pipeline order
PIPELINE_STAGES: Tuple[str, ...] = (
    "admit", "take", "coalesce", "h2d", "dispatch", "device_wait", "d2h",
    "flush_wait", "emit", "group",
)

_NAMES = {s: f"sssj.{s}" for s in PIPELINE_STAGES}


class Span:
    """``with Span(stage, dispatch) as s: …`` — the annotation
    ``sssj.<stage>`` around a timing of the block; ``s.seconds`` holds the
    duration once the block exits.  Records nothing by itself, so it is
    safe on any thread; :meth:`SpanTracer.span` adds the registry."""

    __slots__ = ("_ann", "_t0", "_into", "seconds")

    def __init__(
        self,
        stage: str,
        dispatch: int,
        into: Optional[Tuple[Counter, Counter]] = None,
    ) -> None:
        self._ann = TraceAnnotation(_NAMES[stage], dispatch=dispatch)
        self._into = into
        self.seconds = 0.0

    def __enter__(self) -> "Span":
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        if self._into is not None:
            calls, total = self._into
            calls.inc(1)
            total.inc(self.seconds)


class SpanTracer:
    """Accumulate per-stage host time into a metrics registry.

    The counters of every stage in :data:`PIPELINE_STAGES` are created
    up front (the schema holds them all, at zero until a stage runs) and
    their handles kept, so a span does no name lookup."""

    def __init__(self, registry: MetricsRegistry, prefix: str = "span") -> None:
        self.registry = registry
        self._counters = {
            s: (registry.counter(f"{prefix}/{s}/calls"),
                registry.counter(f"{prefix}/{s}/time_s"))
            for s in PIPELINE_STAGES
        }

    def record(self, stage: str, seconds: float) -> None:
        """Record one span timed elsewhere (the copy thread's stages,
        recorded on the caller thread so the registry has one writer)."""
        calls, total = self._counters[stage]
        calls.inc(1)
        total.inc(float(seconds))

    def span(self, stage: str, dispatch: int) -> Span:
        """Time and annotate a stage:
        ``with tracer.span("coalesce", n): …``."""
        return Span(stage, dispatch, self._counters[stage])
