"""Observability: metrics registry + request tracing (docs/observability.md).

The unit components share is :class:`Obs` — a (registry, tracer, label)
bundle.  The launcher builds ONE enabled bundle and hands each replica
a labelled view (``obs.labelled("r1")``) so every serve series carries
a ``replica`` label while all replicas write to the same registry (this
is what makes the frontend's ``/stats`` aggregation race-free: worker
threads bump atomic registry counters instead of a per-engine dict the
server thread reads concurrently).  A bare engine or pool with no
bundle supplied builds its own metrics-only one; ``Obs.disabled()``
turns every call site into a no-op.
"""

from __future__ import annotations

from dataclasses import dataclass, replace

from .metrics import (COUNT_BUCKETS, LATENCY_BUCKETS, NULL_REGISTRY,
                      MetricsRegistry, counting_traces, exp_buckets,
                      note_scan_path, note_trace)
from .trace import NULL_TRACER, Tracer

__all__ = [
    "Obs", "MetricsRegistry", "Tracer",
    "NULL_REGISTRY", "NULL_TRACER",
    "LATENCY_BUCKETS", "COUNT_BUCKETS", "exp_buckets",
    "counting_traces", "note_scan_path", "note_trace",
]


@dataclass(frozen=True)
class Obs:
    """Shared observability bundle: one registry + tracer + the label
    identifying the emitting replica/component."""

    metrics: MetricsRegistry
    tracer: Tracer
    label: str = "r0"

    @classmethod
    def create(cls, metrics: bool = True, trace: bool = False,
               label: str = "r0") -> "Obs":
        return cls(metrics=MetricsRegistry(enabled=metrics),
                   tracer=Tracer(enabled=trace), label=label)

    @classmethod
    def disabled(cls) -> "Obs":
        return cls(metrics=NULL_REGISTRY, tracer=NULL_TRACER)

    @property
    def enabled(self) -> bool:
        return self.metrics.enabled or self.tracer.enabled

    def labelled(self, label: str) -> "Obs":
        """Same registry/tracer, different emitting label."""
        return replace(self, label=label)
