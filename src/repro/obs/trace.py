"""Request-lifecycle and prune-stage tracing: Chrome-trace JSON export,
and live spans on the profiler's clock.

A :class:`Tracer` accumulates events in the Chrome trace event format
(the ``{"traceEvents": [...]}`` JSON that chrome://tracing and
Perfetto load).  The serve stack emits:

  - one async span per request uid (``ph: b``/``e``, ``id: uid``)
    bracketing submit → retire;
  - retroactive complete spans (``ph: X``) for admit-queue wait,
    prefill chunks, and each decode burst's dispatch→readback window —
    recorded from ``(start, end)`` monotonic stamps after the fact so
    the hot loop never touches the tracer mid-flight;
  - instant events (``ph: i``) for preemption (swap vs recompute),
    CoW page copies, prefix attach, and swap-in/out.

Timestamps are microseconds relative to the tracer's construction,
taken from ``time.monotonic()`` — only deltas matter to the viewer.
``pid`` is always 0; ``tid`` names the emitting replica/component so
each one gets its own track.  A disabled tracer (``NULL_TRACER``)
records nothing, which keeps token streams bit-identical with
tracing on or off (pinned by tests/test_obs.py).

Which calls reach the profiler: only the live :meth:`Tracer.span`.
Every span, enabled tracer or not, also opens a
``jax.profiler.TraceAnnotation`` of the same name whose metadata holds
``track`` and the span's ``args``, so inside a ``jax.profiler`` trace
it is a host event in the same ``.xplane.pb`` as the device ops, on
the same clock (the prune stages, core/pipeline.py).  With no profiler
session active that costs one TraceMe check.  The retroactive
:meth:`complete`, :meth:`instant` and the async request events are
Chrome-JSON only: they are stamped after the fact, which the profiler
cannot take.
"""

from __future__ import annotations

import json
import threading
import time
from contextlib import contextmanager
from typing import Dict, List, Optional

from jax.profiler import TraceAnnotation


class Tracer:
    """Thread-safe Chrome-trace event accumulator."""

    def __init__(self, enabled: bool = True):
        self.enabled = enabled
        self._lock = threading.Lock()
        self._events: List[dict] = []
        self._t0 = time.monotonic()
        self._tids: Dict[str, int] = {}

    # ------------------------------------------------------------ time
    def now(self) -> float:
        """Monotonic stamp for later retroactive spans."""
        return time.monotonic()

    def _us(self, t: float) -> float:
        return (t - self._t0) * 1e6

    def _tid(self, track: str) -> int:
        tid = self._tids.get(track)
        if tid is None:
            tid = len(self._tids)
            self._tids[track] = tid
            self._events.append({
                "name": "thread_name", "ph": "M", "pid": 0, "tid": tid,
                "args": {"name": track},
            })
        return tid

    def _emit(self, ev: dict, track: str) -> None:
        with self._lock:
            ev["pid"] = 0
            ev["tid"] = self._tid(track)
            self._events.append(ev)

    # ---------------------------------------------------------- events
    def complete(self, name: str, start: float, end: float, *,
                 track: str = "main",
                 args: Optional[dict] = None) -> None:
        """Retroactive span from two ``now()`` stamps (ph X)."""
        if not self.enabled:
            return
        ev = {"name": name, "ph": "X", "ts": self._us(start),
              "dur": max(0.0, (end - start) * 1e6)}
        if args:
            ev["args"] = args
        self._emit(ev, track)

    @contextmanager
    def span(self, name: str, *, track: str = "main",
             args: Optional[dict] = None):
        """Live span: a profiler host event always (``track`` and
        ``args`` as its metadata), a Chrome-JSON event when enabled."""
        with TraceAnnotation(name, track=track, **(args or {})):
            if not self.enabled:
                yield
                return
            start = time.monotonic()
            try:
                yield
            finally:
                self.complete(name, start, time.monotonic(),
                              track=track, args=args)

    def instant(self, name: str, *, track: str = "main",
                args: Optional[dict] = None) -> None:
        if not self.enabled:
            return
        ev = {"name": name, "ph": "i", "ts": self._us(time.monotonic()),
              "s": "t"}
        if args:
            ev["args"] = args
        self._emit(ev, track)

    def async_begin(self, name: str, uid: int, *, track: str = "main",
                    args: Optional[dict] = None) -> None:
        """Open the per-request lifecycle span (ph b, id=uid)."""
        if not self.enabled:
            return
        ev = {"name": name, "ph": "b", "cat": "request", "id": int(uid),
              "ts": self._us(time.monotonic())}
        if args:
            ev["args"] = args
        self._emit(ev, track)

    def async_end(self, name: str, uid: int, *, track: str = "main",
                  args: Optional[dict] = None) -> None:
        if not self.enabled:
            return
        ev = {"name": name, "ph": "e", "cat": "request", "id": int(uid),
              "ts": self._us(time.monotonic())}
        if args:
            ev["args"] = args
        self._emit(ev, track)

    # --------------------------------------------------------- readout
    def events(self, name: Optional[str] = None,
               ph: Optional[str] = None) -> List[dict]:
        """Snapshot of recorded events, optionally filtered (tests)."""
        with self._lock:
            evs = list(self._events)
        if name is not None:
            evs = [e for e in evs if e.get("name") == name]
        if ph is not None:
            evs = [e for e in evs if e.get("ph") == ph]
        return evs

    def export(self, path: str) -> int:
        """Write Chrome-trace JSON; returns the number of events."""
        with self._lock:
            evs = list(self._events)
        with open(path, "w") as f:
            json.dump({"traceEvents": evs,
                       "displayTimeUnit": "ms"}, f)
        return len(evs)

    def clear(self) -> None:
        with self._lock:
            self._events = [e for e in self._events
                            if e.get("ph") == "M"]


NULL_TRACER = Tracer(enabled=False)
