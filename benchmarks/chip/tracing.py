"""Profiler trace of part of a run's window, and its reduction.

``Window`` records a profiler trace of ``seconds`` seconds starting
``start_s`` after the measured window opens, from a thread of its own so
that the load generator keeps its schedule, or (``record``) of one call
made in the calling thread.  ``reduce`` reads the
``.xplane.pb`` with JAX's own reader: the operations on each chip's
"XLA Ops" line, their union (busy time), the Pallas kernels by name, the
longest idle gaps with what the host was doing in them, and the host
spans the benchmark itself records.
"""

from __future__ import annotations

import glob
import re
import shutil
import tempfile
import threading
import time
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "chipbench_window"
_KERNEL = re.compile(r"jit\(([A-Za-z0-9_]+)\)/pallas_call")
_SUFFIX = re.compile(r"[._-]\d+$")
# control-flow ops span the ops they run; they count in the busy union
# but not in the per-op breakdown
_CONTAINERS = ("while", "conditional", "call")


class Window:
    """Trace ``seconds`` seconds of the window from a side thread."""

    def __init__(self, start_s: float, seconds: float):
        self.start_s, self.seconds = start_s, seconds
        self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        self.t0 = self.t1 = None
        self.error: Optional[Exception] = None
        self._thread: Optional[threading.Thread] = None

    def record(self, fn):
        """Trace one call of ``fn`` in this thread; returns its result."""
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(WINDOW_SPAN):
                self.t0 = time.perf_counter()
                out = fn()
                self.t1 = time.perf_counter()
        finally:
            jax.profiler.stop_trace()
        self._thread = threading.current_thread()
        return out

    def start(self, window_t0: float) -> None:
        self._thread = threading.Thread(target=self._run, args=(window_t0,),
                                        daemon=True, name="chipbench-trace")
        self._thread.start()

    def _run(self, window_t0: float) -> None:
        import jax
        try:
            time.sleep(max(0.0, window_t0 + self.start_s
                           - time.perf_counter()))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            with jax.profiler.TraceAnnotation(WINDOW_SPAN):
                self.t0 = time.perf_counter()
                time.sleep(self.seconds)
                self.t1 = time.perf_counter()
            jax.profiler.stop_trace()
        except Exception as e:  # noqa: BLE001 — raised again by join()
            self.error = e

    def join(self) -> Optional[dict]:
        """Wait for the trace; returns its reduction (None if it failed)."""
        if self._thread is None:
            return None
        if self._thread is not threading.current_thread():
            self._thread.join()
        if self.error is not None:
            raise RuntimeError(f"trace failed: {self.error!r}")
        paths = glob.glob(f"{self.dir}/**/*.xplane.pb", recursive=True)
        if not paths:
            raise RuntimeError(f"no .xplane.pb under {self.dir}")
        red = reduce(max(paths, key=lambda p: Path(p).stat().st_mtime))
        red["t0"], red["t1"] = self.t0, self.t1
        shutil.rmtree(self.dir, ignore_errors=True)
        return red


def op_name(text: str) -> str:
    """An op event's instruction name without its number: the TPU trace
    names each event by its HLO text, ``%paged_attn.6 = f32[...]
    custom-call(...)``."""
    head = text.split(" = ", 1)[0].strip().lstrip("%")
    return _SUFFIX.sub("", head)


def kernel_name(name: str, stats) -> Optional[str]:
    """The Pallas kernel an op event runs, or None: a custom call that
    carries the kernel's own name (``%nm_spmm_decode.47 = ...
    custom-call(...)``), or whose metadata names the kernel's jitted
    wrapper (``jit(paged_attn)/pallas_call``)."""
    m = _KERNEL.search(name)
    if m:
        return m.group(1)
    for _, v in stats:
        if isinstance(v, str):
            m = _KERNEL.search(v)
            if m:
                return m.group(1)
    if " custom-call(" in name:
        op = op_name(name)
        if op != "custom-call":
            return op
    return None


def load(path: str) -> Tuple[Dict[str, list], list]:
    """Device op events per chip, ``(name, start_ns, end_ns, kernel)``,
    and host events ``(name, start_ns, end_ns, thread)``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    dev: Dict[str, list] = {}
    host: list = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = dev.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for e in line.events:
                    ops.append((e.name, e.start_ns, e.end_ns,
                                kernel_name(e.name, e.stats)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    host.append((e.name, e.start_ns, e.end_ns, line.name))
    return dev, host


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge intervals into disjoint sorted ones."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def op_label(name: str, kernel: Optional[str]) -> str:
    return kernel or op_name(name)


def reduce_events(dev: Dict[str, list], host: list,
                  span: Optional[Tuple[float, float]] = None) -> dict:
    """Busy time, kernel times and idle gaps inside the window ``span``
    (ns; default: the benchmark's own window span on the host, else the
    extent of the device ops), averaged over the chips traced."""
    if span is None:
        wins = [(s, e) for n, s, e, _ in host if n == WINDOW_SPAN]
        if wins:
            span = max(wins, key=lambda w: w[1] - w[0])
        else:
            allops = [(s, e) for ops in dev.values() for _, s, e, _ in ops]
            span = (min(s for s, _ in allops), max(e for _, e in allops))
    w0, w1 = span
    n_dev = max(1, len(dev))
    busy = 0.0
    by_op: Dict[str, float] = defaultdict(float)
    kern_s: Dict[str, float] = defaultdict(float)
    kern_n: Dict[str, int] = defaultdict(int)
    gaps: List[Tuple[float, float]] = []
    for ops in dev.values():
        clipped = []
        for name, s, e, k in ops:
            s, e = max(s, w0), min(e, w1)
            if e <= s:
                continue
            clipped.append((s, e))
            label = op_label(name, k)
            if label not in _CONTAINERS:
                by_op[label] += (e - s) * 1e-9
            if k is not None:
                kern_s[k] += (e - s) * 1e-9
                kern_n[k] += 1
        merged = union(clipped)
        busy += sum(e - s for s, e in merged) * 1e-9
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    idle = []
    for s, e in gaps[:10]:
        idle.append([host_activity(host, (s + e) / 2), (e - s) * 1e-9])
    top = sorted(by_op.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy / n_dev,
        "chips": n_dev,
        "kernel_s": {k: v / n_dev for k, v in kern_s.items()},
        "kernel_calls": {k: v // n_dev for k, v in kern_n.items()},
        "op_s": {k: v / n_dev for k, v in by_op.items()},
        "breakdown": {"device_ops": [[k, v / n_dev] for k, v in top],
                      "idle_gaps": idle},
    }


def host_activity(host: list, t: float) -> str:
    """What the host was doing at time ``t``: the innermost host event
    that covers it (the benchmark's window span excluded)."""
    best = None
    for name, s, e, thread in host:
        if s <= t <= e and name != WINDOW_SPAN:
            if best is None or e - s < best[2] - best[1]:
                best = (name, s, e, thread)
    return "none" if best is None else f"{best[3]}: {best[0]}"


def reduce(path: str) -> dict:
    dev, host = load(path)
    if not dev:
        raise RuntimeError(f"{path}: no TPU device plane in the trace")
    return reduce_events(dev, host)
