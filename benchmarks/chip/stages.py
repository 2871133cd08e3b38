"""Prune stages in a profiler trace: device time by stage, idle time by
program span.

The prune engine (``src/repro/core/pipeline.py``) names its stages in
what a ``jax.profiler`` trace keeps, on the device trace's clock:

- each stage program is named for its stage, so the XLA module it runs
  as is ``jit_prune_capture``, ``jit__prune_hessian_update`` (and
  ``_weighted``), ``jit__prune_hessian_merge``, ``jit_prune_solve``
  (``jit_prune_solve_rows`` on a mesh) or ``jit_prune_propagate``
  (``module_stage``): the "XLA Modules" line of each chip;
- every op of a stage program also carries a ``jax.named_scope``
  (``prune_capture`` ... ``prune_propagate``) in its metadata, which
  ``stage_of`` reads where the trace holds it (the v5e's op events hold
  only their times, so there the module decides);
- every live span of ``repro.obs.Tracer.span`` is a host event whose
  metadata holds its ``track`` and its args (``program_span``).

``reduce`` reads one ``.xplane.pb`` and gives what ``tracing.reduce``
gives, computed the same way, plus

- ``stage_s``: device busy seconds by stage and ``unattributed``, which
  sum to ``busy_s``: each instant the device is busy goes to the
  innermost running op that names a stage, else to ``unattributed``;
- ``idle_by_span``: device idle seconds over all gaps, keyed by the
  innermost program span covering each gap's midpoint (``none`` where
  no program span does);
- ``stage_ops``: the ten longest op kinds of each stage, in seconds;
- ``unattributed_modules``: the ten programs (XLA modules) that hold the
  most unattributed time.
"""

from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

import tracing

STAGES = ("capture", "hessian", "solve", "propagate")
UNATTRIBUTED = "unattributed"
_SCOPE = re.compile(r"(?:^|/)prune_(capture|hessian|solve|propagate)(?=/)")
_MODULE = re.compile(r"^jit_+prune_(capture|hessian|solve|propagate)(?:_|$)")
_MODULE_ID = re.compile(r"\(\d+\)$")


def stage_of(name: str, stats) -> Optional[str]:
    """The prune stage an op event belongs to: the innermost
    ``prune_<stage>`` scope in its name or in a string stat (its
    ``long_name``, or the ``op_name`` metadata), else None."""
    for text in [name] + [v for _, v in stats if isinstance(v, str)]:
        found = _SCOPE.findall(text)
        if found:
            return found[-1]
    return None


def module_stage(module: str) -> Optional[str]:
    """The prune stage of an XLA module (``jit_prune_solve(1234)``), or
    None for any other program (an eager op's ``jit_reshape``)."""
    m = _MODULE.match(_MODULE_ID.sub("", module))
    return m.group(1) if m else None


def attribute(ops: list, runs: list) -> list:
    """One chip's ops ``(name, start, end, kernel, scope_stage)`` with the
    stage completed from the module run ``(name, start, end)`` each op
    starts in: its scope's stage where it has one, else its module's."""
    runs = sorted(runs, key=lambda r: r[1])
    starts = [r[1] for r in runs]
    return [op if op[4] is not None
            else (*op[:4], module_stage(_module_at(runs, starts, op[1])))
            for op in ops]


def program_span(name: str, stats) -> Optional[str]:
    """The label of a host event that ``repro.obs.Tracer.span`` opened
    (it carries a ``track`` stat): the name, and its args in brackets;
    None for every other host event."""
    args = [(k, v) for k, v in stats if k != "track"]
    if not any(k == "track" for k, _ in stats):
        return None
    if not args:
        return name
    return f"{name}[{','.join(f'{k}={v}' for k, v in args)}]"


def load(path: str) -> Tuple[Dict[str, list], list, Dict[str, list]]:
    """Device op events per chip ``(name, start_ns, end_ns, kernel,
    stage)`` (``attribute``), host events ``(name, start_ns, end_ns,
    thread, span)`` (``span`` the program span label or None), and the
    XLA module executions per chip ``(name, start_ns, end_ns)``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    dev: Dict[str, list] = {}
    mods: Dict[str, list] = {}
    host: list = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = dev.setdefault(plane.name, [])
            runs = mods.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for e in line.events:
                        stats = list(e.stats)
                        ops.append((e.name, e.start_ns, e.end_ns,
                                    tracing.kernel_name(e.name, stats),
                                    stage_of(e.name, stats)))
                elif line.name == "XLA Modules":
                    runs += [(e.name, e.start_ns, e.end_ns)
                             for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    host.append((e.name, e.start_ns, e.end_ns, line.name,
                                 program_span(e.name, list(e.stats))))
    dev = {k: attribute(ops, mods[k]) for k, ops in dev.items()}
    return dev, host, mods


def window(dev: Dict[str, list], host: list) -> Tuple[float, float]:
    """The span ``tracing.reduce_events`` reduces over: the benchmark's
    window span on the host, else the extent of the device ops."""
    wins = [(h[1], h[2]) for h in host if h[0] == tracing.WINDOW_SPAN]
    if wins:
        return max(wins, key=lambda w: w[1] - w[0])
    allops = [(op[1], op[2]) for ops in dev.values() for op in ops]
    return (min(s for s, _ in allops), max(e for _, e in allops))


def _stage_split(ops: list, w0: float, w1: float) -> Dict[str, float]:
    """Busy ns of one chip's ops inside [w0, w1], by stage: each busy
    instant goes to the innermost (shortest) running op with a stage."""
    edges = []
    for i, op in enumerate(ops):
        s, e = max(op[1], w0), min(op[2], w1)
        if e > s:
            edges += [(s, 1, i), (e, -1, i)]
    edges.sort(key=lambda x: (x[0], x[1]))
    out: Dict[str, float] = defaultdict(float)
    active: set = set()
    prev = None
    for t, kind, i in edges:
        if active and prev is not None and t > prev:
            staged = [j for j in active if ops[j][4] is not None]
            stage = (min(staged, key=lambda j: ops[j][2] - ops[j][1])
                     if staged else None)
            out[UNATTRIBUTED if stage is None else ops[stage][4]] += t - prev
        prev = t
        if kind > 0:
            active.add(i)
        else:
            active.discard(i)
    return out


def _gaps(ops: list, w0: float, w1: float) -> List[Tuple[float, float]]:
    merged = tracing.union([(max(op[1], w0), min(op[2], w1)) for op in ops
                            if min(op[2], w1) > max(op[1], w0)])
    edges = [w0] + [x for iv in merged for x in iv] + [w1]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def innermost_span(spans: list, t: float) -> str:
    """The innermost of the program spans ``spans`` (host events with a
    label) covering time ``t``, or ``none``."""
    best = None
    for h in spans:
        if h[1] <= t <= h[2]:
            if best is None or h[2] - h[1] < best[2] - best[1]:
                best = h
    return "none" if best is None else best[4]


def _module_at(runs: list, starts: list, t: float) -> str:
    """The module (id dropped) of the run that covers ``t``, or ``?``."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and runs[i][1] <= t <= runs[i][2]:
        return _MODULE_ID.sub("", runs[i][0])
    return "?"


def reduce_events(dev: Dict[str, list], host: list,
                  span: Optional[Tuple[float, float]] = None,
                  mods: Optional[Dict[str, list]] = None) -> dict:
    """``tracing.reduce_events`` of the same events and span, with the
    stage keys added, averaged over the chips traced."""
    span = span or window(dev, host)
    w0, w1 = span
    old = tracing.reduce_events(
        {k: [op[:4] for op in ops] for k, ops in dev.items()},
        [h[:4] for h in host], span=span)
    n_dev = max(1, len(dev))
    stage_s: Dict[str, float] = {s: 0.0 for s in (*STAGES, UNATTRIBUTED)}
    idle: Dict[str, float] = defaultdict(float)
    ops_by: Dict[str, Dict[str, float]] = defaultdict(
        lambda: defaultdict(float))
    unattr_mod: Dict[str, float] = defaultdict(float)
    spans = [h for h in host if len(h) > 4 and h[4] is not None]
    for chip, ops in dev.items():
        ops = [op if len(op) > 4 else (*op, None) for op in ops]
        for k, v in _stage_split(ops, w0, w1).items():
            stage_s[k] += v * 1e-9 / n_dev
        for s, e in _gaps(ops, w0, w1):
            idle[innermost_span(spans, (s + e) / 2)] += (e - s) * 1e-9 / n_dev
        runs = sorted((mods or {}).get(chip, []), key=lambda r: r[1])
        starts = [r[1] for r in runs]
        for name, s, e, k, st in ops:
            s, e = max(s, w0), min(e, w1)
            if e <= s:
                continue
            label = tracing.op_label(name, k)
            if label in tracing._CONTAINERS:
                continue
            ops_by[st or UNATTRIBUTED][label] += (e - s) * 1e-9 / n_dev
            if st is None:
                unattr_mod[_module_at(runs, starts, s)] += \
                    (e - s) * 1e-9 / n_dev
    return {
        **old,
        "stage_s": stage_s,
        "idle_by_span": dict(sorted(idle.items(), key=lambda kv: -kv[1])),
        "stage_ops": {st: [[k, v] for k, v in sorted(
            by.items(), key=lambda kv: -kv[1])[:10]]
            for st, by in ops_by.items()},
        "unattributed_modules": [[k, v] for k, v in sorted(
            unattr_mod.items(), key=lambda kv: -kv[1])[:10]],
    }


def reduce(path: str) -> dict:
    dev, host, mods = load(path)
    if not dev:
        raise RuntimeError(f"{path}: no TPU device plane in the trace")
    return reduce_events(dev, host, mods=mods)
