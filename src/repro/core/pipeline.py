"""Pipelined calibration/solve scheduler — the pruning engine's hot path.

Algorithm 1 is serial over segments, but within a segment there are three
stages whose only dependencies are array values:

  capture(i)    calibration hiddens through segment i (dense weights),
                accumulating the per-linear Hessians
  solve(i)      per-linear layer solves from those Hessians
  propagate(i)  segment i re-run with the *pruned* weights → the inputs
                of segment i+1

The serial engine (``PruningEngine`` with ``pipeline="off"``) runs these
as per-batch eager Python loops with host syncs between stages.  The
scheduler here instead

  - stacks the calibration batches into one batched hidden-state pytree
    per calibration shard and jits each segment's capture/propagate
    apply: one XLA dispatch per stage instead of ``n_batches`` eager
    walks, with one compilation shared by every segment that carries the
    same ``apply.trace_key`` (all period instances of a model compile
    once);
  - shards the calibration set over the mesh's data(+pod) axes: each
    shard accumulates its own :class:`CalibrationSet` and the per-linear
    Hessians merge through ``core.distributed.allreduce_calibration`` —
    one collective per linear, DCN-friendly on multi-pod meshes;
  - never blocks the host mid-segment: jax's async dispatch lets the
    host enqueue segment *i*'s solves, its pruned propagate and segment
    *i+1*'s capture while segment *i*'s solves are still executing.
    Report scalars (sparsity, reconstruction error) stay device arrays
    until the end of the run.  (Exception: on multi-device CPU the
    stages synchronize — see :func:`strict_collective_sync`);
  - donates the propagate inputs (``donate_argnums``, accelerator
    backends) so peak activation memory stays ~one segment.

Dispatch timeline (host runs ahead of the device queue; only
``progress_store`` checkpoints synchronize, on segment boundaries):

  host:   cap(i) solves(i) prop(i) cap(i+1) solves(i+1) ...
  device: ──cap(i)──►─solves(i)──►─prop(i)──►─cap(i+1)──► ...

``PruningEngine.run`` drives :func:`run_pipelined`; the serial loop
remains available as ``pipeline="off"`` and is the semantic reference —
the pipelined path must produce the same masks/weights (tested).
"""

from __future__ import annotations

import contextlib
import dataclasses
import logging
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.calibration import CalibrationSet
from repro.core.pruner import solve_path
from repro.obs import Obs, counting_traces, note_trace

log = logging.getLogger("repro.pipeline")


def strict_collective_sync(mesh) -> bool:
    """Serialize collective-bearing dispatches on multi-device CPU.

    XLA's CPU runtime runs concurrent programs on a thread pool with no
    per-device FIFO ordering, so two *independent* in-flight programs
    that both contain collectives can interleave their rendezvous and
    deadlock (observed with a capture's hessian_allreduce racing a layer
    solve's resharding).  Accelerator runtimes enqueue programs in
    dispatch order per device, and mesh-less runs dispatch single-device
    programs with no collectives at all — only the virtual-device CPU
    configuration *with* a multi-device mesh needs the stage-by-stage
    sync.
    """
    return (mesh is not None and mesh.size > 1
            and jax.default_backend() == "cpu" and jax.device_count() > 1)


@dataclasses.dataclass
class PipelineStats:
    """Per-run scheduler accounting (``engine.last_pipeline_stats``).

    In the default async mode the per-stage seconds measure host
    *dispatch* time (the device queue drains concurrently); with
    ``instrument=True`` every stage blocks until its results are ready,
    so the seconds are true stage costs and ``sum(stages) - wall`` of an
    uninstrumented run measures the overlap won by pipelining.
    """

    segments: int = 0
    calib_shards: int = 1
    batches: int = 0
    capture_s: float = 0.0
    solve_s: float = 0.0
    propagate_s: float = 0.0
    wall_s: float = 0.0
    instrumented: bool = False

    def stage_total(self) -> float:
        return self.capture_s + self.solve_s + self.propagate_s


def _resolve_shards(calib_shard, mesh, dp_axes, n_batches: int) -> int:
    """How many calibration shards to accumulate separately.

    ``"auto"`` uses one shard per data(+pod) slice when the batch count
    allows it; ``"off"``/1 accumulates locally; an int forces a count.
    """
    if isinstance(calib_shard, bool):        # before int tests: True == 1
        calib_shard = "on" if calib_shard else "off"
    if calib_shard in ("off", None, 1):
        return 1
    dp = 1
    if mesh is not None:
        for a in dp_axes:
            if a in mesh.axis_names:
                dp *= mesh.shape[a]
    if isinstance(calib_shard, int):
        return max(1, min(calib_shard, n_batches))
    if calib_shard == "auto":
        return dp if (dp > 1 and n_batches >= dp) else 1
    if calib_shard == "on":
        if dp <= 1:
            return 1
        return min(dp, n_batches)
    raise ValueError(f"calib_shard={calib_shard!r} not in "
                     "('auto', 'on', 'off') or int")


class SegmentScheduler:
    """Batched, jitted, optionally sharded capture/propagate over segments.

    One instance lives for one ``run_pipelined`` call; jitted segment
    applies are cached by ``apply.trace_key`` (falling back to the apply
    object itself), so structurally identical segments share a compile.
    Each run builds its jits anew, so every run traces them again
    (``prune_stage_traces_total{stage}`` counts it).

    Each stage runs in a live ``prune_<stage>`` span (``Tracer.span``:
    a host event in a ``jax.profiler`` trace).  Its device programs are
    named for the stage (``jit_prune_capture``, ``jit__prune_hessian_*``,
    ``jit_prune_solve*``, ``jit_prune_propagate``: the XLA module names a
    device trace shows) and carry it as a ``jax.named_scope`` in every
    op's metadata.
    """

    def __init__(
        self,
        mesh=None,
        dp_axes: Sequence[str] = ("pod", "data"),
        calib_shard="auto",
        donate: Optional[bool] = None,
        instrument: bool = False,
        obs: Optional[Obs] = None,
    ):
        self.mesh = mesh
        self.dp_axes = tuple(a for a in dp_axes
                             if mesh is not None and a in mesh.axis_names)
        self.calib_shard = calib_shard
        if donate is None:
            # buffer donation is a no-op (warning spam) on CPU
            donate = jax.default_backend() != "cpu"
        self.donate = donate
        self.strict = strict_collective_sync(mesh)
        self.stats = PipelineStats(instrumented=instrument)
        self._instrument = instrument
        self._fns: Dict[Any, Callable] = {}
        # stage timing flows through the SAME obs registry/tracer the
        # serve stack uses: prune_stage_seconds_total{stage} mirrors
        # stats.<stage>_s, and every stage window is a live span
        self.obs = obs if obs is not None else Obs.disabled()
        reg = self.obs.metrics
        self._stage_s = reg.counter(
            "prune_stage_seconds_total",
            "Pipelined prune wall seconds by stage "
            "(capture/solve/propagate)", ("stage",))
        self._m_segments = reg.counter(
            "prune_segments_total", "Segments pruned")
        self._traces = reg.counter(
            "prune_stage_traces_total",
            "Traces of the prune stage programs, each with its lowering "
            "and its compile or cache load "
            "(capture/hessian/solve/propagate)", ("stage",))
        self._solve_paths = reg.counter(
            "prune_solve_path_total",
            "Layer solves by how their MRP compensation is solved "
            "(bordered: one factor extended each column block; "
            "resolve: any other)", ("path",))

    # ---------------------------------------------------------- timing
    @contextlib.contextmanager
    def timed(self, stage: str, ready: Callable[[], Any] = lambda: (),
              **args):
        """Run the block in a live ``prune_<stage>`` span (``args`` its
        metadata), counting the stage programs traced in it, and accrue
        its host time into ``stats.<stage>_s``; with instrumentation on
        (or under the multi-device-CPU collective serialization), also
        block on ``ready()``'s arrays so the time is a true device cost
        instead of an async dispatch."""
        with self.obs.tracer.span(f"prune_{stage}", track="prune",
                                  args=args), \
                counting_traces(self._traces):
            t0 = time.monotonic()
            try:
                yield
            finally:
                if self._instrument or self.strict:
                    for leaf in jax.tree.leaves(ready()):
                        jax.block_until_ready(leaf)
                t1 = time.monotonic()
                setattr(self.stats, f"{stage}_s",
                        getattr(self.stats, f"{stage}_s") + t1 - t0)
                self._stage_s.labels(stage=stage).inc(t1 - t0)

    # -------------------------------------------------------- stacking
    def shard_states(self, per_batch_states: Sequence[Any]) -> List[Any]:
        """Stack per-batch calibration states into per-shard batched
        states (tree-concatenate along the leading batch dim)."""
        states = list(per_batch_states)
        self.stats.batches = len(states)
        n = _resolve_shards(self.calib_shard, self.mesh, self.dp_axes,
                            len(states))
        self.stats.calib_shards = n
        groups = [states[i::n] for i in range(n)]
        return [
            jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0), *g)
            if len(g) > 1 else g[0]
            for g in groups
        ]

    # ------------------------------------------------------------- jit
    def _fn(self, seg, capture: bool) -> Callable:
        key = (getattr(seg.apply, "trace_key", seg.apply), capture)
        fn = self._fns.get(key)
        if fn is None:
            apply = seg.apply
            if capture:
                def prune_capture(p, s):
                    note_trace("capture")
                    with jax.named_scope("prune_capture"):
                        return apply(p, s, capture=True)
                fn = jax.jit(prune_capture)
            else:
                def prune_propagate(p, s):
                    note_trace("propagate")
                    with jax.named_scope("prune_propagate"):
                        return apply(p, s, capture=False)[0]
                fn = jax.jit(prune_propagate,
                             donate_argnums=(1,) if self.donate else ())
            self._fns[key] = fn
        return fn

    # ---------------------------------------------------------- stages
    def capture(self, seg, seg_params, shard_states: List[Any]
                ) -> CalibrationSet:
        """Run calibration through ``seg`` in capture mode, one batched
        apply per shard, and merge the per-shard Hessians (collective
        when the shard count matches the mesh's batch axes)."""
        fn = self._fn(seg, capture=True)
        span = self.obs.tracer.span
        where = {"segment": seg.name}
        sets: List[CalibrationSet] = []
        result: List[CalibrationSet] = []
        with self.timed(
                "capture",
                lambda: [a.h for s in result for a in s.accs.values()],
                **where):
            for st in shard_states:
                _, caps = fn(seg_params, st)
                if self.strict:
                    # per-shard programs are mutually independent — on
                    # multi-device CPU their collectives must not overlap
                    jax.block_until_ready(jax.tree.leaves(caps))
                with span("prune_hessian_accumulate", track="prune",
                          args=where):
                    sets.append(CalibrationSet.from_captures(caps))
            with span("prune_hessian_merge", track="prune", args=where):
                if len(sets) == 1:
                    merged = sets[0]
                elif self.mesh is not None and self.dp_axes:
                    from repro.core.distributed import allreduce_calibration

                    merged = allreduce_calibration(sets, self.mesh,
                                                   axis_name=self.dp_axes)
                else:
                    merged = CalibrationSet.merge_all(sets)
            result.append(merged)
        return merged

    def propagate(self, seg, seg_params, shard_states: List[Any]
                  ) -> List[Any]:
        """Re-run ``seg`` (pruned weights) over every shard, donating the
        input hidden buffers; returns the next segment's inputs."""
        fn = self._fn(seg, capture=False)
        out: List[Any] = []
        with self.timed("propagate", lambda: out, segment=seg.name):
            for st in shard_states:
                out.append(fn(seg_params, st))
                if self.strict:
                    jax.block_until_ready(jax.tree.leaves(out[-1]))
        return out


def run_pipelined(
    engine, params: Any, calib_batches: Sequence[Any],
    instrument: bool = False,
) -> Tuple[Any, List]:
    """Drive Algorithm 1 with the pipelined scheduler.

    Semantics match ``PruningEngine`` serial mode exactly: same segment
    order, same skip/resume/checkpoint behavior (``progress_store`` saves
    land on segment boundaries), same reports — only the dispatch
    structure differs.

    Live spans (track ``prune``): ``prune_job`` around the run, one
    ``prune_segment`` per segment holding its ``prune_capture`` (with
    ``prune_hessian_accumulate`` per shard and ``prune_hessian_merge``),
    ``prune_solve`` (one ``prune_solve_linear`` per linear) and
    ``prune_propagate``, and last ``prune_drain``, the host reading the
    report scalars back.
    """
    sched = SegmentScheduler(
        mesh=engine.mesh,
        calib_shard=engine.calib_shard,
        instrument=instrument,
        # engines wired with an obs bundle (launch/prune.py) surface
        # stage seconds through the shared registry; bare engines no-op
        obs=getattr(engine, "obs", None),
    )
    with sched.obs.tracer.span("prune_job", track="prune"):
        return _run(engine, sched, params, calib_batches)


def _run(engine, sched: SegmentScheduler, params: Any,
         calib_batches: Sequence[Any]) -> Tuple[Any, List]:
    from repro.core.engine import LinearReport

    model = engine.model
    segments = model.prunable_segments()
    span = sched.obs.tracer.span

    start_seg = 0
    if engine.progress_store is not None:
        loader = getattr(engine.progress_store, "load_into", None)
        resumed = loader(params) if loader else engine.progress_store.load()
        if resumed is not None:
            start_seg, params = resumed
            log.info("resuming pipelined pruning at segment %d", start_seg)

    t_wall = time.monotonic()

    init_fn = getattr(model, "calib_init", None) or model.first_hidden
    states = sched.shard_states([init_fn(params, b) for b in calib_batches])
    # fast-forward through already-pruned segments (resume): the same
    # jitted propagate path recomputes their (pruned) outputs bit-exactly
    for seg in segments[:start_seg]:
        states = sched.propagate(seg, seg.get_params(params), states)

    # reports carry device scalars until the end of the run — a float()
    # mid-pipeline would stall the dispatch queue
    pending: List[Tuple[str, jax.Array, Any, float, Tuple[int, ...]]] = []

    for si in range(start_seg, len(segments)):
        seg = segments[si]
        where = {"segment": seg.name}
        with span("prune_segment", track="prune",
                  args={"index": si, **where}):
            seg_params = seg.get_params(params)

            calib = sched.capture(seg, seg_params, states)

            linears = seg.linears
            if linears is None:
                linears = model.segment_linears(seg, seg_params)
            seg_params_ref = [seg_params]
            with sched.timed(
                    "solve",
                    lambda: ([r[1] for r in pending[-len(linears):]]
                             + jax.tree.leaves(seg_params_ref[0])),
                    **where):
                for lin in linears:
                    if engine._should_skip(f"{seg.name}.{lin.name}"):
                        continue
                    if lin.name not in calib.accs:
                        raise KeyError(
                            f"segment {seg.name}: no capture for linear "
                            f"{lin.name!r} (captures: "
                            f"{sorted(calib.names())})")
                    with span("prune_solve_linear", track="prune",
                              args={**where, "linear": lin.name}):
                        sched._solve_paths.labels(path=solve_path(
                            engine.spec, engine.method,
                            engine.row_balanced)).inc()
                        w = lin.get(seg_params)
                        hmat = calib.hessian(lin.name)
                        t0 = time.monotonic()
                        # strict mode (multi-device CPU): the loss float()
                        # blocks the per-linear chain so no two collective
                        # programs are ever in flight together
                        res = engine._prune_one(w, hmat, sync=sched.strict)
                        seg_params = lin.set(seg_params, res.w)
                        seg_params_ref[0] = seg_params
                        pending.append((
                            f"{seg.name}.{lin.name}",
                            res.w,
                            (res.mask, res.loss),
                            time.monotonic() - t0,
                            tuple(w.shape),
                        ))

            params = seg.set_params(params, seg_params)
            states = sched.propagate(seg, seg_params, states)
            sched.stats.segments += 1
            sched._m_segments.inc()

            if engine.progress_store is not None:
                # the only mid-run host sync: checkpoints materialize
                # params, always on a segment boundary
                engine.progress_store.save(si + 1, params)

    if engine.progress_store is not None:
        engine.progress_store.finalize()

    # materialize report scalars only now — the mask means / losses are
    # the run's only remaining device work, drained one float() at a time
    with span("prune_drain", track="prune"):
        reports = [
            LinearReport(
                name=name,
                method=engine.method,
                sparsity=float(jnp.mean(mask.astype(jnp.float32))),
                recon_error=float(loss),
                seconds=secs,
                shape=shape,
            )
            for name, _, (mask, loss), secs, shape in pending
        ]
    sched.stats.wall_s = time.monotonic() - t_wall
    engine.last_pipeline_stats = sched.stats
    return params, reports
