"""Whole-model layer-wise pruning engine (paper Sec. 5, SparseGPT-style).

The engine walks a model segment by segment (transformer block by block —
"sequentially load and prune one single block instead of the whole model"),
so peak memory is one segment's weights + Hessians:

  for each segment:
    1. run calibration hiddens through the segment in capture mode,
       accumulating H = mean_t 2 x xᵀ per prunable linear;
    2. prune every linear with core.pruner.prune_matrix (SS/SM/MS/MM/...);
    3. re-run the segment with the *pruned* weights to produce the next
       segment's calibration inputs (error does not compound silently —
       downstream layers calibrate on what they will actually see).

Model contract (duck-typed; implemented by models/):

  model.prunable_segments() -> list[SegmentSpec]
  model.first_hidden(params, batch) -> h        # embedding/frontend output

Fault tolerance: pass ``progress_store`` (ckpt.PruneProgressStore) and the
engine checkpoints (segment index, params) after every segment; ``run``
resumes from the last completed segment automatically.

Distribution: pass ``mesh=`` or construct the engine inside
``repro.dist.use_mesh(mesh)`` and every divisible layer solve runs
row-parallel over the mesh's ``model`` axis (core.distributed,
Remark 4.2); without a mesh the engine is the paper's host-driven loop.

Pipelining: by default (``pipeline="auto"``) the engine drives the
batched/jitted/async scheduler in :mod:`repro.core.pipeline` — stacked
calibration batches, per-data-shard Hessian accumulation merged with
``hessian_allreduce`` (``calib_shard``), and capture/solve/propagate
overlap via async dispatch.  ``pipeline="off"`` keeps the paper's serial
per-batch loop (the semantic reference; identical results, tested).
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax

from repro.core.calibration import CalibrationSet, Capture
from repro.core.pruner import PruneResult, prune_matrix
from repro.core.sparsity import SparsitySpec
from repro.obs import note_trace

log = logging.getLogger("repro.engine")


@functools.lru_cache(maxsize=256)
def _local_solve_fn(spec, method, blocksize, gamma, score, row_chunk,
                    row_balanced):
    def prune_solve(w, h):
        note_trace("solve")
        with jax.named_scope("prune_solve"):
            res = prune_matrix(
                w, h, spec, method=method, blocksize=blocksize, gamma=gamma,
                score=score, row_chunk=row_chunk, row_balanced=row_balanced)
            return res.w, res.mask, res.loss
    return jax.jit(prune_solve)


@dataclasses.dataclass
class LinearSpec:
    """Handle to one prunable weight inside a segment's params.

    ``get`` must return the weight in the paper's (n_out, m_in) orientation
    (``y = w x``); ``set`` writes it back (transposing as needed for the
    model's storage layout).
    """

    name: str
    get: Callable[[Any], jax.Array]
    set: Callable[[Any, jax.Array], Any]


@dataclasses.dataclass
class SegmentSpec:
    """One sequentially-prunable model segment (usually one block)."""

    name: str
    apply: Callable[..., Tuple[jax.Array, Dict[str, Capture]]]
    #      (seg_params, h, capture: bool) -> (h_out, captures)
    linears: List[LinearSpec]
    get_params: Callable[[Any], Any]
    set_params: Callable[[Any, Any], Any]


@dataclasses.dataclass
class LinearReport:
    name: str
    method: str
    sparsity: float
    recon_error: float
    # serial mode: the solve's blocking wall-clock.  Pipelined mode: the
    # host *dispatch* time only (solves execute async; per-linear device
    # time is unobservable without stalling the queue — use
    # engine.last_pipeline_stats for stage-level costs).
    seconds: float
    shape: Tuple[int, int]


class PruningEngine:
    """Drives Algorithm 1 across a whole model."""

    def __init__(
        self,
        model,
        spec: SparsitySpec | str,
        method: str = "SM",
        blocksize: int = 128,
        gamma: float = 0.01,
        score: Optional[str] = None,
        row_chunk: Optional[int] = None,
        row_balanced: bool = False,
        skip: Sequence[str] = (),
        progress_store=None,
        mesh=None,
        pipeline: str = "auto",
        calib_shard="auto",
    ):
        self.model = model
        self.spec = SparsitySpec.parse(spec) if isinstance(spec, str) else spec
        self.method = method
        self.blocksize = blocksize
        self.gamma = gamma
        self.score = score
        self.row_chunk = row_chunk
        self.row_balanced = row_balanced
        self.skip = tuple(skip)
        self.progress_store = progress_store
        if pipeline not in ("auto", "on", "off", True, False, None):
            raise ValueError(
                f"pipeline={pipeline!r} not in ('auto', 'on', 'off')")
        self.pipeline = pipeline
        self.calib_shard = calib_shard
        self.last_pipeline_stats = None
        self._solve_fn = None
        if mesh is None:
            from repro.dist import current_ctx

            ctx = current_ctx()
            mesh = ctx.mesh if ctx is not None else None
        self.mesh = mesh

    # ------------------------------------------------------------------
    def _should_skip(self, name: str) -> bool:
        return any(pat in name for pat in self.skip)

    def _model_parallel(self) -> int:
        """Shards available for the row-parallel layer solve."""
        if self.mesh is None or "model" not in self.mesh.axis_names:
            return 1
        return self.mesh.shape["model"]

    def _local_solve(self) -> Callable:
        """Jitted local layer solve (traceable specs only): returns
        (w_pruned, mask, loss) with the loss left on device — the
        pipelined path must not sync the host per linear.  Cached per
        prune config (module level), so every engine in a process shares
        one compilation per layer shape."""
        if self._solve_fn is None:
            self._solve_fn = _local_solve_fn(
                self.spec, self.method, self.blocksize, self.gamma,
                self.score, self.row_chunk, self.row_balanced)
        return self._solve_fn

    def _prune_one(self, w: jax.Array, hmat: jax.Array,
                   sync: bool = True) -> PruneResult:
        """One layer solve — row-parallel over the mesh's ``model`` axis
        when active and the rows divide (Remark 4.2), else local.

        The sharded path selects masks per-row (its static-shape
        requirement), so unstructured specs only take it when the engine
        was configured ``row_balanced`` — a global-top-k request must not
        silently change selection semantics under a mesh.

        ``sync=False`` (the pipelined scheduler) keeps the result's loss
        a device array and routes traceable local solves through one
        cached jit, so nothing here blocks the async dispatch queue.
        """
        tp = self._model_parallel()
        traceable = self.spec.is_semi_structured or self.row_balanced
        if (tp > 1 and w.ndim == 2 and w.shape[0] % tp == 0 and traceable):
            from repro.core.distributed import prune_matrix_sharded
            from repro.core.pruner import reconstruction_error_traced

            w_new, mask = prune_matrix_sharded(
                w, hmat, self.spec, self.mesh, method=self.method,
                blocksize=self.blocksize, gamma=self.gamma,
                score=self.score, row_chunk=self.row_chunk)
            loss = reconstruction_error_traced(w, w_new, hmat)
            return PruneResult(
                w_new, mask, float(loss) if sync else loss,
                self.method, self.spec)
        if not sync and traceable:
            w_new, mask, loss = self._local_solve()(w, hmat)
            return PruneResult(w_new, mask, loss, self.method, self.spec)
        return prune_matrix(
            w, hmat, self.spec, method=self.method,
            blocksize=self.blocksize, gamma=self.gamma, score=self.score,
            row_chunk=self.row_chunk, row_balanced=self.row_balanced)

    def _pipeline_enabled(self) -> bool:
        return self.pipeline not in ("off", False, None)

    def run(
        self, params: Any, calib_batches: Sequence[Any]
    ) -> Tuple[Any, List[LinearReport]]:
        """Prune the whole model. ``calib_batches``: token batches.

        Dispatches to the pipelined scheduler (core.pipeline) unless
        ``pipeline="off"`` selected the serial reference loop.
        """
        if self._pipeline_enabled():
            from repro.core.pipeline import run_pipelined

            return run_pipelined(self, params, calib_batches)
        return self._run_serial(params, calib_batches)

    def _run_serial(
        self, params: Any, calib_batches: Sequence[Any]
    ) -> Tuple[Any, List[LinearReport]]:
        """The paper's host-driven per-batch loop (``pipeline="off"``)."""
        self.last_pipeline_stats = None
        segments = self.model.prunable_segments()
        reports: List[LinearReport] = []

        start_seg = 0
        hiddens = None
        if self.progress_store is not None:
            loader = getattr(self.progress_store, "load_into", None)
            resumed = loader(params) if loader else self.progress_store.load()
            if resumed is not None:
                start_seg, params = resumed
                log.info("resuming pruning at segment %d", start_seg)

        # calibration hiddens entering the first (or resumed-at) segment
        # (models may provide calib_init when their calibration state is
        # richer than a single hidden array — e.g. enc-dec models flow
        # {"h": decoder, "enc": encoder} through the segments)
        init_fn = getattr(self.model, "calib_init", None) or self.model.first_hidden
        hiddens = [init_fn(params, b) for b in calib_batches]
        for seg in segments[:start_seg]:
            seg_params = seg.get_params(params)
            hiddens = [seg.apply(seg_params, h, capture=False)[0] for h in hiddens]

        for si in range(start_seg, len(segments)):
            seg = segments[si]
            seg_params = seg.get_params(params)

            # 1. capture + accumulate Hessians
            calib = CalibrationSet()
            for h in hiddens:
                _, caps = seg.apply(seg_params, h, capture=True)
                calib.update(caps)

            # 2. prune each linear (specs may resolve lazily from params)
            linears = seg.linears
            if linears is None:
                linears = self.model.segment_linears(seg, seg_params)
            for lin in linears:
                if self._should_skip(f"{seg.name}.{lin.name}"):
                    continue
                if lin.name not in calib.accs:
                    raise KeyError(
                        f"segment {seg.name}: no capture for linear "
                        f"{lin.name!r} (captures: {sorted(calib.names())})")
                w = lin.get(seg_params)
                hmat = calib.hessian(lin.name)
                t0 = time.monotonic()
                res: PruneResult = self._prune_one(w, hmat)
                seg_params = lin.set(seg_params, res.w)
                reports.append(
                    LinearReport(
                        name=f"{seg.name}.{lin.name}",
                        method=self.method,
                        sparsity=res.sparsity,
                        recon_error=res.loss,
                        seconds=time.monotonic() - t0,
                        shape=tuple(w.shape),
                    )
                )

            # 3. write back + propagate with pruned weights
            params = seg.set_params(params, seg_params)
            hiddens = [seg.apply(seg_params, h, capture=False)[0] for h in hiddens]

            if self.progress_store is not None:
                self.progress_store.save(si + 1, params)

        if self.progress_store is not None:
            self.progress_store.finalize()
        return params, reports


def summarize(reports: Sequence[LinearReport]) -> Dict[str, float]:
    if not reports:
        return {"linears": 0}
    return {
        "linears": len(reports),
        "mean_sparsity": float(
            sum(r.sparsity for r in reports) / len(reports)),
        "total_recon_error": float(sum(r.recon_error for r in reports)),
        "total_seconds": float(sum(r.seconds for r in reports)),
    }
