"""Algorithm 1 — accurate post-training pruning (paper Sec. 4.2/4.3).

Method names follow the paper: first letter = mask solution, second =
compensation solution.

  SS  SparseGPT (baseline; sequential freezing)
  SM  𝔖 mask (Eq. 14 scores) + 𝔐 compensation (Eq. 13)   ← paper's pick
  MS  𝔐 mask (Eq. 12 combos) + 𝔖 compensation             [N:M only]
  MM  𝔐 mask + 𝔐 compensation                             [N:M only]
  magnitude / wanda  score-only baselines (no compensation)

Block loop (unstructured & N:M): the accumulated mask grows block by
block, and 𝔐 compensation solves Eq. (13) against the FULL accumulated
mask each block — previously pruned weights stay exactly zero while every
unpruned weight (in ALL blocks, left included) keeps being refined. That
is precisely the paper's fix for SparseGPT's frozen-left-columns drawback.

Two paths compute that solve (:func:`solve_path`).  Where every row
prunes a static count per column block (N:M, or row-balanced
unstructured), the loop runs per chunk of rows and extends one Cholesky
factor per row by a border each block (``mrp.mrp_border_rows``).
Otherwise each block re-solves the whole accumulated mask
(``mrp.mrp_compensate_mask``).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.core import masks as masks_lib
from repro.core import mrp, scores, sparsegpt
from repro.core.hessian import dampened_inverse
from repro.core.sparsity import SparsitySpec

METHODS = ("magnitude", "wanda", "SS", "SM", "MS", "MM")


@dataclasses.dataclass
class PruneResult:
    w: jax.Array          # pruned + compensated weights
    mask: jax.Array       # True = pruned
    loss: float           # Σ Eq.(12) losses (or method analogue)
    method: str
    spec: SparsitySpec
    stats: Dict[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def sparsity(self) -> float:
        return masks_lib.sparsity_of(self.mask)


def reconstruction_error(w0: jax.Array, w1: jax.Array, h: jax.Array) -> float:
    """‖(w1−w0) x‖² evaluated through H: tr(δw H δwᵀ)/2-free form.

    Since H = mean_t 2 x xᵀ,  ‖δw x‖²/T = ½ tr(δw H δwᵀ).
    This is the paper's objective — used everywhere as the quality metric.
    """
    err = reconstruction_error_traced(w0, w1, h)
    return float(err)


def reconstruction_error_traced(
    w0: jax.Array, w1: jax.Array, h: jax.Array
) -> jax.Array:
    """Traceable twin of :func:`reconstruction_error` (no host sync)."""
    dw = (w1 - w0).astype(jnp.float32)
    return 0.5 * jnp.einsum("ij,jk,ik->", dw, h.astype(jnp.float32), dw,
                            precision=jax.lax.Precision.HIGHEST)


def _maybe_float(x):
    """float() outside jit; pass tracers through (keeps prune_matrix
    usable both as a host API and inside jit/shard_map)."""
    return x if isinstance(x, jax.core.Tracer) else float(x)


# ----------------------------------------------------------------------
def _score_mask_block(
    wblk: jax.Array,
    h: jax.Array,
    hinv: jax.Array,
    spec: SparsitySpec,
    score_name: str,
    col0: int,
    row_balanced: bool = False,
) -> jax.Array:
    """Solution 𝔖 mask for one column block (Eq. 14 / baselines)."""
    s = wblk.shape[1]
    hs = jax.lax.dynamic_slice(h, (col0, col0), (s, s))
    hinvs = jax.lax.dynamic_slice(hinv, (col0, col0), (s, s))
    sc = scores.compute_score(score_name, wblk, hs, hinvs)
    if spec.is_semi_structured:
        return masks_lib.nm_mask_from_scores(sc, spec.n, spec.m)
    if row_balanced:
        return masks_lib.unstructured_mask_rowwise(
            sc, spec.pruned_per_row_block(s))
    nppb = int(round(wblk.shape[0] * s * spec.rate))
    return masks_lib.unstructured_mask_from_scores(sc, nppb)


def solve_path(spec: SparsitySpec, method: str, row_balanced: bool) -> str:
    """How Algorithm 1's MRP compensation is solved for a layer:
    ``"bordered"`` when SM/MM prune a static count per row and column
    block (N:M, or row-balanced unstructured), so each block extends the
    previous factor; ``"resolve"`` for every other solve."""
    if method in ("SM", "MM") and (spec.is_semi_structured or row_balanced):
        return "bordered"
    return "resolve"


@functools.partial(jax.jit, static_argnames=(
    "spec", "method", "score_name", "blocksize", "row_balanced",
    "row_chunk"))
def _prune_bordered(w, h, hinv, spec, method, score_name, blocksize,
                    row_balanced, row_chunk):
    """Algorithm 1's block loop for static per-row counts: rows are
    independent (Remark 4.2), so each chunk of rows runs every column
    block, carrying its weights and the factor of its pruned set.

    Returns (w, mask, losses) with losses (n, nblocks) per row."""
    n, m = w.shape
    nblocks = m // blocksize
    per_blk = spec.pruned_per_row_block(blocksize)
    k = nblocks * per_blk
    nm = (spec.n, spec.m) if spec.is_semi_structured else None
    hinv32 = hinv.astype(jnp.float32)
    if row_chunk is None:
        row_chunk = mrp.border_row_chunk(n, k, m, blocksize)

    def prune_rows(w_rows):
        c = w_rows.shape[0]
        w_rows = w_rows.astype(jnp.float32)
        linv = jnp.zeros((c, k, k), jnp.float32)
        mask = jnp.zeros((c, m), bool)
        idx = jnp.zeros((c, 0), jnp.int32)
        losses = []
        for b in range(nblocks):
            c0 = b * blocksize
            wblk = w_rows[:, c0:c0 + blocksize]
            if method == "SM":
                mblk = _score_mask_block(
                    wblk, h, hinv, spec, score_name, c0, row_balanced)
            else:  # MM
                mblk = mrp.select_nm_mask_mrp(
                    wblk, hinv[c0:c0 + blocksize, c0:c0 + blocksize],
                    spec.n, spec.m)
            mask = mask.at[:, c0:c0 + blocksize].set(mblk)
            new, _ = masks_lib.padded_row_indices(mblk, per_blk)
            idx = jnp.concatenate([idx, new + c0], axis=1)
            linv, w_rows, loss = mrp.mrp_border_rows(
                linv, w_rows, hinv32, idx, b * per_blk, c0, blocksize, nm)
            w_rows = jnp.where(mask, 0.0, w_rows)
            losses.append(loss)
        return w_rows.astype(w.dtype), mask, jnp.stack(losses, axis=1)

    if per_blk == 0:
        return w, jnp.zeros((n, m), bool), jnp.zeros((n, nblocks))
    if row_chunk is None or row_chunk >= n:
        return prune_rows(w)
    chunks = -(-n // row_chunk)
    wp = jnp.pad(w, ((0, chunks * row_chunk - n), (0, 0)))
    w_new, mask, losses = jax.lax.map(
        prune_rows, wp.reshape(chunks, row_chunk, m))
    return (w_new.reshape(-1, m)[:n], mask.reshape(-1, m)[:n],
            losses.reshape(-1, nblocks)[:n])


def prune_matrix(
    w: jax.Array,
    h: jax.Array,
    spec: SparsitySpec,
    method: str = "SM",
    blocksize: int = 128,
    gamma: float = 0.01,
    score: Optional[str] = None,
    row_chunk: Optional[int] = None,
    row_balanced: bool = False,
) -> PruneResult:
    """Prune one linear layer's weight matrix. w: (n, m); h: (m, m).

    This is the host-driven per-layer pass (the paper runs it layer by
    layer on one GPU; we run it row-sharded on TPU — see core.distributed).

    ``row_balanced=True`` selects an exact per-row pruned count instead of
    the per-block global count.  With it (or with N:M specs) the whole pass
    is traceable — static shapes, no host sync — so it can be jitted and
    shard_map'd (see core.distributed.prune_matrix_sharded).
    """
    if isinstance(spec, str):
        spec = SparsitySpec.parse(spec)
    if method not in METHODS:
        raise ValueError(f"method {method!r} not in {METHODS}")
    if method in ("MS", "MM") and not spec.is_semi_structured:
        raise ValueError(
            f"Solution 𝔐 mask is combinatorial — N:M only (paper Sec. 4.2.1); "
            f"got method={method} with unstructured {spec}"
        )
    n, m = w.shape
    blocksize = min(blocksize, m)
    if m % blocksize:
        raise ValueError(f"m={m} must be divisible by blocksize={blocksize}")
    spec.validate_block(blocksize)
    w0 = w

    # --- score-only baselines -----------------------------------------
    if method in ("magnitude", "wanda"):
        hinv = dampened_inverse(h, gamma)  # unused by magnitude; cheap enough
        sc = scores.compute_score(method, w, h, hinv)
        if spec.is_semi_structured:
            mask = masks_lib.nm_mask_from_scores(sc, spec.n, spec.m)
        elif row_balanced:
            mask = masks_lib.unstructured_mask_rowwise(
                sc, int(round(m * spec.rate)))
        else:
            mask = masks_lib.unstructured_mask_from_scores(
                sc, int(round(n * m * spec.rate))
            )
        w_new = jnp.where(mask, 0.0, w)
        return PruneResult(
            w_new, mask, _maybe_float(reconstruction_error_traced(w0, w_new, h)), method, spec
        )

    # --- SparseGPT (𝔖𝔖) ------------------------------------------------
    if method == "SS":
        w_new, mask, _ = sparsegpt.sparsegpt_prune(w, h, spec, blocksize, gamma)
        return PruneResult(
            w_new, mask, _maybe_float(reconstruction_error_traced(w0, w_new, h)), method, spec
        )

    hinv = dampened_inverse(h, gamma)

    # --- 𝔐𝔖: combo mask + SparseGPT compensation (N:M only) ------------
    if method == "MS":
        mask = mrp.select_nm_mask_mrp(w, hinv, spec.n, spec.m)
        w_new, _, _ = sparsegpt.sparsegpt_prune(
            w, h, spec, blocksize, gamma, mask_override=mask
        )
        return PruneResult(
            w_new, mask, _maybe_float(reconstruction_error_traced(w0, w_new, h)), method, spec
        )

    # --- 𝔖𝔐 / 𝔐𝔐: Algorithm 1 block loop with MRP compensation ---------
    score_name = score or "obs"
    nblocks = m // blocksize
    # Per-block Eq. (12) losses.  Each block's solve is against the FULL
    # accumulated mask, so entry b supersedes entry b-1 (it re-solves the
    # earlier blocks' weights too) — the honest scalar summary is the
    # FINAL solve's loss, not a sum or a silently-overwritten "total".
    if solve_path(spec, method, row_balanced) == "bordered":
        w_cur, mask_acc, losses = _prune_bordered(
            w, h, hinv, spec=spec, method=method, score_name=score_name,
            blocksize=blocksize, row_balanced=row_balanced,
            row_chunk=row_chunk)
        block_losses = list(jnp.sum(losses, axis=0))
        return _mrp_result(w0, w_cur, mask_acc, h, method, spec,
                           block_losses)
    mask_acc = jnp.zeros((n, m), bool)
    w_cur = w
    block_losses = []
    for b in range(nblocks):
        c0 = b * blocksize
        wblk = jax.lax.dynamic_slice(w_cur, (0, c0), (n, blocksize))
        mblk = _score_mask_block(wblk, h, hinv, spec, score_name, c0)
        mask_acc = jax.lax.dynamic_update_slice(mask_acc, mblk, (0, c0))
        # MRP compensation against the FULL accumulated mask (Algorithm 1).
        w_cur, loss_rows = mrp.mrp_compensate_mask(
            w_cur, hinv, mask_acc, row_chunk=row_chunk)
        block_losses.append(jnp.sum(loss_rows))
    return _mrp_result(w0, w_cur, mask_acc, h, method, spec, block_losses)


def _mrp_result(w0, w_cur, mask_acc, h, method, spec, block_losses):
    return PruneResult(
        w_cur,
        mask_acc,
        _maybe_float(reconstruction_error_traced(w0, w_cur, h)),
        method,
        spec,
        stats={
            "final_mrp_loss": _maybe_float(block_losses[-1]),
            "block_mrp_losses": tuple(
                _maybe_float(bl) for bl in block_losses),
        },
    )
