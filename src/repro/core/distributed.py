"""Distributed pruning: data-parallel Hessians + row-parallel MRP solves.

Remark 4.2 (separate row computation) makes MRP pruning embarrassingly
parallel over weight rows: each row's compensation touches only that row's
pruned set and the (replicated) inverse Hessian.  We exploit it with
``shard_map`` over the ``model`` mesh axis:

  - calibration:  each data shard accumulates a local H = 2 x xᵀ over its
    calibration tokens; ``psum_hessian`` combines shards (token-weighted
    mean, matching HessianAccumulator.merge);
  - pruning:      weight rows are sharded over ``model``; H / Hinv are
    replicated; every shard runs the *same* per-layer pass on its rows.
    N:M masks are per-row ⇒ bitwise identical to the single-device result.
    Unstructured masks use the row-balanced variant (exact per-row counts)
    so selection never needs cross-shard coordination.

No collective happens inside a layer's solve — the only communication in
the whole pruning pass is the Hessian psum, once per linear.

Both entry points resolve the mesh from the active ``repro.dist`` context
when one is not passed explicitly — inside ``use_mesh(mesh)`` the call
sites never thread a mesh by hand.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.calibration import CalibrationSet
from repro.core.hessian import HessianAccumulator
from repro.core.pruner import prune_matrix
from repro.core.sparsity import SparsitySpec
from repro.dist import current_ctx
from repro.dist.sharding import replicated, row_sharding
from repro.obs import note_trace

Axes = Union[str, Sequence[str]]


def _resolve_mesh(mesh: Optional[Mesh]) -> Mesh:
    if mesh is not None:
        return mesh
    ctx = current_ctx()
    if ctx is None:
        raise ValueError(
            "no mesh given and no active device context — pass mesh= or "
            "call inside repro.dist.use_mesh(mesh)")
    return ctx.mesh


def _as_axes(axis_name: Axes) -> Tuple[str, ...]:
    return (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)


# ----------------------------------------------------------------------
# Hessian combination across data shards
# ----------------------------------------------------------------------
def psum_hessian(
    h_local: jax.Array, count_local: jax.Array, axis_name: Axes = "data"
) -> Tuple[jax.Array, jax.Array]:
    """Token-weighted mean of per-shard Hessians (call inside shard_map).

    Matches ``HessianAccumulator.merge``: H = Σ_s H_s·n_s / Σ_s n_s.
    ``axis_name`` may be one axis or several (``("pod", "data")`` reduces
    over DCN and within-pod batch shards in one collective).
    """
    ax = axis_name if isinstance(axis_name, str) else tuple(axis_name)
    total = jax.lax.psum(count_local, ax)
    h = jax.lax.psum(h_local * count_local, ax) / jnp.maximum(total, 1.0)
    return h, total


def hessian_allreduce(
    mesh: Optional[Mesh], h_shards: jax.Array, counts: jax.Array,
    axis_name: Axes = "data"
) -> jax.Array:
    """Host-level convenience: merge per-shard Hessians stacked on axis 0.

    h_shards: (n_shards, m, m) placed along ``axis_name`` (one axis or a
    tuple like ``("pod", "data")`` — n_shards must equal the product of
    the axis sizes); counts: (n_shards,).  ``mesh=None`` resolves the
    active context's mesh.
    """
    mesh = _resolve_mesh(mesh)
    return _allreduce_fn(mesh, _as_axes(axis_name))(h_shards, counts)


@functools.lru_cache(maxsize=64)
def _allreduce_fn(mesh: Mesh, axes: Tuple[str, ...]):
    """Compiled Hessian-merge collective, cached per (mesh, axes) —
    shard_map re-traces on every fresh closure, and the engine calls
    this once per linear per segment."""
    ax_entry = axes if len(axes) > 1 else axes[0]

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(ax_entry), P(ax_entry)),
        out_specs=P(),
    )
    def _merge(hs, cs):
        # each shard holds (1, m, m) / (1,)
        h, _ = psum_hessian(hs[0], cs[0], ax_entry)
        return h

    return jax.jit(_merge)


def allreduce_calibration(
    sets: Sequence[CalibrationSet],
    mesh: Optional[Mesh] = None,
    axis_name: Axes = "data",
) -> CalibrationSet:
    """Merge per-shard :class:`CalibrationSet`s over the mesh's batch axes.

    Each entry of ``sets`` is one data(+pod) shard's accumulated
    calibration state for the same segment; the merged Hessian per linear
    comes from one :func:`hessian_allreduce` collective with the stacked
    per-shard Hessians placed along ``axis_name`` — no host round-trips.
    When the shard count does not match the axis sizes (e.g. calibration
    was split more coarsely than the mesh), falls back to the on-device
    tree merge ``CalibrationSet.merge_all``.
    """
    sets = list(sets)
    if len(sets) == 1:
        return sets[0]
    mesh = _resolve_mesh(mesh)
    axes = _as_axes(axis_name)
    n_axes = 1
    for a in axes:
        n_axes *= mesh.shape[a]
    if len(sets) != n_axes:
        return CalibrationSet.merge_all(sets)

    out = CalibrationSet()
    names = set().union(*(set(s.accs) for s in sets))
    stack_sh = row_sharding(mesh, axes, ndim=3)
    count_sh = row_sharding(mesh, axes, ndim=1)
    for name in sorted(names):
        if any(name not in s.accs for s in sets):
            # a linear some shard never saw (shouldn't happen for dense
            # segments) — degrade to the tree merge for this name only
            accs = [s.accs[name] for s in sets if name in s.accs]
            out.accs[name] = HessianAccumulator.merge_many(accs)
            continue
        accs = [s.accs[name] for s in sets]
        hs = jax.device_put(jnp.stack([a.h for a in accs]), stack_sh)
        cs = jnp.stack([a.count for a in accs])
        h = hessian_allreduce(mesh, hs, jax.device_put(cs, count_sh),
                              axis_name=axes)
        if _cpu_multidevice():
            # XLA's CPU runtime deadlocks on concurrent independent
            # collective programs (see core.pipeline.strict_collective_
            # sync) — drain each linear's allreduce before the next
            jax.block_until_ready(h)
        out.accs[name] = HessianAccumulator(
            accs[0].dim, h=h, count=jnp.sum(cs))
    return out


def _cpu_multidevice() -> bool:
    return jax.default_backend() == "cpu" and jax.device_count() > 1


# ----------------------------------------------------------------------
# Row-parallel layer pruning
# ----------------------------------------------------------------------
def prune_matrix_sharded(
    w: jax.Array,
    h: jax.Array,
    spec: SparsitySpec | str,
    mesh: Optional[Mesh] = None,
    method: str = "SM",
    blocksize: int = 128,
    gamma: float = 0.01,
    score: Optional[str] = None,
    row_chunk: Optional[int] = None,
    model_axis: str = "model",
) -> Tuple[jax.Array, jax.Array]:
    """Row-sharded prune: returns (w_pruned, mask) with w's sharding.

    Rows (output channels) are sharded over ``model_axis``; ``h`` is
    replicated.  Each shard runs the identical traceable pruning pass on
    its rows — zero collectives (Remark 4.2).  ``mesh=None`` resolves the
    active ``repro.dist`` context's mesh.
    """
    mesh = _resolve_mesh(mesh)
    if isinstance(spec, str):
        spec = SparsitySpec.parse(spec)
    n, m = w.shape
    n_shards = mesh.shape[model_axis]
    if n % n_shards:
        raise ValueError(f"rows {n} not divisible by {model_axis}={n_shards}")

    fn = _sharded_prune_fn(
        mesh, spec, method, blocksize, gamma, score, row_chunk, model_axis)
    w_sh = jax.device_put(w, row_sharding(mesh, model_axis))
    h_rep = jax.device_put(h, replicated(mesh))
    return fn(w_sh, h_rep)


@functools.lru_cache(maxsize=256)
def _sharded_prune_fn(
    mesh: Mesh,
    spec: SparsitySpec,
    method: str,
    blocksize: int,
    gamma: float,
    score: Optional[str],
    row_chunk: Optional[int],
    model_axis: str,
):
    """Compiled row-parallel layer solve, cached per (mesh, prune
    config); jit keys on the weight/Hessian shapes, so every linear of
    the same shape across all segments shares one compilation (a fresh
    shard_map closure per call re-traced the whole MRP block loop —
    28 compiles per tiny-LM prune, the wall-clock dominator)."""

    def prune_solve_rows(w_loc, h_rep):
        note_trace("solve")
        with jax.named_scope("prune_solve"):
            res = prune_matrix(
                w_loc,
                h_rep,
                spec,
                method=method,
                blocksize=blocksize,
                gamma=gamma,
                score=score,
                row_chunk=row_chunk,
                row_balanced=True,      # static shapes, per-row selection
            )
            return res.w, res.mask

    return jax.jit(shard_map(
        prune_solve_rows,
        mesh=mesh,
        in_specs=(P(model_axis, None), P(None, None)),
        out_specs=(P(model_axis, None), P(model_axis, None)),
        check_vma=False,
    ))
