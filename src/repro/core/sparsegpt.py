"""SparseGPT (Frantar & Alistarh 2023) — the SRP-based 𝔖𝔖 baseline.

Faithful JAX port of the released sparsegpt.py algorithm, kept for two
roles: (a) the paper's main baseline, and (b) Solution 𝔖 *compensation*
inside our mixed combinations (𝔐𝔖).

Algorithm recap (sequential weight freezing — the thing MRP removes):
  Hinv  = chol_upper( (H + γI)⁻¹ )          # upper Cholesky factor U
  per column block [i1:i2):
    per column i (left→right):
      select pruned entries (by w²/U_ii² within block, or per N:M group)
      q     = w_i with pruned slots zeroed
      err_i = (w_i − q) / U_ii
      w[:, i:] −= err_i ⊗ U[i, i:]          # frozen left, updated right
    w[:, i2:] −= Err_block @ U[i1:i2, i2:]  # lazy trailing update

The per-column loop is inherently sequential (each step reads weights the
previous step wrote) — on TPU it is a `lax.fori_loop`. Our MRP path
replaces the whole loop with one batched solve; see core.mrp.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.sparsity import SparsitySpec


def cholesky_inv_upper(h: jax.Array, gamma: float = 0.01) -> jax.Array:
    """U with (H + γ·mean(diag)·I)⁻¹ = Uᵀ U  (SparseGPT's `Hinv`)."""
    m = h.shape[0]
    damp = jnp.maximum(gamma * jnp.mean(jnp.diag(h)), 1e-8)
    hd = (h + damp * jnp.eye(m, dtype=h.dtype)).astype(jnp.float32)
    chol = jax.scipy.linalg.cho_factor(hd, lower=True)
    hinv = jax.scipy.linalg.cho_solve(chol, jnp.eye(m, dtype=jnp.float32))
    # upper Cholesky of hinv:  hinv = Uᵀ U ⇒ U = chol(hinv, lower=False)
    u = jnp.linalg.cholesky(hinv, upper=True)
    return u


def _column_step(w1, err1, mask1, u1, i, *, lazy_from: int):
    """One inner column update; mask1 column i decides pruning."""
    s = w1.shape[1]
    wcol = w1[:, i]
    d = u1[i, i]
    q = jnp.where(mask1[:, i], 0.0, wcol)
    err = (wcol - q) / d
    # update columns i..s (the frozen-left / updated-right rule)
    row = u1[i, :]                                  # (S,)
    upd = err[:, None] * row[None, :]               # (n, S)
    colmask = (jnp.arange(s) >= i + 1)
    w1 = w1 - upd * colmask[None, :]
    w1 = w1.at[:, i].set(q)
    err1 = err1.at[:, i].set(err)
    return w1, err1


@functools.partial(
    jax.jit, static_argnames=("blocksize", "prune_n", "prune_m", "num_prune_per_block")
)
def _sparsegpt_core(
    w: jax.Array,
    u: jax.Array,
    mask_override: Optional[jax.Array],
    blocksize: int,
    prune_n: int,
    prune_m: int,
    num_prune_per_block: int,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Blocked sequential SparseGPT. Returns (w_new, mask, per-col loss)."""
    n, m = w.shape
    w = w.astype(jnp.float32)
    u = u.astype(jnp.float32)
    nblocks = m // blocksize
    have_override = mask_override is not None
    if not have_override:
        mask_override = jnp.zeros((n, m), bool)

    def block_body(b, carry):
        w, mask_all, losses = carry
        i1 = b * blocksize
        w1 = jax.lax.dynamic_slice(w, (0, i1), (n, blocksize))
        u1 = jax.lax.dynamic_slice(u, (i1, i1), (blocksize, blocksize))
        udiag = jnp.diagonal(u1)

        if have_override:
            mask1 = jax.lax.dynamic_slice(mask_override, (0, i1), (n, blocksize))
        elif prune_n == 0:
            # unstructured: threshold w²/U_jj² within the block, exact count
            scores = (w1**2) / (udiag[None, :] ** 2)
            flat = scores.reshape(-1)
            order = jnp.argsort(flat)
            mask1 = (
                jnp.zeros((n * blocksize,), bool)
                .at[order[:num_prune_per_block]]
                .set(True)
                .reshape(n, blocksize)
            )
        else:
            mask1 = jnp.zeros((n, blocksize), bool)  # filled per group below

        def col_body(i, inner):
            w1, err1, mask1 = inner
            if (not have_override) and prune_n > 0:
                # refresh the group's mask when entering it (i % M == 0),
                # using *current* (already-compensated) weights.
                def refresh(args):
                    w1, mask1 = args
                    gstart = i
                    wg = jax.lax.dynamic_slice(w1, (0, gstart), (n, prune_m))
                    dg = jax.lax.dynamic_slice(udiag, (gstart,), (prune_m,))
                    sc = (wg**2) / (dg[None, :] ** 2)
                    _, idx = jax.lax.top_k(-sc, prune_n)
                    mg = jax.nn.one_hot(idx, prune_m, dtype=jnp.float32).sum(-2) > 0
                    return jax.lax.dynamic_update_slice(mask1, mg, (0, gstart))

                mask1 = jax.lax.cond(
                    i % prune_m == 0, refresh, lambda a: a[1], (w1, mask1)
                )
            w1, err1 = _column_step(w1, err1, mask1, u1, i, lazy_from=blocksize)
            return (w1, err1, mask1)

        err1 = jnp.zeros((n, blocksize), jnp.float32)
        w1, err1, mask1 = jax.lax.fori_loop(
            0, blocksize, col_body, (w1, err1, mask1)
        )

        # lazy trailing update: w[:, i2:] -= Err1 @ U[i1:i2, i2:]
        urows = jax.lax.dynamic_slice(u, (i1, 0), (blocksize, m))
        trailing = jnp.matmul(err1, urows,
                              precision=jax.lax.Precision.HIGHEST)  # (n, m)
        colmask = jnp.arange(m) >= (i1 + blocksize)
        w = w - trailing * colmask[None, :]
        w = jax.lax.dynamic_update_slice(w, w1, (0, i1))
        mask_all = jax.lax.dynamic_update_slice(mask_all, mask1, (0, i1))
        # per-block loss bookkeeping: Σ err² /2 (OBS loss units)
        losses = losses.at[b].set(0.5 * jnp.sum(err1**2))
        return (w, mask_all, losses)

    mask_all = jnp.zeros((n, m), bool)
    losses = jnp.zeros((nblocks,), jnp.float32)
    w, mask_all, losses = jax.lax.fori_loop(
        0, nblocks, block_body, (w, mask_all, losses)
    )
    w = jnp.where(mask_all, 0.0, w)
    return w, mask_all, losses


def sparsegpt_prune(
    w: jax.Array,
    h: jax.Array,
    spec: SparsitySpec,
    blocksize: int = 128,
    gamma: float = 0.01,
    mask_override: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Full SparseGPT (𝔖𝔖), or 𝔖-compensation under a given mask (𝔐𝔖).

    Returns (w_pruned, mask, per-block losses).
    """
    n, m = w.shape
    blocksize = min(blocksize, m)
    if m % blocksize:
        raise ValueError(f"cols {m} must divide by blocksize {blocksize}")
    spec.validate_block(blocksize)
    u = cholesky_inv_upper(h, gamma)
    if spec.is_semi_structured:
        pn, pm = spec.n, spec.m
        nppb = 0
    else:
        pn = pm = 0
        nppb = int(round(n * blocksize * spec.rate))
    dtype = w.dtype
    w_new, mask, losses = _sparsegpt_core(
        w, u, mask_override, blocksize, pn, pm, nppb
    )
    return w_new.astype(dtype), mask, losses
