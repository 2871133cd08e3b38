"""The Multiple Removal Problem (MRP) — the paper's core contribution.

Closed-form optimal solution (Sec. 4.1). For each row q with pruned column
set P (selector E ∈ R^{m×k}), with H = 2xxᵀ + γI and Hinv = H⁻¹:

  Eq. (13):  δw*[q,:] = − w[q,P] · (Eᵀ Hinv E)⁻¹ · Eᵀ Hinv
  Eq. (12):  L*_q     = ½ · w[q,P] · (Eᵀ Hinv E)⁻¹ · w[q,P]ᵀ

TPU-native batching (DESIGN.md §4.1): instead of the paper's per-row GPU
loop we pad every row's pruned set to a common k_max and run ONE batched
symmetric solve over all rows:

  A_q = Hinv[P_q, P_q]   (k_max×k_max, identity-padded)
  z_q = A_q⁻¹ w[q, P_q]  (zero-padded rhs ⇒ padding rows solve to zero)
  δw[q, :] = − scatter(z_q) @ Hinv      (one dense (n,m)@(m,m) matmul)
  L_q      = ½ ⟨z_q, w[q, P_q]⟩

Identity padding makes the padded solve *exactly* equal to the unpadded
one, so this is the paper's optimal solution, not an approximation.
Rows are independent (Remark 4.2) ⇒ the row dimension shards freely over
the `model` mesh axis (core.distributed).
"""

from __future__ import annotations

import functools
import itertools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import masks as masks_lib

# The (rows, m) @ (m, m) compensation product runs in three bf16 passes
# on a TPU (~1e-5 relative, far under the bf16 rounding of the stored
# weights); six-pass HIGHEST compiles ~3x slower for each block's shape.
COMP_PRECISION = jax.lax.Precision.HIGH


# ----------------------------------------------------------------------
# Batched padded-row compensation (Solutions 𝔐 for compensation)
# ----------------------------------------------------------------------
def _gather_submatrix(hinv: jax.Array, idx: jax.Array, valid: jax.Array,
                      nm: Optional[Tuple[int, int]] = None) -> jax.Array:
    """A = Hinv[idx, idx] with identity padding on invalid slots.

    hinv: (m, m); idx: (n, k); valid: (n, k) → (n, k, k).

    ``nm=(N, M)`` declares N:M structure: slot ``i`` of every row lies in
    column group ``i // N`` (each group of M columns holds exactly N
    pruned, listed in order).  A row's columns then differ only by their
    offset inside the group, so A is built by selecting among the M
    candidate rows/columns of each group — exact (a sum of one term and
    zeros) and free of the per-element gather, which a TPU runs at a
    few hundred million elements per second (seconds per layer solve at
    a published width).
    """
    k = idx.shape[1]
    eye = jnp.eye(k, dtype=hinv.dtype)
    vv = valid[:, :, None] & valid[:, None, :]
    if nm is None:
        sub = hinv[idx[:, :, None], idx[:, None, :]]      # (n, k, k)
        return jnp.where(vv, sub, eye[None])
    n_per, m_grp = nm
    c = idx.shape[0]
    g = k // n_per
    off = (idx.reshape(c, g, n_per)
           - m_grp * jnp.arange(g, dtype=idx.dtype)[None, :, None])
    hg = hinv[:g * m_grp].reshape(g, m_grp, -1)           # (g, M, m)
    rows = sum(jnp.where((off == a)[..., None], hg[None, :, None, a], 0.0)
               for a in range(m_grp))                     # (c, g, N, m)
    cols = rows.reshape(c, k, -1)[:, :, :g * m_grp].reshape(c, k, g, m_grp)
    sub = sum(jnp.where((off == b)[:, None], cols[..., b, None], 0.0)
              for b in range(m_grp))                      # (c, k, g, N)
    return jnp.where(vv, sub.reshape(c, k, k), eye[None])


# per-chunk working set of the batched solve (the (rows, k, m) selects
# and the (rows, k, k) factor): rows are chunked to stay under this
ROW_CHUNK_BYTES = 1 << 30


def auto_row_chunk(n: int, k: int, m: int) -> Optional[int]:
    """Rows per chunk that keep one chunk's (rows, k, max(k, m)) f32
    working set under :data:`ROW_CHUNK_BYTES`; None when all rows fit."""
    rows = max(8, ROW_CHUNK_BYTES // (4 * k * max(k, m)))
    return None if rows >= n else rows


@functools.partial(jax.jit, static_argnames=("row_chunk", "nm"))
def mrp_compensate(
    w: jax.Array,
    hinv: jax.Array,
    idx: jax.Array,
    valid: jax.Array,
    row_chunk: Optional[int] = None,
    nm: Optional[Tuple[int, int]] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Apply Eq. (13) compensation for the pruned sets given per row.

    Args:
      w:     (n, m) weights (pruned slots may hold any value; they are
             zeroed exactly by the optimal δw).
      hinv:  (m, m) dampened inverse Hessian.
      idx:   (n, k_max) per-row pruned columns (padded).
      valid: (n, k_max) validity of idx slots.
      row_chunk: process rows in chunks of this size (memory control for
             the (chunk, k, k) gather); None = :func:`auto_row_chunk`.
      nm:    (N, M) when every row prunes exactly N of each leading group
             of M columns (see :func:`_gather_submatrix`).

    Returns:
      (w_new, loss_per_row) — w_new has *exact* zeros at pruned slots;
      loss_per_row is Eq. (12)'s per-row L* (float32, shape (n,)).
    """
    n, m = w.shape
    w32 = w.astype(jnp.float32)
    hinv = hinv.astype(jnp.float32)
    if row_chunk is None:
        row_chunk = auto_row_chunk(n, idx.shape[1], m)

    def solve_rows(w_rows, idx_rows, valid_rows):
        a = _gather_submatrix(hinv, idx_rows, valid_rows, nm)      # (c,k,k)
        wp = jnp.take_along_axis(w_rows, idx_rows, axis=1)
        wp = jnp.where(valid_rows, wp, 0.0)                        # (c,k)
        # A is a principal submatrix of a PD matrix ⇒ PD ⇒ Cholesky solve.
        chol = jax.scipy.linalg.cho_factor(a, lower=True)
        z = jax.scipy.linalg.cho_solve(chol, wp[..., None])[..., 0]  # (c,k)
        z = jnp.where(valid_rows, z, 0.0)
        loss = 0.5 * jnp.sum(z * wp, axis=1)                       # (c,)
        # Scatter z back to full width and do ONE dense matmul with Hinv
        # (a TPU's default f32 matmul is one bf16 pass: see COMP_PRECISION)
        zfull = jnp.zeros_like(w_rows).at[
            jnp.arange(w_rows.shape[0])[:, None], idx_rows
        ].add(jnp.where(valid_rows, z, 0.0))
        delta = -jnp.matmul(zfull, hinv, precision=COMP_PRECISION)
        return w_rows + delta, loss

    if row_chunk is None or row_chunk >= n:
        w_new, loss = solve_rows(w32, idx, valid)
    else:
        pad = (-n) % row_chunk
        wp_ = jnp.pad(w32, ((0, pad), (0, 0)))
        ip_ = jnp.pad(idx, ((0, pad), (0, 0)))
        vp_ = jnp.pad(valid, ((0, pad), (0, 0)))
        nb = (n + pad) // row_chunk
        w_new, loss = jax.lax.map(
            lambda args: solve_rows(*args),
            (
                wp_.reshape(nb, row_chunk, m),
                ip_.reshape(nb, row_chunk, -1),
                vp_.reshape(nb, row_chunk, -1),
            ),
        )
        w_new = w_new.reshape(-1, m)[:n]
        loss = loss.reshape(-1)[:n]

    # Enforce exact zeros at pruned slots (δw analytically cancels w there;
    # this removes residual float error).  A float scatter-add: a bool
    # scatter-max compiles ~10x slower for a TPU.
    mask = jnp.zeros((n, m), jnp.float32).at[
        jnp.arange(n)[:, None], idx
    ].add(valid.astype(jnp.float32)) > 0
    w_new = jnp.where(mask, 0.0, w_new)
    return w_new.astype(w.dtype), loss


def mrp_compensate_mask(
    w: jax.Array,
    hinv: jax.Array,
    mask: jax.Array,
    k_max: Optional[int] = None,
    row_chunk: Optional[int] = None,
    nm: Optional[Tuple[int, int]] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Convenience wrapper: boolean mask (True = pruned) → Eq. (13).

    ``k_max`` defaults to the concrete per-row max (host sync + bucketing).
    ``nm`` declares the mask N:M over its leading ``k_max / N`` groups
    (exact count per group — see :func:`_gather_submatrix`).
    """
    if k_max is None:
        k_max = masks_lib.bucket_k(masks_lib.max_row_count(mask))
    k_max = min(int(k_max), mask.shape[1])
    idx, valid = masks_lib.padded_row_indices(mask, k_max)
    return mrp_compensate(w, hinv, idx, valid, row_chunk=row_chunk, nm=nm)


# ----------------------------------------------------------------------
# Eq. (12) losses for N:M combination enumeration (Solution 𝔐 for masks)
# ----------------------------------------------------------------------
def nm_combinations(n_prune: int, m_group: int) -> jnp.ndarray:
    """All C(M,N) index combinations, shape (n_combos, N), int32."""
    combos = list(itertools.combinations(range(m_group), n_prune))
    return jnp.asarray(combos, dtype=jnp.int32)


@functools.partial(jax.jit, static_argnames=("n_prune", "m_group"))
def nm_group_losses(
    w: jax.Array, hinv: jax.Array, n_prune: int, m_group: int
) -> jax.Array:
    """Eq. (12) loss of every pruning combination in every M-group.

    Interactions *within* a group are exact (the (EᵀHinvE)⁻¹ term);
    groups are treated independently (paper Sec. 4.2.1: 6^G joint search
    is unaffordable, so the paper also scopes 𝔐 to within-group).

    Returns losses of shape (n, G, n_combos).
    """
    n, m = w.shape
    if m % m_group:
        raise ValueError(f"cols {m} not divisible by M={m_group}")
    g = m // m_group
    combos = nm_combinations(n_prune, m_group)             # (C, N)
    ncombo = combos.shape[0]

    w32 = w.astype(jnp.float32).reshape(n, g, m_group)
    # Per-group Hinv sub-blocks: columns of group j are j*M + [0..M).
    base = (jnp.arange(g, dtype=jnp.int32) * m_group)[:, None]          # (G,1)
    gcols = base + jnp.arange(m_group, dtype=jnp.int32)[None, :]        # (G,M)
    hg = hinv[gcols[:, :, None], gcols[:, None, :]].astype(jnp.float32)  # (G,M,M)

    # A_c = hg[combo, combo] for each combo: (G, C, N, N)
    a = hg[:, combos[:, :, None], combos[:, None, :]]                  # (G,C,N,N)
    # w_c: (n, G, C, N)
    wc = w32[:, :, combos]                                             # (n,G,C,N)
    # Solve A_c z = w_c batched; N is tiny (e.g. 2) so this is cheap.
    a_b = jnp.broadcast_to(a[None], (n, g, ncombo, n_prune, n_prune))
    z = jnp.linalg.solve(a_b, wc[..., None])[..., 0]
    loss = 0.5 * jnp.sum(z * wc, axis=-1)                              # (n,G,C)
    return loss


@functools.partial(jax.jit, static_argnames=("n_prune", "m_group"))
def select_nm_mask_mrp(
    w: jax.Array, hinv: jax.Array, n_prune: int, m_group: int
) -> jax.Array:
    """Solution 𝔐 mask: per group, pick the combination minimizing Eq. (12)."""
    n, m = w.shape
    losses = nm_group_losses(w, hinv, n_prune, m_group)   # (n,G,C)
    best = jnp.argmin(losses, axis=-1)                    # (n,G)
    combos = nm_combinations(n_prune, m_group)            # (C,N)
    chosen = combos[best]                                 # (n,G,N)
    onehot = jax.nn.one_hot(chosen, m_group, dtype=jnp.float32).sum(-2) > 0
    return onehot.reshape(n, m)


# ----------------------------------------------------------------------
# Reference-style direct per-row solution (oracle for tests; no padding)
# ----------------------------------------------------------------------
def mrp_row_reference(w_row, hinv, pruned_cols):
    """Literal Eq. (13)/(12) for ONE row — used as a test oracle.

    NumPy-style (no jit); pruned_cols: 1D int array.
    """
    import numpy as np

    w_row = np.asarray(w_row, np.float64)
    hinv = np.asarray(hinv, np.float64)
    p = np.asarray(pruned_cols, np.int64)
    if p.size == 0:
        return w_row.copy(), 0.0
    wp = w_row[p]                                   # (k,)
    a = hinv[np.ix_(p, p)]                          # (k,k)
    z = np.linalg.solve(a, wp)
    delta = -(z @ hinv[p, :])                       # (m,)
    loss = 0.5 * float(wp @ z)
    out = w_row + delta
    out[p] = 0.0
    return out, loss
