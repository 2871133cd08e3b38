"""The Multiple Removal Problem (MRP) — the paper's core contribution.

Closed-form optimal solution (Sec. 4.1). For each row q with pruned column
set P (selector E ∈ R^{m×k}), with H = 2xxᵀ + γI and Hinv = H⁻¹:

  Eq. (13):  δw*[q,:] = − w[q,P] · (Eᵀ Hinv E)⁻¹ · Eᵀ Hinv
  Eq. (12):  L*_q     = ½ · w[q,P] · (Eᵀ Hinv E)⁻¹ · w[q,P]ᵀ

TPU-native batching (DESIGN.md §4.1): instead of the paper's per-row GPU
loop we batch the rows and solve them together.

Bordered factor (:func:`mrp_border_rows`): the path N:M and row-balanced
masks take (a static pruned count per row and column block).  A row's
pruned columns are kept in ascending order and each of Algorithm 1's
column blocks appends its own after the earlier ones, so

  A_q(b) = [[A_q(b-1), B_q], [B_qᵀ, C_q]],  B_q = Hinv[P_old, P_new],
                                            C_q = Hinv[P_new, P_new]

and its Cholesky factor is the previous one plus a border:

  L21 = (L11⁻¹ B_q)ᵀ,  L22 = chol(C_q − L21 L21ᵀ).

The previous block left exact zeros at P_old, so the right-hand side is
[0; v] with v = w[q, P_new], and Eq. (13)/(12) read

  z_new = L22⁻ᵀ L22⁻¹ v,  z_old = −L11⁻ᵀ L21ᵀ z_new,  L_q = ½ ⟨z_new, v⟩:

the same solution as a fresh solve, at a 128-wide factor per block.
The factor is carried as its inverse, so the border's triangular
solves are matmuls (one Cholesky and one triangular inverse of the
128-wide L22 a block).

Re-solve (:func:`mrp_compensate`): any mask, e.g. unstructured with a
global count per block, whose per-row counts vary.  Every row's pruned
set is padded to a common k_max and ONE batched symmetric solve runs
over all rows:

  A_q = Hinv[P_q, P_q]   (k_max×k_max, identity-padded)
  z_q = A_q⁻¹ w[q, P_q]  (zero-padded rhs ⇒ padding rows solve to zero)
  L_q = ½ ⟨z_q, w[q, P_q]⟩

Identity padding makes the padded solve *exactly* equal to the unpadded
one, so this is the paper's optimal solution, not an approximation.

Both paths then compensate with one dense matmul,
δw[q, :] = − scatter(z_q) @ Hinv, and leave exact zeros at the pruned
slots.  Rows are independent (Remark 4.2) ⇒ the row dimension shards
freely over the `model` mesh axis (core.distributed).
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
def _group_offsets(idx: jax.Array, nm: Tuple[int, int]) -> jax.Array:
    """(n, k) columns of an N:M set → (n, k / N, N) offsets inside their
    group: slot ``i`` lies in column group ``i // N``."""
    n_per, m_grp = nm
    g = idx.shape[1] // n_per
    return (idx.reshape(idx.shape[0], g, n_per)
            - m_grp * jnp.arange(g, dtype=idx.dtype)[None, :, None])


def _cross_submatrix(hsub: jax.Array, ridx: jax.Array, cidx: jax.Array,
                     nm: Optional[Tuple[int, int]] = None) -> jax.Array:
    """hsub[ridx_q, cidx_q] for every row q.

    hsub: (a, b); ridx: (n, kr) in [0, a); cidx: (n, kc) in [0, b)
    → (n, kr, kc).

    ``nm=(N, M)`` declares N:M structure on both index sets: slot ``i``
    lies in column group ``i // N`` (each group of M columns holds
    exactly N, listed in order).  A row's columns then differ only by
    their offset inside the group, so the submatrix is built by
    selecting among the M candidate rows/columns of each group — exact
    (a sum of one term and zeros) and free of the per-element gather,
    which a TPU runs at a few hundred million elements per second
    (seconds per layer solve at a published width).
    """
    if nm is None:
        return hsub[ridx[:, :, None], cidx[:, None, :]]
    m_grp = nm[1]
    c, kr = ridx.shape
    roff, coff = _group_offsets(ridx, nm), _group_offsets(cidx, nm)
    gr, gc = roff.shape[1], coff.shape[1]
    hg = hsub[:gr * m_grp].reshape(gr, m_grp, -1)         # (gr, M, b)
    rows = sum(jnp.where((roff == a)[..., None], hg[None, :, None, a], 0.0)
               for a in range(m_grp))                     # (c, gr, N, b)
    cols = rows.reshape(c, kr, -1)[:, :, :gc * m_grp].reshape(
        c, kr, gc, m_grp)
    sub = sum(jnp.where((coff == b)[:, None], cols[..., b, None], 0.0)
              for b in range(m_grp))                      # (c, kr, gc, N)
    return sub.reshape(c, kr, cidx.shape[1])


def _gather_submatrix(hinv: jax.Array, idx: jax.Array,
                      valid: jax.Array) -> jax.Array:
    """A = Hinv[idx, idx] with identity padding on invalid slots.

    hinv: (m, m); idx: (n, k); valid: (n, k) → (n, k, k).
    """
    eye = jnp.eye(idx.shape[1], dtype=hinv.dtype)
    vv = valid[:, :, None] & valid[:, None, :]
    return jnp.where(vv, _cross_submatrix(hinv, idx, idx), eye[None])


def _compensate(w_rows: jax.Array, hinv: jax.Array, idx: jax.Array,
                z: jax.Array, width: Optional[int] = None,
                nm: Optional[Tuple[int, int]] = None) -> jax.Array:
    """w + δw of Eq. (13): place z (rows, k) at idx, every column under
    ``width`` (default: all), and do ONE dense matmul with Hinv's first
    ``width`` rows (a TPU's default f32 matmul is one bf16 pass: see
    COMP_PRECISION).  ``nm`` places z by an exact select per group, as
    :func:`_cross_submatrix` reads, in place of a scatter."""
    c = w_rows.shape[0]
    width = hinv.shape[0] if width is None else width
    if nm is None:
        zfull = jnp.zeros((c, width), z.dtype).at[
            jnp.arange(c)[:, None], idx].add(z)
    else:
        off = _group_offsets(idx, nm)                     # (c, g, N)
        zg = z.reshape(off.shape)
        cand = jnp.arange(nm[1], dtype=off.dtype)
        zfull = jnp.sum(jnp.where(off[..., None] == cand, zg[..., None],
                                  0.0), axis=2).reshape(c, -1)
        zfull = jnp.pad(zfull, ((0, 0), (0, width - zfull.shape[1])))
    return w_rows - jnp.matmul(zfull, hinv[:width],
                               precision=COMP_PRECISION)


# per-chunk working set of a batched solve (its (rows, k, k) factor and
# the submatrices around it): rows are chunked to stay under this
ROW_CHUNK_BYTES = 1 << 30


def auto_row_chunk(n: int, k: int, m: int) -> Optional[int]:
    """Rows per chunk that keep one chunk's (rows, k, max(k, m)) f32
    working set under :data:`ROW_CHUNK_BYTES`; None when all rows fit."""
    rows = max(8, ROW_CHUNK_BYTES // (4 * k * max(k, m)))
    return None if rows >= n else rows


@functools.partial(jax.jit, static_argnames=("row_chunk",))
def mrp_compensate(
    w: jax.Array,
    hinv: jax.Array,
    idx: jax.Array,
    valid: jax.Array,
    row_chunk: Optional[int] = None,
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
        a = _gather_submatrix(hinv, idx_rows, valid_rows)      # (c,k,k)
        wp = jnp.take_along_axis(w_rows, idx_rows, axis=1)
        wp = jnp.where(valid_rows, wp, 0.0)                        # (c,k)
        # A is a principal submatrix of a PD matrix ⇒ PD ⇒ Cholesky solve.
        chol = jax.scipy.linalg.cho_factor(a, lower=True)
        z = jax.scipy.linalg.cho_solve(chol, wp[..., None])[..., 0]  # (c,k)
        z = jnp.where(valid_rows, z, 0.0)
        loss = 0.5 * jnp.sum(z * wp, axis=1)                       # (c,)
        return _compensate(w_rows, hinv, idx_rows, z), loss

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
    row_chunk: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Convenience wrapper: boolean mask (True = pruned) → Eq. (13).

    Rows are padded to the concrete per-row max (host sync + bucketing).
    """
    k_max = min(masks_lib.bucket_k(masks_lib.max_row_count(mask)),
                mask.shape[1])
    idx, valid = masks_lib.padded_row_indices(mask, k_max)
    return mrp_compensate(w, hinv, idx, valid, row_chunk=row_chunk)


# ----------------------------------------------------------------------
# Bordered factor across Algorithm 1's column blocks (static k per block)
# ----------------------------------------------------------------------
def border_row_chunk(n: int, k: int, m: int, bs: int) -> Optional[int]:
    """Rows per chunk of the bordered path: one chunk's (rows, k, k) f32
    factor, a column block's (rows, k, bs) working set and its (rows, m)
    weights stay under :data:`ROW_CHUNK_BYTES`.  The chunks are evened
    out (less than one padded row per chunk); None when all rows fit."""
    rows = max(8, ROW_CHUNK_BYTES // (4 * (k * (k + bs) + m)))
    if rows >= n:
        return None
    chunks = -(-n // rows)
    return -(-n // chunks)


def _lower_inverse(low: jax.Array) -> jax.Array:
    """Inverse of batched lower-triangular (..., k, k) matrices, by
    doubling the width of its diagonal blocks.

    With D_b the block diagonal of width b of L and E_b the entries of
    L that join two neighbouring blocks into one of width 2b,
    D_2b = D_b + E_b and (D_b⁻¹ E_b)² = 0, so
    D_2b⁻¹ = D_b⁻¹ − D_b⁻¹ E_b D_b⁻¹, from D_1⁻¹ = 1 / diag(L): log2(k)
    rounds of two batched matmuls.  (A TPU runs a 128-wide triangular
    inverse as a slow sequential kernel, and a CPU's batched triangular
    solve against many right-hand sides stalls for seconds when other
    processes hold its cores.)"""
    r = jnp.arange(low.shape[-1])
    eye = r[:, None] == r[None, :]
    inv = jnp.where(eye, 1.0 / jnp.where(eye, low, 1.0), 0.0)
    b = 1
    while b < r.size:
        join = ((r[:, None] // (2 * b) == r[None, :] // (2 * b))
                & (r[:, None] // b != r[None, :] // b))
        inv = inv - _mm("...ij,...jk->...ik", inv,
                        _mm("...ij,...jk->...ik",
                            jnp.where(join, low, 0.0), inv))
        b *= 2
    return inv


def _mm(spec: str, a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def mrp_border_rows(
    linv: jax.Array,
    w_rows: jax.Array,
    hinv: jax.Array,
    idx: jax.Array,
    k_old: int,
    c0: int,
    bs: int,
    nm: Optional[Tuple[int, int]] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Eq. (13) for one column block, extending the rows' factor.

    The factor is carried as its inverse M = L⁻¹ (lower triangular):
    with M11 = L11⁻¹ the border is L21ᵀ = M11 B, and the new rows of M
    are M22 = L22⁻¹ and M21 = −M22 L21 M11.  A block then costs batched
    matmuls and one kn-wide Cholesky and triangular inverse.

    Args:
      linv:   (c, K, K) f32; its leading (k_old, k_old) block is the
              inverse of the lower Cholesky factor of Hinv[P_old, P_old]
              for each row.
      w_rows: (c, m) f32 with exact zeros at P_old.
      hinv:   (m, m) f32 dampened inverse Hessian.
      idx:    (c, k_old + kn) pruned columns in ascending order: P_old,
              then this block's kn columns, all in [c0, c0 + bs).
      nm:     (N, M) when every row prunes exactly N of each group of M
              (see :func:`_cross_submatrix`).

    Returns:
      (linv, w_rows, loss): the inverse factor of Hinv[P, P] in the
      leading (k_old + kn) block, the compensated weights (pruned slots
      hold float residue: the caller zeroes them, as the next block
      needs) and Eq. (12)'s per-row L* of this block, shape (c,).
    """
    k = idx.shape[1]
    old, new = idx[:, :k_old], idx[:, k_old:]
    loc = new - c0
    cmat = _cross_submatrix(hinv[c0:c0 + bs, c0:c0 + bs], loc, loc, nm)
    if k_old:
        m11 = linv[:, :k_old, :k_old]
        x = _mm("cij,cjk->cik", m11,                        # L21ᵀ
                _cross_submatrix(hinv[:c0, c0:c0 + bs], old, loc, nm))
        cmat = cmat - _mm("cki,ckj->cij", x, x)
    m22 = _lower_inverse(jnp.linalg.cholesky(cmat))
    v = jnp.take_along_axis(w_rows, new, axis=1)             # (c, kn)
    z = _mm("cji,cj->ci", m22, _mm("cij,cj->ci", m22, v))
    loss = 0.5 * jnp.sum(z * v, axis=1)                      # rhs [0; v]
    rows = m22
    if k_old:
        # read M11 for good before the rows are written: one in-place
        # update of the carried factor, no copy of it
        z_old = _mm("cji,cj->ci", m11, _mm("cki,ci->ck", x, z))
        m21 = -_mm("cij,cjk->cik", m22, _mm("cji,cjk->cik", x, m11))
        rows, z_old = jax.lax.optimization_barrier(
            (jnp.concatenate([m21, m22], axis=2), z_old))
        z = jnp.concatenate([-z_old, z], axis=1)
    linv = linv.at[:, k_old:k, :k].set(rows)
    return linv, _compensate(w_rows, hinv, idx, z, c0 + bs, nm), loss


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
