"""The plain reference of the 2:4 SM prune: float64 NumPy.

For one projection it takes the calibration inputs the reference forward
(``forward.block``) feeds it, forms H = 2 X^T X / T, and runs the paper's
SM sweep (Algorithm 1) row by row: per column block, the Eq. (14) score
2:4-selects the two lowest of each group of four, then every row is
re-solved (Eq. 13) against its whole accumulated mask.  Copied from the
repository's smoke script and solver oracle, so that it imports nothing
of the program under test.
"""

from __future__ import annotations

import numpy as np


def dampened_inverse(h: np.ndarray, gamma: float) -> np.ndarray:
    """(H + gamma * mean(diag H) * I)^-1, the dampening relative to the
    mean diagonal."""
    m = h.shape[0]
    damp = max(gamma * float(np.mean(np.diag(h))), 1e-8)
    return np.linalg.inv(h + damp * np.eye(m))


def mrp_row(w_row: np.ndarray, hinv: np.ndarray, pruned: np.ndarray):
    """Eq. (13) for one row: the optimal update that zeroes the columns
    ``pruned`` and moves the rest to compensate."""
    if pruned.size == 0:
        return w_row.copy()
    wp = w_row[pruned]
    z = np.linalg.solve(hinv[np.ix_(pruned, pruned)], wp)
    out = w_row - z @ hinv[pruned, :]
    out[pruned] = 0.0
    return out


def sm_sweep(w0: np.ndarray, h: np.ndarray, blocksize: int, gamma: float,
             n_prune: int = 2, group: int = 4):
    """The SM sweep on the rows of ``w0`` (n, m): returns the pruned
    weights and the mask (True = pruned)."""
    hinv = dampened_inverse(h, gamma)
    diag = np.diag(hinv)
    w = w0.astype(np.float64).copy()
    n, m = w.shape
    blocksize = min(blocksize, m)
    mask = np.zeros((n, m), bool)
    for c0 in range(0, m, blocksize):
        cols = slice(c0, c0 + blocksize)
        score = (w[:, cols] ** 2 / (2.0 * diag[cols])).reshape(n, -1, group)
        low = np.argsort(score, axis=-1)[..., :n_prune]
        blk = np.zeros(score.shape, bool)
        np.put_along_axis(blk, low, True, axis=-1)
        mask[:, cols] = blk.reshape(n, -1)
        for q in range(n):
            w[q] = mrp_row(w[q], hinv, np.nonzero(mask[q])[0])
    return w, mask


def recon_error(w: np.ndarray, w0: np.ndarray, h: np.ndarray) -> float:
    """The layer objective 1/2 tr(dW H dW^T) over the given rows."""
    d = np.asarray(w, np.float64) - w0
    return 0.5 * float(np.einsum("ij,jk,ik->", d, h, d))


def project(w0: np.ndarray, hinv: np.ndarray, mask: np.ndarray):
    """The optimal weights for a given mask: each row of ``w0`` with the
    columns ``mask`` zeroed and the rest compensated (Eq. 13 from the
    dense row).  The sweep's output equals this for its own final mask,
    since each of its re-solves projects onto a smaller subspace."""
    return np.stack([mrp_row(w0[q], hinv, np.nonzero(mask[q])[0])
                     for q in range(w0.shape[0])])


def round_fp8(w: np.ndarray) -> np.ndarray:
    """Each row rounded to float8 e4m3 with a scale of its own (largest
    magnitude to 448): the precision below bfloat16."""
    import ml_dtypes
    s = np.max(np.abs(w), axis=1, keepdims=True) / 448.0
    s = np.where(s > 0, s, 1.0)
    return (w / s).astype(ml_dtypes.float8_e4m3fn).astype(np.float64) * s
