"""The SM sweep of every row of a projection, on the device in float32.

``sm_sweep.py`` is the float64 reference of a sample of rows.  Through
how many rows a block's output flows, all of them are needed to carry
the calibration inputs on to the next block, and float64 on the host
takes minutes for one block.  This is the same sweep, vectorised over
rows at ``Precision.HIGHEST``: per column block the Eq. (14) score
2:4-selects the two lowest of each group of four, then every row is
re-solved (Eq. 13) against its whole accumulated mask.  Its numbers
agree with the float64 sweep's to float32 rounding (tested at smoke
size); it imports nothing of the program under test.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from reference import sm_sweep

# rows per chunk keep the (rows, k, m) selections under this many bytes
CHUNK_BYTES = 1 << 28


def sweep(w0, h: np.ndarray, blocksize: int, gamma: float,
          n_prune: int = 2, group: int = 4):
    """Prune every row of ``w0`` (n, m; rows are outputs) against the
    Hessian ``h`` (float64); returns the pruned rows, float32."""
    import jax
    import jax.numpy as jnp
    hinv = jnp.asarray(sm_sweep.dampened_inverse(h, gamma), jnp.float32)
    w = jnp.asarray(w0, jnp.float32)
    n, m = w.shape
    blocksize = min(blocksize, m)
    idx = jnp.zeros((n, 0), jnp.int32)
    with jax.default_matmul_precision("highest"):
        for c0 in range(0, m, blocksize):
            w, idx = _block(w, hinv, idx, c0=c0, bs=blocksize,
                            n_prune=n_prune, group=group)
    return w


@functools.partial(jax.jit, static_argnames=("c0", "bs", "n_prune", "group"))
def _block(w, hinv, idx, *, c0: int, bs: int, n_prune: int, group: int):
    hi = jax.lax.Precision.HIGHEST
    n, m = w.shape
    diag = jnp.diagonal(hinv)[c0:c0 + bs]
    score = (w[:, c0:c0 + bs] ** 2 / (2.0 * diag)).reshape(n, -1, group)
    low = jnp.sort(jnp.argsort(score, axis=-1)[..., :n_prune], axis=-1)
    base = c0 + group * jnp.arange(bs // group, dtype=jnp.int32)
    new = (base[None, :, None] + low.astype(jnp.int32)).reshape(n, -1)
    idx = jnp.concatenate([idx, new], axis=1)
    k = idx.shape[1]
    chunk = int(max(1, min(n, CHUNK_BYTES // (4 * k * m))))
    pad = (-n) % chunk
    cols = jnp.arange(m, dtype=jnp.int32)

    def rows(args):
        wc, ic = args                                     # (c, m), (c, k)
        sel = hinv[ic]                                    # (c, k, m)
        onehot = (ic[..., None] == cols).astype(jnp.float32)
        a = jnp.einsum("ckm,cjm->ckj", sel, onehot, precision=hi)
        wp = jnp.take_along_axis(wc, ic, axis=1)
        chol = jax.scipy.linalg.cho_factor(a, lower=True)
        z = jax.scipy.linalg.cho_solve(chol, wp[..., None])[..., 0]
        return wc - jnp.einsum("ck,ckm->cm", z, sel, precision=hi)

    wp_ = jnp.pad(w, ((0, pad), (0, 0)))
    ip_ = jnp.pad(idx, ((0, pad), (0, 0)))
    nb = (n + pad) // chunk
    out = jax.lax.map(rows, (wp_.reshape(nb, chunk, m),
                             ip_.reshape(nb, chunk, k)))
    out = out.reshape(-1, m)[:n]
    pruned = jnp.zeros((n, m), bool).at[
        jnp.arange(n)[:, None], idx].set(True)
    return jnp.where(pruned, 0.0, out), idx
