"""Calibration Hessian accumulation: H = 2 x xᵀ (+ γ I).

For the layer-wise quadratic loss L'(w) = ‖w x‖² the Hessian w.r.t. any
weight row is H = 2 x xᵀ (paper Sec. 2.3.1). We accumulate it streaming
over calibration batches so the full activation matrix never has to be
materialized (SparseGPT does the same).

Numerical conventions (shared by SparseGPT's public code and this paper):
  - accumulate in float32 regardless of activation dtype;
  - normalize by the running number of columns (tokens) so magnitudes stay
    bounded — scaling H by a constant does not change the solutions of
    Eq. (11)–(14) beyond the dampening trade-off, but keeps γ comparable
    across layers;
  - dampening (Remark 4.1): γ · mean(diag H) added to the diagonal.

Distributed: each data-parallel shard accumulates its local H and the
results are summed with `jax.lax.psum` (see core.distributed) — the sums
commute with the normalization here because we track token counts.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import note_trace


# The Hessian products are f32 at full precision: a TPU's default f32
# matmul is one bf16 pass, which rounds f32 activations before the
# dampened inverse amplifies the error.
HIGHEST = jax.lax.Precision.HIGHEST


def token_outer_product(x: jax.Array) -> jax.Array:
    """2 · x xᵀ for x of shape (m, B) — float32, the paper's Hessian term."""
    x32 = x.astype(jnp.float32)
    return 2.0 * jnp.matmul(x32, x32.T, precision=HIGHEST)


@jax.jit
def _prune_hessian_update(h: jax.Array, count: jax.Array, x: jax.Array):
    """Numerically stable streaming mean of 2xxᵀ over tokens.

    Keeps H as the *mean* over tokens seen so far: H_n = H_{n-1} * (n_prev/n)
    + 2 x xᵀ / n. Equivalent to dividing the total sum by total tokens.
    """
    note_trace("hessian")
    with jax.named_scope("prune_hessian"):
        x32 = x.astype(jnp.float32)
        b = x32.shape[1]
        new_count = count + b
        scale_old = count / new_count
        h = h * scale_old + (2.0 / new_count) * jnp.matmul(
            x32, x32.T, precision=HIGHEST)
        return h, new_count


@jax.jit
def _prune_hessian_update_weighted(h: jax.Array, count: jax.Array,
                                   x: jax.Array, wts: jax.Array):
    """Weighted streaming mean: H = Σ_t w_t · 2 x_t x_tᵀ / Σ_t w_t.

    Used for MoE expert linears where each expert only sees its routed
    tokens (weights are routing validity 0/1 or gate probabilities).
    """
    note_trace("hessian")
    with jax.named_scope("prune_hessian"):
        x32 = x.astype(jnp.float32)
        w32 = wts.astype(jnp.float32)
        b = jnp.sum(w32)
        new_count = count + b
        denom = jnp.maximum(new_count, 1e-12)
        scale_old = count / denom
        xw = x32 * w32[None, :]
        h = h * scale_old + (2.0 / denom) * jnp.matmul(
            xw, x32.T, precision=HIGHEST)
        return h, new_count


@jax.jit
def _prune_hessian_merge(hs: jax.Array, cs: jax.Array):
    """Weighted mean of stacked (S, m, m) Hessians by (S,) token counts."""
    note_trace("hessian")
    with jax.named_scope("prune_hessian"):
        total = jnp.sum(cs)
        h = (jnp.einsum("s,sij->ij", cs, hs, precision=HIGHEST)
             / jnp.maximum(total, 1.0))
        return jnp.where(total > 0, h, hs[0]), total


@dataclasses.dataclass
class HessianAccumulator:
    """Streaming accumulator for the layer Hessian H = mean_t 2 x_t x_tᵀ.

    Usage:
        acc = HessianAccumulator(m)
        for batch in calib_batches:       # batch: (m, B) layer inputs
            acc.update(batch)
        h = acc.finalize()                # (m, m) float32
    """

    dim: int
    h: Optional[jax.Array] = None
    count: Optional[jax.Array] = None

    def __post_init__(self):
        if self.h is None:
            self.h = jnp.zeros((self.dim, self.dim), jnp.float32)
        if self.count is None:
            self.count = jnp.zeros((), jnp.float32)

    def update(self, x: jax.Array) -> None:
        """x: (m, B) — columns are calibration tokens for this layer."""
        if x.ndim != 2 or x.shape[0] != self.dim:
            raise ValueError(f"expected ({self.dim}, B) activations, got {x.shape}")
        self.h, self.count = _prune_hessian_update(self.h, self.count, x)

    def update_tokens(self, tokens_first: jax.Array) -> None:
        """Convenience for (num_tokens, m) layouts (batch*seq flattened)."""
        self.update(tokens_first.T)

    def update_weighted(self, x: jax.Array, weights: jax.Array) -> None:
        """Weighted update. x: (m, B); weights: (B,) non-negative.

        Equivalent to ``update`` restricted to the tokens with weight 1 —
        used for MoE expert layers (routing validity masks / gate probs).
        """
        if x.ndim != 2 or x.shape[0] != self.dim:
            raise ValueError(f"expected ({self.dim}, B) activations, got {x.shape}")
        if weights.shape != (x.shape[1],):
            raise ValueError(
                f"weights {weights.shape} incompatible with x {x.shape}")
        self.h, self.count = _prune_hessian_update_weighted(
            self.h, self.count, x, weights)

    def merge(self, other: "HessianAccumulator") -> "HessianAccumulator":
        """Merge two accumulators (e.g. from different data shards)."""
        total = self.count + other.count
        h = jnp.where(
            total > 0,
            (self.h * self.count + other.h * other.count) / jnp.maximum(total, 1.0),
            self.h,
        )
        return HessianAccumulator(self.dim, h=h, count=total)

    @staticmethod
    def merge_many(accs: "list[HessianAccumulator]") -> "HessianAccumulator":
        """Token-weighted mean of N accumulators in one fused device op.

        Equivalent to folding :meth:`merge` pairwise, but a single
        einsum over the stacked Hessians — no host round-trips, one
        dispatch regardless of shard count (the calibration-sharding
        merge path, core.pipeline).
        """
        if len(accs) == 1:
            return accs[0]
        dim = accs[0].dim
        if any(a.dim != dim for a in accs):
            raise ValueError(
                f"cannot merge accumulators of dims {[a.dim for a in accs]}")
        hs, cs = _prune_hessian_merge(jnp.stack([a.h for a in accs]),
                                      jnp.stack([a.count for a in accs]))
        return HessianAccumulator(dim, h=hs, count=cs)

    def finalize(self) -> jax.Array:
        return self.h


def dampened_inverse(h: jax.Array, gamma: float = 0.01) -> jax.Array:
    """(H + γ·mean(diag H)·I)⁻¹ via Cholesky (Remark 4.1).

    γ is relative to the mean diagonal (SparseGPT's `percdamp` convention)
    so the same γ works across layers of very different activation scale.
    Falls back to increasing dampening if the factorization produces
    non-finite values (rank-deficient calibration sets).
    """
    m = h.shape[0]
    damp = gamma * jnp.mean(jnp.diag(h))
    # Dead input channels (all-zero activations) make H singular even after
    # relative dampening if mean diag is 0; add tiny absolute floor.
    damp = jnp.maximum(damp, 1e-8)
    hd = h + damp * jnp.eye(m, dtype=h.dtype)
    # chol-solve against I == inverse; cho_factor keeps it O(m^3/3).
    chol = jax.scipy.linalg.cho_factor(hd, lower=True)
    inv = jax.scipy.linalg.cho_solve(chol, jnp.eye(m, dtype=h.dtype))
    return inv


def dampened_inverse_np(h: np.ndarray, gamma: float = 0.01) -> np.ndarray:
    """NumPy twin of :func:`dampened_inverse` for host-side tooling."""
    m = h.shape[0]
    damp = max(gamma * float(np.mean(np.diag(h))), 1e-8)
    hd = h + damp * np.eye(m, dtype=h.dtype)
    return np.linalg.inv(hd)
