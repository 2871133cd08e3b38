"""The paper's math: MRP closed-form solution (core.mrp) vs oracles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import random_psd_hessian
from repro.core import masks as masks_lib
from repro.core import mrp
from repro.core.hessian import dampened_inverse


def _random_mask(rng, n, m, max_k):
    mask = np.zeros((n, m), bool)
    for i in range(n):
        k = rng.integers(0, max_k + 1)
        cols = rng.choice(m, size=k, replace=False)
        mask[i, cols] = True
    return mask


@pytest.mark.parametrize("n,m,max_k", [(8, 32, 6), (16, 64, 16), (5, 48, 1)])
def test_mrp_compensate_matches_rowwise_oracle(rng, n, m, max_k):
    """Batched padded solve == literal per-row Eq. (13)/(12)."""
    key = jax.random.key(n * m)
    w = jax.random.normal(key, (n, m))
    hinv = np.linalg.inv(np.asarray(
        random_psd_hessian(jax.random.key(1), m), np.float64))
    mask = _random_mask(rng, n, m, max_k)

    w_new, loss = mrp.mrp_compensate_mask(
        w, jnp.asarray(hinv, jnp.float32), jnp.asarray(mask))
    w_new = np.asarray(w_new)
    for i in range(n):
        ref_row, ref_loss = mrp.mrp_row_reference(
            np.asarray(w)[i], hinv, np.where(mask[i])[0])
        np.testing.assert_allclose(w_new[i], ref_row, atol=2e-4)
        np.testing.assert_allclose(float(loss[i]), ref_loss, rtol=2e-3,
                                   atol=1e-5)


def test_pruned_slots_exactly_zero(rng):
    n, m = 12, 40
    w = jax.random.normal(jax.random.key(0), (n, m))
    h = random_psd_hessian(jax.random.key(1), m)
    hinv = dampened_inverse(h)
    mask = jnp.asarray(_random_mask(rng, n, m, 10))
    w_new, _ = mrp.mrp_compensate_mask(w, hinv, mask)
    assert jnp.all(jnp.where(mask, w_new, 0.0) == 0.0)
    # unpruned weights moved (compensation is active)
    assert float(jnp.abs(jnp.where(mask, 0.0, w_new - w)).max()) > 0


def test_row_chunking_equivalent(rng):
    n, m = 16, 32
    w = jax.random.normal(jax.random.key(2), (n, m))
    hinv = dampened_inverse(random_psd_hessian(jax.random.key(3), m))
    mask = jnp.asarray(_random_mask(rng, n, m, 8))
    w_a, l_a = mrp.mrp_compensate_mask(w, hinv, mask)
    w_b, l_b = mrp.mrp_compensate_mask(w, hinv, mask, row_chunk=4)
    np.testing.assert_allclose(np.asarray(w_a), np.asarray(w_b), atol=1e-5)
    np.testing.assert_allclose(np.asarray(l_a), np.asarray(l_b), rtol=1e-4)


def test_srp_is_special_case():
    """Single pruned weight: MRP loss reduces to Eq. (14) = w²/(2·Hinv_jj)."""
    m = 24
    w = jax.random.normal(jax.random.key(4), (1, m))
    hinv = dampened_inverse(random_psd_hessian(jax.random.key(5), m))
    j = 7
    mask = jnp.zeros((1, m), bool).at[0, j].set(True)
    _, loss = mrp.mrp_compensate_mask(w, hinv, mask)
    expected = float(w[0, j]) ** 2 / (2.0 * float(hinv[j, j]))
    np.testing.assert_allclose(float(loss[0]), expected, rtol=1e-5)


def test_mrp_loss_beats_independent_srp_sum():
    """Eq. (12) with interactions ≤ sum of independent SRP losses is NOT
    generally true, but the achieved ‖δw x‖² of the JOINT solve must be ≤
    the error of applying SRP compensations independently (the paper's
    core advantage)."""
    m, n = 32, 6
    key = jax.random.key(6)
    w = jax.random.normal(key, (n, m))
    h = random_psd_hessian(jax.random.key(7), m)
    hinv = dampened_inverse(h, gamma=1e-4)
    rng = np.random.default_rng(1)
    mask = jnp.asarray(_random_mask(rng, n, m, 8))

    w_joint, _ = mrp.mrp_compensate_mask(w, hinv, mask)

    # independent SRP: each pruned weight compensated in isolation, summed
    w_srp = np.asarray(w, np.float64).copy()
    hinv64 = np.asarray(hinv, np.float64)
    for i, j in zip(*np.where(np.asarray(mask))):
        delta = -(float(w[i, j]) / hinv64[j, j]) * hinv64[j, :]
        w_srp[i] += delta
    w_srp[np.asarray(mask)] = 0.0

    h64 = np.asarray(h, np.float64)

    def recon(wn):
        d = np.asarray(wn, np.float64) - np.asarray(w, np.float64)
        return 0.5 * np.einsum("ij,jk,ik->", d, h64, d)

    assert recon(w_joint) <= recon(w_srp) + 1e-9


def test_nm_group_losses_and_mask():
    """Eq. (12) combo enumeration: losses positive, mask = argmin combo,
    exactly N pruned per group."""
    n, m = 10, 32
    w = jax.random.normal(jax.random.key(8), (n, m))
    hinv = dampened_inverse(random_psd_hessian(jax.random.key(9), m))
    losses = mrp.nm_group_losses(w, hinv, 2, 4)
    assert losses.shape == (n, 8, 6)
    assert bool(jnp.all(losses > 0))
    mask = mrp.select_nm_mask_mrp(w, hinv, 2, 4)
    assert masks_lib.validate_nm(np.asarray(mask), 2, 4)
    # chosen combo == argmin of enumerated losses
    best = jnp.argmin(losses, axis=-1)
    combos = mrp.nm_combinations(2, 4)
    chosen = combos[best]
    for i in range(n):
        for g in range(8):
            cols = set((4 * g + np.asarray(chosen[i, g])).tolist())
            got = set(np.where(np.asarray(mask[i, 4 * g:4 * g + 4]))[0]
                      + 4 * g)
            assert cols == got


def test_mm_mask_not_worse_than_sm_mask_on_average():
    """The 𝔐 mask minimizes Eq.(12) within each group exactly, so its
    summed group loss must be ≤ the 𝔖 (diagonal) mask's group loss."""
    n, m = 32, 64
    w = jax.random.normal(jax.random.key(10), (n, m))
    hinv = dampened_inverse(random_psd_hessian(jax.random.key(11), m))
    losses = mrp.nm_group_losses(w, hinv, 2, 4)        # (n, G, 6)

    mask_m = mrp.select_nm_mask_mrp(w, hinv, 2, 4)
    from repro.core.scores import obs_score
    from repro.core.masks import nm_mask_from_scores
    mask_s = nm_mask_from_scores(obs_score(w, hinv), 2, 4)

    def group_loss(mask):
        combos = np.asarray(mrp.nm_combinations(2, 4))
        mg = np.asarray(mask).reshape(n, -1, 4)
        total = 0.0
        for i in range(n):
            for g in range(mg.shape[1]):
                cols = tuple(np.where(mg[i, g])[0])
                ci = [t for t, c in enumerate(map(tuple, combos))
                      if c == cols][0]
                total += float(losses[i, g, ci])
        return total

    assert group_loss(mask_m) <= group_loss(mask_s) + 1e-6


@pytest.mark.parametrize("k", [1, 7, 32, 128])
def test_lower_inverse(k):
    """The doubling inverse of batched lower-triangular factors: lower
    triangular, and M L = I at f32 accuracy (widths that are not powers
    of two included)."""
    a = jax.random.normal(jax.random.key(k), (3, k, 2 * k))
    low = jnp.linalg.cholesky(jnp.einsum("cij,ckj->cik", a, a)
                              + k * jnp.eye(k))
    inv = np.asarray(mrp._lower_inverse(low), np.float64)
    assert np.all(np.triu(inv, 1) == 0.0)
    np.testing.assert_allclose(inv @ np.asarray(low, np.float64),
                               np.broadcast_to(np.eye(k), (3, k, k)),
                               atol=1e-5)


def test_border_rows_extends_the_factor():
    """Two bordered blocks give the same weights, loss and inverse
    factor as the re-solve against both blocks' columns, and the
    factor's leading block is that of the first block alone."""
    n, m, bs = 6, 32, 16
    hinv = dampened_inverse(random_psd_hessian(jax.random.key(9), m))
    w = jax.random.normal(jax.random.key(10), (n, m))
    mask = jnp.asarray(masks_lib.nm_mask_from_scores(
        jax.random.uniform(jax.random.key(11), (n, m)), 2, 4))
    idx, _ = masks_lib.padded_row_indices(mask, m // 2)
    k1, k = bs // 2, m // 2
    linv = jnp.zeros((n, k, k))
    m1 = jnp.where(jnp.arange(m) < bs, mask, False)
    w1, _ = mrp.mrp_compensate_mask(w, hinv, m1)
    linv, w_b, _ = mrp.mrp_border_rows(linv, w, hinv, idx[:, :k1], 0, 0,
                                       bs, (2, 4))
    w_b = jnp.where(m1, 0.0, w_b)
    np.testing.assert_allclose(np.asarray(w_b), np.asarray(w1), atol=1e-5)
    linv, w_b, loss = mrp.mrp_border_rows(linv, w_b, hinv, idx, k1, bs,
                                          bs, (2, 4))
    w_b = jnp.where(mask, 0.0, w_b)
    w2, loss2 = mrp.mrp_compensate_mask(w1, hinv, mask)
    np.testing.assert_allclose(np.asarray(w_b), np.asarray(w2), atol=1e-5)
    np.testing.assert_allclose(np.asarray(loss), np.asarray(loss2),
                               rtol=1e-4, atol=1e-6)
    a = np.asarray(hinv, np.float64)[np.asarray(idx)[:, :, None],
                                     np.asarray(idx)[:, None, :]]
    eye = np.broadcast_to(np.eye(k), (n, k, k))
    lv = np.asarray(linv, np.float64)
    np.testing.assert_allclose(lv @ a @ np.swapaxes(lv, 1, 2), eye,
                               atol=1e-5)
