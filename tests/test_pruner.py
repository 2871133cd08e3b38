"""Algorithm 1 (core.pruner): methods, sparsity exactness, orderings."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import random_psd_hessian
from repro.core import masks as masks_lib
from repro.core.pruner import METHODS, prune_matrix, reconstruction_error
from repro.core.sparsity import SparsitySpec


@pytest.fixture(scope="module")
def problem():
    key = jax.random.key(0)
    n, m = 32, 128
    w = jax.random.normal(key, (n, m)) * (
        1.0 + jnp.arange(m)[None, :] / m)     # mild column structure
    h = random_psd_hessian(jax.random.key(1), m)
    return w, h


@pytest.mark.parametrize("method", METHODS)
def test_nm_sparsity_exact(problem, method):
    w, h = problem
    res = prune_matrix(w, h, "2:4", method=method, blocksize=64)
    assert masks_lib.validate_nm(np.asarray(res.mask), 2, 4)
    assert bool(jnp.all(jnp.where(res.mask, res.w, 0.0) == 0.0))
    assert abs(res.sparsity - 0.5) < 1e-6


@pytest.mark.parametrize("method", ["magnitude", "wanda", "SS", "SM"])
def test_unstructured_sparsity_exact(problem, method):
    w, h = problem
    res = prune_matrix(w, h, "0.5", method=method, blocksize=64)
    n, m = w.shape
    assert int(np.asarray(res.mask).sum()) == pytest.approx(
        n * m // 2, abs=n)  # per-block rounding
    assert bool(jnp.all(jnp.where(res.mask, res.w, 0.0) == 0.0))


def test_m_mask_rejected_for_unstructured(problem):
    w, h = problem
    with pytest.raises(ValueError):
        prune_matrix(w, h, "0.5", method="MM")


def test_reconstruction_orderings(problem):
    """The paper's central claim at layer level:
    recon(SM) ≤ recon(SS) and recon(MM) ≤ recon(MS); compensated methods
    beat score-only baselines."""
    w, h = problem
    errs = {}
    for method in METHODS:
        res = prune_matrix(w, h, "2:4", method=method, blocksize=64)
        errs[method] = reconstruction_error(w, res.w, h)
    assert errs["SM"] <= errs["SS"] * 1.01
    assert errs["MM"] <= errs["MS"] * 1.01
    assert errs["SM"] <= errs["wanda"]
    assert errs["SM"] <= errs["magnitude"]
    assert errs["SS"] <= errs["magnitude"]


def test_unstructured_sm_beats_ss(problem):
    w, h = problem
    e = {}
    for method in ("SS", "SM"):
        res = prune_matrix(w, h, "0.5", method=method, blocksize=32)
        e[method] = reconstruction_error(w, res.w, h)
    assert e["SM"] <= e["SS"] * 1.01


def test_blocksize_all_vs_blocks(problem):
    """S=all (one block) must also satisfy SM ≤ SS; and both blockings
    produce valid N:M masks."""
    w, h = problem
    m = w.shape[1]
    for bs in (32, m):
        r_ss = prune_matrix(w, h, "2:4", method="SS", blocksize=bs)
        r_sm = prune_matrix(w, h, "2:4", method="SM", blocksize=bs)
        assert reconstruction_error(w, r_sm.w, h) <= \
            reconstruction_error(w, r_ss.w, h) * 1.01


def test_row_balanced_traceable(problem):
    """row_balanced unstructured pruning must be jit-able (static shapes,
    no host sync) — the distributed row-parallel path depends on it."""
    w, h = problem

    @jax.jit
    def run(w, h):
        res = prune_matrix(w, h, SparsitySpec.parse("0.5"), method="SM",
                           blocksize=64, row_balanced=True)
        return res.w, res.mask

    w_new, mask = run(w, h)
    assert (np.asarray(mask).sum(1) == w.shape[1] // 2).all()
    assert bool(jnp.all(jnp.where(mask, w_new, 0.0) == 0.0))


def test_sm_compensation_updates_left_blocks(problem):
    """SparseGPT freezes columns left of the current block; our SM must
    keep refining them (the paper's fix). Verify some weight in block 0
    changes again while pruning block 1."""
    w, h = problem
    res1 = prune_matrix(w, h, "2:4", method="SM", blocksize=64)
    # prune only the first 64 columns (one block) by slicing: first-block
    # compensation in isolation
    res_first = prune_matrix(w[:, :128], h[:128, :128], "2:4", method="SM",
                             blocksize=128)
    # the first block's unpruned weights in the full run differ from the
    # isolated run — proof the later block's solve updated them again
    m0 = ~np.asarray(res1.mask)[:, :64]
    a = np.asarray(res1.w)[:, :64][m0]
    b = np.asarray(res_first.w)[:, :64][m0]
    assert np.abs(a - b).max() > 1e-6


def _resolve_oracle(w, h, spec, method, blocksize, row_balanced):
    """Algorithm 1 with a full re-solve each column block: a loop of
    ``mrp.mrp_compensate_mask`` against the accumulated mask, each block
    selected from the oracle's own weights.  Returns the weights, mask
    and per-block losses, and the weights before the last block."""
    from repro.core import mrp
    from repro.core.hessian import dampened_inverse
    from repro.core.pruner import _score_mask_block

    spec = SparsitySpec.parse(spec)
    hinv = dampened_inverse(h)
    n, m = w.shape
    mask = jnp.zeros((n, m), bool)
    losses, w_prev = [], w
    for c0 in range(0, m, blocksize):
        wblk = w[:, c0:c0 + blocksize]
        if method == "SM":
            mblk = _score_mask_block(wblk, h, hinv, spec, "obs", c0,
                                     row_balanced)
        else:
            mblk = mrp.select_nm_mask_mrp(
                wblk, hinv[c0:c0 + blocksize, c0:c0 + blocksize],
                spec.n, spec.m)
        mask = mask.at[:, c0:c0 + blocksize].set(mblk)
        w_prev = w
        w, loss = mrp.mrp_compensate_mask(w, hinv, mask)
        losses.append(float(jnp.sum(loss)))
    return w, mask, losses, w_prev


@pytest.mark.parametrize("spec,method,row_balanced", [
    ("2:4", "SM", False), ("2:4", "MM", False), ("0.5", "SM", True)])
def test_bordered_matches_resolve_loop(spec, method, row_balanced):
    """The bordered factor (one Cholesky extended each column block) is
    the same Eq. (13) solution as re-solving the whole accumulated mask
    every block: weights, masks and losses agree at f32 tolerance, on 5
    column blocks, m > n (mlp.wo's aspect) and a row chunk that does not
    divide n; and the last block matches the float64 per-row oracle."""
    from repro.core import mrp
    from repro.core.hessian import dampened_inverse_np
    from repro.core.pruner import solve_path

    n, m, bs = 22, 320, 64
    w = jax.random.normal(jax.random.key(7), (n, m)) * (
        1.0 + jnp.arange(m)[None, :] / m)
    h = random_psd_hessian(jax.random.key(8), m)
    assert solve_path(SparsitySpec.parse(spec), method,
                      row_balanced) == "bordered"
    res = prune_matrix(w, h, spec, method=method, blocksize=bs,
                       row_chunk=5, row_balanced=row_balanced)
    w_ref, mask_ref, losses_ref, w_prev = _resolve_oracle(
        w, h, spec, method, bs, row_balanced)

    np.testing.assert_array_equal(np.asarray(res.mask), np.asarray(mask_ref))
    np.testing.assert_allclose(np.asarray(res.w), np.asarray(w_ref),
                               atol=2e-5)
    np.testing.assert_allclose(res.stats["block_mrp_losses"], losses_ref,
                               rtol=1e-4)
    assert res.stats["final_mrp_loss"] == res.stats["block_mrp_losses"][-1]
    assert bool(jnp.all(jnp.where(res.mask, res.w, 0.0) == 0.0))

    hinv64 = dampened_inverse_np(np.asarray(h, np.float64))
    mask = np.asarray(res.mask)
    for q in (0, 11, n - 1):
        row, _ = mrp.mrp_row_reference(
            np.asarray(w_prev)[q], hinv64, np.where(mask[q])[0])
        np.testing.assert_allclose(np.asarray(res.w)[q], row, atol=1e-4)


def test_global_unstructured_takes_resolve():
    from repro.core.pruner import solve_path

    for spec, method, rb in [("0.5", "SM", False), ("2:4", "SS", False),
                             ("2:4", "MS", False), ("0.5", "wanda", True)]:
        assert solve_path(SparsitySpec.parse(spec), method, rb) == "resolve"
