"""Pipelined calibration/solve scheduler (core.pipeline): equivalence
with the serial reference loop, resume-on-segment-boundary semantics,
and scheduler bookkeeping."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt import PruneProgressStore
from repro.core import PruningEngine
from repro.core.engine import summarize
from repro.core.pipeline import SegmentScheduler, _resolve_shards
from repro.data import calibration_batches
from repro.dist import make_mesh
from repro.obs import Obs


@pytest.fixture(scope="module")
def calib(tiny_lm):
    model, params, pipe = tiny_lm
    return calibration_batches(model.cfg, n_samples=16, seq_len=64, batch=8)


def _leaves32(tree):
    return [np.asarray(x, np.float32) for x in jax.tree.leaves(tree)]


def test_pipelined_matches_serial(tiny_lm, calib):
    """Default (pipelined) engine == pipeline="off" reference on
    paper_tiny_lm.  The jitted batched capture fuses differently than
    the eager per-batch walk (and accumulates the Hessian in one update
    instead of a streaming mean), so float-level score ties may flip a
    tiny fraction of mask entries — the contract is ≥ 99.9% mask
    agreement, identical per-linear sparsity, and indistinguishable
    pruned-model quality."""
    from conftest import eval_ppl

    model, params, pipe = tiny_lm
    ref, ref_reports = PruningEngine(
        model, "2:4", method="SM", blocksize=64,
        pipeline="off").run(params, calib)
    eng = PruningEngine(model, "2:4", method="SM", blocksize=64)
    eng.obs = Obs.create()
    got, reports = eng.run(params, calib)

    total = mismatched = 0
    for a, b in zip(_leaves32(ref), _leaves32(got)):
        agree = (a == 0) == (b == 0)
        total += agree.size
        mismatched += int((~agree).sum())
    assert mismatched / total < 1e-3, f"{mismatched}/{total} mask flips"
    assert [r.name for r in reports] == [r.name for r in ref_reports]
    assert [r.sparsity for r in reports] == [r.sparsity for r in ref_reports]
    np.testing.assert_allclose(
        summarize(reports)["total_recon_error"],
        summarize(ref_reports)["total_recon_error"], rtol=0.05)
    ppl_ref, ppl_got = eval_ppl(model, ref, pipe), eval_ppl(model, got, pipe)
    assert abs(ppl_got - ppl_ref) / ppl_ref < 0.02
    s = eng.last_pipeline_stats
    assert s is not None
    assert s.segments == model.cfg.num_layers
    assert s.calib_shards == 1          # no mesh → local accumulation
    assert s.batches == len(calib)
    # all period segments share one capture + one propagate trace
    traces = eng.obs.metrics.get("prune_stage_traces_total")
    by_stage = {k[0]: c.value for k, c in traces.children()}
    assert by_stage["capture"] == 1 and by_stage["propagate"] == 1


def test_pipelined_unstructured_fallback(tiny_lm, calib):
    """Unstructured global top-k is not traceable — the pipelined path
    must fall back to the host solve and still match the serial loop."""
    model, params, pipe = tiny_lm
    ref, _ = PruningEngine(model, "0.5", method="SM", blocksize=64,
                           pipeline="off").run(params, calib)
    got, reports = PruningEngine(model, "0.5", method="SM",
                                 blocksize=64).run(params, calib)
    total = mismatched = 0
    for a, b in zip(_leaves32(ref), _leaves32(got)):
        agree = (a == 0) == (b == 0)
        total += agree.size
        mismatched += int((~agree).sum())
    assert mismatched / total < 1e-3, f"{mismatched}/{total} mask flips"
    assert abs(summarize(reports)["mean_sparsity"] - 0.5) < 0.02


@pytest.mark.parametrize("spec,path", [("2:4", "bordered"),
                                       ("0.5", "resolve")])
def test_solve_path_counted_per_linear(tiny_lm, calib, spec, path):
    """``prune_solve_path_total{path}`` counts every linear solve once,
    under the path its spec takes: 2:4 extends one factor per column
    block, unstructured with a global count re-solves."""
    model, params, _ = tiny_lm
    eng = PruningEngine(model, spec, method="SM", blocksize=64)
    eng.obs = Obs.create()
    _, reports = eng.run(params, calib)
    fam = eng.obs.metrics.get("prune_solve_path_total")
    counts = {k[0]: c.value for k, c in fam.children()}
    assert counts == {path: len(reports)}
    assert len(reports) == 7 * model.cfg.num_layers


def test_pipeline_resume_on_segment_boundary(tiny_lm, calib, tmp_path):
    """Interrupt mid-run → every checkpoint lands on a segment boundary
    (params identical to the uninterrupted run's state after the same
    number of segments) and the resumed run's final params are
    bit-identical to the uninterrupted run."""
    model, params, pipe = tiny_lm
    out = str(tmp_path / "prog")

    class Recorder:
        """In-memory progress store: snapshots every segment-boundary save."""

        def __init__(self):
            self.saves = []

        def load_into(self, template):
            return None

        def save(self, next_segment, p):
            self.saves.append((next_segment, _leaves32(p)))

        def finalize(self):
            pass

    rec = Recorder()
    ref_params, _ = PruningEngine(
        model, "2:4", method="SM", blocksize=64,
        progress_store=rec).run(params, calib)
    assert [s for s, _ in rec.saves] == list(
        range(1, model.cfg.num_layers + 1))

    class Bomb(PruneProgressStore):
        def __init__(self, root, fuse):
            super().__init__(root)
            self.fuse = fuse

        def save(self, next_segment, p):
            super().save(next_segment, p)
            self.fuse -= 1
            if self.fuse == 0:
                raise RuntimeError("simulated node failure")

    with pytest.raises(RuntimeError):
        PruningEngine(model, "2:4", method="SM", blocksize=64,
                      progress_store=Bomb(out, fuse=2)).run(params, calib)

    # the surviving checkpoint is exactly the uninterrupted run's state
    # at the same segment boundary (bit-identical)
    seg_idx, ckpt = PruneProgressStore(out).load_into(params)
    assert seg_idx == 2
    for a, b in zip(dict(rec.saves)[seg_idx], _leaves32(ckpt)):
        np.testing.assert_array_equal(a, b)

    res_params, reports = PruningEngine(
        model, "2:4", method="SM", blocksize=64,
        progress_store=PruneProgressStore(out)).run(params, calib)
    # only the remaining segments were pruned in the resumed run...
    assert len(reports) == (model.cfg.num_layers - seg_idx) * 7
    # ...and the final params are bit-identical to the uninterrupted run
    for a, b in zip(_leaves32(ref_params), _leaves32(res_params)):
        np.testing.assert_array_equal(a, b)


def test_scheduler_stacking_and_shard_resolution():
    """shard_states round-robins batches into stacked per-shard trees."""
    batches = [{"h": jnp.full((2, 3), float(i))} for i in range(6)]
    sched = SegmentScheduler(mesh=None, calib_shard=2)
    states = sched.shard_states(batches)
    assert len(states) == 2
    assert states[0]["h"].shape == (6, 3)
    np.testing.assert_array_equal(
        np.asarray(states[0]["h"][:, 0]), [0, 0, 2, 2, 4, 4])
    assert sched.stats.calib_shards == 2 and sched.stats.batches == 6

    # no mesh → "auto"/"on" degrade to local accumulation
    assert _resolve_shards("auto", None, (), 8) == 1
    assert _resolve_shards("on", None, (), 8) == 1
    assert _resolve_shards("off", None, (), 8) == 1
    # booleans alias on/off (and must not be swallowed by int handling)
    assert _resolve_shards(True, None, (), 8) == 1
    assert _resolve_shards(False, None, (), 8) == 1
    assert _resolve_shards(3, None, (), 8) == 3
    mesh = make_mesh((1, 1), ("data", "model"))
    assert _resolve_shards("auto", mesh, ("data",), 8) == 1
    with pytest.raises(ValueError):
        _resolve_shards("definitely", None, (), 8)


def test_engine_rejects_unknown_pipeline_mode(tiny_lm):
    model, params, pipe = tiny_lm
    with pytest.raises(ValueError):
        PruningEngine(model, "2:4", pipeline="sideways")
