"""Fault-tolerant trainer: resume bit-exactness, NaN guard, stragglers."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.data import DataPipeline
from repro.models import LM
from repro.optim import AdamW
from repro.train import StragglerError, TrainConfig, Trainer
from repro.train.loop import make_train_step


def _mk(tmp_path, name, **kw):
    cfg = get_smoke("paper_tiny_lm")
    model = LM(cfg)
    pipe = DataPipeline(cfg, global_batch=4, seq_len=32, seed=0)
    opt = AdamW(lr=1e-3)
    defaults = dict(total_steps=20, global_batch=4, seq_len=32,
                    ckpt_every=5, out_dir=str(tmp_path / name), log_every=5)
    defaults.update(kw)
    tc = TrainConfig(**defaults)
    return Trainer(model, opt, pipe, tc), model


def _params_equal(a, b, atol=0.0):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x, np.float32),
                                   np.asarray(y, np.float32), atol=atol)


def test_loss_decreases(tmp_path):
    tr, _ = _mk(tmp_path, "a", total_steps=40)
    tr.run()
    lines = [json.loads(line) for line in
             open(tr.metrics_path)]
    assert lines[-1]["loss"] < lines[0]["loss"]


def test_resume_bit_exact(tmp_path):
    """Crash at step 10 of 20 → resume → same params as uninterrupted."""
    tr_full, _ = _mk(tmp_path, "full")
    p_full, _, _ = tr_full.run()

    tr_a, _ = _mk(tmp_path, "interrupted")
    tr_a.run(max_steps=10)            # "crash" after 10 steps
    tr_b, _ = _mk(tmp_path, "interrupted")   # new process, same dir
    p_resumed, _, info = tr_b.run()
    assert info["steps"] == 10        # only the remaining steps ran
    _params_equal(p_full, p_resumed)


def test_resume_skips_corrupt_checkpoint(tmp_path):
    tr, _ = _mk(tmp_path, "c")
    tr.run(max_steps=10)
    # corrupt the newest checkpoint (torn write on dying host)
    step = tr.store.latest_step()
    path = tr.store._step_dir(step) + "/arrays.npz"
    with open(path, "r+b") as f:
        f.seek(100)
        f.write(b"\x00" * 64)
    tr2, _ = _mk(tmp_path, "c")
    start, *_ = tr2.restore_or_init()
    assert start < step               # walked back to an older valid ckpt


def test_nan_guard_skips_update(tmp_path):
    cfg = get_smoke("paper_tiny_lm")
    model = LM(cfg)

    class PoisonModel:
        cfg = model.cfg

        def loss_fn(self, params, batch):
            loss, m = model.loss_fn(params, batch)
            # poison: NaN loss when flag set
            loss = jnp.where(batch["poison"], jnp.nan, loss)
            return loss, m

    opt = AdamW(lr=1e-3)
    step = jax.jit(make_train_step(PoisonModel(), opt))
    params = model.init(jax.random.key(0))
    opt_state = opt.init(params)
    toks = jax.random.randint(jax.random.key(1), (4, 32), 0, cfg.vocab_size)
    batch = {"tokens": toks, "labels": toks, "poison": jnp.asarray(True)}
    p1, o1, _, m = step(params, opt_state, jnp.zeros(()), batch)
    assert float(m["skipped"]) == 1.0
    _params_equal(params, p1)         # untouched
    batch["poison"] = jnp.asarray(False)
    p2, _, _, m2 = step(params, opt_state, jnp.zeros(()), batch)
    assert float(m2["skipped"]) == 0.0


def test_microbatch_accumulation_close_to_full_batch(tmp_path):
    cfg = get_smoke("paper_tiny_lm")
    model = LM(cfg)
    opt = AdamW(lr=1e-3, clip_norm=None)
    params = model.init(jax.random.key(0))
    toks = jax.random.randint(jax.random.key(2), (8, 32), 0, cfg.vocab_size)
    batch = {"tokens": toks, "labels": toks}
    s1 = jax.jit(make_train_step(model, opt, microbatches=1))
    s4 = jax.jit(make_train_step(model, opt, microbatches=4))
    p1, *_ = s1(params, opt.init(params), jnp.zeros(()), batch)
    p4, *_ = s4(params, opt.init(params), jnp.zeros(()), batch)
    # mean-of-microbatch grads == full-batch grads (same tokens/weights)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p4)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=5e-5)


def test_straggler_abort_checkpoints(tmp_path, monkeypatch):
    tr, _ = _mk(tmp_path, "s", total_steps=200,
                straggler_factor=0.0,     # every step is a "straggler"
                straggler_abort=2)
    with pytest.raises(StragglerError):
        tr.run()
    assert tr.straggler_events >= 2
    # it checkpointed before dying → a new trainer resumes
    tr2, _ = _mk(tmp_path, "s", total_steps=200, straggler_abort=10**9)
    start, *_ = tr2.restore_or_init()
    assert start > 0


def test_grad_compression_trains(tmp_path):
    """int8 EF-compressed grads still reduce the loss (error feedback)."""
    tr, _ = _mk(tmp_path, "g", total_steps=40, grad_compression=True)
    tr.run()
    lines = [json.loads(line) for line in open(tr.metrics_path)]
    assert lines[-1]["loss"] < lines[0]["loss"]


def test_mesh_headsplit_parity():
    """ROADMAP head-split hazard, TRAINING path: on a 2x4 mesh where the
    model axis would split a head (d_model=64, 2 heads, hd=32 -> 16
    columns/shard), the jax 0.4.x CPU partitioner mis-executes the
    rope/attention chain.  The Trainer now shards with the param_specs
    whole-heads guard (head_dim=cfg.hd) — mesh losses must track the
    single-device run step for step.  Subprocess for the same reason as
    test_dist.py: the parent must keep its single CPU device."""
    import os
    import subprocess
    import sys
    import textwrap

    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = (
        os.path.join(os.path.dirname(__file__), "..", "src")
        + os.pathsep + env.get("PYTHONPATH", ""))
    code = """
        import jax, numpy as np
        from repro.data import DataPipeline
        from repro.dist import use_mesh
        from repro.models import LM
        from repro.models.base import ArchConfig
        from repro.optim import AdamW
        from repro.train.loop import make_train_step
        from repro.dist.sharding import shard_params

        cfg = ArchConfig(name="headsplit", family="dense", num_layers=2,
                         d_model=64, num_heads=2, num_kv_heads=2,
                         d_ff=128, vocab_size=128, period=("attn",),
                         mlp_kind="swiglu", dtype="float32")
        model = LM(cfg)
        pipe = DataPipeline(cfg, global_batch=4, seq_len=32, seed=0)
        opt = AdamW(lr=1e-3)
        step_fn = make_train_step(model, opt)

        def losses(mesh, **kw):
            params = model.init(jax.random.key(0))
            if mesh is not None:
                params = shard_params(params, mesh,
                                      fsdp_axes=("data",), **kw)
            state = opt.init(params)
            ef = jax.numpy.zeros(())
            jstep = jax.jit(step_fn)
            out = []
            for s in range(5):
                params, state, ef, m = jstep(params, state, ef,
                                             pipe.batch_at(s))
                out.append(float(m["loss"]))
            return out

        base = losses(None)
        from repro.dist import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        with use_mesh(mesh):
            guarded = losses(mesh, head_dim=cfg.hd)   # Trainer's layout
        err = max(abs(a - b) for a, b in zip(base, guarded))
        assert err < 1e-4, f"guarded mesh training diverged: {err}"
        print("OK", err)
    """
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, \
        f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    assert "OK" in out.stdout
