"""Checkpoint store: atomicity, hashes, retention, elastic restore."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.ckpt import CheckpointStore, load_pytree, save_pytree
from repro.dist import make_mesh


def _tree(seed=0):
    k = jax.random.key(seed)
    return {
        "a": jax.random.normal(k, (8, 16)),
        "nested": {"b": jnp.arange(10, dtype=jnp.int32),
                   "c": jnp.float32(3.5)},
    }


def test_roundtrip(tmp_path):
    t = _tree()
    path = str(tmp_path / "ck")
    save_pytree(path, t, extra={"step": 7})
    loaded, extra = load_pytree(path, t)
    assert extra["step"] == 7
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(loaded)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_hash_detects_corruption(tmp_path):
    path = str(tmp_path / "ck")
    save_pytree(path, _tree())
    with open(os.path.join(path, "arrays.npz"), "r+b") as f:
        f.seek(50)
        f.write(b"\xde\xad")
    with pytest.raises(IOError):
        load_pytree(path, _tree())


def test_shape_mismatch_rejected(tmp_path):
    path = str(tmp_path / "ck")
    save_pytree(path, _tree())
    bad = _tree()
    bad["a"] = jnp.zeros((4, 4))
    with pytest.raises(ValueError):
        load_pytree(path, bad)


def test_store_retention_and_latest(tmp_path):
    store = CheckpointStore(str(tmp_path), keep=2)
    for s in (5, 10, 15, 20):
        store.save(s, _tree(s))
    assert store.list_steps() == [15, 20]
    assert store.latest_step() == 20
    got = store.restore(_tree())
    assert got is not None and got[0] == 20


def test_store_walks_past_corrupt(tmp_path):
    store = CheckpointStore(str(tmp_path), keep=5)
    store.save(1, _tree(1))
    store.save(2, _tree(2))
    with open(str(tmp_path / "step_00000002/arrays.npz"), "r+b") as f:
        f.seek(10)
        f.write(b"\x00\x00\x00")
    step, tree, _ = store.restore(_tree())
    assert step == 1


def test_elastic_restore_different_sharding(tmp_path):
    """Save under one sharding, restore under another mesh/sharding —
    values identical (the trainer's elastic-restart path)."""
    mesh1 = make_mesh((1, 1), ("data", "model"))
    t = _tree()
    t_sharded = jax.device_put(
        t, NamedSharding(mesh1, P()))
    path = str(tmp_path / "ck")
    save_pytree(path, t_sharded)

    mesh2 = make_mesh((1,), ("x",))
    loaded, _ = load_pytree(path, t)
    placed = jax.device_put(loaded, NamedSharding(mesh2, P()))
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(placed)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_atomic_no_partial_visible(tmp_path):
    """A failed save never leaves a readable-but-wrong checkpoint."""
    store = CheckpointStore(str(tmp_path), keep=3)
    store.save(1, _tree(1))
    # simulate a crash mid-save: a stale tmp dir lying around
    os.makedirs(str(tmp_path / "step_00000002.tmp-9999"), exist_ok=True)
    assert store.latest_step() == 1
    got = store.restore(_tree())
    assert got[0] == 1
