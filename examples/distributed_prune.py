"""Distributed pruning (Remark 4.2 + multi-pod calibration) — run with
virtual devices to see the sharded paths match single-device results:

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
      PYTHONPATH=src python examples/distributed_prune.py

Demonstrates the three distributed pieces the PruningEngine composes:
per-pod×data-shard calibration merged with one collective per linear
(``allreduce_calibration``), the row-parallel layer solve, and the
engine's pipelined scheduler driving both.
"""

import os

if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import time

import jax
import jax.numpy as jnp

from repro import SparsitySpec, current_ctx, prune_matrix, use_mesh
from repro.core.calibration import CalibrationSet
from repro.core.distributed import (
    allreduce_calibration,
    prune_matrix_sharded,
)
from repro.dist import make_mesh


def main():
    print(f"devices: {jax.device_count()}")
    # 2 pods × 2 data shards × 2-way model parallel
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    n, m = 64, 128
    key = jax.random.key(0)
    w = jax.random.normal(key, (n, m)) * 0.1

    with use_mesh(mesh):
        ctx = current_ctx()
        print(f"active context: dp={ctx.dp} over {ctx.dp_axes}, "
              f"tp={ctx.tp} over {ctx.tp_axis!r}")

        # 1. multi-pod calibration: every pod×data slice accumulates its
        #    own CalibrationSet over its calibration tokens; the merge is
        #    one hessian_allreduce collective per linear (DCN-friendly —
        #    this is what PruningEngine(calib_shard=...) does per segment)
        sets = []
        for s in range(ctx.dp):
            x = jax.random.normal(jax.random.fold_in(key, s),
                                  (4, 64 + 16 * s, m))
            sets.append(CalibrationSet.from_captures({"wq": x}))
        calib = allreduce_calibration(sets, None, axis_name=ctx.dp_axes)
        h = calib.hessian("wq")
        print(f"merged Hessian from {len(sets)} pod×data shards "
              f"({int(calib.accs['wq'].count)} tokens)")

        # 2. row-parallel MRP prune over the `model` axis — zero
        #    collectives inside the layer (rows are independent,
        #    Remark 4.2); the context supplies the mesh.
        t0 = time.monotonic()
        w_sh, mask_sh = prune_matrix_sharded(w, h, "2:4", method="SM",
                                             blocksize=64)
        t_sh = time.monotonic() - t0

    # 3. single-device reference (outside the context)
    res = prune_matrix(w, h, SparsitySpec.parse("2:4"), method="SM",
                       blocksize=64, row_balanced=True)
    diff = float(jnp.abs(w_sh - res.w).max())
    same_mask = bool(jnp.all(mask_sh == res.mask))
    print(f"sharded prune: {t_sh:.2f}s; |Δw| vs single-device = {diff:.2e}; "
          f"identical mask: {same_mask}")
    print(f"sparsity: {float(jnp.mean(mask_sh)):.3f}")


if __name__ == "__main__":
    main()
