"""Prune jobs on a (1, chips) mesh: ``prune_jobs`` with the engine built
inside ``repro.dist.use_mesh``, so that every layer solve runs
row-parallel over the mesh's ``model`` axis (Remark 4.2: the rows of a
linear are independent).  The window, the reference and the controls
are ``prune_jobs``'s: the mathematics does not change with the split."""

from __future__ import annotations

import jax


def run(ctx) -> dict:
    from repro.dist import make_mesh, use_mesh
    mesh = make_mesh((1, ctx.chips), ("data", "model"),
                     devices=jax.devices()[:ctx.chips])
    with use_mesh(mesh):
        return ctx.catalog.driver("prune_jobs").run(ctx)
