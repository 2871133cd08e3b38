"""Mesh construction (functions only — importing this module never
touches jax device state; jax locks the device count on first use, and
the dry-run must set XLA_FLAGS before that happens).

Axis-naming convention (docs/dist_api.md): ``pod`` (DCN, gradient/batch
outer axis), ``data`` (batch + FSDP), ``model`` (tensor/expert parallel).

``mesh_from_spec`` / ``add_mesh_argument`` / ``mesh_context`` are the
common ``--mesh`` entry path shared by the launch CLIs
(launch/train.py, launch/prune.py, launch/serve.py).
"""

from __future__ import annotations

import contextlib
from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str], devices=None):
    """The one mesh constructor: every axis ``Auto`` (GSPMD propagates
    shardings; ``with_sharding_constraint`` and the embed gather accept
    them).  ``jax.make_mesh`` alone now defaults to ``Explicit`` axes,
    which reject both.  ``devices`` defaults to ``jax.devices()``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(shape),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single pod (256 chips) or 2×16×16 (512 chips, 2 pods)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def dp_axes_of(mesh) -> Tuple[str, ...]:
    """The batch-sharding axes of a mesh (the pod/data subset present)."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def make_host_mesh():
    """1×1 mesh over the local device (CPU tests of mesh-aware code)."""
    return make_mesh((1, 1), ("data", "model"))


def mesh_from_spec(spec: Optional[str]):
    """Resolve a ``--mesh`` CLI spec to a mesh (or ``None``).

    Accepted specs:
      ``none``/``""``/None  no mesh — single-device operation;
      ``host``              1×1 local mesh (exercises mesh code on CPU);
      ``production``        16×16 single pod;
      ``production-2pod``   2×16×16 two pods;
      ``AxB`` / ``AxBxC``   explicit shape, e.g. ``2x4`` → (data, model),
                            ``2x4x4`` → (pod, data, model).
    """
    if spec is None or spec in ("", "none"):
        return None
    if spec == "host":
        return make_host_mesh()
    if spec == "production":
        return make_production_mesh()
    if spec in ("production-2pod", "multipod"):
        return make_production_mesh(multi_pod=True)
    dims = spec.lower().split("x")
    if all(d.isdigit() for d in dims) and len(dims) in (2, 3):
        shape = tuple(int(d) for d in dims)
        axes = ("data", "model") if len(dims) == 2 else (
            "pod", "data", "model")
        return make_mesh(shape, axes)
    raise ValueError(f"unrecognized --mesh spec {spec!r}")


def add_mesh_argument(parser) -> None:
    """Attach the shared ``--mesh`` flag to an argparse parser."""
    parser.add_argument(
        "--mesh", default="none",
        help="device mesh: none | host | production | production-2pod "
             "| AxB[xC] (see repro.dist.mesh.mesh_from_spec)")


def mesh_context(spec: Optional[str]):
    """``use_mesh`` over ``mesh_from_spec(spec)`` — a no-op null context
    (yielding ``None``) when the spec resolves to no mesh."""
    from repro.dist.api import use_mesh

    mesh = mesh_from_spec(spec)
    if mesh is None:
        return contextlib.nullcontext(None)
    return use_mesh(mesh)
