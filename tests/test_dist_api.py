"""repro.dist context API — host-mesh only (1×1 over the local CPU
device, no virtual devices needed)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.dist import (
    FSDP_EXCLUDE_EMBED,
    batch_spec,
    constrain,
    current_ctx,
    dp_axes_of,
    make_host_mesh,
    make_mesh,
    mesh_from_spec,
    param_specs,
    shard_params,
    use_mesh,
)


def test_current_ctx_none_outside_mesh():
    assert current_ctx() is None


def test_use_mesh_populates_context():
    mesh = make_host_mesh()
    with use_mesh(mesh) as ctx:
        assert current_ctx() is ctx
        assert ctx.mesh is mesh
        assert ctx.dp_axes == ("data",)
        assert ctx.dp == 1
        assert ctx.tp_axis == "model"
        assert ctx.tp == 1
    assert current_ctx() is None


def test_use_mesh_without_model_axis_degrades_tp():
    mesh = make_mesh((1,), ("data",))
    with use_mesh(mesh) as ctx:
        assert ctx.tp_axis is None
        assert ctx.tp == 1


def test_nested_use_mesh_restores_outer_context():
    outer = make_host_mesh()
    inner = make_mesh((1,), ("data",))
    with use_mesh(outer) as octx:
        with use_mesh(inner) as ictx:
            assert current_ctx() is ictx
        assert current_ctx() is octx
    assert current_ctx() is None


def test_use_mesh_pops_context_on_error():
    mesh = make_host_mesh()
    with pytest.raises(RuntimeError):
        with use_mesh(mesh):
            raise RuntimeError("boom")
    assert current_ctx() is None


def test_constrain_noop_without_context():
    x = jnp.arange(8.0).reshape(2, 4)
    assert constrain(x, "data", None) is x


def test_constrain_identity_on_host_mesh():
    x = jnp.arange(8.0).reshape(2, 4)
    with use_mesh(make_host_mesh()):
        y = constrain(x, "data", "model")
        y2 = jax.jit(lambda a: constrain(a, "data", None))(x)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
    np.testing.assert_array_equal(np.asarray(y2), np.asarray(x))


def test_shard_params_respects_fsdp_exclude_embed():
    from repro.configs import get_smoke
    from repro.models import LM

    model = LM(get_smoke("qwen3_14b"))
    params = model.init(jax.random.key(0))
    mesh = make_host_mesh()
    specs = param_specs(params, mesh, fsdp_axes=("data",),
                        fsdp_exclude=FSDP_EXCLUDE_EMBED)
    # excluded params never carry a data (FSDP) axis...
    def axes_of(spec):
        out = set()
        for entry in spec:
            if entry is None:
                continue
            out.update(entry if isinstance(entry, tuple) else (entry,))
        return out

    assert "data" not in axes_of(specs["embed"]["tok"])
    if "head" in specs["unembed"]:
        assert "data" not in axes_of(specs["unembed"]["head"])
    # ...while regular block kernels do
    included = param_specs(params, mesh, fsdp_axes=("data",))
    assert "data" in axes_of(included["embed"]["tok"])
    wq = specs["layers"]["s0"]["attn"]["wq"]
    assert "data" in axes_of(wq) and "model" in axes_of(wq)

    # placement round-trips values on the host mesh
    placed = shard_params(params, mesh, fsdp_axes=("data",),
                          fsdp_exclude=FSDP_EXCLUDE_EMBED)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(placed)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_shard_params_no_context_is_identity():
    params = {"w": jnp.ones((4, 4))}
    assert shard_params(params) is params


def test_batch_spec_covers_pod_data_axes():
    mesh = make_host_mesh()
    assert batch_spec(mesh) == P("data")
    assert batch_spec(mesh, ()) == P()
    assert dp_axes_of(mesh) == ("data",)


def test_mesh_from_spec():
    assert mesh_from_spec("none") is None
    assert mesh_from_spec(None) is None
    host = mesh_from_spec("host")
    assert host.axis_names == ("data", "model")
    explicit = mesh_from_spec("1x1")
    assert explicit.axis_names == ("data", "model")
    with pytest.raises(ValueError):
        mesh_from_spec("not-a-mesh")
