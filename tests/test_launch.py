"""Launch layer: build_lowerable + compile on a small virtual mesh
(subprocess — the main test process keeps its single CPU device)."""

from test_dist import run_with_devices


def test_lower_compile_smoke_cells():
    """Every shape kind lowers AND compiles for a smoke config on a 2×4
    mesh — the same code path the 512-chip dry-run exercises."""
    run_with_devices("""
        import jax, dataclasses
        import numpy as np
        from repro import configs as cfglib
        from repro.dist import make_mesh, use_mesh
        from repro.launch.dryrun import build_lowerable, OptFlags
        from repro.utils.hlo import collective_bytes

        mesh = make_mesh((2, 4), ("data", "model"))
        cfg = dataclasses.replace(
            cfglib.get_smoke("qwen3_14b"), name="launch-smoke")
        for shape in ("train_4k", "prefill_32k", "decode_32k"):
            fn, args, shardings, model = build_lowerable(
                "qwen3_14b", shape, mesh, cfg_override=cfg,
                opt=OptFlags.level(6))
            with use_mesh(mesh):
                compiled = jax.jit(
                    fn, in_shardings=shardings).lower(*args).compile()
            cost = compiled.cost_analysis()
            assert float(cost.get("flops", 0)) > 0
            stats = collective_bytes(compiled.as_text(), trip_counts=(2,))
            print(shape, "ok", stats.total_count, "collectives")
        print("OK")
    """, n=8)


def test_mesh_functions_pure():
    """make_production_mesh is a function (importing launch.mesh must not
    initialize jax devices) and axes match the spec."""
    run_with_devices("""
        import repro.launch.mesh as m   # import BEFORE any jax device use
        mesh = m.make_production_mesh()
        assert mesh.axis_names == ("data", "model")
        assert mesh.devices.shape == (16, 16), mesh.devices.shape
        assert m.dp_axes_of(mesh) == ("data",)
        print("OK")
    """, n=512)


def test_multi_pod_mesh_axes():
    run_with_devices("""
        import repro.launch.mesh as m
        mesh = m.make_production_mesh(multi_pod=True)
        assert mesh.axis_names == ("pod", "data", "model")
        assert m.dp_axes_of(mesh) == ("pod", "data")
        print("OK")
    """, n=512)
