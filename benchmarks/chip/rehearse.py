"""Compile a cell's programs at its real size for a described TPU v5e,
without a chip, and print each program's ``memory_analysis()``.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/rehearse.py <cell> [...]

Serve cells: the decode burst and the prefill-chunk burst of the served
path, with the cell's pool and the packed weights as shapes.  Prune
cells: one block's capture and propagate and the layer solves.  The
Pallas kernels compile as on the chip (dispatch forced off interpret).
A compile that passes here is not a chip run: it says the compiler
accepts the programs and how many bytes each needs.
"""

from __future__ import annotations

import os
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402


def _gb(x) -> str:
    return f"{x / 1e9:.3f} GB"


def _report(name, compiled, t):
    m = compiled.memory_analysis()
    print(f"{name}: compiled in {t:.1f}s; arguments "
          f"{_gb(m.argument_size_in_bytes)}, outputs "
          f"{_gb(m.output_size_in_bytes)}, temporaries "
          f"{_gb(m.temp_size_in_bytes)}, aliased "
          f"{_gb(m.alias_size_in_bytes)}", flush=True)
    return m


def rehearse_serve(ctx, dev):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import SingleDeviceSharding

    import weights
    from repro.kernels import ops
    from repro.models import LM
    from repro.serve import fused

    one = SingleDeviceSharding(dev)

    def on(tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
            tree)

    sv = ctx.cell["serve"]
    model = LM(ctx.arch())
    params = on(jax.eval_shape(weights.build_fn(model, packed=True),
                               jax.random.key(0)))
    kv = on(jax.eval_shape(lambda: model.init_paged_cache(
        sv["num_pages"], sv["page_size"],
        jnp.int8 if sv.get("kv_dtype") == "int8" else None)))
    p_max = -(-sv["max_len"] // sv["page_size"])
    tables = jax.ShapeDtypeStruct((sv["max_batch"], p_max), jnp.int32,
                                  sharding=one)
    state = on({k: jax.ShapeDtypeStruct(np.shape(v), np.asarray(v).dtype)
                for k, v in fused.init_burst_state(
                    sv["max_batch"], sv["steps_per_sync"] + 1).items()})
    key = on(jax.eval_shape(lambda: jax.random.key(0)))
    i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one)
    chunk = {"tokens": jax.ShapeDtypeStruct((1, sv["prefill_chunk"]),
                                            jnp.int32, sharding=one),
             "start": i32, "length": i32, "slot": i32, "uid": i32,
             "max_new": i32, "pos0": i32}
    kw = dict(temperature=0.0, top_k=None, top_p=None, eos_id=None)
    total = 0
    for leaf in jax.tree.leaves((params, kv)):
        total += int(np.prod(leaf.shape)) * jnp.dtype(leaf.dtype).itemsize
    print(f"resident: params + pool {_gb(total)}", flush=True)
    with ops.override_dispatch(interpret=False, force_pallas=False):
        burst = fused.make_continuous_burst(model, sv["page_size"], **kw)
        t = time.perf_counter()
        c = burst.lower(params, kv, tables, state, key).compile()
        _report("decode burst", c, time.perf_counter() - t)
        names = _kernels(c)
        print(f"  kernels: {names}", flush=True)
        pburst = fused.make_prefill_burst(model, sv["page_size"],
                                          sv["prefill_chunk"], **kw)
        t = time.perf_counter()
        c = pburst.lower(params, kv, tables, state, key, chunk).compile()
        _report("prefill-chunk burst", c, time.perf_counter() - t)
        print(f"  kernels: {_kernels(c)}", flush=True)


def _kernels(compiled):
    from repro.utils.hlo import tpu_kernel_names
    return sorted(set(tpu_kernel_names(compiled.as_text())))


def rehearse_prune(ctx, dev):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    import weights
    from repro.core.engine import _local_solve_fn
    from repro.core.sparsity import SparsitySpec
    from repro.models import LM

    one = SingleDeviceSharding(dev)
    model = LM(ctx.arch())
    pr, cal = ctx.mix["prune"], ctx.mix["calibration"]
    params = jax.eval_shape(weights.build_fn(model, packed=False),
                            jax.random.key(0))
    seg = model.prunable_segments()[0]
    sp = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                                     sharding=one),
                      jax.eval_shape(seg.get_params, params))
    per_shard = cal["samples"] // pr.get("calib_shard", 1)
    h = jax.ShapeDtypeStruct((per_shard, cal["length"],
                              model.cfg.d_model), model.dtype, sharding=one)
    t = time.perf_counter()
    c = jax.jit(lambda p, s: seg.apply(p, s, capture=True)).lower(
        sp, h).compile()
    _report(f"capture (one block, {per_shard} sequences)", c,
            time.perf_counter() - t)
    spec = SparsitySpec.parse(pr["sparsity"])
    solve = _local_solve_fn(spec, pr["method"], pr["blocksize"],
                            pr["gamma"], None, None, False)
    seen = set()
    for lin in seg.linears:
        w = jax.eval_shape(lin.get, sp)
        if w.shape in seen:
            continue
        seen.add(w.shape)
        ws = jax.ShapeDtypeStruct(w.shape, w.dtype, sharding=one)
        hs = jax.ShapeDtypeStruct((w.shape[1],) * 2, jnp.float32,
                                  sharding=one)
        t = time.perf_counter()
        c = solve.lower(ws, hs).compile()
        _report(f"solve {lin.name} {w.shape}", c, time.perf_counter() - t)


def main(argv=None) -> int:
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    cat = harness.Catalog()
    failed = False
    for name in (argv if argv is not None else sys.argv[1:]):
        cell = cat.cell(name)
        mix = cat.mix(cell["mix"])
        ctx = harness.Ctx(workload=name, seed=0, seconds=0, trace=False,
                          cell=cell, config=cat.config(cell["config"]),
                          mix=mix, catalog=cat, t_start=0.0)
        print(f"== {name}", flush=True)
        try:
            if mix["driver"] == "prune_jobs":
                rehearse_prune(ctx, topo.devices[0])
            else:
                rehearse_serve(ctx, topo.devices[0])
        except Exception as e:  # noqa: BLE001 — reported, next cell
            failed = True
            print(f"FAILED {name}: {type(e).__name__}: "
                  f"{str(e).splitlines()[0]}", flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
