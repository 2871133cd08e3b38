"""Distributed pruning / collectives — run in a subprocess with 8 virtual
devices (XLA device count is locked at first jax init, so the main test
process must keep its single CPU device)."""

import os
import subprocess
import sys
import textwrap


SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_with_devices(code: str, n: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_row_parallel_prune_matches_single_device():
    """shard_map row-parallel MRP pruning == single-device result
    (Remark 4.2: rows are independent)."""
    run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.distributed import prune_matrix_sharded
        from repro.core.pruner import prune_matrix
        from repro.core.sparsity import SparsitySpec

        from repro.dist import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        n, m = 32, 64
        w = jax.random.normal(jax.random.key(0), (n, m))
        x = jax.random.normal(jax.random.key(1), (m, 4 * m))
        h = 2.0 * x @ x.T / (4 * m)

        for spec in ("2:4", "0.5"):
            w_sh, mask_sh = prune_matrix_sharded(
                w, h, spec, mesh, method="SM", blocksize=32)
            res = prune_matrix(w, h, SparsitySpec.parse(spec), method="SM",
                               blocksize=32, row_balanced=True)
            np.testing.assert_allclose(
                np.asarray(w_sh), np.asarray(res.w), atol=2e-4)
            np.testing.assert_array_equal(
                np.asarray(mask_sh), np.asarray(res.mask))
        print("OK")
    """)


def test_hessian_psum_across_data_shards():
    run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.distributed import hessian_allreduce
        from repro.core.hessian import HessianAccumulator

        from repro.dist import make_mesh
        mesh = make_mesh((8,), ("data",))
        m = 16
        xs = [jax.random.normal(jax.random.key(i), (m, 10 + 7 * i))
              for i in range(8)]
        accs = []
        for x in xs:
            a = HessianAccumulator(m); a.update(x); accs.append(a)
        ref = accs[0]
        for a in accs[1:]:
            ref = ref.merge(a)
        h_shards = jnp.stack([a.h for a in accs])
        counts = jnp.stack([a.count for a in accs])
        merged = hessian_allreduce(mesh, h_shards, counts)
        np.testing.assert_allclose(np.asarray(merged), np.asarray(ref.h),
                                   rtol=1e-4)
        print("OK")
    """)


def test_compressed_psum_close_to_exact():
    run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.optim.compression import compressed_psum

        from repro.dist import make_mesh
        mesh = make_mesh((8,), ("pods",))
        n = 1024
        xs = jax.random.normal(jax.random.key(0), (8, n))

        def body(x):
            return compressed_psum(x[0], "pods")

        out = jax.jit(shard_map(
            body, mesh=mesh, in_specs=P("pods"), out_specs=P("pods"),
        ))(xs)
        got = np.asarray(out).reshape(8, -1)[0]
        want = np.asarray(xs.mean(0))
        # int8 quantization error ≈ amax/127 per element, two rounds
        scale = np.abs(np.asarray(xs)).max() / 127
        assert np.abs(got - want).max() < 4 * scale
        print("OK")
    """)


def test_moe_expert_parallel_matches_single_device():
    run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np, dataclasses
        from repro.configs import get_smoke
        from repro.dist.api import use_mesh
        from repro.models import LM

        cfg = get_smoke("phi3_5_moe_42b_a6_6b")
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
        model = LM(cfg)
        params = model.init(jax.random.key(0))
        toks = jax.random.randint(jax.random.key(1), (4, 16), 0,
                                  cfg.vocab_size)
        batch = {"tokens": toks, "labels": toks}
        ref, _ = model.forward(params, batch)

        from repro.dist import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        with use_mesh(mesh):
            dist, _ = jax.jit(model.forward)(params, batch)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(dist),
                                   atol=2e-3)
        print("OK")
    """)


def test_sharded_train_step_matches_single_device():
    """pjit on a 2×4 mesh == single-device step (same seed, same batch)."""
    run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_smoke
        from repro.dist import use_mesh
        from repro.dist.sharding import batch_sharding, param_shardings
        from repro.models import LM
        from repro.optim import AdamW
        from repro.train import make_train_step

        cfg = get_smoke("qwen3_14b")
        model = LM(cfg)
        opt = AdamW(lr=1e-3)
        params = model.init(jax.random.key(0))
        opt_state = opt.init(params)
        toks = jax.random.randint(jax.random.key(1), (8, 16), 0,
                                  cfg.vocab_size)
        batch = {"tokens": toks, "labels": toks}
        step = make_train_step(model, opt)
        p_ref, o_ref, _, m_ref = jax.jit(step)(
            params, opt_state, jnp.zeros(()), batch)

        from repro.dist import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        psh = param_shardings(params, mesh)
        bsh = batch_sharding(mesh)
        params_d = jax.device_put(params, psh)
        opt_d = type(opt_state)(
            jax.device_put(opt_state.step),
            jax.device_put(opt_state.mu, psh),
            jax.device_put(opt_state.nu, psh))
        batch_d = {k: jax.device_put(v, bsh) for k, v in batch.items()}
        with use_mesh(mesh):
            p_d, o_d, _, m_d = jax.jit(step)(
                params_d, opt_d, jnp.zeros(()), batch_d)
        assert abs(float(m_ref["loss"]) - float(m_d["loss"])) < 1e-4
        for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_d)):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                atol=5e-4)
        print("OK")
    """)


def test_sharded_calibration_matches_local_accumulation():
    """CalibrationSets accumulated per pod×data shard and merged with
    allreduce_calibration == one local accumulation over all tokens
    (the calibration-sharding path of core.pipeline)."""
    run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.calibration import CalibrationSet
        from repro.core.distributed import allreduce_calibration

        from repro.dist import make_mesh
        mesh = make_mesh((2, 4), ("pod", "data"))
        m, key = 16, jax.random.key(0)
        shard_caps = []
        for s in range(8):
            k = jax.random.fold_in(key, s)
            x = jax.random.normal(k, (3 + s % 2, 5, m))   # uneven tokens
            wts = (jax.random.uniform(jax.random.fold_in(k, 1),
                                      x.shape[:-1]) > 0.3)
            shard_caps.append({
                "attn.wq": x,
                "moe.wi": (x * 0.5, wts.astype(jnp.float32)),
            })
        sets = [CalibrationSet.from_captures(c) for c in shard_caps]
        merged = allreduce_calibration(sets, mesh,
                                       axis_name=("pod", "data"))

        ref = CalibrationSet()
        for c in shard_caps:
            ref.update(c)
        for name in ("attn.wq", "moe.wi"):
            np.testing.assert_allclose(
                np.asarray(merged.hessian(name)),
                np.asarray(ref.hessian(name)), rtol=1e-4, atol=1e-5)
            np.testing.assert_allclose(
                float(merged.accs[name].count),
                float(ref.accs[name].count), rtol=1e-5)
        print("OK")
    """)


def test_pipelined_engine_sharded_calibration_matches_serial():
    """Whole-engine parity: pipelined run with calibration sharded over
    the pod×data axes of a (2, 2, 2) mesh == the serial single-device
    reference (float-tie mask flips only)."""
    run_with_devices("""
        import jax, numpy as np
        from repro.configs import get_smoke
        from repro.core import PruningEngine
        from repro.data import calibration_batches
        from repro.dist import use_mesh
        from repro.models import LM

        cfg = get_smoke("paper_tiny_lm")
        model = LM(cfg)
        params = model.init(jax.random.key(0))
        calib = calibration_batches(cfg, n_samples=64, seq_len=32, batch=8)

        ref, ref_reports = PruningEngine(
            model, "2:4", method="SM", blocksize=32,
            pipeline="off").run(params, calib)

        from repro.dist import make_mesh
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        with use_mesh(mesh):
            eng = PruningEngine(model, "2:4", method="SM", blocksize=32,
                                calib_shard="on")
            got, reports = eng.run(params, calib)
        s = eng.last_pipeline_stats
        assert s.calib_shards == 4, s          # one per pod×data slice
        assert len(reports) == len(ref_reports)

        total = mismatched = 0
        for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(got)):
            a = np.asarray(a, np.float32)
            b = np.asarray(b, np.float32)
            agree = (a == 0) == (b == 0)
            total += agree.size
            mismatched += int((~agree).sum())
        assert mismatched / total < 1e-3, (mismatched, total)
        print("OK")
    """)
