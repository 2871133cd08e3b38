"""The Mamba prune cell and the four-chip prune cell: their data files
load through the catalog, the Mamba reference agrees with the program at
smoke size on the CPU, a tiny Mamba prune cell runs correct, and its
driver's ``judge`` catches a planted difference."""

import numpy as np
import pytest

from chipbench_fixtures import CHIP, TINY_BENCH, write_tiny

import harness  # noqa: E402
import run as run_py  # noqa: E402
from reference import mamba_forward  # noqa: E402

NEW_CELLS = ("mamba_2_8b.prune_ssm", "qwen1_5_0_5b.prune_mesh1x4")


def tiny_mamba() -> dict:
    return {"arch": "mamba_2_8b", "source": "test",
            "overrides": {"d_model": 64, "vocab_size": 256, "num_layers": 2,
                          "ssm_state": 16, "ssm_expand": 2, "ssm_conv": 4,
                          "norm_eps": 1e-5, "dtype": "float32"},
            "hidden_size": 64, "num_hidden_layers": 2, "vocab_size": 256,
            "tie_word_embeddings": True, "reduced": {}, "dtype": "float32",
            "sparsity": "2:4"}


@pytest.fixture(scope="module")
def catalog(tmp_path_factory):
    import json
    root = write_tiny(tmp_path_factory.mktemp("tiny"))

    def w(rel, obj):
        (root / rel).write_text(json.dumps(obj))

    w("configs/tinymamba.json", tiny_mamba())
    w("mixes/tprune_ssm.json", {
        "driver": "prune_ssm_jobs",
        "calibration": {"samples": 8, "length": 32, "batch": 4},
        "prune": {"sparsity": "2:4", "method": "SM", "blocksize": 32,
                  "gamma": 0.01}, "why": "test"})
    w("cells/tinymamba.prune_ssm.json", {
        "config": "tinymamba", "mix": "tprune_ssm", "blocks_per_job": 2,
        "reference": {"rows": 4},
        "limits": {"mask_disagree_max.b0": 0.02,
                   "recon_excess_max.b0": 0.01, "comp_dev_max.b0": 1e-3},
        "why": "test"})
    return harness.Catalog([root, CHIP])


@pytest.mark.parametrize("name", NEW_CELLS)
def test_new_cells_load(name):
    cat = harness.Catalog()
    cell = cat.cell(name)
    cfg = harness.build_arch(cat.config(cell["config"]),
                             cell["blocks_per_job"])
    mix = cat.mix(cell["mix"])
    assert cfg.num_layers == 2
    assert mix["prune"] == cat.mix("prune")["prune"]
    assert mix["calibration"] == cat.mix("prune")["calibration"]
    assert callable(cat.driver(mix["driver"]).run)


def test_mamba_config_at_published_widths():
    cfg = harness.build_arch(harness.Catalog().config("mamba_2_8b"))
    assert (cfg.num_layers, cfg.d_model, cfg.d_inner, cfg.ssm_state,
            cfg.dt_rank, cfg.ssm_conv, cfg.vocab_size) == (
                64, 2560, 5120, 16, 160, 4, 50280)
    assert cfg.residual_in_fp32 and cfg.tie_embeddings


def test_reference_layer_matches_program():
    import jax
    import jax.numpy as jnp

    from repro.models import LM
    from repro.models.ssm import mamba_apply
    cfg = harness.build_arch(tiny_mamba())
    params = jax.jit(LM(cfg).init)(harness.seed_key(4))
    toks = np.arange(2 * 40).reshape(2, 40) % cfg.vocab_size
    x = mamba_forward.embed(params, toks)
    p = mamba_forward.layer_params(params, 0)
    want = mamba_forward.layer(p, x, 1e-5)
    with jax.default_matmul_precision("highest"):
        got, _ = mamba_apply(p["mamba"], x, cfg)
    err = float(jnp.max(jnp.abs(got - want)))
    assert err < 1e-4 * max(1.0, float(jnp.max(jnp.abs(want))))


SEED = 2**33 + 5


@pytest.fixture(scope="module")
def mamba_out(catalog):
    import jax
    return run_py.run_cell("tinymamba.prune_ssm", SEED, 1.0, False,
                           catalog=catalog, bench=TINY_BENCH,
                           devs=jax.devices()[:1])


def test_mamba_prune_cell_is_correct(mamba_out):
    assert mamba_out["correct"], mamba_out["_checks"]
    assert mamba_out["attempted"] >= 1 and "setup_s" in mamba_out["metrics"]
    # both pruned layers are compared, every projection of each
    notes = mamba_out["_notes"]
    assert "comp_dev_max.b0" in notes and "comp_dev_max.b1" in notes
    assert notes["recon_excess_max.b1"] < 0.01
    # off the TPU the scan takes the chunked jnp path, traced once by
    # each capture and propagate program of every job
    assert set(mamba_out["_notes"]["scan_paths"]) == {"chunked"}


@pytest.fixture(scope="module")
def mamba_ref(catalog, mamba_out):
    """The reference of the tiny cell, with the dense layer 0 in the
    place of the program's pruned one (the judge below compares the
    reference's own sweep)."""
    import jax

    import traffic
    from repro.models import LM
    run = mamba_out["_run"]
    ctx = harness.Ctx(
        workload="tinymamba.prune_ssm", seed=SEED, seconds=0.0, trace=False,
        cell=run["cell"], config=run["config"], mix=run["mix"],
        catalog=catalog, t_start=0.0)
    driver = catalog.driver("prune_ssm_jobs")
    model = LM(run["arch"])
    params = jax.jit(model.init)(harness.seed_key(SEED))
    toks = traffic.calibration(run["mix"], SEED, model.cfg.vocab_size)
    return ctx, driver, driver.reference(ctx, params, toks, params)


def _planted(ref, where):
    def rows(blk, name, idx):
        w = ref["by"][blk, name]["w_ref"].copy()
        if (blk, name) == where:
            w[0] *= 1.05                    # one row of one projection
        return w
    return rows


def test_mamba_judge_catches_a_planted_difference(mamba_ref):
    ctx, driver, ref = mamba_ref
    assert all(c["ok"] for c in driver.checks(
        ctx, driver.judge(ctx, ref, _planted(ref, None))))
    res = driver.judge(ctx, ref, _planted(ref, (0, "mamba.out_proj")))
    assert not all(c["ok"] for c in driver.checks(ctx, res))
    assert res["worst"]["comp"] == [0, "mamba.out_proj"]


def test_mamba_judge_reads_layer_1(mamba_ref):
    # a planted row in layer 1 moves layer 1's numbers, not layer 0's
    ctx, driver, ref = mamba_ref
    clean = driver.judge(ctx, ref, _planted(ref, None))
    res = driver.judge(ctx, ref, _planted(ref, (1, "mamba.in_proj")))
    assert res["worst"]["comp"] == [1, "mamba.in_proj"]
    assert res["comp_dev_max.b1"] > 0.01 > clean["comp_dev_max.b1"]
    assert res["recon_excess_max.b1"] > clean["recon_excess_max.b1"]
    assert res["comp_dev_max.b0"] == clean["comp_dev_max.b0"]


MESH_RUN = r"""
import json, pathlib, sys, tempfile
sys.path[:0] = [sys.argv[1], sys.argv[1] + "/tests"]
from chipbench_fixtures import CHIP, TINY_BENCH, write_tiny
import harness, run as run_py
import jax
root = write_tiny(pathlib.Path(tempfile.mkdtemp()))
mix = json.loads((root / "mixes/tprune.json").read_text())
(root / "mixes/tprune_mesh.json").write_text(
    json.dumps({**mix, "driver": "prune_jobs_mesh"}))
cell = json.loads((root / "cells/tiny.prune.json").read_text())
(root / "cells/tiny.prune_mesh.json").write_text(
    json.dumps({**cell, "mix": "tprune_mesh"}))
cat = harness.Catalog([root, CHIP])
base = cat.driver("prune_jobs")
made = base._engine
meshes = []
def engine(*a, **k):
    eng = made(*a, **k)
    meshes.append(eng._model_parallel())
    return eng
base._engine = engine
out = run_py.run_cell("tiny.prune_mesh", 2**33 + 9, 1.0, False, catalog=cat,
                      bench=TINY_BENCH, devs=jax.devices()[:4])
print(json.dumps({"correct": out["correct"], "model_parallel": meshes,
                  "devices": jax.device_count()}))
"""


def test_mesh_cell_runs_row_parallel_and_correct():
    # the four-chip driver on four virtual CPU devices (a fresh process:
    # the device count is fixed at JAX's first use)
    import json
    import os
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(CHIP.parents[1] / "src"))
    out = subprocess.run([sys.executable, "-c", MESH_RUN, str(CHIP)],
                         env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["devices"] == 4 and res["correct"]
    assert res["model_parallel"] and set(res["model_parallel"]) == {4}


def test_scan_metric_readers():
    # the roofline's calls and bytes come from the configuration and the
    # mix: 2 layers x (capture + propagate) x 16 shards of 8 x 2048
    cat = harness.Catalog()
    cell = cat.cell("mamba_2_8b.prune_ssm")
    run = {"config": cat.config(cell["config"]), "mix": cat.mix(cell["mix"]),
           "cell": cell, "peaks": harness.peaks("TPU v5 lite"),
           "trace": {"kernel_s": {"selective_scan": 0.5}, "busy_s": 4.0,
                     "window_s": 5.0}}
    roof = cat.metric("selective_scan_roofline.prune_ssm")
    flops, byts = roof.call_cost(8, 2048, 5120, 16)
    assert byts == 4 * (3 * 8 * 2048 * 5120 + 2 * 8 * 2048 * 16
                        + 5120 * 16 + 2 * 8 * 5120 * 16)
    want = 100.0 * 64 * byts / 819e9 / 0.5        # HBM-bound
    assert abs(roof.read(run) - want) < 1e-9 * want
    assert cat.metric("prune_scan_share.prune_ssm").read(run) == 12.5
    for name in ("selective_scan_roofline.prune_ssm",
                 "prune_scan_share.prune_ssm"):
        assert cat.metric(name).read({**run, "trace": None}) is None
        assert cat.metric(name).read(
            {**run, "trace": {**run["trace"], "kernel_s": {}}}) is None
