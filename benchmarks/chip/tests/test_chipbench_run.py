"""The harness end to end on the CPU at a tiny size (the chip checks
aside), the refusals, and the faults that ``correct`` has to catch: a
served token altered where it is produced; a prune step that returns its
state unchanged; half of the calibration batch left out."""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from chipbench_fixtures import CHIP, TINY_BENCH, write_tiny

import harness  # noqa: E402
import run as run_py  # noqa: E402

REPO = CHIP.parents[1]
SECONDS = 1.0


@pytest.fixture(scope="module")
def catalog(tmp_path_factory):
    root = write_tiny(tmp_path_factory.mktemp("tiny"))
    return harness.Catalog([root, CHIP])


def _run(catalog, cell, seed=2**33 + 9, control=False):
    import jax
    return run_py.run_cell(cell, seed, SECONDS, False, catalog=catalog,
                           bench=TINY_BENCH, devs=jax.devices()[:1],
                           control=control)


def test_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "qwen1_5_0_5b.chat", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=REPO, env=env, capture_output=True,
        text=True, timeout=300)
    assert out.returncode == 2
    assert '"correct"' not in out.stdout
    assert "no TPU" in out.stderr


def test_fails_without_the_program(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(CHIP, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "qwen1_5_0_5b.chat", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, env=env, capture_output=True,
        text=True, timeout=300)
    assert out.returncode != 0
    assert '"correct"' not in out.stdout


def test_chat_cell_runs_and_is_correct(catalog):
    out = _run(catalog, "tiny.chat")
    assert out["correct"], out["_checks"]
    assert out["failed"] == 0 and out["attempted"] == 6
    assert set(out["metrics"]) == {"setup_s", "ttft_p90_ms", "itl_p95_ms",
                                   "tokens_per_s"}
    assert out["device"]["platform"] == "cpu"      # a test, never a result
    (chk,) = out["_checks"]
    assert chk["name"] == "logit_gap_max" and chk["value"] < chk["limit"]


def test_docs_cell_runs_and_is_correct(catalog):
    out = _run(catalog, "tiny3.docs")
    assert out["correct"], out["_checks"]
    assert set(out["metrics"]) == {"setup_s", "tokens_per_s"}


def test_altered_token_is_caught(catalog, monkeypatch):
    from repro.serve import fused
    real = fused.sample_rows

    def altered(logits, *a, **k):
        return (real(logits, *a, **k) + 1) % logits.shape[-1]

    monkeypatch.setattr(fused, "sample_rows", altered)
    out = _run(catalog, "tiny.chat")
    assert not out["correct"]


@pytest.fixture(scope="module")
def prune_out(catalog):
    return _run(catalog, "tiny.prune")


def test_prune_cell_is_correct(prune_out):
    assert prune_out["correct"], prune_out["_checks"]
    names = [c["name"] for c in prune_out["_checks"]]
    assert names == ["mask_disagree_max.b0", "recon_excess_max.b0",
                     "comp_dev_max.b0", "recon_excess_max.b1"]


def test_prune_state_unchanged_is_caught(catalog, monkeypatch):
    from repro.core import PruningEngine
    monkeypatch.setattr(PruningEngine, "run",
                        lambda self, params, calib: (params, []))
    out = _run(catalog, "tiny.prune")
    assert not out["correct"]


def test_prune_half_batch_is_caught(catalog, monkeypatch):
    from repro.core.pipeline import SegmentScheduler
    real = SegmentScheduler.shard_states

    def half(self, states):
        states = list(states)
        return real(self, states[: len(states) // 2])

    monkeypatch.setattr(SegmentScheduler, "shard_states", half)
    out = _run(catalog, "tiny.prune")
    assert not out["correct"], out["_checks"]


def test_prune_altered_answer_is_caught(catalog, monkeypatch):
    # one projection of block 0 altered where the engine produces it
    from repro.core import PruningEngine
    real = PruningEngine.run

    def altered(self, params, calib):
        pruned, info = real(self, params, calib)
        s0 = pruned["layers"]["s0"]
        mlp = {**s0["mlp"], "wo": s0["mlp"]["wo"].at[0].multiply(1.05)}
        layers = {**pruned["layers"], "s0": {**s0, "mlp": mlp}}
        return {**pruned, "layers": layers}, info

    monkeypatch.setattr(PruningEngine, "run", altered)
    out = _run(catalog, "tiny.prune")
    assert not out["correct"], out["_checks"]


def test_prune_controls_come_out_not_correct(catalog):
    # the float8 control and the half-batch fault, judged by the cell's
    # own checks
    out = _run(catalog, "tiny.prune", seed=5, control=True)
    assert out["correct"], out["_checks"]
    ctl = out["_controls"]
    assert set(ctl) == {"fp8", "half_batch"}
    for name in ("fp8", "half_batch"):
        assert not all(c["ok"] for c in ctl[name]["checks"]), name


def test_controls_script_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "benchmarks/chip/controls.py", "--workload",
         "qwen1_5_0_5b.prune", "--seeds", "1"], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 2 and "no TPU" in out.stderr


def test_result_line_shape(capsys):
    harness.emit({"correct": True, "attempted": 1, "failed": 0,
                  "metrics": {}, "device": {}},
                 [harness.check("logit_gap_max", 0.1, 0.5)])
    cap = capsys.readouterr()
    line = json.loads(cap.out.strip().splitlines()[-1])
    assert list(line)[-1] == "checks"
    assert line["checks"] == {"logit_gap_max": {"value": 0.1, "limit": 0.5}}
    assert cap.err.strip().splitlines()[-1].startswith("check logit_gap_max")
    assert np.isfinite(line["checks"]["logit_gap_max"]["value"])
