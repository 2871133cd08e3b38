"""Calibration-pipeline benchmark: serial vs pipelined PruningEngine.

Measures, on the trained tiny LM over an 8-virtual-device
(pod, data, model) mesh:

  - end-to-end prune wall-clock of the serial reference loop
    (``pipeline="off"``) vs the async scheduler (core.pipeline) with
    calibration sharded over the 4 pod×data slices;
  - the instrumented capture/solve/propagate stage costs and the overlap
    fraction the async dispatch wins back;
  - mask/weight equivalence of the two paths (the scheduler must be a
    pure perf change).

The XLA device count locks at first jax import, so ``run()`` spawns a
subprocess on the CPU backend with
``--xla_force_host_platform_device_count=8`` (the same trick as
tests/test_dist.py) and parses its JSON report.  The parent touches no
JAX: the child trains the cached model itself, and never asks for an
accelerator that a parent process might hold.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from typing import List

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def run(fast: bool = False) -> List["BenchResult"]:
    from benchmarks.common import BenchResult

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = (os.path.join(REPO, "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    cmd = [sys.executable, "-m", "benchmarks.calib_pipeline", "--child"]
    if fast:
        cmd.append("--fast")
    out = subprocess.run(cmd, capture_output=True, text=True, env=env,
                         cwd=REPO, timeout=1800)
    if out.returncode != 0:
        raise RuntimeError(
            f"calib_pipeline child failed:\n{out.stdout}\n{out.stderr}")
    r = json.loads(out.stdout.strip().splitlines()[-1])

    # equivalence gate: masks may flip only on float-level score ties
    # (different Hessian reduction order), quality must be unchanged
    ppl_gap = abs(r["ppl_pipelined"] - r["ppl_serial"]) / r["ppl_serial"]
    if r["mask_agreement"] < 0.999 or ppl_gap > 0.02:
        raise RuntimeError(
            f"pipelined != serial: mask_agreement={r['mask_agreement']:.5f} "
            f"ppl {r['ppl_serial']:.4f} vs {r['ppl_pipelined']:.4f}")
    speedup = r["serial_s"] / max(r["pipelined_s"], 1e-9)
    local_speedup = r["local_serial_s"] / max(r["local_pipelined_s"], 1e-9)
    overlap = max(0.0, 1.0 - r["pipelined_s"] / max(r["stage_total_s"], 1e-9))
    local_overlap = max(0.0, 1.0 - r["local_pipelined_warm_s"]
                        / max(r["local_stage_total_s"], 1e-9))
    return [
        BenchResult("calib_pipeline/local/serial",
                    r["local_serial_s"] * 1e6,
                    f"wall={r['local_serial_s']:.2f}s"),
        BenchResult("calib_pipeline/local/pipelined",
                    r["local_pipelined_s"] * 1e6,
                    f"wall={r['local_pipelined_s']:.2f}s "
                    f"speedup={local_speedup:.2f}x",
                    metrics={"prune_wall_s": r["local_pipelined_s"],
                             "speedup": local_speedup}),
        BenchResult(
            "calib_pipeline/local/stages", r["local_stage_total_s"] * 1e6,
            f"capture={r['local_capture_s']:.2f}s "
            f"solve={r['local_solve_s']:.2f}s "
            f"propagate={r['local_propagate_s']:.2f}s "
            f"overlap={local_overlap:.0%}"),
        BenchResult("calib_pipeline/mesh/serial", r["serial_s"] * 1e6,
                    f"wall={r['serial_s']:.2f}s"),
        BenchResult("calib_pipeline/mesh/pipelined", r["pipelined_s"] * 1e6,
                    f"wall={r['pipelined_s']:.2f}s speedup={speedup:.2f}x "
                    f"shards={r['calib_shards']}",
                    metrics={"prune_wall_s": r["pipelined_s"],
                             "speedup": speedup}),
        BenchResult(
            "calib_pipeline/mesh/stages", r["stage_total_s"] * 1e6,
            f"capture={r['capture_s']:.2f}s solve={r['solve_s']:.2f}s "
            f"propagate={r['propagate_s']:.2f}s overlap={overlap:.0%}"),
    ]


# ----------------------------------------------------------------------
# child: runs under 8 virtual devices
# ----------------------------------------------------------------------
def _child(fast: bool) -> None:
    import time

    import jax
    import numpy as np

    from benchmarks.common import eval_ppl, trained_model
    from repro.core import PruningEngine
    from repro.core.pipeline import run_pipelined
    from repro.data import calibration_batches
    from repro.dist import make_mesh, use_mesh

    model, params, pipe = trained_model("lm")
    n_samples = 128 if fast else 256
    calib = calibration_batches(model.cfg, n_samples=n_samples,
                                seq_len=64, batch=8)
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))

    def timed(engine_kwargs, runner=None, with_mesh=True):
        import contextlib

        ctx = use_mesh(mesh) if with_mesh else contextlib.nullcontext()
        with ctx:
            eng = PruningEngine(model, "2:4", method="SM", blocksize=64,
                                **engine_kwargs)
            t0 = time.monotonic()
            if runner is None:
                pruned, _ = eng.run(params, calib)
            else:
                pruned, _ = runner(eng)
            for leaf in jax.tree.leaves(pruned):
                jax.block_until_ready(leaf)
            return eng, pruned, time.monotonic() - t0

    # pipelined runs FIRST (cold compile caches); the serial reference
    # then inherits any warm solve cache — measured speedups are
    # therefore conservative lower bounds
    _, _, local_pipe_s = timed({}, with_mesh=False)
    _, _, local_serial_s = timed({"pipeline": "off"}, with_mesh=False)
    # local instrumented pass: single-device stage costs — against the
    # async local wall this measures the dispatch overlap
    ileng, _, _ = timed(
        {}, runner=lambda e: run_pipelined(e, params, calib,
                                           instrument=True),
        with_mesh=False)
    ilstats = ileng.last_pipeline_stats
    # warm async pass — same compile state as the instrumented pass, so
    # stage_total vs this wall isolates the dispatch overlap
    _, _, local_warm_s = timed({}, with_mesh=False)

    eng, p_pipe, pipelined_s = timed({})
    stats = eng.last_pipeline_stats
    _, p_serial, serial_s = timed({"pipeline": "off"})
    # instrumented pass: block per stage → true stage costs; its
    # stage_total vs the async pass's wall measures the overlap won
    ieng, _, _ = timed(
        {}, runner=lambda e: run_pipelined(e, params, calib,
                                           instrument=True))
    istats = ieng.last_pipeline_stats

    total, agreeing = 0, 0
    for a, b in zip(jax.tree.leaves(p_serial), jax.tree.leaves(p_pipe)):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        agree = (a == 0) == (b == 0)
        total += agree.size
        agreeing += int(agree.sum())

    print(json.dumps({
        "serial_s": serial_s,
        "pipelined_s": pipelined_s,
        "local_serial_s": local_serial_s,
        "local_pipelined_s": local_pipe_s,
        "local_pipelined_warm_s": local_warm_s,
        "local_capture_s": ilstats.capture_s,
        "local_solve_s": ilstats.solve_s,
        "local_propagate_s": ilstats.propagate_s,
        "local_stage_total_s": ilstats.stage_total(),
        "calib_shards": stats.calib_shards,
        "capture_s": istats.capture_s,
        "solve_s": istats.solve_s,
        "propagate_s": istats.propagate_s,
        "stage_total_s": istats.stage_total(),
        "mask_agreement": agreeing / total,
        "ppl_serial": eval_ppl(model, p_serial, pipe),
        "ppl_pipelined": eval_ppl(model, p_pipe, pipe),
    }))


if __name__ == "__main__":
    if "--child" in sys.argv:
        _child("--fast" in sys.argv)
    else:
        for res in run(fast="--fast" in sys.argv):
            print(res.csv())
