"""Device time by prune stage, idle time by program span, and stage
traces per job, of the prune cell on the chip.

    python3 benchmarks/chip/stage_profile.py --seeds <n> [<n> ...]

For each seed, in one process, with the prune cell built as the
benchmark's prune driver builds it (``drivers/prune_jobs.py``): one
warm-up job; two plain jobs on the host clock, each reading
``prune_stage_traces_total`` before and after; one plain job under
``jax.profiler``, reduced by ``stages.reduce``; last one job with
``instrument=True``, whose stage seconds ``prune_solve_share`` reads.
Prints one JSON line per seed.  Without a TPU it exits with code 2 and
prints no result.
"""

from __future__ import annotations

import argparse
import gc
import glob
import json
import shutil
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402
import stages  # noqa: E402
import tracing  # noqa: E402
import traffic  # noqa: E402
import weights  # noqa: E402

CELL = "qwen1_5_0_5b.prune"
JOBS = 2


def _traces(engine) -> dict:
    fam = engine.obs.metrics.get("prune_stage_traces_total")
    return {k[0]: c.value for k, c in (fam.children() if fam else [])}


def profile(catalog: harness.Catalog, workload: str, seed: int,
            jobs: int) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.models import LM
    cell = catalog.cell(workload)
    config, mix = catalog.config(cell["config"]), catalog.mix(cell["mix"])
    driver = catalog.driver(mix["driver"])
    blocks = int(cell["blocks_per_job"])
    model = LM(harness.build_arch(config, blocks))
    params = weights.make(model, harness.seed_key(seed), packed=False)
    toks = traffic.calibration(mix, seed, model.cfg.vocab_size)
    b = mix["calibration"]["batch"]
    calib = [{"tokens": jnp.asarray(toks[i:i + b])}
             for i in range(0, toks.shape[0], b)]
    engine = driver._engine(model, mix["prune"])

    def job(instrument=False):
        t = time.perf_counter()
        driver._job(engine, params, calib, instrument=instrument)
        return time.perf_counter() - t

    warm_s = job()
    job_s, traces = [], []
    for _ in range(jobs):
        before = _traces(engine)
        job_s.append(job())
        traces.append({k: v - before.get(k, 0.0)
                       for k, v in _traces(engine).items()
                       if v != before.get(k, 0.0)})
    tw = tracing.Window(0.0, 0.0)
    tw.record(lambda: driver._job(engine, params, calib))
    traced_s = tw.t1 - tw.t0
    path = max(glob.glob(f"{tw.dir}/**/*.xplane.pb", recursive=True),
               key=lambda p: Path(p).stat().st_mtime)
    red = stages.reduce(path)
    shutil.rmtree(tw.dir, ignore_errors=True)
    st0 = driver._stage_seconds(engine)
    job(instrument=True)
    st1 = driver._stage_seconds(engine)
    split = {k: st1[k] - st0[k] for k in driver.STAGES}
    st = red["stage_s"]
    calib_s = st["capture"] + st["hessian"] + st["propagate"]
    out = {
        "seed": seed, "workload": workload, "blocks_per_job": blocks,
        "device_kind": jax.devices()[0].device_kind,
        "warm_job_s": warm_s, "job_s": job_s, "traces_per_job": traces,
        "traced_job_s": traced_s,
        "tracing_cost": traced_s / statistics.median(job_s) - 1.0,
        "per_block": {
            "prune_solve_device_s": st["solve"] / blocks,
            "prune_calib_device_s": calib_s / blocks,
            "prune_unattributed_device_s": st["unattributed"] / blocks,
            "prune_retraces_per_job": statistics.mean(
                sum(t.values()) for t in traces),
            "busy_s": red["busy_s"] / blocks},
        "window_s": red["window_s"], "busy_s": red["busy_s"],
        "stage_s": st, "idle_by_span": red["idle_by_span"],
        "stage_ops": red["stage_ops"],
        "unattributed_modules": red["unattributed_modules"],
        "breakdown": red["breakdown"],
        "instrumented_stage_s": split,
        "solve_share": 100.0 * split["solve"] / sum(split.values()),
    }
    del engine, params, calib
    gc.collect()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    try:
        harness.require_chips(1)
    except harness.NoChip as e:
        print(f"stage_profile.py: {e}; no result", file=sys.stderr,
              flush=True)
        return 2
    harness.enable_cache()
    catalog = harness.Catalog()
    for seed in args.seeds:
        print(json.dumps(profile(catalog, CELL, seed, JOBS)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
