"""Prune jobs on a Mamba model: ``prune_jobs``'s window, on Mamba-1
layers.

Each job is ``PruningEngine.run`` (pipelined capture / solve /
propagate) on the cell's first ``blocks_per_job`` layers and the mix's
calibration set, and ends on ``block_until_ready`` of its pruned params;
the window's rules are ``prune_jobs``'s (the first job always starts; a
job is started only if the previous job's time still fits; a job the
window cuts counts nothing), and so are its engine, its job and its
stage seconds, reached through the catalog.  The traced run profiles one
more job after the window, whose reduction holds the
``selective_scan`` kernel's device seconds, and then runs one job with
``instrument=True`` for the stage seconds.

The weights are the model's own initialiser's from the seed (S4D-real
A, dt spanning [1e-3, 1e-1], D = 1), made on the device: the generic
rules of ``weights.py`` do not know the state-space leaves.  The last
window job's layers are compared with the reference
(``reference/mamba_forward.py``), on row samples drawn from the seed.
"""

from __future__ import annotations

import gc
import time

import jax
import numpy as np

import harness
import tracing
import traffic
from reference import mamba_forward, sm_sweep

LINEARS = mamba_forward.LINEARS


def run(ctx) -> dict:
    import jax.numpy as jnp

    from repro.models import LM
    base = ctx.catalog.driver("prune_jobs")
    devs = jax.devices()[:ctx.chips]
    prune = ctx.mix["prune"]
    model = LM(ctx.arch())
    params = jax.jit(model.init)(harness.seed_key(ctx.seed))
    jax.block_until_ready(params)
    toks = traffic.calibration(ctx.mix, ctx.seed, model.cfg.vocab_size)
    b = ctx.mix["calibration"]["batch"]
    calib = [{"tokens": jnp.asarray(toks[i:i + b])}
             for i in range(0, toks.shape[0], b)]
    engine = base._engine(model, prune)
    t_w = time.perf_counter()
    pruned = base._job(engine, params, calib)             # warm-up: compiles
    last = warm_s = time.perf_counter() - t_w
    t0 = time.perf_counter()
    setup_s = t0 - ctx.t_start
    c0 = ctx.compiles()
    t1 = t0 + ctx.seconds
    jobs = []
    while not jobs or time.perf_counter() + last <= t1:
        s = time.perf_counter()
        pruned = base._job(engine, params, calib)
        e = time.perf_counter()
        last = e - s
        jobs.append((s, e, model.cfg.num_layers))
    c1 = ctx.compiles()
    red, stages, scan_paths = None, None, None
    if ctx.trace:
        tw = tracing.Window(0.0, 0.0)
        tw.record(lambda: base._job(engine, params, calib))
        red = tw.join()
        st0 = base._stage_seconds(engine)
        base._job(engine, params, calib, instrument=True)
        st1 = base._stage_seconds(engine)
        stages = {k: st1[k] - st0[k] for k in base.STAGES}
    fam = engine.obs.metrics.get("ssm_scan_path_total")
    if fam is not None:
        scan_paths = {k[0]: c.value for k, c in fam.children()}
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)
    del engine
    gc.collect()
    t_ref = time.perf_counter()
    ref = reference(ctx, params, toks, pruned)
    res = judge(ctx, ref, base._rows_of(pruned))
    t_ref = time.perf_counter() - t_ref
    controls = (run_controls(ctx, model, params, calib, ref)
                if ctx.control else None)
    done = [j for j in jobs if j[1] <= t1]
    return {"kind": "prune", "setup_s": setup_s, "window": (t0, t1),
            "seconds": ctx.seconds, "jobs": done, "stages": stages,
            "trace": red, "attempted": len(jobs), "failed": 0,
            "memory_peak_bytes": peak, "checks": checks(ctx, res),
            "controls": controls,
            "notes": {"worst": res.pop("worst"), **res,
                      "job_s": [e - s for s, e, _ in jobs],
                      "warm_job_s": warm_s, "reference_s": t_ref,
                      "scan_paths": scan_paths,
                      "compiles_in_window": None if c0 is None else c1 - c0},
            "arch": model.cfg, "config": ctx.config, "cell": ctx.cell,
            "mix": ctx.mix}


def judge(ctx, ref, rows_of) -> dict:
    """``prune_jobs``'s numbers (mask disagreement, reconstruction
    excess, compensation deviation; worst of each block) over the
    Mamba projections' row samples."""
    return ctx.catalog.driver("prune_jobs").judge(ctx, ref, rows_of)


def checks(ctx, res: dict) -> list:
    return ctx.catalog.driver("prune_jobs").checks(ctx, res)


def run_controls(ctx, model, params, calib, ref) -> dict:
    """``fp8``: the float64 sweep of each sampled row rounded to float8
    e4m3, its output rounded again, in the program's place;
    ``half_batch``: the program on half the calibration batches.  Each
    is judged by the cell's own checks."""
    base = ctx.catalog.driver("prune_jobs")
    prune = ctx.mix["prune"]

    def fp8_rows(blk, name, rows):
        t = ref["by"][blk, name]
        w, _ = sm_sweep.sm_sweep(sm_sweep.round_fp8(t["w0"]), t["h"],
                                 prune["blocksize"], prune["gamma"])
        return sm_sweep.round_fp8(w)

    out = {"fp8": judge(ctx, ref, fp8_rows)}
    half = base._job(base._engine(model, prune), params,
                     calib[:len(calib) // 2])
    out["half_batch"] = judge(ctx, ref, base._rows_of(half))
    return {k: {"checks": checks(ctx, v), **v} for k, v in out.items()}


# ----------------------------------------------------------------- reference
def hessians(ctx, params, toks, pruned) -> list:
    """Per layer, H = 2 X^T X / T of each projection's input, from the
    reference forward (f32 at HIGHEST).  Layer 0 sees the calibration
    tokens through the dense weights alone.  Layer 1 sees them through
    the program's pruned layer 0, which is itself compared: the plain
    sweep of every row of layer 0 that the attention cells use
    (``sm_device``) took 141 s for ``in_proj`` alone on a v5e.  So layer
    1's numbers judge the program's propagate through its pruned layer
    (the selective scan, the float32 residual) and layer 1's capture and
    solves, given layer 0 as the program pruned it."""
    import jax.numpy as jnp
    eps = float(ctx.config["overrides"]["norm_eps"])
    batch = ctx.mix["calibration"]["batch"]
    n = int(ctx.cell["blocks_per_job"])
    dense = [mamba_forward.layer_params(params, i) for i in range(n)]
    done = [mamba_forward.layer_params(pruned, i) for i in range(n - 1)]
    hs = [{k: jnp.zeros((_weight(p, k).shape[0],) * 2, jnp.float32)
           for k in LINEARS} for p in dense]
    for i in range(0, toks.shape[0], batch):
        x = mamba_forward.embed(params, toks[i:i + batch])
        for blk, p in enumerate(dense):
            hs[blk] = mamba_forward.capture(p, x, hs[blk], eps)
            if blk + 1 < n:
                x = mamba_forward.propagate(done[blk], x, eps)
    return [{k: np.asarray(v, np.float64) / toks.size for k, v in h.items()}
            for h in hs]


def _weight(p, name: str):
    mod, w = name.split(".")
    return p[mod][w]


def reference(ctx, params, toks, pruned) -> dict:
    """The float64 SM sweep of row samples drawn from the seed, of every
    projection of every layer, on the reference's Hessians."""
    base = ctx.catalog.driver("prune_jobs")
    prune = ctx.mix["prune"]
    rows_per = int(ctx.cell.get("reference", {}).get("rows", 16))
    rng = np.random.default_rng([ctx.seed, 11])
    tasks = []
    for blk, h in enumerate(hessians(ctx, params, toks, pruned)):
        p = mamba_forward.layer_params(params, blk)
        for name in LINEARS:
            w0 = np.asarray(_weight(p, name), np.float64).T    # (out, in)
            rows = np.sort(rng.choice(w0.shape[0], rows_per, replace=False))
            tasks.append({"blk": blk, "name": name, "rows": rows,
                          "w0": w0[rows], "h": h[name]})

    def sweep(t):
        t["hinv"] = sm_sweep.dampened_inverse(t["h"], prune["gamma"])
        t["w_ref"], t["mask_ref"] = sm_sweep.sm_sweep(
            t["w0"], t["h"], prune["blocksize"], prune["gamma"])
        t["e_ref"] = sm_sweep.recon_error(t["w_ref"], t["w0"], t["h"])

    base._pool(sweep, tasks)
    return {"tasks": tasks, "by": {(t["blk"], t["name"]): t for t in tasks}}
