"""Prune jobs: the one-off prune a user runs, repeated over the window.

Each job is ``PruningEngine.run`` as ``repro.launch.prune`` drives it
(pipelined capture / solve / propagate) on the cell's model and the
mix's calibration set, and ends on ``block_until_ready`` of its pruned
params.  A job that would not finish inside the window (judged by the
previous job's time) is not started, apart from the first, which always
starts; one that the window cuts is not counted.  The traced run measures the same window, then profiles
one more job of the same path, and last runs one job with
``instrument=True`` (which blocks after every stage) for the stage
seconds alone.

The last window job's pruned blocks are compared with the reference
(``reference``), on row samples drawn from the seed.
"""

from __future__ import annotations

import functools
import gc
import os
import time
from concurrent.futures import ThreadPoolExecutor

import jax
import numpy as np

import harness
import tracing
import traffic
import weights
from reference import forward, sm_device, sm_sweep

LINEARS = ("attn.wq", "attn.wk", "attn.wv", "attn.wo",
           "mlp.wi", "mlp.wg", "mlp.wo")
STAGES = ("capture", "solve", "propagate")


def _engine(model, prune: dict):
    from repro.core import PruningEngine
    from repro.obs import Obs
    eng = PruningEngine(model, prune["sparsity"], method=prune["method"],
                        blocksize=prune["blocksize"], gamma=prune["gamma"],
                        calib_shard=prune.get("calib_shard", "auto"))
    eng.obs = Obs.create(metrics=True, trace=False)
    return eng


def _job(engine, params, calib, instrument: bool = False):
    from repro.core.pipeline import run_pipelined
    if instrument:
        pruned, _ = run_pipelined(engine, params, calib, instrument=True)
    else:
        pruned, _ = engine.run(params, calib)
    jax.block_until_ready(pruned)
    return pruned


def _stage_seconds(engine) -> dict:
    fam = engine.obs.metrics.get("prune_stage_seconds_total")
    out = {s: 0.0 for s in STAGES}
    for key, child in (fam.children() if fam is not None else []):
        out[key[0]] = child.value
    return out


def run(ctx) -> dict:
    import jax.numpy as jnp

    from repro.models import LM
    devs = jax.devices()[:ctx.chips]
    prune = ctx.mix["prune"]
    model = LM(ctx.arch())
    params = weights.make(model, harness.seed_key(ctx.seed), packed=False)
    toks = traffic.calibration(ctx.mix, ctx.seed, model.cfg.vocab_size)
    b = ctx.mix["calibration"]["batch"]
    calib = [{"tokens": jnp.asarray(toks[i:i + b])}
             for i in range(0, toks.shape[0], b)]
    engine = _engine(model, prune)
    t_w = time.perf_counter()
    pruned = _job(engine, params, calib)                   # warm-up: compiles
    last = warm_s = time.perf_counter() - t_w
    t0 = time.perf_counter()
    setup_s = t0 - ctx.t_start
    c0 = ctx.compiles()
    t1 = t0 + ctx.seconds
    jobs = []
    while not jobs or time.perf_counter() + last <= t1:
        s = time.perf_counter()
        pruned = _job(engine, params, calib)
        e = time.perf_counter()
        last = e - s
        jobs.append((s, e, model.cfg.num_layers))
    c1 = ctx.compiles()
    red, stages = None, None
    if ctx.trace:
        tw = tracing.Window(0.0, 0.0)
        tw.record(lambda: _job(engine, params, calib))
        red = tw.join()
        st0 = _stage_seconds(engine)
        _job(engine, params, calib, instrument=True)
        st1 = _stage_seconds(engine)
        stages = {k: st1[k] - st0[k] for k in STAGES}
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)
    del engine
    gc.collect()
    t_ref = time.perf_counter()
    ref = reference(ctx, params, toks)
    res = judge(ctx, ref, _rows_of(pruned))
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
                      "compiles_in_window": None if c0 is None else c1 - c0},
            "arch": model.cfg, "config": ctx.config, "cell": ctx.cell,
            "mix": ctx.mix}


def checks(ctx, res: dict) -> list:
    """The numbers the cell gives a limit, each beside it (a number with
    no limit is only noted)."""
    return [harness.check(n, res[n], lim)
            for n, lim in ctx.cell.get("limits", {}).items()]


# ------------------------------------------------------------------ controls
def run_controls(ctx, model, params, calib, ref) -> dict:
    """The control and a planted fault, each judged by the same checks
    (so each has to come out not correct):

    - ``fp8``: the reference put in the program's place at the precision
      below the configuration's bfloat16: the sweep run on each row
      rounded to float8 e4m3, its output rounded again;
    - ``half_batch``: the program on the first half of the calibration
      batches (the Hessians the mean over the rest).
    """
    prune = ctx.mix["prune"]

    def fp8_rows(blk, name, rows):
        t = ref["by"][blk, name]
        w, _ = sm_sweep.sm_sweep(sm_sweep.round_fp8(t["w0"]), t["h"],
                                 prune["blocksize"], prune["gamma"])
        return sm_sweep.round_fp8(w)

    out = {"fp8": judge(ctx, ref, fp8_rows)}
    half = _job(_engine(model, prune), params, calib[:len(calib) // 2])
    out["half_batch"] = judge(ctx, ref, _rows_of(half))
    return {k: {"checks": checks(ctx, v), **v} for k, v in out.items()}


# ----------------------------------------------------------------- reference
def _shared_input(name: str) -> str:
    """Projections that read the same input share its Hessian."""
    return {"attn.wk": "attn.wq", "attn.wv": "attn.wq",
            "mlp.wg": "mlp.wi"}.get(name, name)


def _weight(p, name: str):
    mod, w = name.split(".")
    return p[mod][w]


@functools.partial(jax.jit, static_argnames=("ck",))
def _capture(p, xs, hs, ck):
    """Accumulate 2 X^T X of each projection's input over the sequences
    ``xs`` (B, T, d) through the block ``p``."""
    import jax.numpy as jnp
    positions = jnp.arange(xs.shape[1], dtype=jnp.int32)

    def one(carry, x):
        caps = {}
        forward.block(p, x, positions, dict(ck), caps=caps)
        return {k: carry[k] + 2.0 * jnp.matmul(
            caps[k].T, caps[k], precision=forward.HIGHEST)
            for k in carry}, None
    return jax.lax.scan(one, hs, xs)[0]


@functools.partial(jax.jit, static_argnames=("ck",))
def _propagate(p, xs, ck):
    import jax.numpy as jnp
    positions = jnp.arange(xs.shape[1], dtype=jnp.int32)
    return jax.lax.map(lambda x: forward.block(p, x, positions, dict(ck)),
                       xs)


def _hessians(ctx, params, toks) -> list:
    """Per block, H = 2 X^T X / T of each projection's input, from the
    reference forward (f32 at HIGHEST).  Block b sees the calibration
    tokens through the reference's own pruned blocks before it, every
    row swept on the device (``sm_device``): nothing the program made."""
    import jax.numpy as jnp
    ck = tuple(sorted(forward.consts(ctx.config).items()))
    prune = ctx.mix["prune"]
    n_prune, group = (int(v) for v in prune["sparsity"].split(":"))
    batch = ctx.mix["calibration"]["batch"]
    emb = jnp.asarray(params["embed"]["tok"], jnp.float32)
    xs_all = [emb[jnp.asarray(toks[i:i + batch])]
              for i in range(0, toks.shape[0], batch)]
    out = []
    n_blocks = int(ctx.cell["blocks_per_job"])
    for blk in range(n_blocks):
        p = forward.layer_params(params, blk)
        hs = {k: jnp.zeros((_weight(p, k).shape[0],) * 2, jnp.float32)
              for k in ("attn.wq", "attn.wo", "mlp.wi", "mlp.wo")}
        for xs in xs_all:
            hs = _capture(p, xs, hs, ck)
        h = {k: np.asarray(v, np.float64) / toks.size for k, v in hs.items()}
        out.append(h)
        if blk + 1 == n_blocks:
            break
        for name in LINEARS:
            mod, w = name.split(".")
            swept = sm_device.sweep(
                jnp.asarray(p[mod][w], jnp.float32).T, h[_shared_input(name)],
                prune["blocksize"], prune["gamma"], n_prune, group)
            p = {**p, mod: {**p[mod], w: swept.T}}
        xs_all = [_propagate(p, xs, ck) for xs in xs_all]
    return out


def _pool(fn, items):
    """``fn`` over ``items`` in threads, two BLAS threads each (the
    float64 solves are small: more threads per solve only contend)."""
    from threadpoolctl import threadpool_limits
    with threadpool_limits(limits=2, user_api="blas"), \
            ThreadPoolExecutor(max_workers=min(8, os.cpu_count() or 1)) as ex:
        return list(ex.map(fn, items))


def reference(ctx, params, toks) -> dict:
    """The float64 SM sweep of row samples drawn from the seed, of every
    projection of every block, on the reference's Hessians."""
    prune = ctx.mix["prune"]
    rows_per = int(ctx.cell.get("reference", {}).get("rows", 16))
    hs = _hessians(ctx, params, toks)
    rng = np.random.default_rng([ctx.seed, 11])
    tasks = []
    for blk, h_blk in enumerate(hs):
        p = forward.layer_params(params, blk)
        hinv = {k: sm_sweep.dampened_inverse(v, prune["gamma"])
                for k, v in h_blk.items()}
        for name in LINEARS:
            w0 = np.asarray(_weight(p, name), np.float64).T    # (out, in)
            rows = np.sort(rng.choice(w0.shape[0], rows_per, replace=False))
            key = _shared_input(name)
            tasks.append({"blk": blk, "name": name, "rows": rows,
                          "w0": w0[rows], "h": h_blk[key],
                          "hinv": hinv[key]})

    def sweep(t):
        t["w_ref"], t["mask_ref"] = sm_sweep.sm_sweep(
            t["w0"], t["h"], prune["blocksize"], prune["gamma"])
        t["e_ref"] = sm_sweep.recon_error(t["w_ref"], t["w0"], t["h"])

    _pool(sweep, tasks)
    return {"tasks": tasks, "by": {(t["blk"], t["name"]): t for t in tasks}}


def _rows_of(pruned):
    """The program's rows of a projection, as the reference indexes
    them: ``rows_of(block, name, rows) -> (len(rows), in)`` float64."""
    def rows_of(blk, name, rows):
        w = _weight(forward.layer_params(pruned, blk), name)
        return np.asarray(w, np.float64).T[rows]
    return rows_of


def judge(ctx, ref, rows_of) -> dict:
    """Worst, over every projection of every block (``<number>_max``)
    and over those of block ``b`` (``<number>_max.b<b>``), of

    - ``mask_disagree``: the share of sampled weights whose pruned/kept
      state differs from the float64 sweep's;
    - ``recon_excess``: (program's layer objective - sweep's) / sweep's;
    - ``comp_dev``: per row, |w - w*| / |w*|, where w* is the optimal
      compensation (float64) for the program's own mask: the solver's
      error, apart from which weights it chose.
    """
    def one(t):
        w1 = rows_of(t["blk"], t["name"], t["rows"])
        mask = w1 == 0
        dis = float(np.mean(mask != t["mask_ref"]))
        exc = (sm_sweep.recon_error(w1, t["w0"], t["h"]) - t["e_ref"]) \
            / t["e_ref"]
        w_opt = sm_sweep.project(t["w0"], t["hinv"], mask)
        dev = (np.linalg.norm(w1 - w_opt, axis=1)
               / np.linalg.norm(w_opt, axis=1))
        return (t["blk"], t["name"], dis, exc, dev)

    per = _pool(one, ref["tasks"])
    out = {"worst": {k: list(max(per, key=lambda p: f(p))[:2])
                     for k, f in (("mask", lambda p: p[2]),
                                  ("recon", lambda p: p[3]),
                                  ("comp", lambda p: np.max(p[4])))}}
    for sfx, sel in [("", per)] + [
            (f".b{b}", [p for p in per if p[0] == b])
            for b in sorted({p[0] for p in per})]:
        devs = np.concatenate([p[4] for p in sel])
        out[f"mask_disagree_max{sfx}"] = max(p[2] for p in sel)
        out[f"recon_excess_max{sfx}"] = max(p[3] for p in sel)
        out[f"comp_dev_max{sfx}"] = float(np.max(devs))
        out[f"comp_dev_median{sfx}"] = float(np.median(devs))
    return out
