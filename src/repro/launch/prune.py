"""Pruning driver: the paper's Algorithm 1 over a whole checkpointed model.

  python -m repro.launch.prune --arch paper-tiny-lm \\
      --ckpt /tmp/repro_train --sparsity 2:4 --method SM --out /tmp/pruned

Resumable: progress is checkpointed per segment (kill + rerun continues
at the interrupted transformer block).  SIGTERM lands on the same path
as Ctrl-C: the current segment's checkpointed progress survives and the
stage trace (``--trace-out``) is exported on the way out.
"""

from __future__ import annotations

import argparse
import os
import signal

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs as cfglib
from repro.ckpt import CheckpointStore, PruneProgressStore, save_pytree
from repro.core import PruningEngine
from repro.core.engine import summarize
from repro.data import DataPipeline, calibration_batches
from repro.dist import add_mesh_argument, mesh_context
from repro.models import LM
from repro.obs import Obs
from repro.utils.compile_cache import enable_compile_cache


def load_trained_params(model: LM, ckpt_dir: str):
    tpl = jax.tree.map(lambda s: np.zeros(s.shape, s.dtype),
                       jax.eval_shape(model.init, jax.random.key(0)))
    store = CheckpointStore(ckpt_dir)
    restored = store.restore({"params": tpl, "opt": None, "ef": None})
    if restored is None:
        raise FileNotFoundError(f"no checkpoint under {ckpt_dir}")
    _, tree, _ = restored
    return jax.tree.map(jnp.asarray, tree["params"])


def eval_ppl(model: LM, params, pipe: DataPipeline, n: int = 8) -> float:
    tot = cnt = 0.0
    for i in range(n):
        _, m = model.loss_fn(params, pipe.eval_batch(i))
        tot += float(m["ce"]) * float(m["tokens"])
        cnt += float(m["tokens"])
    return float(np.exp(tot / cnt))


def install_sigterm_handler() -> None:
    """Orchestrator SIGTERM → KeyboardInterrupt: the per-segment
    progress store has already checkpointed everything solved so far
    (rerun resumes), and the ``finally`` below still exports the stage
    trace instead of losing it (ISSUE-10 satellite)."""

    def _raise(signum, frame):
        raise KeyboardInterrupt

    try:
        signal.signal(signal.SIGTERM, _raise)
    except ValueError:
        pass   # not the main thread


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="paper_tiny_lm")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--ckpt", required=True)
    ap.add_argument("--sparsity", default="2:4",
                    help='"0.5" unstructured or "N:M"')
    ap.add_argument("--method", default="SM",
                    choices=("magnitude", "wanda", "SS", "SM", "MS", "MM"))
    ap.add_argument("--blocksize", type=int, default=64)
    ap.add_argument("--gamma", type=float, default=0.01)
    ap.add_argument("--calib-samples", type=int, default=32)
    ap.add_argument("--calib-seq", type=int, default=64)
    ap.add_argument("--pipeline", default="auto",
                    choices=("auto", "on", "off"),
                    help="batched/async calibration-solve scheduler "
                         "(core.pipeline); 'off' = the paper's serial loop")
    ap.add_argument("--calib-shard", default="auto",
                    choices=("auto", "on", "off"),
                    help="accumulate calibration Hessians per data(+pod) "
                         "shard and merge with hessian_allreduce")
    ap.add_argument("--out", default="/tmp/repro_pruned")
    ap.add_argument("--metrics", default=True,
                    action=argparse.BooleanOptionalAction,
                    help="prune-pipeline stage timing through the obs "
                         "registry (prune_stage_seconds_total{stage}; "
                         "docs/observability.md)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write Chrome-trace JSON of the pipelined "
                         "capture/solve/propagate stage spans here")
    add_mesh_argument(ap)
    args = ap.parse_args()
    install_sigterm_handler()
    enable_compile_cache()

    cfg = (cfglib.get_smoke(args.arch) if args.smoke
           else cfglib.get_config(args.arch))
    # created up front so an interrupted run (Ctrl-C / SIGTERM) still
    # exports whatever stage spans it recorded before dying
    obs = Obs.create(metrics=args.metrics, trace=args.trace_out is not None)
    try:
        _run(args, cfg, obs)
    finally:
        if args.trace_out:
            n = obs.tracer.export(args.trace_out)
            print(f"wrote {n} trace events -> {args.trace_out}")


def _run(args, cfg, obs: Obs) -> None:
    with mesh_context(args.mesh):
        model = LM(cfg)
        params = load_trained_params(model, args.ckpt)
        pipe = DataPipeline(cfg, 16, args.calib_seq, seed=0)
        print(f"dense ppl: {eval_ppl(model, params, pipe):.4f}")

        calib = calibration_batches(
            cfg, n_samples=args.calib_samples, seq_len=args.calib_seq)
        # the engine resolves the active mesh: layer solves run
        # row-parallel over the `model` axis when one is present
        engine = PruningEngine(
            model, args.sparsity, method=args.method,
            blocksize=args.blocksize, gamma=args.gamma,
            progress_store=PruneProgressStore(args.out),
            pipeline=args.pipeline, calib_shard=args.calib_shard)
        # stage timing + spans flow through the same registry/tracer
        # the serve stack uses (core.pipeline reads engine.obs)
        engine.obs = obs
        pruned, reports = engine.run(params, calib)
        s = summarize(reports)
        print(f"pruned {s['linears']} linears, mean sparsity "
              f"{s['mean_sparsity']:.3f}, total recon error "
              f"{s['total_recon_error']:.4f}")
        ps = engine.last_pipeline_stats
        if ps is not None:
            traces = obs.metrics.get("prune_stage_traces_total")
            traced = "".join(
                f", {int(c.value)} {k[0]} trace(s)"
                for k, c in (traces.children() if traces else []))
            print(f"pipeline: {ps.segments} segments, "
                  f"{ps.calib_shards} calib shard(s){traced}, "
                  f"wall {ps.wall_s:.2f}s")
        print(f"{args.method} {args.sparsity} ppl: "
              f"{eval_ppl(model, pruned, pipe):.4f}")
    save_pytree(os.path.join(args.out, "pruned_params"), pruned,
                extra={"method": args.method, "sparsity": args.sparsity})
    print(f"saved to {args.out}/pruned_params")


if __name__ == "__main__":
    main()
