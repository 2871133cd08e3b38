"""Training driver: ``python -m repro.launch.train --arch paper-tiny-lm``.

CPU-scale end-to-end: builds the model, synthetic pipeline, AdamW, and
runs the fault-tolerant Trainer (resumable; kill and rerun to test).
On a real cluster the same entry point runs under the production mesh
(``--mesh production`` inside a multi-host jax.distributed setup) — the
pipeline and Trainer resolve the mesh from the ``use_mesh`` context.
"""

from __future__ import annotations

import argparse


from repro import configs as cfglib
from repro.data import DataPipeline
from repro.dist import add_mesh_argument, mesh_context
from repro.models import LM
from repro.optim import AdamW
from repro.optim.schedules import warmup_cosine
from repro.train import Trainer, TrainConfig
from repro.utils.compile_cache import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="paper_tiny_lm")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--grad-compression", action="store_true")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--out", default="/tmp/repro_train")
    ap.add_argument("--seed", type=int, default=0)
    add_mesh_argument(ap)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = (cfglib.get_smoke(args.arch) if args.smoke
           else cfglib.get_config(args.arch))
    with mesh_context(args.mesh):
        model = LM(cfg)
        pipe = DataPipeline(cfg, args.batch, args.seq, seed=args.seed)
        opt = AdamW(lr=warmup_cosine(args.lr, args.steps // 10, args.steps))
        tc = TrainConfig(
            total_steps=args.steps, global_batch=args.batch,
            seq_len=args.seq, ckpt_every=args.ckpt_every, out_dir=args.out,
            microbatches=args.microbatches,
            grad_compression=args.grad_compression)
        trainer = Trainer(model, opt, pipe, tc)
        params, _, info = trainer.run()
    print(f"trained {info['steps']} steps "
          f"(stragglers: {info['straggler_events']}); "
          f"checkpoints in {args.out}")


if __name__ == "__main__":
    main()
