import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this proves the distribution config is coherent without
hardware: ``jax.jit(step, in_shardings=…).lower(**ShapeDtypeStructs)``
must partition (sharding propagation succeeds), ``.compile()`` must
produce an SPMD executable (collectives legal, memory analyzable), and we
record ``memory_analysis()`` / ``cost_analysis()`` + the HLO collective
byte census for §Roofline.

Usage:
  python -m repro.launch.dryrun --arch qwen3-14b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod both] \
      --out experiments/dryrun

The XLA_FLAGS line above MUST run before any jax import (device count is
locked at backend init) — keep it the first statement of this module, and
never set it globally (smoke tests/benches want 1 device).
"""

import argparse
import dataclasses
import json
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs as cfglib
from repro.dist.sharding import (
    batch_sharding,
    named_shardings,
    param_specs,
    replicated,
)
from repro.launch.mesh import dp_axes_of, make_production_mesh
from repro.models.transformer import LM
from repro.optim import AdamW
from repro.train import make_train_step
from repro.utils.compile_cache import enable_compile_cache
from repro.utils.hlo import collective_bytes

# TPU v5e per-chip constants (roofline)
PEAK_FLOPS = 197e12          # bf16
HBM_BW = 819e9               # bytes/s
ICI_BW = 50e9                # bytes/s/link


@dataclasses.dataclass(frozen=True)
class OptFlags:
    """§Perf hillclimb switches (all off = paper-faithful baseline)."""
    fsdp_embed_fix: bool = False   # iter 1: no FSDP on embed/lm-head
    serve_resident: bool = False   # iter 2a: no FSDP for prefill/decode
    serve_moe_2d: bool = False     # iter 2b: MoE experts model×data 2-D
    banded_local: bool = False     # iter 3a: banded sliding-window attn
    flash_acct: bool = False       # iter 3b: flash-kernel flop accounting
    seq_par_attn: bool = False     # iter 4: sequence-parallel long attn
    sparse_24: bool = False        # iter 5: 2:4-packed serving weights
    seq_cache: bool = False        # iter 6: S-sharded decode KV cache

    @staticmethod
    def level(n: int) -> "OptFlags":
        """1: head/embed fix · 2: +resident serving · 3: +banded/flash
        attention · 4: +sequence-parallel attention · 5: +S-sharded
        decode cache · 6: +2:4-packed serving weights (the paper's
        technique applied). serve_moe_2d is cell-specific (kimi HBM
        feasibility) and set explicitly."""
        return OptFlags(
            fsdp_embed_fix=n >= 1,
            serve_resident=n >= 2,
            banded_local=n >= 3, flash_acct=n >= 3,
            seq_par_attn=n >= 4,
            seq_cache=n >= 5,
            sparse_24=n >= 6)


def depth_variant(cfg, k: int):
    """Same arch at depth k periods, scan disabled — used to extrapolate
    HLO costs that XLA's CPU cost model counts once per while body
    (cost(depth n) = A + n·B; two compiles solve for A, B)."""
    kw = dict(num_layers=len(cfg.prefix) + k * len(cfg.period),
              scan_layers=False)
    if cfg.encdec:
        kw["enc_layers"] = k
    return dataclasses.replace(cfg, **kw)


def build_lowerable(arch_id: str, shape: str, mesh, *,
                    fsdp: bool = True, remat: Optional[str] = None,
                    depth_k: Optional[int] = None,
                    cfg_override=None, opt: Optional[OptFlags] = None):
    """Returns (fn, args, in_shardings) ready for jit().lower()."""
    from repro.dist.sharding import FSDP_EXCLUDE_EMBED
    from repro.models import layers as layers_lib

    opt = opt or OptFlags()
    cfg = cfg_override or cfglib.get_config(arch_id)
    if remat is not None:
        cfg = dataclasses.replace(cfg, remat=remat)
    if depth_k is not None:
        cfg = depth_variant(cfg, depth_k)
    from repro.models import moe as moe_lib
    layers_lib.BANDED_LOCAL_ATTN = opt.banded_local
    layers_lib.SEQ_PAR_ATTN = opt.seq_par_attn
    layers_lib.HEAD_GATHER = opt.fsdp_embed_fix
    moe_lib.FORCE_PLAIN_GSPMD = opt.serve_moe_2d
    model = LM(cfg)
    sp = cfglib.SHAPES[shape]
    dp = dp_axes_of(mesh)
    dp_total = int(np.prod([mesh.shape[a] for a in dp]))
    specs = cfglib.input_specs(cfg, shape)
    params = model.init_shapes()
    if sp.kind != "train" and opt.serve_resident:
        fsdp = False
    fsdp_axes = dp if fsdp else ()
    pspec = param_specs(
        params, mesh, fsdp_axes=fsdp_axes,
        fsdp_exclude=FSDP_EXCLUDE_EMBED if opt.fsdp_embed_fix else (),
        serve_moe=(sp.kind != "train" and opt.serve_moe_2d))
    psh = named_shardings(mesh, pspec)
    # batch < #data-shards (long_500k): replicate batch, shard the cache's
    # sequence dim over data instead (context parallelism)
    seq_shard = sp.global_batch % dp_total != 0
    bsh = replicated(mesh) if seq_shard else batch_sharding(mesh, dp)
    rep = replicated(mesh)

    if sp.kind == "train":
        optimizer = AdamW(lr=1e-4, moment_dtype="bfloat16")
        step_fn = make_train_step(model, optimizer)
        opt_state = jax.eval_shape(optimizer.init, params)
        ef = jax.ShapeDtypeStruct((), jnp.float32)
        batch = {k: specs[k] for k in specs}
        osh = type(opt_state)(rep, psh, psh)
        args = (params, opt_state, ef, batch)
        shardings = (psh, osh, rep,
                     {k: bsh for k in batch})
        return step_fn, args, shardings, model

    if sp.kind == "prefill":
        def prefill_step(params, batch, cache):
            return model.prefill(params, batch, cache)
        cache = specs["cache"]
        csh = named_shardings(mesh, model.cache_specs(mesh, dp, seq_shard=seq_shard,
                                          prefer_seq=opt.seq_cache))
        batch = {k: v for k, v in specs.items() if k != "cache"}
        args = (params, batch, cache)
        shardings = (psh, {k: bsh for k in batch}, csh)
        return prefill_step, args, shardings, model

    # decode
    def serve_step(params, token, cache, pos):
        return model.decode_step(params, token, cache, pos)
    csh = named_shardings(mesh, model.cache_specs(mesh, dp, seq_shard=seq_shard,
                                      prefer_seq=opt.seq_cache))
    args = (params, specs["token"], specs["cache"], specs["pos"])
    shardings = (psh, bsh, csh, rep)
    return serve_step, args, shardings, model


def _compile_cell(arch_id, shape, mesh, *, fsdp, depth_k=None,
                  cfg_override=None, opt=None):
    from repro.dist.api import use_mesh
    from repro.launch.mesh import dp_axes_of as _dp

    fn, args, shardings, model = build_lowerable(
        arch_id, shape, mesh, fsdp=fsdp, depth_k=depth_k,
        cfg_override=cfg_override, opt=opt)
    with use_mesh(mesh, dp_axes=_dp(mesh)):
        lowered = jax.jit(fn, in_shardings=shardings).lower(*args)
        compiled = lowered.compile()
    return compiled, model


def _extrapolate(v1: float, v2: float, n: int) -> float:
    """cost(k) = A + k·B from k=1,2 → cost(n); clamped non-negative."""
    b = max(0.0, v2 - v1)
    a = max(0.0, v1 - b)
    return a + n * b


def run_cell(arch_id: str, shape: str, *, multi_pod: bool,
             fsdp: bool = True, verbose: bool = True,
             extra_tag: str = "", cfg_override=None,
             opt: Optional[OptFlags] = None) -> Dict[str, Any]:
    """Lower + compile one cell; return the §Roofline record.

    Compute & memory roofline terms come from launch.analytic (closed
    form — XLA's CPU cost model counts while bodies once, so raw HLO
    flops/bytes are kept as diagnostics only); the collective term is
    measured from the compiled HLO with scan-body collectives scaled by
    their statically-known trip counts (op_name loop-nesting metadata).
    """
    from repro.launch.analytic import analytic_cell

    mesh_name = "2x16x16" if multi_pod else "16x16"
    cell = f"{arch_id}×{shape}×{mesh_name}{extra_tag}"
    cfg = cfg_override or cfglib.get_config(arch_id)
    ok, reason = cfglib.shape_is_applicable(cfg, shape)
    if not ok:
        if verbose:
            print(f"[skip] {cell}: {reason}")
        return {"arch": arch_id, "shape": shape, "mesh": mesh_name,
                "status": "skipped", "reason": reason}

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod(mesh.devices.shape))
    opt = opt or OptFlags()
    t0 = time.monotonic()
    try:
        compiled, model = _compile_cell(
            arch_id, shape, mesh, fsdp=fsdp, cfg_override=cfg_override,
            opt=opt)
        t_compile = time.monotonic() - t0
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()

        # --- trip-count-scaled collective census --------------------------
        # Collectives inside scan bodies appear once in the HLO text; the
        # op_name metadata records the loop nesting, and we know every
        # loop's trip count statically: level 0 = the layer scan
        # (n_periods; the encoder scan in enc-dec archs has the same trip
        # count by construction), level 1 = the inner sequential scan
        # (online-attention KV chunks, or the sLSTM token recurrence).
        sp = cfglib.SHAPES[shape]
        n_per = max(1, cfg.n_periods)
        if "slstm" in cfg.period and sp.kind != "decode":
            inner = sp.seq_len                       # sLSTM token scan
        elif sp.kind == "prefill" and sp.seq_len > 8192:
            from repro.models.layers import ONLINE_ATTN_CHUNK
            inner = max(1, sp.seq_len // ONLINE_ATTN_CHUNK)
        else:
            inner = 1
        trips = (n_per, inner)
        coll = collective_bytes(compiled.as_text(), trip_counts=trips)
        coll_wire = coll.wire_bytes
        coll_total = coll.total_bytes
        coll_counts = coll.counts
        coll_op_bytes = dict(coll.operand_bytes)
        # raw HLO numbers (loop bodies counted ONCE — diagnostic only)
        flops_hlo = float(cost.get("flops", 0.0))
        bytes_hlo = float(cost.get("bytes accessed", 0.0))

        # --- analytic roofline terms -------------------------------------
        attn_impl = ("flash" if opt.flash_acct
                     else "banded" if opt.banded_local else "dense")
        ana = analytic_cell(cfg, sp.kind, sp.global_batch, sp.seq_len,
                            attn_impl=attn_impl, sparse_24=opt.sparse_24)
        flops_dev = ana["flops"] / chips
        bytes_dev = ana["bytes"] / chips
        t_compute = flops_dev / PEAK_FLOPS
        t_memory = bytes_dev / HBM_BW
        t_coll = coll_wire / ICI_BW
        dominant = max(
            (("compute", t_compute), ("memory", t_memory),
             ("collective", t_coll)), key=lambda kv: kv[1])[0]
        bound = max(t_compute, t_memory, t_coll)
        counts = model.param_counts()
        tokens = sp.global_batch * sp.seq_len if sp.kind == "train" else (
            sp.global_batch * (sp.seq_len if sp.kind == "prefill" else 1))
        mult = 6 if sp.kind == "train" else 2
        model_flops = mult * counts["active"] * tokens / chips
        rec = {
            "arch": arch_id, "shape": shape, "mesh": mesh_name,
            "status": "ok", "chips": chips,
            "compile_s": round(t_compile, 1),
            "flops_per_device": flops_dev,
            "bytes_per_device": bytes_dev,
            "flops_hlo_per_device": flops_hlo,
            "bytes_hlo_per_device": bytes_hlo,
            "collective_bytes_per_device": coll_total,
            "collective_wire_bytes": coll_wire,
            "collective_counts": coll_counts,
            "collective_op_bytes": coll_op_bytes,
            "t_compute_s": t_compute, "t_memory_s": t_memory,
            "t_collective_s": t_coll, "dominant": dominant,
            "roofline_fraction": t_compute / bound if bound else None,
            "model_flops_per_device": model_flops,
            "useful_flop_ratio": (model_flops / flops_dev
                                  if flops_dev else None),
            "peak_memory_per_device": int(
                getattr(mem, "temp_size_in_bytes", 0)
                + getattr(mem, "argument_size_in_bytes", 0)),
            "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
            "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
            "params_total": counts["total"],
            "params_active": counts["active"],
            "opt": dataclasses.asdict(opt),
        }
        if verbose:
            print(f"[ok]  {cell}: compile {t_compile:.0f}s | "
                  f"{flops_dev/1e9:.1f} GF/dev {bytes_dev/1e6:.1f} MB/dev "
                  f"coll {coll_wire/1e6:.1f} MB/dev → {dominant}-bound "
                  f"(c={t_compute*1e3:.2f}ms m={t_memory*1e3:.2f}ms "
                  f"x={t_coll*1e3:.2f}ms) roofline={rec['roofline_fraction']:.2f}")
        return rec
    except Exception as e:  # a failure here is a bug in the system
        if verbose:
            print(f"[FAIL] {cell}: {e}")
            traceback.print_exc()
        return {"arch": arch_id, "shape": shape, "mesh": mesh_name,
                "status": "failed", "error": f"{type(e).__name__}: {e}"}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", choices=("off", "on", "both"),
                    default="off")
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--opt-level", type=int, default=0,
                    help="§Perf hillclimb level (0=baseline)")
    ap.add_argument("--out", default=None, help="JSONL output path")
    args = ap.parse_args()
    enable_compile_cache()

    arch_ids = [a for a in cfglib.ARCH_IDS if a != "paper_tiny_lm"] \
        if (args.all or args.arch is None) else [cfglib.canonical(args.arch)]
    shapes = list(cfglib.SHAPES) if (args.all or args.shape is None) \
        else [args.shape]
    pods = {"off": [False], "on": [True], "both": [False, True]}[
        args.multi_pod]

    records = []
    for arch in arch_ids:
        for shape in shapes:
            for mp in pods:
                rec = run_cell(arch, shape, multi_pod=mp,
                               fsdp=not args.no_fsdp,
                               opt=OptFlags.level(args.opt_level))
                records.append(rec)
                if args.out:
                    with open(args.out, "a") as f:
                        f.write(json.dumps(rec) + "\n")
    failed = [r for r in records if r["status"] == "failed"]
    print(f"\n{len(records)} cells: "
          f"{sum(r['status'] == 'ok' for r in records)} ok, "
          f"{sum(r['status'] == 'skipped' for r in records)} skipped, "
          f"{len(failed)} FAILED")
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
