"""Benchmark harness: one module per paper table + kernels + roofline.

  PYTHONPATH=src python -m benchmarks.run [--only table1,...] [--fast]
  PYTHONPATH=src python -m benchmarks.run --smoke --json BENCH_$SHA.json

Prints ``name,us_per_call,derived`` CSV (and writes
experiments/bench_results.csv).  ``--json`` additionally writes a
machine-readable report — tokens/sec, utilization, prune wall-clock —
that the CI ``bench-gate`` job uploads as an artifact and diffs against
the checked-in ``benchmarks/baseline.json`` (see benchmarks.gate;
refresh the baseline with ``--json benchmarks/baseline.json``).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

MODULES = ("table1", "table2", "table3", "ablation", "kernelbench",
           "roofline", "calib_pipeline", "serve_throughput")
# the CI smoke subset: cheap, but together they exercise the trained-model
# cache, a full engine run (both pipeline modes), the continuous-batching
# serve runtime (paged KV + state pool + scheduler) and the CSV plumbing
SMOKE_MODULES = ("calib_pipeline", "serve_throughput")


def _git_sha() -> str:
    sha = os.environ.get("GITHUB_SHA")
    if sha:
        return sha
    try:
        return subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            cwd=os.path.dirname(__file__)).stdout.strip() or "unknown"
    except OSError:
        return "unknown"


def write_json(path: str, results) -> None:
    import jax

    report = {
        "sha": _git_sha(),
        "jax": jax.__version__,
        "results": {
            r.name: {"us_per_call": r.us_per_call, "derived": r.derived,
                     "metrics": r.metrics}
            for r in results
        },
    }
    with open(path, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"# wrote {path}", file=sys.stderr)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help=f"comma-separated subset of {MODULES}")
    ap.add_argument("--fast", action="store_true",
                    help="reduced sweeps (CI smoke)")
    ap.add_argument("--smoke", action="store_true",
                    help=f"CI smoke: --fast over {SMOKE_MODULES} "
                         "(unless --only narrows further)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write a machine-readable BENCH report "
                         "(the CI bench-gate artifact / baseline.json)")
    args = ap.parse_args()
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.smoke:
        args.fast = True
    default = list(SMOKE_MODULES) if args.smoke else list(MODULES)
    chosen = args.only.split(",") if args.only else default
    results = []
    for name in chosen:
        if name not in MODULES:
            raise SystemExit(f"unknown benchmark {name!r}; pick from "
                             f"{MODULES}")
        mod = __import__(f"benchmarks.{name}", fromlist=["run"])
        print(f"# running {name} ...", file=sys.stderr, flush=True)
        results.extend(mod.run(fast=args.fast))

    print("name,us_per_call,derived")
    lines = [r.csv() for r in results]
    for line in lines:
        print(line)
    out_path = os.path.join(os.path.dirname(__file__), "..",
                            "experiments", "bench_results.csv")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        f.write("name,us_per_call,derived\n")
        f.write("\n".join(lines) + "\n")
    if args.json:
        write_json(args.json, results)


if __name__ == "__main__":
    main()
