"""Find an open-loop cell's knee: the highest fixed rate at which the
backlog does not grow over the window.

    python3 benchmarks/chip/sweep.py --workload <cell> --rates 2,4,6 \\
        --seconds 30 --seed <n>

One process; for each rate, a run of the cell at that rate (same seed,
so the same set of sizes), printing what was offered and finished, the
TTFT percentiles, and the TTFT p90 of the window's last third against
its first third (a growing backlog shows as a rising tail).  The cell's
rate is then set by hand to about 4/5 of the knee; nothing here is part
of a measured run.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402
import run as run_py  # noqa: E402


def summary(run: dict, rate: float) -> dict:
    t0, t1 = run["window"]
    due = [r for r in run["requests"] if r["due"] <= t1]
    ttft = [(r["events"][0][0] - r["due"]) * 1e3 for r in due if r["events"]]
    third = (t1 - t0) / 3
    first = [(r["events"][0][0] - r["due"]) * 1e3 for r in due
             if r["events"] and r["due"] < t0 + third]
    last = [(r["events"][0][0] - r["due"]) * 1e3 for r in due
            if r["events"] and r["due"] >= t1 - third]
    done_in = sum(1 for r in due if r["finished"] and r["events"]
                  and r["events"][-1][0] <= t1)
    return {"rate": rate, "due": len(due), "finished_in_window": done_in,
            "ttft_p50_ms": harness.quantile(ttft, 0.5),
            "ttft_p90_ms": harness.quantile(ttft, 0.9),
            "ttft_p90_first_third_ms": harness.quantile(first, 0.9),
            "ttft_p90_last_third_ms": harness.quantile(last, 0.9),
            "registry": run["registry"]["counters"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    try:
        devs = harness.require_chips(1)
    except harness.NoChip as e:
        print(f"sweep.py: {e}", file=sys.stderr)
        return 2
    harness.enable_cache()
    cat = harness.Catalog()
    bench = harness.benchmark_json()
    for rate in (float(x) for x in args.rates.split(",")):
        cell = dict(cat.cell(args.workload), rate_per_s=rate)
        t = time.perf_counter()
        out = run_py.run_cell(args.workload, args.seed, args.seconds, False,
                              catalog=cat, bench=bench, devs=devs,
                              cell=cell)
        s = summary(out["_run"], rate)
        s["metrics"] = {k: v["value"] for k, v in out["metrics"].items()}
        s["correct"] = out["correct"]
        s["wall_s"] = time.perf_counter() - t
        print(json.dumps(s), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
