"""Chip benchmark: one run of one cell.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Builds the cell named in ``BENCHMARK.json`` from its data files
(``cells/<cell>.json`` and the configuration and mix it names), makes its
weights and traffic from ``--seed``, warms every shape the cell uses
(set-up, reported as ``setup_s``), measures for ``--seconds``, then checks
what the timed path produced against the plain reference.  With
``--trace 0`` it reports the cell's end-to-end metrics, with ``--trace 1``
its per-layer ones, read from a profiler trace of part of the window.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last the compared numbers beside their limits under
``checks``).  Without a TPU, or with fewer chips than the cell asks for,
it exits with code 2 and prints no result: there is no CPU fallback.
The controls of the comparison are read by ``controls.py``, never here.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             catalog: harness.Catalog, bench: dict, devs,
             control: bool = False, t_start: float = None,
             cell: dict = None,
             counter: harness.CompileCounter = None) -> dict:
    """One run of ``workload`` on ``devs``; returns the result line (a
    dict, with the raw run under ``_run``) and prints nothing.  ``cell``
    replaces the cell file's contents (the knee sweep's rates)."""
    cell = cell or catalog.cell(workload)
    entry = next((w for w in bench.get("workloads", [])
                  if w["name"] == workload), None)
    if entry is not None and (entry["config"], entry["traffic"]) != (
            cell["config"], cell["mix"]):
        raise harness.DataError(
            f"{workload}: BENCHMARK.json names ({entry['config']}, "
            f"{entry['traffic']}), the cell file ({cell['config']}, "
            f"{cell['mix']})")
    mix = catalog.mix(cell["mix"])
    ctx = harness.Ctx(
        workload=workload, seed=seed, seconds=seconds, trace=trace,
        cell=cell, config=catalog.config(cell["config"]), mix=mix,
        catalog=catalog, chips=len(devs), control=control,
        t_start=time.perf_counter() if t_start is None else t_start,
        counter=counter)
    run = catalog.driver(mix["driver"]).run(ctx)
    kind = devs[0].device_kind
    run["peaks"] = (harness.peaks(kind) if devs[0].platform == "tpu"
                    else None)
    metrics = {}
    for m in harness.metrics_for(bench, workload, trace):
        value = catalog.metric(m["name"]).read(run)
        if value is not None and math.isfinite(value):
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs),
              "memory_peak_bytes": run["memory_peak_bytes"]}
    out = {"correct": all(c["ok"] for c in run["checks"]),
           "attempted": run["attempted"], "failed": run["failed"],
           "metrics": metrics, "device": device}
    red = run.get("trace")
    if trace and red is not None:
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        out["breakdown"] = red["breakdown"]
    out["_checks"] = run["checks"]
    out["_controls"] = run.get("controls")
    out["_notes"] = run.get("notes", {})
    out["_run"] = run
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    catalog = harness.Catalog()
    bench = harness.benchmark_json()
    entry = next((w for w in bench.get("workloads", [])
                  if w["name"] == args.workload), None)
    chips = entry["chips"] if entry is not None else 1
    try:
        devs = harness.require_chips(chips)
    except harness.NoChip as e:
        print(f"run.py: {e}; no result", file=sys.stderr, flush=True)
        return 2
    print(f"run.py: platform {devs[0].platform}, device_kind "
          f"{devs[0].device_kind!r}, {len(devs)} device(s); compile cache "
          f"{harness.enable_cache()}", file=sys.stderr, flush=True)
    compiles = harness.CompileCounter()
    out = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                   catalog=catalog, bench=bench, devs=devs, t_start=T_START,
                   counter=compiles)
    checks = out.pop("_checks")
    notes = out.pop("_notes")
    out.pop("_run")
    out.pop("_controls")
    notes["compiles_in_run"] = compiles.n
    print("run.py: " + json.dumps({"seed": args.seed, "notes": notes},
                                  default=str), file=sys.stderr, flush=True)
    harness.emit(out, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
