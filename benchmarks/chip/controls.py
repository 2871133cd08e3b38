"""Read a cell's comparison on many seeds, with its controls and faults.

    python3 benchmarks/chip/controls.py --workload <cell> \\
        --seeds 11,12,13 --control-seeds 11,12,13 --seconds 0

One process, one seed after another: for each seed a run of the cell as
``run.py`` makes it (``--seconds`` of window; 0 compares the warm-up
job or batch), printing its compared numbers; on the control seeds also
the driver's controls and planted faults, each judged by the cell's own
checks and limits, so that each has to come out ``correct: false``.
This is how a cell's limits are set (the largest sound reading below,
the smallest control reading above); nothing here is part of a measured
run.  One JSON line per seed on standard output.
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


def readings(out: dict) -> dict:
    """One seed's line: the program's checks and each control's."""
    def judged(checks):
        return {"correct": all(c["ok"] for c in checks),
                "checks": {c["name"]: c["value"] for c in checks}}

    line = {"program": judged(out["_checks"]), "notes": out["_notes"]}
    ctl = out.get("_controls") or {}
    line["controls"] = {k: {**judged(v["checks"]),
                            **{n: x for n, x in v.items() if n != "checks"}}
                        for k, v in ctl.items()}
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=0.0)
    args = ap.parse_args(argv)
    try:
        devs = harness.require_chips(1)
    except harness.NoChip as e:
        print(f"controls.py: {e}", file=sys.stderr)
        return 2
    harness.enable_cache()
    cat = harness.Catalog()
    bench = harness.benchmark_json()
    ctl = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        out = run_py.run_cell(args.workload, seed, args.seconds, False,
                              catalog=cat, bench=bench, devs=devs,
                              control=seed in ctl)
        line = {"seed": seed, **readings(out),
                "wall_s": time.perf_counter() - t}
        print(json.dumps(line, default=str), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
