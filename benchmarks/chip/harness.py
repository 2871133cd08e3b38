"""Shared machinery of the chip benchmark.

Everything that belongs to one configuration, traffic mix, cell, driver
kind or metric lives in a file of its own and is found by name:

    configs/<config>.json   model configuration as run (sizes, source, cuts)
    mixes/<mix>.json        one traffic shape, read by ``traffic.py``
    cells/<cell>.json       what belongs to one cell only
    drivers/<kind>.py       one file per driver kind (``run(ctx) -> Run``)
    metrics/<metric>.py     one reader per metric (``read(run) -> float|None``)

This module loads those files (refusing any key it does not know),
checks for the chip, keeps the compile cache and the peaks table, and
prints the result line.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import sys
from pathlib import Path
from typing import Any, Dict, Iterable, List, Optional, Sequence

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]

# ---------------------------------------------------------------- data files
# allowed keys of each kind of data file (anything else is refused)
CONFIG_KEYS = {
    "arch", "overrides", "source", "reduced", "assumed", "deployment",
    "dtype", "sparsity", "why",
}
# the published config's keys, as run; each is checked against the
# ArchConfig the file builds (HF_TO_ARCH), so the two cannot drift apart
HF_TO_ARCH = {
    "hidden_size": "d_model", "intermediate_size": "d_ff",
    "num_attention_heads": "num_heads", "num_key_value_heads": "num_kv_heads",
    "head_dim": "hd", "num_hidden_layers": "num_layers",
    "vocab_size": "vocab_size", "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps", "tie_word_embeddings": "tie_embeddings",
    "torch_dtype": "dtype",
}
HF_KEYS = set(HF_TO_ARCH) | {
    "max_position_embeddings", "hidden_act", "attention_bias",
    "model_type", "use_sliding_window", "max_window_layers",
    "sliding_window", "bos_token_id", "eos_token_id",
    "initializer_range", "attention_dropout", "use_cache",
}
MIX_KEYS = {"driver", "prompt", "output", "arrivals", "calibration",
            "prune", "why"}
CELL_KEYS = {"config", "mix", "rate_per_s", "clients", "blocks_per_job",
             "serve", "pool", "trace", "reference", "limits", "why"}
SERVE_KEYS = {"max_batch", "max_len", "page_size", "num_pages",
              "prefill_chunk", "steps_per_sync", "kv_dtype",
              "host_swap_pages"}
LENGTH_KEYS = {"dist", "median", "sigma", "min", "max"}


class DataError(ValueError):
    """A data file is missing, malformed or carries an unknown key."""


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def check_keys(obj: dict, allowed: Iterable[str], where: str,
               required: Iterable[str] = ()) -> dict:
    if not isinstance(obj, dict):
        raise DataError(f"{where}: expected a JSON object")
    unknown = sorted(set(obj) - set(allowed))
    if unknown:
        raise DataError(f"{where}: unknown key(s) {unknown}")
    missing = sorted(set(required) - set(obj))
    if missing:
        raise DataError(f"{where}: missing key(s) {missing}")
    return obj


class Catalog:
    """Finds data files, drivers and metric readers by name, searching
    ``dirs`` in order (a test puts a temporary directory first)."""

    def __init__(self, dirs: Sequence[Path] = (HERE,)):
        self.dirs = [Path(d) for d in dirs]

    def path(self, sub: str, name: str, suffix: str) -> Path:
        for d in self.dirs:
            p = d / sub / f"{name}{suffix}"
            if p.is_file():
                return p
        raise DataError(f"no {sub}/{name}{suffix} under "
                        f"{[str(d) for d in self.dirs]}")

    def json(self, sub: str, name: str) -> dict:
        p = self.path(sub, name, ".json")
        try:
            return json.loads(p.read_text())
        except json.JSONDecodeError as e:
            raise DataError(f"{p}: {e}") from e

    def config(self, name: str) -> dict:
        return check_keys(self.json("configs", name), CONFIG_KEYS | HF_KEYS,
                          f"configs/{name}.json",
                          ("arch", "source", "reduced", "dtype", "sparsity"))

    def mix(self, name: str) -> dict:
        m = check_keys(self.json("mixes", name), MIX_KEYS,
                       f"mixes/{name}.json", ("driver", "why"))
        for k in ("prompt", "output"):
            if k in m:
                check_keys(m[k], LENGTH_KEYS, f"mixes/{name}.json {k}",
                           LENGTH_KEYS)
        return m

    def cell(self, name: str) -> dict:
        c = check_keys(self.json("cells", name), CELL_KEYS,
                       f"cells/{name}.json", ("config", "mix", "why"))
        if "serve" in c:
            check_keys(c["serve"], SERVE_KEYS, f"cells/{name}.json serve")
        return c

    def module(self, sub: str, name: str):
        return load_module(self.path(sub, name, ".py"))

    def driver(self, kind: str):
        return self.module("drivers", kind)

    def metric(self, name: str):
        return self.module("metrics", name)


def load_module(path: Path):
    """Import a file by path (metric files carry dots in their names)."""
    mod_name = "chipbench_" + "_".join(path.relative_to(
        path.parents[1]).with_suffix("").parts).replace(".", "_")
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


# ----------------------------------------------------------------- benchmark
def benchmark_json(root: Path = ROOT) -> dict:
    p = root / "BENCHMARK.json"
    return json.loads(p.read_text()) if p.is_file() else {}


def metrics_for(bench: dict, workload: str, trace: bool) -> List[dict]:
    """The metrics a run of ``workload`` reports: with ``trace`` the
    per-layer ones that list it (or, listing none, move one of its
    end-to-end metrics), else its end-to-end ones."""
    e2e = [m for m in bench.get("end_to_end", [])
           if workload in m.get("workloads", [workload])]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench.get("per_layer", [])
            if workload in m.get("workloads", [])
            or ("workloads" not in m and m["moves"] in names)]


# ------------------------------------------------------------------- context
@dataclasses.dataclass
class Ctx:
    """Everything a driver needs, resolved from the data files."""

    workload: str
    seed: int
    seconds: float
    trace: bool
    cell: dict
    config: dict
    mix: dict
    catalog: Catalog
    t_start: float                       # process clock at start of set-up
    control: bool = False                # also read the controls (controls.py)
    chips: int = 1
    counter: Optional["CompileCounter"] = None

    def compiles(self) -> Optional[int]:
        """Backend compilations so far (None where they are not counted)."""
        return None if self.counter is None else self.counter.n

    def arch(self):
        """The ArchConfig this configuration runs: the repo arch with the
        file's overrides, checked against the published keys the file
        states.  A prune cell's job model is its first ``blocks_per_job``
        blocks."""
        return build_arch(self.config, self.cell.get("blocks_per_job"))


def build_arch(config: dict, num_layers: Optional[int] = None):
    sys.path.insert(0, str(ROOT / "src"))
    from repro import configs as cfglib
    cfg = cfglib.get_config(config["arch"])
    try:
        cfg = dataclasses.replace(cfg, **config.get("overrides", {}))
    except TypeError as e:
        raise DataError(f"config {config['arch']}: bad override: {e}") from e
    for hf, attr in HF_TO_ARCH.items():
        if hf in config and getattr(cfg, attr) != config[hf]:
            raise DataError(f"config {config['arch']}: {hf} "
                            f"{config[hf]!r} but the arch builds "
                            f"{attr}={getattr(cfg, attr)!r}")
    if num_layers is not None:
        cfg = dataclasses.replace(cfg, num_layers=int(num_layers))
    return cfg


def require_chips(n: int):
    """The devices to run on; raises :class:`NoChip` unless JAX sees at
    least ``n`` TPU chips.  There is no CPU fallback."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:                       # no backend at all
        raise NoChip(f"JAX found no accelerator: {e}") from e
    if not devs or devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform "
                     f"{devs[0].platform if devs else None!r})")
    if len(devs) < n:
        raise NoChip(f"cell needs {n} chips, JAX found {len(devs)}")
    return devs[:n]


def enable_cache() -> str:
    """JAX's persistent compilation cache inside the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), keeping every program, also the
    small ones, so that only a cell's first run compiles."""
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    from repro.utils.compile_cache import enable_compile_cache
    where = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


class CompileCounter:
    """Counts XLA backend compilations; a run reads it at the
    window's edges (``Ctx.compiles``)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring
        self.n = 0

        def listen(event, duration, **_):
            if event == self.EVENT:
                self.n += 1

        jax.monitoring.register_event_duration_secs_listener(listen)


def seed_key(seed: int):
    """A JAX key that keeps every bit of a seed above 32 bits."""
    import jax
    lo, hi = seed & 0x7FFFFFFF, (seed >> 31) & 0x7FFFFFFF
    return jax.random.fold_in(jax.random.key(lo), hi)


def peaks(device_kind: str) -> dict:
    table = json.loads((HERE / "peaks.json").read_text())
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise DataError(f"no peaks for device kind {device_kind!r} in "
                        f"peaks.json ({sorted(table['devices'])})") from None


# --------------------------------------------------------------------- stats
def quantile(values: Sequence[float], q: float) -> float:
    """Linear-interpolated quantile (numpy's default) of a sample."""
    xs = sorted(values)
    if not xs:
        return math.nan
    pos = q * (len(xs) - 1)
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def hist_quantile(bounds: Sequence[float], counts: Sequence[int],
                  q: float) -> Optional[float]:
    """Quantile of a fixed-bucket histogram given per-bucket counts (the
    last bucket is the +Inf tail), interpolated inside the bucket the
    rank lands in, as Prometheus' ``histogram_quantile`` does."""
    total = sum(counts)
    if total == 0:
        return None
    rank, acc = q * total, 0
    for i, c in enumerate(counts):
        prev, acc = acc, acc + c
        if acc >= rank and c:
            if i >= len(bounds):
                return bounds[-1]
            lo = bounds[i - 1] if i > 0 else 0.0
            return lo + (bounds[i] - lo) * (rank - prev) / c
    return bounds[-1]


# -------------------------------------------------------------------- output
def emit(result: Dict[str, Any], checks: List[Dict[str, Any]]) -> None:
    """Print the compared numbers beside their limits, last on standard
    error and last in the result line, then the result line itself."""
    for c in checks:
        print(f"check {c['name']}: {c['value']!r} limit {c['limit']!r} "
              f"({'<=' if c.get('le', True) else '>='}) "
              f"{'ok' if c['ok'] else 'FAIL'}", file=sys.stderr, flush=True)
    line = dict(result)
    line["checks"] = {c["name"]: {"value": c["value"],
                                  "limit": c["limit"] if math.isfinite(
                                      c["limit"]) else None}
                      for c in checks}
    print(json.dumps(line), flush=True)


def check(name: str, value: float, limit: float, le: bool = True) -> dict:
    ok = (value <= limit) if le else (value >= limit)
    return {"name": name, "value": float(value), "limit": float(limit),
            "le": le, "ok": bool(ok and math.isfinite(value))}
