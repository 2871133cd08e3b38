"""Parse collective ops + byte counts out of lowered/compiled HLO text.

``compiled.cost_analysis()`` reports FLOPs and HBM bytes but NOT collective
traffic, so the roofline's collective term is derived here by scanning the
module text for ``all-reduce`` / ``all-gather`` / ``reduce-scatter`` /
``all-to-all`` / ``collective-permute`` ops and summing their operand sizes
(per the spec).  Works on both post-optimization HLO (``compiled.as_text()``)
and StableHLO (``lowered.as_text()``).

Conventions:
  - SPMD modules are per-device programs, so summed operand bytes are
    *per-device* bytes.  ``collective_bytes`` in the roofline is defined as
    global bytes = per-device bytes x chips, making the spec's
    ``collective_bytes / (chips x link_bw)`` come out as per-device bytes
    over per-device link bandwidth.
  - ``wire_bytes`` additionally applies the standard ring-cost multipliers
    (all-reduce 2(k-1)/k ~ 2x, others (k-1)/k ~ 1x) for a tighter estimate;
    both are reported.
  - async pairs (``all-reduce-start``/``-done``) are counted once (at start).
"""

from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Dict, List

_DTYPE_BYTES = {
    "pred": 1,
    "s4": 1, "u4": 1,
    "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1, "f8e4m3b11fnuz": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
    # stablehlo spellings
    "i1": 1, "i8": 1, "i16": 2, "i32": 4, "i64": 8, "ui8": 1, "ui16": 2,
    "ui32": 4, "ui64": 8,
}

COLLECTIVE_OPS = (
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

# ring-cost multiplier in units of operand bytes (k->inf limit)
_WIRE_MULT = {
    "all-reduce": 2.0,
    "all-gather": 1.0,
    "reduce-scatter": 1.0,
    "all-to-all": 1.0,
    "collective-permute": 1.0,
}

# hlo:  f32[128,256]{1,0}   |   bf16[4,8]
_HLO_TYPE_RE = re.compile(r"\b([a-z0-9]+)\[([\d,]*)\]")
# `%x = f32[256,4096]{1,0} all-reduce(%y), ...` — group(1) captures the
# RESULT type (post-opt HLO names operands, sizes live in the result
# type).  Result size == wire-relevant size for all-reduce / all-to-all /
# collective-permute / all-gather (the gathered output); reduce-scatter
# is undercounted by ~group size (XLA emits RS rarely in these modules —
# caveat recorded in EXPERIMENTS.md §Roofline).
_HLO_OP_RE = re.compile(
    r"=\s*(\([^)]*\)|[a-z0-9]+\[[^\]]*\](?:\{[^}]*\})?)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\("
)
# stablehlo:  stablehlo.all_reduce ... : (tensor<512x1024xf32>, ...) -> ...
_SHLO_OP_RE = re.compile(
    r"(?:stablehlo|mhlo)\.(all_reduce|all_gather|reduce_scatter|all_to_all|"
    r"collective_permute|collective_broadcast)"
)
_SHLO_TENSOR_RE = re.compile(r"tensor<([0-9x]*)x?([a-z0-9]+)>")


@dataclasses.dataclass
class CollectiveStats:
    """Aggregated per-opcode collective statistics for one module."""

    counts: Dict[str, int]
    operand_bytes: Dict[str, int]   # per-device bytes by opcode

    @property
    def total_count(self) -> int:
        return sum(self.counts.values())

    @property
    def total_bytes(self) -> int:
        return sum(self.operand_bytes.values())

    @property
    def wire_bytes(self) -> float:
        return sum(
            _WIRE_MULT.get(op, 1.0) * b for op, b in self.operand_bytes.items()
        )


def _type_bytes(dtype: str, dims: str) -> int:
    size = _DTYPE_BYTES.get(dtype)
    if size is None:
        return 0
    n = 1
    if dims:
        for d in dims.split(","):
            d = d.strip()
            if d:
                n *= int(d)
    return n * size


def _shlo_tensor_bytes(shape_part: str, dtype: str) -> int:
    size = _DTYPE_BYTES.get(dtype)
    if size is None:
        return 0
    n = 1
    if shape_part:
        for d in shape_part.split("x"):
            if d:
                n *= int(d)
    return n * size


_OPNAME_RE = re.compile(r'op_name="([^"]*)"')


def _loop_depth(line: str) -> int:
    """How many nested scan/while bodies the op executes inside — each
    lax.scan level contributes one 'while/body' segment to the jax
    op_name metadata.  XLA emits (and costs) loop bodies once; the true
    per-step execution count is the product of the enclosing trip counts
    (launch/dryrun.py supplies them per cell)."""
    m = _OPNAME_RE.search(line)
    if not m:
        return 0
    return m.group(1).count("while/body")


def parse_collectives(hlo_text: str) -> List[dict]:
    """Record per collective op: {op, operand_bytes, loop_depth, line}."""
    records: List[dict] = []
    for line in hlo_text.splitlines():
        m = _HLO_OP_RE.search(line)
        if m:
            op = m.group(2)
            types = _HLO_TYPE_RE.findall(m.group(1))   # result type(s)
            obytes = sum(_type_bytes(dt, dims) for dt, dims in types)
            records.append({"op": op, "operand_bytes": obytes,
                            "loop_depth": _loop_depth(line),
                            "line": line.strip()})
            continue
        m = _SHLO_OP_RE.search(line)
        if m:
            op = m.group(1).replace("_", "-")
            tensors = _SHLO_TENSOR_RE.findall(line)
            if tensors:
                # first tensor(s) are operands; take the first (input) tensor
                shape, dt = tensors[0]
                obytes = _shlo_tensor_bytes(shape, dt)
            else:
                obytes = 0
            records.append({"op": op, "operand_bytes": obytes,
                            "loop_depth": _loop_depth(line),
                            "line": line.strip()})
    return records


def collective_bytes(hlo_text: str,
                     trip_counts: tuple = ()) -> CollectiveStats:
    """Aggregate per-device collective bytes by opcode.

    ``trip_counts``: execution multiplier per loop-nesting level — ops at
    loop_depth d are scaled by Π trip_counts[:d] (defaults: no scaling,
    matching raw single-execution HLO text).
    """
    counts: Dict[str, int] = defaultdict(int)
    obytes: Dict[str, int] = defaultdict(int)
    for rec in parse_collectives(hlo_text):
        mult = 1.0
        for lvl in range(min(rec["loop_depth"], len(trip_counts))):
            mult *= trip_counts[lvl]
        counts[rec["op"]] += max(1, round(mult))
        obytes[rec["op"]] += rec["operand_bytes"] * mult
    return CollectiveStats(counts=dict(counts), operand_bytes=dict(obytes))


_KERNEL_CALL = re.compile(
    r'custom_call_target="tpu_custom_call".*?op_name="[^"]*?'
    r'jit\(([A-Za-z0-9_]+)\)/pallas_call')


def tpu_kernel_names(hlo_text: str) -> List[str]:
    """Names of the Pallas kernels compiled into a TPU module: the jitted
    wrapper around each ``tpu_custom_call`` (``paged_attn``,
    ``nm_spmm_decode``, ...), read from the op metadata of
    ``compiled.as_text()``, in order of appearance."""
    return _KERNEL_CALL.findall(hlo_text)
