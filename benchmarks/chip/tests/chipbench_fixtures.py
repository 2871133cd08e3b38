"""Shared helpers of the benchmark's tests: a tiny cell in a temporary
directory, found before the benchmark's own files, run on the CPU."""

from __future__ import annotations

import json
import sys
from pathlib import Path

CHIP = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))
sys.path.insert(0, str(CHIP.parents[1] / "src"))

TINY_SERVE = {"max_batch": 4, "max_len": 80, "page_size": 8,
              "prefill_chunk": 16, "steps_per_sync": 4, "kv_dtype": "fp32",
              "host_swap_pages": 0, "num_pages": 41}


def tiny_config(arch: str = "qwen1_5_0_5b") -> dict:
    """A smoke-width configuration of the Qwen family (f32 keeps the
    tests' numbers away from bf16 ties)."""
    qk3 = arch == "qwen3_14b"
    over = {"d_model": 64, "num_heads": 4, "num_kv_heads": 2 if qk3 else 4,
            "d_ff": 128, "vocab_size": 256, "num_layers": 2,
            "rope_theta": 1e6, "dtype": "float32"}
    if qk3:
        over["head_dim"] = 16
    return {"arch": arch, "overrides": over, "source": "test",
            "hidden_size": 64, "intermediate_size": 128,
            "num_attention_heads": 4, "num_key_value_heads": over[
                "num_kv_heads"], "num_hidden_layers": 2, "vocab_size": 256,
            "rope_theta": 1e6, "rms_norm_eps": 1e-6,
            "tie_word_embeddings": not qk3, "torch_dtype": "float32",
            "reduced": {}, "dtype": "float32", "sparsity": "2:4",
            **({"head_dim": 16} if qk3 else {})}


def write_tiny(root: Path) -> Path:
    """Configs, mixes and cells of a tiny benchmark under ``root``."""
    def w(rel, obj):
        p = root / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(json.dumps(obj))

    w("configs/tiny.json", tiny_config())
    w("configs/tiny3.json", tiny_config("qwen3_14b"))
    w("mixes/tchat.json", {
        "driver": "open_loop", "arrivals": "poisson",
        "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.5,
                   "min": 4, "max": 60},
        "output": {"dist": "lognormal", "median": 6, "sigma": 0.5,
                   "min": 2, "max": 12}, "why": "test"})
    w("mixes/tdocs.json", {
        "driver": "closed_loop",
        "prompt": {"dist": "lognormal", "median": 40, "sigma": 0.5,
                   "min": 8, "max": 60},
        "output": {"dist": "lognormal", "median": 4, "sigma": 0.5,
                   "min": 2, "max": 8}, "why": "test"})
    w("mixes/tprune.json", {
        "driver": "prune_jobs",
        "calibration": {"samples": 8, "length": 32, "batch": 4},
        "prune": {"sparsity": "2:4", "method": "SM", "blocksize": 32,
                  "gamma": 0.01}, "why": "test"})
    w("cells/tiny.chat.json", {
        "config": "tiny", "mix": "tchat", "rate_per_s": 6.0,
        "serve": TINY_SERVE, "limits": {"logit_gap_max": 0.01},
        "why": "test"})
    w("cells/tiny3.docs.json", {
        "config": "tiny3", "mix": "tdocs", "clients": 3,
        "serve": TINY_SERVE, "limits": {"logit_gap_max": 0.01},
        "why": "test"})
    w("cells/tiny.prune.json", {
        "config": "tiny", "mix": "tprune", "blocks_per_job": 2,
        "reference": {"rows": 8},
        "limits": {"mask_disagree_max.b0": 0.02,
                   "recon_excess_max.b0": 0.01, "comp_dev_max.b0": 1e-3,
                   "recon_excess_max.b1": 0.01},
        "why": "test"})
    return root


TINY_BENCH = {
    "end_to_end": [
        {"name": "setup_s", "unit": "s"},
        {"name": "ttft_p90_ms", "unit": "ms", "workloads": ["tiny.chat"]},
        {"name": "itl_p95_ms", "unit": "ms", "workloads": ["tiny.chat"]},
        {"name": "tokens_per_s", "unit": "tokens/s",
         "workloads": ["tiny.chat", "tiny3.docs"]},
        {"name": "prune_s_per_block", "unit": "s",
         "workloads": ["tiny.prune"]}],
    "per_layer": [],
}
