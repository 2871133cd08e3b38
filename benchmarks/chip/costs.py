"""Operations and bytes the benchmarked work needs, counted from shapes.

Everything here reads the configuration file's published keys (and the
cell's), never the program: a model step's useful FLOPs with 2:4
projections counted at their nonzeros, a prune job's FLOPs, and the
token accounting of a serve run's window that the metric readers share.
"""

from __future__ import annotations

from typing import Dict, Iterator, List, Tuple


def shape(config: dict) -> Dict[str, int]:
    """The sizes the cost functions use, from the configuration file."""
    d = config["hidden_size"]
    h = config["num_attention_heads"]
    hd = config.get("head_dim") or d // h
    return {"d": d, "f": config["intermediate_size"], "h": h,
            "kv": config["num_key_value_heads"], "hd": hd,
            "layers": config["num_hidden_layers"],
            "vocab": config["vocab_size"]}


def projections(s: Dict[str, int]) -> List[Tuple[str, int, int]]:
    """(name, K in, N out) of each projection of one block."""
    d, f, h, kv, hd = s["d"], s["f"], s["h"], s["kv"], s["hd"]
    return [("attn.wq", d, h * hd), ("attn.wk", d, kv * hd),
            ("attn.wv", d, kv * hd), ("attn.wo", h * hd, d),
            ("mlp.wi", d, f), ("mlp.wg", d, f), ("mlp.wo", f, d)]


def block_params(s: Dict[str, int]) -> int:
    return sum(k * n for _, k, n in projections(s))


# ------------------------------------------------------------------ serving
def token_flops(s: Dict[str, int], context: int, logits: bool,
                density: float = 0.5) -> float:
    """Useful FLOPs of one token through the model: the projections at
    their nonzeros (``density`` of the dense weights), attention over
    ``context`` positions, and the head when the token's logits are
    needed."""
    lin = 2.0 * density * block_params(s)
    att = 4.0 * context * s["h"] * s["hd"]
    head = 2.0 * s["d"] * s["vocab"] if logits else 0.0
    return s["layers"] * (lin + att) + head


def prompt_flops(s: Dict[str, int], plen: int, density: float = 0.5
                 ) -> float:
    """A prompt's prefill: every position through the blocks (causal
    context), the head at the last position only."""
    lin = 2.0 * density * block_params(s) * plen
    att = 4.0 * s["h"] * s["hd"] * plen * (plen + 1) / 2.0
    return s["layers"] * (lin + att) + 2.0 * s["d"] * s["vocab"]


def events_in(recs: List[dict], t0: float, t1: float
              ) -> Iterator[Tuple[dict, int, float, int]]:
    """(request, index of the event's first token in the request's
    stream, time, tokens) of every streamed event received in [t0, t1]."""
    for r in recs:
        i = 0
        for t, n in r["events"]:
            if t0 <= t <= t1:
                yield r, i, t, n
            i += n


def window_flops(run: dict, density: float = 0.5) -> float:
    """Useful FLOPs of the work a serve run finished in its window: the
    prefill of each request whose first token came in it, and every
    output token emitted in it (token 0 rides the prefill)."""
    s = shape(run["config"])
    t0, t1 = run["window"]
    total = 0.0
    for r, i, _, n in events_in(run["requests"], t0, t1):
        if i == 0:
            total += prompt_flops(s, r["prompt_len"], density)
        for j in range(max(i, 1), i + n):
            total += token_flops(s, r["prompt_len"] + j, True, density)
    return total


# ------------------------------------------------------------------ pruning
def prune_block_flops(s: Dict[str, int], samples: int, length: int,
                      blocksize: int) -> float:
    """FLOPs of pruning one block 2:4 with the SM sweep: the dense
    capture forward, one Hessian per distinct projection input, the
    inverse of each projection's Hessian, the per-row solves of every
    column block (Cholesky of the pruned set, two triangular solves,
    the rank-k update of the row) and the pruned propagate forward."""
    t = samples * length
    fwd = 2.0 * t * block_params(s) + \
        2.0 * samples * length * length * s["h"] * s["hd"]
    d, f, hd_all = s["d"], s["f"], s["h"] * s["hd"]
    hess = 2.0 * t * (d * d + hd_all * hd_all + d * d + f * f)
    solve = 0.0
    for _, m, n in projections(s):
        solve += float(m) ** 3
        b = min(blocksize, m)
        for j in range(1, m // b + 1):
            k = j * b / 2.0
            solve += n * (k ** 3 / 3.0 + 2.0 * k * k + 2.0 * k * m)
    return 2.0 * fwd + hess + solve
