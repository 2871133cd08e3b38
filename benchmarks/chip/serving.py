"""What the serve drivers share: building the served path, warming its
shapes, the client that records every streamed token, the registry
snapshots, and the reference comparison that decides ``correct``.

The served path is the one a user builds: ``repro.launch.serve
.make_router`` in continuous mode over packed 2:4 weights, driven
through ``Router.submit`` with a callback per request.
"""

from __future__ import annotations

import gc
import threading
import time
from typing import Dict, List, Optional

import numpy as np

import harness
import weights
from reference import forward

# registry series the per-layer metrics read (deltas over the window)
COUNTERS = ("serve_host_syncs_total", "serve_tokens_total",
            "serve_device_steps_total", "serve_slot_steps_total",
            "serve_prefill_chunks_total")
HISTOGRAMS = ("serve_queue_wait_seconds",)
# requests due in the window whose first token has not come this long
# after the window closed count as failed
FIRST_TOKEN_GRACE_S = 60.0
# served tokens the reference compares, drawn from finished requests
REF_TOKENS = 256
REF_PAD = 512


class Client:
    """Submits requests and records, per request, the time and size of
    every streamed event as the client receives it."""

    def __init__(self, router):
        self.router = router
        self.lock = threading.Lock()
        self.recs: Dict[int, dict] = {}
        self.first = threading.Condition(self.lock)

    def submit(self, req, due: float) -> dict:
        from repro.serve.frontend import CompletionRequest
        rec = {"uid": req.uid, "due": due, "prompt": req.prompt,
               "prompt_len": int(req.prompt.size), "max_new": req.max_new,
               "submit": time.perf_counter(), "events": [], "tokens": [],
               "finished": False, "reason": None,
               "done": threading.Event()}
        with self.lock:
            self.recs[req.uid] = rec

        def on_event(ev):
            t = time.perf_counter()
            with self.lock:
                if ev.tokens:
                    rec["events"].append((t, len(ev.tokens)))
                    rec["tokens"].extend(int(x) for x in ev.tokens)
                if ev.finished:
                    rec["finished"], rec["reason"] = True, ev.finish_reason
                self.first.notify_all()
            if ev.finished:
                rec["done"].set()

        creq = CompletionRequest(prompt=req.prompt.tolist(),
                                 max_tokens=req.max_new, uid=req.uid)
        self.router.submit(creq, on_event, uid=req.uid)
        return rec

    def wait_first_tokens(self, deadline: float) -> None:
        with self.lock:
            while any(not r["events"] and not r["finished"]
                      for r in self.recs.values()):
                left = deadline - time.perf_counter()
                if left <= 0:
                    return
                self.first.wait(timeout=min(left, 0.5))

    def wait_finished(self, deadline: float) -> None:
        for rec in list(self.recs.values()):
            rec["done"].wait(timeout=max(0.0, deadline - time.perf_counter()))

    def cancel_rest(self) -> None:
        for rec in list(self.recs.values()):
            if not rec["finished"]:
                self.router.cancel(rec["uid"])


def setup(ctx):
    """Model, weights (packed, from the seed), router, registry; every
    shape the cell's traffic uses is compiled before this returns."""
    from repro.launch.serve import make_router
    from repro.models import LM
    from repro.obs import Obs
    from repro.serve import ServeConfig

    model = LM(ctx.arch())
    params = weights.make(model, harness.seed_key(ctx.seed), packed=True)
    config = ServeConfig(mode="continuous", temperature=0.0, eos_id=None,
                         metrics=True, trace=False,
                         **ctx.cell["serve"]).validate()
    obs = Obs.create(metrics=True, trace=False)
    router = make_router(model, params, config, obs=obs)
    eng = router.replicas[0].engine
    if eng.mode != "continuous" or eng.n_sparse_leaves == 0:
        raise RuntimeError(f"served path is {eng.mode!r} with "
                           f"{eng.n_sparse_leaves} packed leaves")
    warm(router, eng, config, model.cfg.vocab_size)
    return model, params, router, obs


def warm(router, eng, config, vocab: int) -> None:
    """Compile what the window runs: the prefill-chunk burst and the
    decode burst (two requests of two chunks each, decoding for more than
    one sync interval), and the block-table row upload for every count
    of changed rows the batch can have."""
    import jax.numpy as jnp

    from repro.serve.frontend import CompletionRequest
    rng = np.random.default_rng(12345)
    creqs = [CompletionRequest(
        prompt=rng.integers(0, vocab, size=config.prefill_chunk + 1,
                            dtype=np.int32).tolist(),
        max_tokens=2 * config.steps_per_sync + 2, uid=-1 - i)
        for i in range(2)]
    router.complete(creqs)
    tables = eng.pool.block_tables
    dev = jnp.asarray(tables)
    for n in range(1, tables.shape[0] + 1):
        rows = list(range(n))
        dev = dev.at[jnp.asarray(rows, jnp.int32)].set(
            jnp.asarray(tables[rows]))
    dev.block_until_ready()


def snapshot(obs) -> dict:
    reg = obs.metrics
    snap = {"t": time.perf_counter(), "counters": {}, "hists": {}}
    for name in COUNTERS:
        fam = reg.get(name)
        snap["counters"][name] = fam.total() if fam is not None else 0.0
    for name in HISTOGRAMS:
        fam = reg.get(name)
        counts, bounds = None, ()
        for _, child in (fam.children() if fam is not None else []):
            cum = child.cumulative()
            per = [cum[0]] + [cum[i] - cum[i - 1] for i in range(1, len(cum))]
            counts = per if counts is None else [a + b for a, b in
                                                 zip(counts, per)]
            bounds = child.bounds
        snap["hists"][name] = (tuple(bounds), counts or [])
    return snap


def delta(s0: dict, s1: dict) -> dict:
    out = {"counters": {k: s1["counters"][k] - s0["counters"][k]
                        for k in s1["counters"]}, "hists": {}}
    for k, (bounds, c1) in s1["hists"].items():
        c0 = s0["hists"][k][1] or [0] * len(c1)
        out["hists"][k] = (bounds, [a - b for a, b in zip(c1, c0)])
    return out


def memory_peak(devs) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


def sample(recs: List[dict], seed: int, tokens: int = REF_TOKENS
           ) -> List[dict]:
    """Finished requests for the reference: the longest, then others in
    an order drawn from the seed, until ``tokens`` served tokens."""
    done = [r for r in recs if r["finished"] and r["reason"] == "length"
            and r["tokens"]]
    if not done:
        return []
    done.sort(key=lambda r: (r["prompt_len"] + len(r["tokens"]), r["uid"]))
    out = [done.pop()]
    rng = np.random.default_rng([seed, 7])
    for i in rng.permutation(len(done)):
        if sum(len(r["tokens"]) for r in out) >= tokens:
            break
        out.append(done[int(i)])
    return out


def reference_gaps(params, config: dict, recs: List[dict],
                   control: bool = False) -> dict:
    """Widest gap, over every served token of ``recs``, by which the
    token's reference logit lies below the reference's best at that
    position (and, with ``control``, the same for the token that the
    float8 control puts first)."""
    import jax.numpy as jnp
    worst, worst_ctl, n_tok = 0.0, 0.0, 0
    for r in recs:
        out = np.asarray(r["tokens"], np.int64)
        toks = np.concatenate([np.asarray(r["prompt"], np.int64), out[:-1]])
        n = toks.size
        padded = np.zeros(-(-n // REF_PAD) * REF_PAD, np.int64)
        padded[:n] = toks
        rows = np.arange(r["prompt_len"] - 1, n)
        ref = forward.logits_at(params, padded, rows, config)
        best = jnp.max(ref, axis=-1)
        gap = best - ref[jnp.arange(rows.size), jnp.asarray(out)]
        worst = max(worst, float(jnp.max(gap)))
        n_tok += out.size
        if control:
            ctl = forward.logits_at(params, padded, rows, config, mode="fp8")
            pick = jnp.argmax(ctl, axis=-1)
            cgap = best - ref[jnp.arange(rows.size), pick]
            worst_ctl = max(worst_ctl, float(jnp.max(cgap)))
    return {"gap": worst, "gap_control": worst_ctl if control else None,
            "tokens": n_tok, "requests": len(recs)}


def finish(ctx, router, client, devs) -> dict:
    """Close the served path and compare a sample of what it served with
    the reference.  Returns the check results and memory peak."""
    peak = memory_peak(devs)
    client.cancel_rest()
    router.close()
    recs = list(client.recs.values())
    sam = sample(recs, ctx.seed, ctx.cell.get("reference", {}).get(
        "tokens", REF_TOKENS))
    return {"memory_peak_bytes": peak, "sample": sam}


def check_sample(ctx, params, sam: List[dict]) -> tuple:
    gc.collect()                # the served path's pool and programs go
    limits = ctx.cell.get("limits", {})
    if not sam:
        return [harness.check("served_requests_compared", 0, 1, le=False)], {}
    g = reference_gaps(params, ctx.config, sam, control=ctx.control)
    limit = limits.get("logit_gap_max", float("inf"))
    checks = [harness.check("logit_gap_max", g["gap"], limit)]
    if ctx.control:
        g["controls"] = {"fp8": {"checks": [
            harness.check("logit_gap_max", g["gap_control"], limit)]}}
    return checks, g


def late_ms(recs: List[dict], q: float = 0.99) -> Optional[float]:
    """How late the generator sent requests (submit - due), in ms."""
    lates = [(r["submit"] - r["due"]) * 1e3 for r in recs]
    return harness.quantile(lates, q) if lates else None
