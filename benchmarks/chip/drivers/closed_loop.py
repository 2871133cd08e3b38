"""Closed loop: the cell's fixed number of clients, each sending its next
request only after the previous one has finished (a batch job, or
callers that each wait for a reply).

The requests come from one fixed set of sizes (``traffic.requests``),
taken in turn by whichever client is free.  Nothing new is sent after
the window closes; requests in flight then finish (or are cancelled at
the grace limit) before the sample is compared with the reference.
"""

from __future__ import annotations

import threading
import time

import serving
import tracing
import traffic

# requests in the fixed set; more than the window can take
POOL = 256


def run(ctx) -> dict:
    import jax
    devs = jax.devices()[:ctx.chips]
    model, params, router, obs = serving.setup(ctx)
    reqs = traffic.requests(ctx.mix, POOL, ctx.seed, model.cfg.vocab_size)
    client = serving.Client(router)
    lock = threading.Lock()
    todo = iter(reqs)
    tw = None
    if ctx.trace:
        t = ctx.cell.get("trace", {})
        tw = tracing.Window(t.get("start_s", 0.3 * ctx.seconds),
                            t.get("seconds", 0.3 * ctx.seconds))
    s0 = serving.snapshot(obs)
    t0 = time.perf_counter()
    setup_s = t0 - ctx.t_start
    c0 = ctx.compiles()
    t1 = t0 + ctx.seconds

    def one_client():
        while time.perf_counter() < t1:
            with lock:
                r = next(todo, None)
            if r is None:
                return
            rec = client.submit(r, time.perf_counter())
            rec["done"].wait(timeout=max(0.0, t1 - time.perf_counter())
                             + serving.FIRST_TOKEN_GRACE_S)

    if tw is not None:
        tw.start(t0)
    threads = [threading.Thread(target=one_client, daemon=True,
                                name=f"chipbench-client{i}")
               for i in range(int(ctx.cell["clients"]))]
    for th in threads:
        th.start()
    time.sleep(max(0.0, t1 - time.perf_counter()))
    s1 = serving.snapshot(obs)
    c1 = ctx.compiles()
    red = tw.join() if tw is not None else None
    client.wait_first_tokens(t1 + serving.FIRST_TOKEN_GRACE_S)
    client.wait_finished(time.perf_counter() + serving.FIRST_TOKEN_GRACE_S)
    fin = serving.finish(ctx, router, client, devs)
    for th in threads:
        th.join()
    recs = list(client.recs.values())
    client.router = router = None
    checks, gaps = serving.check_sample(ctx, params, fin["sample"])
    failed = sum(1 for r in recs if not r["events"])
    return {"kind": "serve", "setup_s": setup_s, "window": (t0, t1),
            "seconds": ctx.seconds, "requests": recs,
            "registry": serving.delta(s0, s1), "trace": red,
            "attempted": len(recs), "failed": failed,
            "memory_peak_bytes": fin["memory_peak_bytes"],
            "checks": checks, "controls": gaps.get("controls"),
            "notes": {"compiles_in_window": None if c0 is None else c1 - c0,
                      "compared_tokens": gaps.get("tokens"),
                      "compared_requests": gaps.get("requests")},
            "arch": model.cfg, "config": ctx.config, "cell": ctx.cell,
            "mix": ctx.mix}
