"""Open loop: requests sent on a Poisson schedule at the cell's fixed
rate, whether or not earlier ones have finished (independent users).

Each request is timed from when it was due, so a stall also delays the
requests behind it.  After the window closes nothing more is sent; the
run waits for the first token of every request that was due, and for
the sampled requests to finish, then compares them with the reference.
"""

from __future__ import annotations

import time

import serving
import tracing
import traffic


def run(ctx) -> dict:
    import jax
    devs = jax.devices()[:ctx.chips]
    model, params, router, obs = serving.setup(ctx)
    reqs = traffic.open_loop(ctx.mix, float(ctx.cell["rate_per_s"]),
                             ctx.seconds, ctx.seed, model.cfg.vocab_size)
    client = serving.Client(router)
    tw = None
    if ctx.trace:
        t = ctx.cell.get("trace", {})
        tw = tracing.Window(t.get("start_s", 0.3 * ctx.seconds),
                            t.get("seconds", 0.3 * ctx.seconds))
    s0 = serving.snapshot(obs)
    t0 = time.perf_counter()
    setup_s = t0 - ctx.t_start
    c0 = ctx.compiles()
    if tw is not None:
        tw.start(t0)
    for r in reqs:
        time.sleep(max(0.0, t0 + r.due - time.perf_counter()))
        client.submit(r, t0 + r.due)
    t1 = t0 + ctx.seconds
    time.sleep(max(0.0, t1 - time.perf_counter()))
    s1 = serving.snapshot(obs)
    c1 = ctx.compiles()
    red = tw.join() if tw is not None else None
    client.wait_first_tokens(t1 + serving.FIRST_TOKEN_GRACE_S)
    client.wait_finished(time.perf_counter() + serving.FIRST_TOKEN_GRACE_S)
    fin = serving.finish(ctx, router, client, devs)
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
                      "generator_late_p99_ms": serving.late_ms(recs),
                      "compared_tokens": gaps.get("tokens"),
                      "compared_requests": gaps.get("requests")},
            "arch": model.cfg, "config": ctx.config, "cell": ctx.cell,
            "mix": ctx.mix}
