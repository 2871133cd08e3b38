"""itl_p95_ms: gaps between successive streamed tokens of a request as
the client receives them, p95 over every gap that ends in the window
(host clock).  Tokens that arrive in one event have gap 0."""

import harness


def gaps(run):
    t0, t1 = run["window"]
    out = []
    for r in run["requests"]:
        prev = None
        for t, n in r["events"]:
            if t0 <= t <= t1:
                if prev is not None:
                    out.append((t - prev) * 1e3)
                out.extend([0.0] * (n - 1))
            prev = t
    return out


def read(run):
    g = gaps(run)
    return harness.quantile(g, 0.95) if g else None
