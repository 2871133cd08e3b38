"""ttft_p90_ms: time to first token, counted from when the request was
due (not sent), p90 over every request due in the window (host clock, as
the client receives the token).  A request that never got a first token
counts as infinitely late."""

import math

import harness


def read(run):
    t0, t1 = run["window"]
    ttft = [(r["events"][0][0] - r["due"]) * 1e3 if r["events"] else math.inf
            for r in run["requests"] if t0 <= r["due"] <= t1]
    return harness.quantile(ttft, 0.9) if ttft else None
