"""tokens_per_s: prompt tokens of the requests whose first token came in
the window, plus the output tokens emitted in it, over the window's
length (host clock)."""

import costs


def read(run):
    t0, t1 = run["window"]
    n = 0
    for r, i, _, k in costs.events_in(run["requests"], t0, t1):
        n += k + (r["prompt_len"] if i == 0 else 0)
    return n / (t1 - t0)
