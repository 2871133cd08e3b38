"""prefill_chunks_per_s.docs: engine + scheduler.  Prefill chunks run
in the window (``serve_prefill_chunks_total``, a program counter) over
the window's length."""


def read(run):
    n = run["registry"]["counters"].get("serve_prefill_chunks_total", 0.0)
    t0, t1 = run["window"]
    return n / (t1 - t0) if n else None
