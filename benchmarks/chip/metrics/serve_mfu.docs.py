"""serve_mfu.docs: model step.  Useful FLOPs of the work finished in
the window (each prompt's prefill, every output token; 2:4 projections
at their nonzeros, attention over the live context; ``costs.py``) over
the window's length times the chip's bf16 peak, in %."""

import costs


def read(run):
    if run.get("peaks") is None:
        return None
    t0, t1 = run["window"]
    flops = costs.window_flops(run)
    return 100.0 * flops / ((t1 - t0) * run["peaks"]["bf16_flops"])
