"""prune_mfu: solver.  FLOPs of the window's completed prune jobs
(capture forward, Hessians, the SM solves and the propagate forward,
counted from shapes in ``costs.prune_block_flops``) over the time to
the last job's end times the chip's bf16 peak, in %.  The window's jobs
run the plain path, in the traced run as in the others; the time is the
host clock's."""

import costs


def read(run):
    jobs = run.get("jobs") or []
    if not jobs or run.get("peaks") is None:
        return None
    s = costs.shape(run["config"])
    cal, prune = run["mix"]["calibration"], run["mix"]["prune"]
    per_block = costs.prune_block_flops(s, cal["samples"], cal["length"],
                                        prune["blocksize"])
    blocks = sum(b for _, _, b in jobs)
    t = max(e for _, e, _ in jobs) - run["window"][0]
    return 100.0 * per_block * blocks / (t * run["peaks"]["bf16_flops"])
