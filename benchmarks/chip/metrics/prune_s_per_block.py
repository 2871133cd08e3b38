"""prune_s_per_block: the window's time up to the end of its last
completed prune job, over the blocks those jobs pruned (host clock; each
job ends on ``block_until_ready`` of its pruned params).  A job the
window cuts counts neither its blocks nor its time."""


def read(run):
    jobs = run.get("jobs") or []
    if not jobs:
        return None
    t0 = run["window"][0]
    return (max(e for _, e, _ in jobs) - t0) / sum(b for _, _, b in jobs)
