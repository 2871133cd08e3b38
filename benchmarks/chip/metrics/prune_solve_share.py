"""prune_solve_share: prune engine.  The solve stage's share of the
pipeline's stage seconds (``prune_stage_seconds_total{stage}``, program
counters), in %, of the one job that the traced run drives after its
window with ``instrument=True``: that job blocks after each stage, so
its seconds are device time and not dispatch time.  The window's own
jobs run the plain path."""


def read(run):
    st = run.get("stages")
    if not st or sum(st.values()) <= 0:
        return None
    return 100.0 * st["solve"] / sum(st.values())
