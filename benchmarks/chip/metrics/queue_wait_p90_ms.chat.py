"""queue_wait_p90_ms.chat: front end.  p90 of the registry's
``serve_queue_wait_seconds`` (submit to admission into a batch slot,
host clock, recorded by the scheduler) over the observations made in
the window, from the histogram's bucket counts."""

import harness

SERIES = "serve_queue_wait_seconds"


def read(run):
    bounds, counts = run["registry"]["hists"].get(SERIES, ((), []))
    q = harness.hist_quantile(bounds, counts, 0.9) if counts else None
    return None if q is None else q * 1e3
