"""device_idle.prune: device.  Share of the traced span in which no
operation ran on the chip: 1 - (union of the "XLA Ops" intervals) over
the span, from the profiler trace, in %."""


def read(run):
    red = run.get("trace")
    if not red or red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
