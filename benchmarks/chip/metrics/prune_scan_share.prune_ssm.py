"""prune_scan_share.prune_ssm: model step.  The Mamba selective scan
kernel's share of the traced prune job's device time: its summed
device seconds over the busy seconds (the union of the "XLA Ops"
intervals), from the profiler trace, in %."""

KERNEL = "selective_scan"


def read(run):
    red = run.get("trace")
    if not red or red["busy_s"] <= 0 or not red["kernel_s"].get(KERNEL):
        return None
    return 100.0 * red["kernel_s"][KERNEL] / red["busy_s"]
