"""selective_scan_roofline.prune_ssm: kernels.  The Mamba selective
scan kernel's roofline share over the traced prune job: the least time
its calls could take at the chip's peaks over their summed device time,
in %.

The calls and their shapes come from the configuration and the mix,
never from the program: each pruned layer scans every calibration shard
twice (capture and propagate), B = samples / calib_shard sequences of
T = length tokens at Di = expand * hidden_size, N = ssm_state.  A call
moves dt, x and y (B, T, Di), b and c (B, T, N), A (Di, N) and the
first and last states (B, Di, N), all f32, and runs about 6 FLOPs per
(t, d, n): exp(dt A), its product with the state, dt x b, the sum, and
the product and sum with c."""

import math

KERNEL = "selective_scan"


def call_cost(b: int, t: int, di: int, n: int):
    """(FLOPs, bytes) of one call."""
    byts = 4 * (3 * b * t * di + 2 * b * t * n + di * n + 2 * b * di * n)
    return 6.0 * b * t * di * n, byts


def read(run):
    red, peaks = run.get("trace"), run.get("peaks")
    if not red or peaks is None or not red["kernel_s"].get(KERNEL):
        return None
    over = run["config"]["overrides"]
    cal = run["mix"]["calibration"]
    shards = run["mix"]["prune"]["calib_shard"]
    flops, byts = call_cost(cal["samples"] // shards, cal["length"],
                            over["ssm_expand"] * run["config"]["hidden_size"],
                            over["ssm_state"])
    calls = 2 * int(run["cell"]["blocks_per_job"]) * shards
    bound = calls * max(flops / peaks["bf16_flops"],
                        byts / peaks["hbm_bytes_per_s"])
    share = 100.0 * bound / red["kernel_s"][KERNEL]
    return share if math.isfinite(share) and bound > 0 else None
