"""nm_spmm_decode_roofline.chat: kernels.  The packed 2:4 decode
matmul's roofline share over the traced span: the least time its calls
could take at the chip's peaks over their summed device time, in %.

Each fused decode step calls the kernel once for each of the seven
packed projections of each layer, with the whole batch (``max_batch``
rows, padded to 8) as M; so the trace's call count gives the steps,
and each call's bytes (packed values bf16 + positions int8, the
activations and the f32 output) and FLOPs (the dense product the
kernel runs on the decompressed tile) come from the projection's
shape."""

import math

import costs

KERNEL = "nm_spmm_decode"


def call_cost(m: int, k: int, n: int):
    """(FLOPs, bytes) of one call: x (m, k) bf16 @ packed (k, n)."""
    mp = max(8, -(-m // 8) * 8)
    pk, pn = -(-k // 128) * 128, -(-n // 128) * 128
    flops = 2.0 * mp * pk * pn
    byts = pk // 2 * pn * (2 + 1) + mp * pk * 2 + mp * pn * 4 + pn * 4
    return flops, byts


def bound_per_step(s, m: int, peaks) -> float:
    """Least seconds of one step's calls (one per projection per layer)."""
    t = 0.0
    for _, k, n in costs.projections(s):
        f, b = call_cost(m, k, n)
        t += max(f / peaks["bf16_flops"], b / peaks["hbm_bytes_per_s"])
    return s["layers"] * t


def read(run):
    red, peaks = run.get("trace"), run.get("peaks")
    if not red or peaks is None or not red["kernel_s"].get(KERNEL):
        return None
    s = costs.shape(run["config"])
    calls = red["kernel_calls"][KERNEL]
    per_step = len(costs.projections(s)) * s["layers"]
    steps = calls / per_step
    m = run["cell"]["serve"]["max_batch"]
    bound = steps * bound_per_step(s, m, peaks)
    share = 100.0 * bound / red["kernel_s"][KERNEL]
    return share if math.isfinite(share) else None
