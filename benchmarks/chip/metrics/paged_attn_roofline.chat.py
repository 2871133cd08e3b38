"""paged_attn_roofline.chat: kernels.  The paged decode attention's
roofline share over the traced span: the least time its calls could
take over their summed device time, in %.

The work is that of the tokens decoded in the traced span (from the
tokens the client received in it): a token at context c reads, in each
layer, the K and V pages of its c positions (whole pages) and
runs 4 c heads hd FLOPs.  Pages hold the model's dtype unless the cell
serves int8 KV (``kv_dtype`` "fp32" keeps the model's dtype)."""

import math

import costs

KERNEL = "paged_attn"


def token_cost(s, context: int, page: int, kv_bytes: int):
    """(FLOPs, bytes) of one layer's call for one token at ``context``."""
    pages = -(-context // page)
    byts = 2 * pages * page * s["kv"] * s["hd"] * kv_bytes
    flops = 4.0 * context * s["h"] * s["hd"]
    return flops, byts


def read(run):
    red, peaks = run.get("trace"), run.get("peaks")
    if not red or peaks is None or not red["kernel_s"].get(KERNEL):
        return None
    s = costs.shape(run["config"])
    serve = run["cell"]["serve"]
    kv_bytes = (1 if serve.get("kv_dtype", "fp32") == "int8"
                else 2 if run["config"]["dtype"] == "bfloat16" else 4)
    page = serve.get("page_size", 16)
    bound = 0.0
    for r, i, _, n in costs.events_in(run["requests"], red["t0"], red["t1"]):
        for j in range(max(i, 1), i + n):
            f, b = token_cost(s, r["prompt_len"] + j, page, kv_bytes)
            bound += max(f / peaks["bf16_flops"],
                         b / peaks["hbm_bytes_per_s"])
    share = 100.0 * s["layers"] * bound / red["kernel_s"][KERNEL]
    return share if math.isfinite(share) and bound > 0 else None
