"""The plain reference forward pass of the Qwen decoder family.

Written from the published description (pre-norm RMSNorm blocks, rotary
embedding on the two halves of each head, grouped-query causal attention
with optional QKV bias and per-head RMSNorm of q and k, a SwiGLU MLP, a
tied or untied head), in float32 at ``Precision.HIGHEST``, one sequence
and one layer at a time, with no cache, kernel or batching.  It imports
nothing of the program under test: it reads the params tree by its key
names, and rebuilds each packed 2:4 projection densely itself.

``mode="fp8"`` is the control: every projection's inputs and weights
rounded to float8 (e4m3, scaled per row and per output column) before
the product, the precision below the configuration's bfloat16.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0                      # largest finite float8_e4m3fn


def dense(w) -> jax.Array:
    """A projection as a dense f32 (K, N) matrix.  A packed one holds,
    for each group of four input rows, its two kept values (``vals``)
    and their positions in the group (``idx``)."""
    if not isinstance(w, dict):
        return jnp.asarray(w, jnp.float32)
    vals = jnp.asarray(w["vals"], jnp.float32)
    idx = jnp.asarray(w["idx"], jnp.int32)
    k2, n = vals.shape
    v = vals.reshape(k2 // 2, 2, n)
    i = idx.reshape(k2 // 2, 2, n)
    pos = jnp.arange(4, dtype=jnp.int32)[None, :, None]
    out = (jnp.where(i[:, 0:1] == pos, v[:, 0:1], 0.0)
           + jnp.where(i[:, 1:2] == pos, v[:, 1:2], 0.0))
    return out.reshape(k2 * 2, n)


def _round8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm(x, w, mode: str = "f32"):
    """x (T, K) @ w (K, N) in float32, or through float8 for the control."""
    if mode == "fp8":
        x, w = _round8(x, -1), _round8(w, 0)
    return jnp.matmul(x, w, precision=HIGHEST)


def rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * jnp.asarray(scale, jnp.float32)


def rope(x, positions, theta):
    """Rotary embedding on (T, heads, hd): the first and second halves
    of each head are the two coordinates rotated together."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[:, None].astype(jnp.float32) * freq       # (T, half)
    sin, cos = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, q_block: int = 512):
    """Causal grouped attention; q (T, H, hd), k/v (T, KV, hd).  Queries
    run in blocks so the score matrix of a long sequence fits."""
    t, h, hd = q.shape
    kvh = k.shape[1]
    g = h // kvh
    outs = []
    for q0 in range(0, t, q_block):
        qb = q[q0:q0 + q_block].reshape(-1, kvh, g, hd)
        s = jnp.einsum("tkgd,skd->kgts", qb, k, precision=HIGHEST)
        s = s / math.sqrt(hd)
        qpos = q0 + jnp.arange(qb.shape[0])
        ok = jnp.arange(t)[None, :] <= qpos[:, None]
        s = jnp.where(ok[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("kgts,skd->tkgd", p, v, precision=HIGHEST)
        outs.append(o.reshape(-1, h * hd))
    return jnp.concatenate(outs, 0)


def block(p: Dict, x, positions, c: Dict, mode: str = "f32",
          caps: Optional[Dict] = None):
    """One decoder block on one sequence x (T, d).  ``caps`` collects
    each projection's input under its name (for the prune reference)."""
    a, m = p["attn"], p["mlp"]
    nh, kvh, hd = c["heads"], c["kv_heads"], c["head_dim"]
    t = x.shape[0]
    hin = rmsnorm(x, a["ln"]["scale"], c["eps"])

    def proj(name, inp, w, b=None):
        if caps is not None:
            caps[name] = inp
        y = mm(inp, dense(w), mode)
        return y if b is None else y + jnp.asarray(b, jnp.float32)

    q = proj("attn.wq", hin, a["wq"], a.get("bq")).reshape(t, nh, hd)
    k = proj("attn.wk", hin, a["wk"], a.get("bk")).reshape(t, kvh, hd)
    v = proj("attn.wv", hin, a["wv"], a.get("bv")).reshape(t, kvh, hd)
    if "q_norm" in a:
        q = rmsnorm(q, a["q_norm"]["scale"], c["eps"])
        k = rmsnorm(k, a["k_norm"]["scale"], c["eps"])
    q = rope(q, positions, c["rope_theta"])
    k = rope(k, positions, c["rope_theta"])
    x = x + proj("attn.wo", attention(q, k, v), a["wo"])
    hin = rmsnorm(x, m["ln"]["scale"], c["eps"])
    up = proj("mlp.wi", hin, m["wi"])
    gate = proj("mlp.wg", hin, m["wg"])
    return x + proj("mlp.wo", jax.nn.silu(gate) * up, m["wo"])


def layer_params(params: Dict, i: int) -> Dict:
    """Layer ``i`` of the stacked blocks (leading axis = layer)."""
    return jax.tree.map(lambda x: x[i], params["layers"]["s0"])


def consts(cfg: Dict) -> Dict:
    return {"heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg.get("head_dim")
            or cfg["hidden_size"] // cfg["num_attention_heads"],
            "eps": cfg["rms_norm_eps"], "rope_theta": cfg["rope_theta"],
            "layers": cfg["num_hidden_layers"],
            "tied": cfg["tie_word_embeddings"]}


@functools.partial(jax.jit, static_argnames=("c", "mode"))
def _block_jit(p, x, positions, c, mode):
    return block(p, x, positions, dict(c), mode)


def logits_at(params: Dict, tokens, rows, cfg: Dict, mode: str = "f32",
              vocab_block: int = 32768):
    """Reference logits of ``tokens`` (T,) at positions ``rows`` (R,):
    row r predicts token r + 1.  Layer by layer, f32; the head runs in
    vocabulary blocks so its f32 copy never sits whole in memory."""
    c = consts(cfg)
    ck = tuple(sorted(c.items()))
    tokens = jnp.asarray(tokens, jnp.int32)
    x = jnp.asarray(params["embed"]["tok"], jnp.float32)[tokens]
    positions = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    for i in range(c["layers"]):
        x = _block_jit(layer_params(params, i), x, positions, ck, mode)
    h = rmsnorm(x[jnp.asarray(rows)], params["unembed"]["ln"]["scale"],
                c["eps"])
    head = (params["embed"]["tok"] if c["tied"]
            else params["unembed"]["head"])
    vocab = head.shape[0] if c["tied"] else head.shape[1]
    outs = []
    for v0 in range(0, vocab, vocab_block):
        w = (jnp.asarray(head[v0:v0 + vocab_block], jnp.float32).T
             if c["tied"] else
             jnp.asarray(head[:, v0:v0 + vocab_block], jnp.float32))
        outs.append(mm(h, w, mode))
    return jnp.concatenate(outs, -1)
