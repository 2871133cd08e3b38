"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""

from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

NM_COMBOS_24 = np.array(
    [(0, 1), (0, 2), (0, 3), (1, 2), (1, 3), (2, 3)], dtype=np.int32)


# ----------------------------------------------------------------------
# 2:4 compressed format
# ----------------------------------------------------------------------
def compress_24(w: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Dense (K, N) with 2:4 sparsity along K → (vals (K/2,N), idx (K/2,N)).

    Every group of 4 consecutive K-rows holds ≤2 nonzeros per column; the
    two kept entries' in-group positions go to idx (int8, ascending), the
    values to vals.  (Groups with <2 nonzeros pad with zeros at unused
    slots — idx still valid.)
    """
    k, n = w.shape
    assert k % 4 == 0, f"K={k} must divide by 4"
    g = w.reshape(k // 4, 4, n)
    nz = (g != 0)
    # order: nonzeros first (stable by position)
    rank = jnp.cumsum(nz, axis=1) * nz          # 1,2 at kept slots
    pos = jnp.arange(4, dtype=jnp.int32)[None, :, None]
    idx0 = jnp.min(jnp.where(rank == 1, pos, 4), axis=1)
    idx1 = jnp.min(jnp.where(rank == 2, pos, 4), axis=1)
    # groups with <2 nonzeros: point the unused slot at position 0 value 0
    idx0c = jnp.where(idx0 == 4, 0, idx0)
    idx1c = jnp.where(idx1 == 4, 0, idx1)
    v0 = jnp.take_along_axis(g, idx0c[:, None, :], axis=1)[:, 0, :]
    v1 = jnp.take_along_axis(g, idx1c[:, None, :], axis=1)[:, 0, :]
    v0 = jnp.where(idx0 == 4, 0, v0)
    v1 = jnp.where(idx1 == 4, 0, v1)
    vals = jnp.stack([v0, v1], axis=1).reshape(k // 2, n)
    idx = jnp.stack([idx0c, idx1c], axis=1).reshape(k // 2, n).astype(jnp.int8)
    return vals, idx


def decompress_24(vals: jax.Array, idx: jax.Array) -> jax.Array:
    """(K/2, N) pairs → dense (K, N)."""
    k2, n = vals.shape
    g = k2 // 2
    v = vals.reshape(g, 2, n)
    ix = idx.reshape(g, 2, n).astype(jnp.int32)
    r = jnp.arange(4, dtype=jnp.int32)[None, :, None]       # (1,4,1)
    dense = jnp.sum(
        v[:, :, None, :] * (ix[:, :, None, :] == r[:, None, :, :]).astype(
            vals.dtype),
        axis=1)                                             # (g,4,n)
    return dense.reshape(g * 4, n)


def activate(y: jax.Array, activation) -> jax.Array:
    """The decode-epilogue activation: None | "silu" | "gelu".  Shared
    by the fused nm_spmm_decode kernel and the jnp oracle so both sides
    of the dispatch run the identical op sequence."""
    if activation is None:
        return y
    if activation == "silu":
        return jax.nn.silu(y)
    if activation == "gelu":
        return jax.nn.gelu(y)
    raise ValueError(f"unknown epilogue activation {activation!r}")


def nm_spmm_ref(x: jax.Array, vals: jax.Array, idx: jax.Array,
                bias=None, activation=None) -> jax.Array:
    """y = act(x @ decompress(vals, idx) + bias). x: (..., K) → (..., N)
    f32.  The decompress is an exact inverse of :func:`compress_24`, so
    on f32 inputs this is bit-identical to the dense ``x @ w`` — the
    property that lets the serve engine swap packed leaves in without
    perturbing greedy token streams."""
    w = decompress_24(vals, idx)
    y = x.astype(jnp.float32) @ w.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return activate(y, activation)


# ----------------------------------------------------------------------
def hessian_accum_ref(x: jax.Array) -> jax.Array:
    """H = 2 · x xᵀ for x (m, T) — f32."""
    x32 = x.astype(jnp.float32)
    return 2.0 * (x32 @ x32.T)


# ----------------------------------------------------------------------
def nm_select_ref(w: jax.Array, hinv: jax.Array) -> jax.Array:
    """Solution 𝔐 2:4 mask via Eq. (12) — reference (loops over combos).

    w: (R, C) paper orientation; hinv: (C, C). Returns bool mask (R, C),
    True = pruned, exactly 2 per group of 4.
    """
    r, c = w.shape
    g = c // 4
    w32 = w.astype(jnp.float32).reshape(r, g, 4)
    cols = (jnp.arange(g) * 4)[:, None] + jnp.arange(4)[None, :]
    hg = hinv[cols[:, :, None], cols[:, None, :]].astype(jnp.float32)  # (g,4,4)
    losses = []
    for (p, q) in np.asarray(NM_COMBOS_24):
        app = hg[:, p, p][None]
        aqq = hg[:, q, q][None]
        apq = hg[:, p, q][None]
        wp = w32[:, :, p]
        wq = w32[:, :, q]
        det = app * aqq - apq * apq
        loss = 0.5 * (wp * wp * aqq - 2 * wp * wq * apq + wq * wq * app) / det
        losses.append(loss)
    losses = jnp.stack(losses, axis=-1)                      # (r,g,6)
    best = jnp.argmin(losses, axis=-1)                       # (r,g)
    combo_mask = np.zeros((6, 4), bool)
    for ci, (p, q) in enumerate(np.asarray(NM_COMBOS_24)):
        combo_mask[ci, p] = combo_mask[ci, q] = True
    mask = jnp.asarray(combo_mask)[best]                     # (r,g,4)
    return mask.reshape(r, c)


# ----------------------------------------------------------------------
def paged_attn_ref(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                   block_tables: jax.Array, lengths: jax.Array,
                   window=None, k_scale=None, v_scale=None) -> jax.Array:
    """Paged GQA decode oracle (and the CPU serving path — jittable).

    q: (B, KV, G, hd); k/v_pages: (P, page_size, KV, hd); block_tables:
    (B, P_max) int32 physical page ids; lengths: (B,) valid KV entries.
    Gathers each request's pages contiguous, then runs exactly the
    einsum/softmax sequence of models.layers._sdpa so paged greedy
    decode is bit-identical to the dense cache path.  Returns
    (B, KV, G, hd) in v.dtype (idle rows, length 0, are garbage — the
    caller masks them).

    ``k_scale``/``v_scale`` (P, page_size, KV) f32 engage the int8
    KV-page path (serve/kvpool.py ``kv_dtype="int8"``): pages are
    dequantized row-wise right after the gather (``int8 * scale``) and
    attention proceeds in f32 exactly as above — output dtype f32.
    """
    b, kvh, g, hd = q.shape
    _, page_size, _, _ = k_pages.shape
    p_max = block_tables.shape[1]
    s_len = p_max * page_size
    k = k_pages[block_tables].reshape(b, s_len, kvh, hd)
    v = v_pages[block_tables].reshape(b, s_len, kvh, hd)
    if k_scale is not None:
        ks = k_scale[block_tables].reshape(b, s_len, kvh)
        vs = v_scale[block_tables].reshape(b, s_len, kvh)
        k = k.astype(jnp.float32) * ks[..., None]
        v = v.astype(jnp.float32) * vs[..., None]
    # the einsum strings (incl. the T=1 dim) mirror layers._sdpa exactly
    # — any other contraction layout lowers to a different f32 reduction
    # order and breaks decode bit-parity with the dense cache
    qg = q[:, None]                                   # (B, 1, KV, G, hd)
    scores = jnp.einsum("btkgd,bskd->bkgts", qg, k).astype(jnp.float32)
    scores = scores / math.sqrt(hd)
    kpos = jnp.arange(s_len, dtype=jnp.int32)[None, :]
    ok = kpos < lengths[:, None]
    if window is not None:
        ok &= kpos >= lengths[:, None] - window
    scores = jnp.where(ok[:, None, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgts,bskd->btkgd", probs, v)
    return out[:, 0]                                  # (B, KV, G, hd)


# ----------------------------------------------------------------------
def flash_attn_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                   causal: bool = True) -> jax.Array:
    """q,k,v: (BH, T, D). Plain softmax attention — f32 output."""
    bh, t, d = q.shape
    s = jnp.einsum("btd,bsd->bts", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / jnp.sqrt(jnp.float32(d))
    if causal:
        mask = jnp.tril(jnp.ones((t, t), bool))
        s = jnp.where(mask[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bts,bsd->btd", p, v.astype(jnp.float32))


def selective_scan_ref(dt: jax.Array, x: jax.Array, b: jax.Array,
                       c: jax.Array, a: jax.Array, init=None):
    """Mamba-1 selective scan, one time step after another in f32:
    s_t = exp(dt_t A) s_{t-1} + dt_t x_t b_t, y_t = s_t c_t.
    dt, x: (B, T, Di); b, c: (B, T, N); a: (Di, N); init (B, Di, N).
    Returns (y (B, T, Di), last state (B, Di, N))."""
    f32 = jnp.float32
    dt, x, b, c, a = (v.astype(f32) for v in (dt, x, b, c, a))
    s0 = (jnp.zeros((dt.shape[0], dt.shape[2], b.shape[2]), f32)
          if init is None else init.astype(f32))

    def step(s, xs):
        dt_t, x_t, b_t, c_t = xs                      # (B,Di) / (B,N)
        s = (jnp.exp(dt_t[..., None] * a) * s
             + (dt_t * x_t)[..., None] * b_t[:, None, :])
        return s, jnp.einsum("bdn,bn->bd", s, c_t,
                             precision=jax.lax.Precision.HIGHEST)

    last, ys = jax.lax.scan(step, s0, tuple(
        jnp.swapaxes(v, 0, 1) for v in (dt, x, b, c)))
    return jnp.swapaxes(ys, 0, 1), last
