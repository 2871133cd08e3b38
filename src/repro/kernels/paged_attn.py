"""Paged-attention decode — Pallas TPU kernel over block-table pages.

Continuous-batching decode (serve.engine) keeps each request's KV cache
in fixed-size pages scattered across a global pool; a per-request block
table maps logical KV positions to physical pages.  This kernel computes
one decode step of grouped (GQA) attention directly over the paged pool:
the block table rides in as a *scalar-prefetch* operand so each grid
step's K/V page DMA is issued from ``block_tables[b, p]`` — the gather
never materializes a per-request contiguous cache (the jnp oracle in
ref.paged_attn_ref does exactly that, and is the CPU serving path).

Grid (B, P_max); the page axis is the innermost *sequential* axis —
accumulator + running max/sum live in VMEM scratch across page steps
(same online-softmax structure as flash_attn.py).  Each step moves one
whole page, all KV heads: blocks are (1, page_size, KV, hd) (scales
(1, page_size, KV)), so the last two block dims equal the pool's and
Mosaic's (8, 128) tiling rule holds for any head count.  The heads are
handled inside the body as a batch dim of VPU multiply-reduces — the
scores are a (page_size, G, KV, 1) tile, the softmax reduces over the
leading page axis — so nothing is sliced along a tiled dim.  Pages past
a request's length are skipped via @pl.when (their DMA still issues but
runs no FLOPs; the mosaic pipeliner overlaps it with live compute), and
an idle slot (length 0) computes nothing and emits zeros.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_attn_kernel(bt_ref, len_ref, q_ref, *refs, page_size: int,
                       window: Optional[int], scale: float,
                       quantized: bool = False):
    if quantized:
        # int8 KV pages ride with per-row f32 scales (serve/kvpool.py
        # kv_dtype="int8"); dequant happens on the VMEM tile right
        # after load — HBM still moves only the int8 bytes
        k_ref, ks_ref, v_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = refs
    else:
        k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref = refs
    b = pl.program_id(0)
    p = pl.program_id(1)
    n_pages = pl.num_programs(1)
    length = len_ref[b]

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # a page is live when it overlaps the valid key range
    # [max(0, length-window), length) — every live page has >= 1 unmasked
    # key, so the -1e30 mask never produces an all-masked softmax row
    live = p * page_size < length
    if window is not None:
        live &= (p + 1) * page_size > length - window

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale          # (G, KV, hd)
        k = k_ref[0].astype(jnp.float32)                  # (ps, KV, hd)
        v = v_ref[0].astype(jnp.float32)                  # (ps, KV, hd)
        if quantized:
            k = k * ks_ref[0][:, :, None]
            v = v * vs_ref[0][:, :, None]
        s = jnp.sum(k[:, None] * q[None], axis=-1,
                    keepdims=True)                        # (ps, G, KV, 1)
        kpos = p * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        ok = kpos < length
        if window is not None:
            ok &= kpos >= length - window
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_ref[...]                               # (G, KV, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0))
        pmat = jnp.exp(s - m_new[None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(pmat, axis=0)
        acc_ref[...] = acc_ref[...] * alpha + jnp.sum(pmat * v[:, None],
                                                      axis=0)
        m_ref[...] = m_new

    @pl.when(p == n_pages - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_attn(
    q: jax.Array,                # (B, KV, G, hd)
    k_pages: jax.Array,          # (P, page_size, KV, hd)
    v_pages: jax.Array,          # (P, page_size, KV, hd)
    block_tables: jax.Array,     # (B, P_max) int32 — physical page ids
    lengths: jax.Array,          # (B,) int32 — valid KV entries per request
    *,
    window: Optional[int] = None,
    interpret: bool = False,
    k_scale: Optional[jax.Array] = None,   # (P, page_size, KV) f32
    v_scale: Optional[jax.Array] = None,
) -> jax.Array:
    """One paged GQA decode step. Returns (B, KV, G, hd) f32.

    When ``k_scale``/``v_scale`` are given, k/v_pages are int8 and each
    page tile is dequantized row-wise in VMEM (``int8 * scale``) — the
    scale blocks ride the same block-table prefetch as their pages.
    """
    b, kvh, g, hd = q.shape
    _, page_size, _, _ = k_pages.shape
    p_max = block_tables.shape[1]
    scale = 1.0 / math.sqrt(hd)
    quantized = k_scale is not None
    # the kernel reads q group-major, (G, KV, hd) per request, so the
    # tiled last two dims match the page tile's (KV, hd)
    qt = jnp.swapaxes(q, 1, 2)                            # (B, G, KV, hd)
    page_spec = pl.BlockSpec((1, page_size, kvh, hd),
                             lambda bb, pp, bt, ln: (bt[bb, pp], 0, 0, 0))
    scale_spec = pl.BlockSpec((1, page_size, kvh),
                              lambda bb, pp, bt, ln: (bt[bb, pp], 0, 0))
    q_spec = pl.BlockSpec((1, g, kvh, hd),
                          lambda bb, pp, bt, ln: (bb, 0, 0, 0))
    in_specs = [q_spec, page_spec]
    operands = [qt, k_pages]
    if quantized:
        in_specs.append(scale_spec)
        operands.append(k_scale)
    in_specs.append(page_spec)
    operands.append(v_pages)
    if quantized:
        in_specs.append(scale_spec)
        operands.append(v_scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, p_max),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((g, kvh, hd), jnp.float32),   # output accumulator
            pltpu.VMEM((g, kvh, 1), jnp.float32),    # running max m
            pltpu.VMEM((g, kvh, 1), jnp.float32),    # running sum l
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_attn_kernel, page_size=page_size,
                          window=window, scale=scale, quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, g, kvh, hd), jnp.float32),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
      *operands)
    return jnp.swapaxes(out, 1, 2)
