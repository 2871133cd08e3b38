"""Mamba-1 selective scan — Pallas TPU kernel with the state in VMEM.

    s_t = exp(dt_t · A) ⊙ s_{t-1} + (dt_t · x_t) ⊗ b_t      (Di, N)
    y_t = s_t · c_t                                           (Di,)

A jnp scan has to hold ``exp(dt·A)`` and ``(dt·x) ⊗ b`` as (T, Di, N)
tensors in HBM, N times the bytes of its inputs.  This kernel streams
only the (B, T, Di) inputs and output and the (B, T, N) ``b``/``c``
through VMEM, and keeps the (N, bd) state of one batch row and one
``bd``-wide tile of Di in a VMEM scratch across T.

Grid (B, Di/bd, T/tc): the T axis is innermost and sequential
("arbitrary"), the other two are independent.  The state lies N on
sublanes and Di on lanes, so a step is a few (N, bd) vector ops.  ``b``
and ``c`` come in transposed, (B, N, T), so that a group of 128 steps
loads as one lane-aligned (N, 128) tile; each step takes its column by a
lane mask and a lane reduce (off the recurrence's critical path).  The
``D·x`` skip and the ``silu(z)`` gate stay outside the kernel.

Padding: T is padded with dt = 0 (an identity step: exp(0) = 1 and a
zero input), so the last state is the state after the last real step;
Di is padded with A = 0 and sliced off.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
UNROLL = 8                  # steps written out per loop iteration


def _scan_kernel(dt_ref, x_ref, b_ref, c_ref, a_ref, s0_ref,
                 y_ref, st_ref, s_scr, *, tc: int):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        s_scr[...] = s0_ref[0]

    a = a_ref[...]                                         # (N, bd)
    n = a.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (n, LANES), 1)

    def group(g, s):
        t0 = pl.multiple_of(g * LANES, LANES)
        bg = b_ref[0, :, pl.ds(t0, LANES)]                 # (N, 128)
        cg = c_ref[0, :, pl.ds(t0, LANES)]

        def steps(u, s):
            # Mosaic unrolls a loop wholly or not at all
            for k in range(UNROLL):
                j = u * UNROLL + k
                t = t0 + j
                dt = dt_ref[0, pl.ds(t, 1), :]             # (1, bd)
                x = x_ref[0, pl.ds(t, 1), :]
                hit = lane == j
                bt = jnp.sum(jnp.where(hit, bg, 0.0), axis=1, keepdims=True)
                ct = jnp.sum(jnp.where(hit, cg, 0.0), axis=1, keepdims=True)
                s = jnp.exp(dt * a) * s + (dt * x) * bt    # (N, bd)
                y_ref[0, pl.ds(t, 1), :] = jnp.sum(s * ct, axis=0,
                                                   keepdims=True)
            return s

        return jax.lax.fori_loop(0, LANES // UNROLL, steps, s)

    s = jax.lax.fori_loop(0, tc // LANES, group, s_scr[...])
    s_scr[...] = s

    @pl.when(ti == pl.num_programs(2) - 1)
    def _last():
        st_ref[0] = s


@functools.partial(jax.jit, static_argnames=("chunk",))
def selective_scan_chunked(dt: jax.Array, x: jax.Array, b: jax.Array,
                           c: jax.Array, a: jax.Array,
                           init: Optional[jax.Array] = None, *,
                           chunk: int = 128) -> Tuple[jax.Array, jax.Array]:
    """The same scan in jnp, for backends without the kernel: a
    ``lax.scan`` over T in chunks of ``chunk`` that carries the
    (B, Di, N) f32 state, each chunk an ``associative_scan``.  Peak
    memory is O(B·chunk·Di·N), not O(B·T·Di·N).  Same arguments and
    result as :func:`selective_scan`."""
    bsz, t, di = dt.shape
    n = b.shape[-1]
    f32 = jnp.float32
    chunk = min(chunk, t)
    tp = -(-t // chunk) * chunk

    def split(v):                        # (B, T, k) -> (T/chunk, B, chunk, k)
        v = jnp.pad(v.astype(f32), ((0, 0), (0, tp - t), (0, 0)))
        return jnp.moveaxis(v.reshape(bsz, tp // chunk, chunk, -1), 1, 0)

    a = a.astype(f32)

    def combine(e1, e2):
        a1, b1 = e1
        a2, b2 = e2
        return a2 * a1, a2 * b1 + b2

    def body(s, xs):
        dtc, xc, bc, cc = xs
        abar = jnp.exp(dtc[..., None] * a)                 # (B,C,Di,N)
        bx = (dtc * xc)[..., None] * bc[:, :, None, :]
        aprod, states = jax.lax.associative_scan(combine, (abar, bx), axis=1)
        states = states + aprod * s[:, None]
        return states[:, -1], jnp.einsum("btdn,btn->btd", states, cc)

    s0 = (jnp.zeros((bsz, di, n), f32) if init is None
          else init.astype(f32))
    last, ys = jax.lax.scan(body, s0, (split(dt), split(x), split(b),
                                       split(c)))
    y = jnp.moveaxis(ys, 0, 1).reshape(bsz, tp, di)[:, :t]
    return y, last


@functools.partial(jax.jit, static_argnames=("bd", "tc", "interpret"))
def selective_scan(dt: jax.Array, x: jax.Array, b: jax.Array, c: jax.Array,
                   a: jax.Array, init: Optional[jax.Array] = None, *,
                   bd: int = 512, tc: int = 512,
                   interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """The selective scan of ``models.ssm`` (see the module docstring).

    dt, x: (B, T, Di); b, c: (B, T, N); a: (Di, N) (negative);
    init: (B, Di, N) carry-in state or None (zeros).  Everything is
    computed in f32.  Returns (y (B, T, Di), last state (B, Di, N)).
    ``bd`` (a multiple of 128) and ``tc`` (of 128) are the Di and T
    tiles; both shrink to the padded extent of a smaller input.
    """
    bsz, t, di = dt.shape
    n = b.shape[-1]
    f32 = jnp.float32
    bd = min(bd, -(-di // LANES) * LANES)
    tc = min(tc, -(-t // LANES) * LANES)
    tp, dp = -(-t // tc) * tc, -(-di // bd) * bd
    pad_td = ((0, 0), (0, tp - t), (0, dp - di))
    dt_p = jnp.pad(dt.astype(f32), pad_td)
    x_p = jnp.pad(x.astype(f32), pad_td)
    b_p = jnp.pad(jnp.swapaxes(b.astype(f32), 1, 2),
                  ((0, 0), (0, 0), (0, tp - t)))           # (B, N, Tp)
    c_p = jnp.pad(jnp.swapaxes(c.astype(f32), 1, 2),
                  ((0, 0), (0, 0), (0, tp - t)))
    a_p = jnp.pad(a.astype(f32).T, ((0, 0), (0, dp - di)))  # (N, Dp)
    s0 = (jnp.zeros((bsz, n, dp), f32) if init is None else
          jnp.pad(jnp.swapaxes(init.astype(f32), 1, 2),
                  ((0, 0), (0, 0), (0, dp - di))))
    row = pl.BlockSpec((1, tc, bd), lambda i, d, k: (i, k, d))
    col = pl.BlockSpec((1, n, tc), lambda i, d, k: (i, 0, k))
    state = pl.BlockSpec((1, n, bd), lambda i, d, k: (i, 0, d))
    y, st = pl.pallas_call(
        functools.partial(_scan_kernel, tc=tc),
        grid=(bsz, dp // bd, tp // tc),
        in_specs=[row, row, col, col,
                  pl.BlockSpec((n, bd), lambda i, d, k: (0, d)), state],
        out_specs=[row, state],
        out_shape=[jax.ShapeDtypeStruct((bsz, tp, dp), f32),
                   jax.ShapeDtypeStruct((bsz, n, dp), f32)],
        scratch_shapes=[pltpu.VMEM((n, bd), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(dt_p, x_p, b_p, c_p, a_p, s0)
    return y[:, :t, :di], jnp.swapaxes(st[:, :, :di], 1, 2)
