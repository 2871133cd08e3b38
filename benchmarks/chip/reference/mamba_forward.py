"""The plain reference of one Mamba-1 layer (Gu & Dao 2023).

Written from the published description of ``state-spaces/mamba``'s
``Mamba`` mixer in a pre-norm residual block: RMSNorm; ``in_proj`` to
(x, z); a causal depthwise convolution of width ``d_conv`` over x, with
bias, and SiLU; ``x_proj`` to (dt, B, C); ``dt_proj`` with its bias and
softplus; the selective scan s_t = exp(dt_t A) s_{t-1} + dt_t x_t B_t,
y_t = C_t s_t + D x_t; the gate y * SiLU(z); ``out_proj``; the residual
added in float32.  Everything runs in float32 at ``Precision.HIGHEST``,
and the scan is a sequential ``lax.scan`` over time, one step after
another, for one batch of sequences at a time.  It imports nothing of
the program under test and shares nothing with its chunked scan or its
kernel: it reads the params tree by its key names.

Departures from the published model, none of which a prune job sees:
every calibration sequence starts from a zero state and a zero
convolution window (no cache, no chunked prefill); there is no head.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
LINEARS = ("mamba.in_proj", "mamba.x_proj", "mamba.dt_proj",
           "mamba.out_proj")


def rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * jnp.asarray(scale, jnp.float32)


def scan(dt, x, b, c, a):
    """The selective scan, one time step after another.  dt, x
    (B, T, Di); b, c (B, T, N); a (Di, N) negative; zero initial
    state.  Returns y (B, T, Di) without the D skip."""
    def step(s, xs):
        dt_t, x_t, b_t, c_t = xs                          # (B, Di) / (B, N)
        s = (jnp.exp(dt_t[..., None] * a) * s
             + (dt_t * x_t)[..., None] * b_t[:, None, :])
        return s, jnp.einsum("bdn,bn->bd", s, c_t, precision=HIGHEST)

    s0 = jnp.zeros((dt.shape[0], dt.shape[2], b.shape[2]), jnp.float32)
    _, ys = jax.lax.scan(step, s0, tuple(
        jnp.swapaxes(v, 0, 1) for v in (dt, x, b, c)))
    return jnp.swapaxes(ys, 0, 1)


def layer(p: Dict, x, eps: float, caps: Optional[Dict] = None):
    """One Mamba layer on a batch x (B, T, d) f32; returns x + mixer(x).
    ``caps`` collects each projection's input under its name."""
    m = p["mamba"]

    def proj(name, inp, w):
        if caps is not None:
            caps[name] = inp
        return jnp.matmul(inp, jnp.asarray(w, jnp.float32),
                          precision=HIGHEST)

    a = -jnp.exp(jnp.asarray(m["a_log"], jnp.float32))    # (Di, N)
    di, n = a.shape
    conv_w = jnp.asarray(m["conv_w"], jnp.float32)        # (Di, d_conv)
    rank = m["dt_proj"].shape[0]
    xz = proj("mamba.in_proj", rmsnorm(x, m["ln"]["scale"], eps),
              m["in_proj"])
    xi, z = xz[..., :di], xz[..., di:]
    t, width = x.shape[1], conv_w.shape[1]
    xp = jnp.pad(xi, ((0, 0), (width - 1, 0), (0, 0)))
    conv = sum(xp[:, k:k + t, :] * conv_w[:, k] for k in range(width))
    xc = jax.nn.silu(conv + jnp.asarray(m["conv_b"], jnp.float32))
    dbc = proj("mamba.x_proj", xc, m["x_proj"])
    dt = proj("mamba.dt_proj", dbc[..., :rank], m["dt_proj"])
    dt = jax.nn.softplus(dt + jnp.asarray(m["dt_bias"], jnp.float32))
    y = scan(dt, xc, dbc[..., rank:rank + n], dbc[..., rank + n:], a)
    y = (y + jnp.asarray(m["d"], jnp.float32) * xc) * jax.nn.silu(z)
    return x + proj("mamba.out_proj", y, m["out_proj"])


def layer_params(params: Dict, i: int) -> Dict:
    """Layer ``i`` of the stacked layers (leading axis = layer)."""
    return jax.tree.map(lambda v: v[i], params["layers"]["s0"])


def embed(params: Dict, tokens):
    """The residual stream entering layer 0, in float32."""
    return jnp.asarray(params["embed"]["tok"], jnp.float32)[
        jnp.asarray(tokens, jnp.int32)]


@functools.partial(jax.jit, static_argnames=("eps",))
def capture(p, x, hs, eps: float):
    """``hs`` plus 2 X^T X of each projection's input over the batch x
    (B, T, d) through layer ``p``."""
    caps: Dict = {}
    layer(p, x, eps, caps=caps)
    out = {}
    for k, acc in hs.items():
        xk = caps[k].reshape(-1, caps[k].shape[-1])
        out[k] = acc + 2.0 * jnp.matmul(xk.T, xk, precision=HIGHEST)
    return out


@functools.partial(jax.jit, static_argnames=("eps",))
def propagate(p, x, eps: float):
    return layer(p, x, eps)
