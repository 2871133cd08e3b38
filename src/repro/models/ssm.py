"""State-space / recurrent blocks: Mamba-1, xLSTM mLSTM and sLSTM.

All blocks follow the layers.py conventions: pre-RMSNorm + residual,
params as flat dicts with (in, out) linear kernels, optional ``caps``
capture of every linear input (for the pruning engine), and two code
paths — full-sequence (training / prefill) and single-token decode with
an explicit recurrent-state cache (the reason SSM archs run long_500k:
state is O(1) in sequence length).

Mamba-1 (Gu & Dao 2023):  selective SSM; the scan carries its state
                           over T (a Pallas kernel on TPU, chunks of an
                           associative scan elsewhere).
mLSTM  (Beck et al. 2024): matrix-memory LSTM, attention-like parallel
                           form over T, stabilized exponential gating.
sLSTM  (Beck et al. 2024): scalar-memory recurrent LSTM with block-diag
                           per-head recurrence — inherently sequential,
                           lax.scan over T.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.base import ArchConfig
from repro.models.layers import Params, _dense_init, linear, rmsnorm, rmsnorm_init


# ======================================================================
# Mamba-1
# ======================================================================
def mamba_init(key, cfg: ArchConfig, dtype) -> Params:
    d, di, n, r, ck = (
        cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.dt_rank, cfg.ssm_conv)
    ks = jax.random.split(key, 6)
    # S4D-real initialization for A; dt bias init so softplus(dt) spans
    # [1e-3, 1e-1] as in the reference implementation.
    a = jnp.tile(jnp.arange(1, n + 1, dtype=jnp.float32)[None, :], (di, 1))
    dt_init = jnp.exp(
        jax.random.uniform(ks[4], (di,), jnp.float32)
        * (math.log(1e-1) - math.log(1e-3)) + math.log(1e-3)
    )
    dt_bias = dt_init + jnp.log(-jnp.expm1(-dt_init))  # inverse softplus
    return {
        "ln": rmsnorm_init(d, dtype),
        "in_proj": _dense_init(ks[0], d, 2 * di, dtype),
        "conv_w": (jax.random.normal(ks[1], (di, ck), jnp.float32)
                   / math.sqrt(ck)).astype(dtype),
        "conv_b": jnp.zeros((di,), dtype),
        "x_proj": _dense_init(ks[2], di, r + 2 * n, dtype),
        "dt_proj": _dense_init(ks[3], r, di, dtype, scale=r**-0.5),
        "dt_bias": dt_bias.astype(jnp.float32),
        "a_log": jnp.log(a),
        "d": jnp.ones((di,), jnp.float32),
        "out_proj": _dense_init(
            ks[5], di, d, dtype, scale=1.0 / math.sqrt(di * 2 * cfg.num_layers)),
    }


def _mamba_ssm_scan(dt, x, b, c, a, init=None):
    """Selective-scan core: ``kernels.ops.selective_scan`` (the Pallas
    kernel on TPU, a jnp scan over T in chunks elsewhere), under the
    ``ssm_scan`` scope.

    dt, x: (B,T,Di) f32;  b, c: (B,T,N) f32;  a: (Di,N) f32 (negative).
    ``init`` (B,Di,N): carry-in state (chunked prefill continuation).
    Returns (y: (B,T,Di), last state (B,Di,N)).
    """
    from repro.kernels import ops
    with jax.named_scope("ssm_scan"):
        return ops.selective_scan(dt, x, b, c, a, init)


def mamba_apply(
    p: Params,
    h: jax.Array,
    cfg: ArchConfig,
    *,
    caps=None,
    cache: Optional[Params] = None,
    pos=None,
    prefix: str = "mamba.",
    paged: Optional[Params] = None,
) -> Tuple[jax.Array, Optional[Params]]:
    """Returns (h + mamba(h), new_cache).

    cache = {"conv": (B, ck-1, Di), "ssm": (B, Di, N)} for decode (T==1).

    Chunked prefill (``paged`` with "slot"/"start"/"lengths", T>1, the
    continuous-batching runtime): cache leaves are the slot-pooled
    state (max_slots leading dim, serve.kvpool.StatePool); the chunk
    continues slot ``slot``'s state — conv window carried in, scan
    seeded with the carried SSM state — and positions past the prompt
    length leave the state untouched (dt masked to 0 ⇒ identity step).
    """
    di, n, r, ck = cfg.d_inner, cfg.ssm_state, cfg.dt_rank, cfg.ssm_conv
    bsz, t, _ = h.shape
    # the mixer computes in the model dtype; with ``residual_in_fp32``
    # the residual stream ``h`` is f32 and so is ``h + out``
    dtype = jnp.dtype(cfg.dtype) if cfg.residual_in_fp32 else h.dtype
    h_in = rmsnorm(p["ln"], h, cfg.norm_eps).astype(dtype)
    xz = linear(h_in, p["in_proj"], caps=caps, name=f"{prefix}in_proj")
    x, z = jnp.split(xz, 2, axis=-1)                       # (B,T,Di) each

    conv_w = p["conv_w"].astype(jnp.float32)               # (Di, ck)
    x32 = x.astype(jnp.float32)
    chunk = cache is not None and t > 1 and paged is not None
    prefill = cache is not None and t > 1 and not chunk

    if chunk:
        slot = paged["slot"]
        conv0 = jax.lax.dynamic_slice_in_dim(cache["conv"], slot, 1, axis=0)
        ssm0 = jax.lax.dynamic_slice_in_dim(cache["ssm"], slot, 1, axis=0)
        tpos = paged["start"] + jnp.arange(t, dtype=jnp.int32)
        valid = (tpos < paged["lengths"][0])[None, :]      # (1, T)
        # conv over [carried window ; chunk]
        xp = jnp.concatenate([conv0.astype(jnp.float32), x32], axis=1)
        stacked = jnp.stack(
            [xp[:, i:i + t, :] for i in range(ck)], axis=-1)
        xc = jnp.einsum("btdk,dk->btd", stacked, conv_w)
        # carry-out: the window ending at the last VALID input
        vc = jnp.clip(paged["lengths"][0] - paged["start"], 0, t)
        new_conv = jax.lax.dynamic_slice_in_dim(xp, vc, ck - 1, axis=1)
    elif cache is None or prefill:
        # causal depthwise conv over T: pad left ck-1
        xp = jnp.pad(x32, ((0, 0), (ck - 1, 0), (0, 0)))
        stacked = jnp.stack(
            [xp[:, i:i + t, :] for i in range(ck)], axis=-1)  # (B,T,Di,ck)
        xc = jnp.einsum("btdk,dk->btd", stacked, conv_w)
        new_conv = xp[:, t:, :]                            # last ck-1 inputs
    else:
        # decode: conv over [cache ; x_t]  (window of the last ck inputs)
        win = jnp.concatenate([cache["conv"].astype(jnp.float32), x32], axis=1)
        xc = jnp.einsum("btd,dt->bd", win, conv_w)[:, None, :]
        new_conv = win[:, 1:, :].astype(cache["conv"].dtype)
    xc = xc + p["conv_b"].astype(jnp.float32)[None, None]
    xc = jax.nn.silu(xc)

    dbc = linear(xc.astype(dtype), p["x_proj"], caps=caps,
                 name=f"{prefix}x_proj").astype(jnp.float32)
    dt_r, b, c = jnp.split(dbc, [r, r + n], axis=-1)
    dt = linear(dt_r.astype(dtype), p["dt_proj"], caps=caps,
                name=f"{prefix}dt_proj").astype(jnp.float32)
    dt = jax.nn.softplus(dt + p["dt_bias"][None, None])
    a = -jnp.exp(p["a_log"])                               # (Di,N)

    if chunk:
        # padded tail positions: dt=0 ⇒ abar=1, bx=0 — identity steps,
        # so the carry-out equals the state at the last valid token
        dt = jnp.where(valid[..., None], dt, 0.0)
        y, last_state = _mamba_ssm_scan(
            dt, xc, b, c, a, init=ssm0.astype(jnp.float32))
        new_cache = dict(cache)
        new_cache["conv"] = jax.lax.dynamic_update_slice_in_dim(
            cache["conv"], new_conv.astype(cache["conv"].dtype),
            slot, axis=0)
        new_cache["ssm"] = jax.lax.dynamic_update_slice_in_dim(
            cache["ssm"], last_state.astype(cache["ssm"].dtype),
            slot, axis=0)
    elif cache is None or prefill:
        y, last_state = _mamba_ssm_scan(dt, xc, b, c, a)
        new_cache = None
        if prefill:
            new_cache = {"conv": new_conv.astype(cache["conv"].dtype),
                         "ssm": last_state.astype(cache["ssm"].dtype)}
    else:
        abar = jnp.exp(dt[:, 0, :, None] * a[None])        # (B,Di,N)
        bx = (dt[:, 0] * xc[:, 0])[..., None] * b[:, 0, None, :]
        ssm = abar * cache["ssm"].astype(jnp.float32) + bx  # (B,Di,N)
        y = jnp.einsum("bdn,bn->bd", ssm, c[:, 0])[:, None, :]
        new_conv = new_conv.astype(cache["conv"].dtype)
        ssm = ssm.astype(cache["ssm"].dtype)
        if paged is not None:
            # continuous batching: pos (B,) marks live decode slots;
            # idle/prefilling slots keep their state untouched (pages
            # get this for free via the scrap page — state rows can't)
            act = pos >= 0
            new_conv = jnp.where(act[:, None, None], new_conv,
                                 cache["conv"])
            ssm = jnp.where(act[:, None, None], ssm, cache["ssm"])
        new_cache = {"conv": new_conv, "ssm": ssm}

    y = y + p["d"].astype(jnp.float32)[None, None] * xc
    y = y * jax.nn.silu(z.astype(jnp.float32))
    out = linear(y.astype(dtype), p["out_proj"], caps=caps,
                 name=f"{prefix}out_proj")
    return h + out.astype(h.dtype), new_cache


def mamba_cache_init(cfg: ArchConfig, batch, dtype):
    return {
        "conv": jnp.zeros((batch, cfg.ssm_conv - 1, cfg.d_inner), dtype),
        "ssm": jnp.zeros((batch, cfg.d_inner, cfg.ssm_state), jnp.float32),
    }


# ======================================================================
# xLSTM mLSTM (matrix memory, parallel form)
# ======================================================================
# the quadratic parallel form materializes (B,T,S,NH) decay/score
# matrices — 537GB at 32k — so long sequences switch to the CHUNKWISE
# form (intra-chunk parallel + inter-chunk recurrent state), the same
# strategy as the xLSTM paper's kernels. Threshold shared with attention.
MLSTM_CHUNK_THRESHOLD = 8192
MLSTM_CHUNK = 1024


def _mlstm_chunkwise(q, k, v, logi, logf, chunk, init=None):
    """Chunkwise-parallel stabilized mLSTM.

    q (pre-scaled), k, v: (B, T, NH, hd) f32; logi, logf: (B, T, NH).
    ``init``: carry-in (c0, n0, m0) — fresh zero state when None.
    Returns h: (B, T, NH, hd) f32.  Matches the quadratic parallel form
    (tested) at O(T·chunk) memory.
    """
    b, t, nh, hd = q.shape
    assert t % chunk == 0
    nck = t // chunk

    def to_chunks(x):
        return jnp.moveaxis(
            x.reshape(b, nck, chunk, *x.shape[2:]), 1, 0)

    qs, ks, vs = to_chunks(q), to_chunks(k), to_chunks(v)
    lis, lfs = to_chunks(logi), to_chunks(logf)

    def body(carry, xs):
        c0, n0, m0 = carry            # (b,nh,hd,hd), (b,nh,hd), (b,nh)
        qc, kc, vc, lic, lfc = xs     # (b,C,nh,hd) / (b,C,nh)
        fcum = jnp.cumsum(lfc, axis=1)                  # (b,C,nh)
        # intra-chunk decay D_ts = F_t − F_s + i_s  (s ≤ t)
        dmat = (fcum[:, :, None, :] - fcum[:, None, :, :]
                + lic[:, None, :, :])                   # (b,t,s,nh)
        tri = jnp.tril(jnp.ones((chunk, chunk), bool))
        dmat = jnp.where(tri[None, :, :, None], dmat, -jnp.inf)
        a_max = jnp.max(dmat, axis=2)                   # (b,C,nh)
        m_inter = fcum + m0[:, None, :]                 # (b,C,nh)
        m_t = jnp.maximum(a_max, m_inter)
        msafe = jnp.where(jnp.isfinite(m_t), m_t, 0.0)
        intra = jnp.exp(dmat - msafe[:, :, None, :])
        scores = jnp.einsum("bthd,bshd->btsh", qc, kc) * intra
        w_inter = jnp.exp(m_inter - msafe)              # (b,C,nh)
        num = (jnp.einsum("btsh,bshd->bthd", scores, vc)
               + w_inter[..., None]
               * jnp.einsum("bhde,bthd->bthe", c0, qc))
        lsum = (jnp.sum(scores, axis=2)
                + w_inter * jnp.einsum("bhd,bthd->bth", n0, qc))
        h = num / jnp.maximum(
            jnp.abs(lsum), jnp.exp(-msafe))[..., None]
        # inter-chunk state update (decay the carry by the whole chunk,
        # absorb this chunk's keys at their remaining decay)
        f_all = fcum[:, -1, :]                          # (b,nh)
        s_max = jnp.max(f_all[:, None, :] - fcum + lic, axis=1)
        m1 = jnp.maximum(f_all + m0, s_max)
        wts = jnp.exp(f_all[:, None, :] - fcum + lic - m1[:, None, :])
        decay = jnp.exp(f_all + m0 - m1)                # (b,nh)
        c1 = (decay[..., None, None] * c0
              + jnp.einsum("bch,bchd,bche->bhde", wts, kc, vc))
        n1 = decay[..., None] * n0 + jnp.einsum("bch,bchd->bhd", wts, kc)
        return (c1, n1, m1), h

    if init is None:
        init = (jnp.zeros((b, nh, hd, hd), jnp.float32),
                jnp.zeros((b, nh, hd), jnp.float32),
                jnp.full((b, nh), -1e30, jnp.float32))
    final, hs = jax.lax.scan(body, init, (qs, ks, vs, lis, lfs))
    return jnp.moveaxis(hs, 0, 1).reshape(b, t, nh, hd), final


def mlstm_init(key, cfg: ArchConfig, dtype) -> Params:
    d = cfg.d_model
    di = cfg.mlstm_proj * d
    nh = cfg.num_heads
    ks = jax.random.split(key, 6)
    return {
        "ln": rmsnorm_init(d, dtype),
        "wq": _dense_init(ks[0], d, di, dtype),
        "wk": _dense_init(ks[1], d, di, dtype),
        "wv": _dense_init(ks[2], d, di, dtype),
        "wi": _dense_init(ks[3], d, nh, jnp.float32, scale=0.1 / math.sqrt(d)),
        "wf": _dense_init(ks[4], d, nh, jnp.float32, scale=0.1 / math.sqrt(d)),
        "bi": jnp.zeros((nh,), jnp.float32),
        "bf": jnp.full((nh,), 3.0, jnp.float32),   # forget-open init
        "wo": _dense_init(
            ks[5], di, d, dtype, scale=1.0 / math.sqrt(di * 2 * cfg.num_layers)),
    }


def mlstm_apply(
    p: Params,
    h: jax.Array,
    cfg: ArchConfig,
    *,
    caps=None,
    cache: Optional[Params] = None,
    pos=None,
    prefix: str = "mlstm.",
    paged: Optional[Params] = None,
) -> Tuple[jax.Array, Optional[Params]]:
    """Stabilized mLSTM. cache = {"c": (B,NH,hd,hd), "n": (B,NH,hd),
    "m": (B,NH)} for decode.

    Chunked prefill (``paged`` given, T>1): one chunkwise-parallel step
    over the chunk seeded with slot ``paged["slot"]``'s pooled carry;
    padded tail positions contribute nothing (input gate masked to
    exp(-inf)=0, forget gate to log 1 = 0)."""
    d = cfg.d_model
    di = cfg.mlstm_proj * d
    nh = cfg.num_heads
    hd = di // nh
    bsz, t, _ = h.shape
    h_in = rmsnorm(p["ln"], h, cfg.norm_eps)
    q = linear(h_in, p["wq"], caps=caps, name=f"{prefix}wq")
    k = linear(h_in, p["wk"], caps=caps, name=f"{prefix}wk")
    v = linear(h_in, p["wv"], caps=caps, name=f"{prefix}wv")
    q = q.reshape(bsz, t, nh, hd).astype(jnp.float32) / math.sqrt(hd)
    k = k.reshape(bsz, t, nh, hd).astype(jnp.float32)
    v = v.reshape(bsz, t, nh, hd).astype(jnp.float32)
    h32 = h_in.astype(jnp.float32)
    logi = h32 @ p["wi"] + p["bi"]                          # (B,T,NH)
    logf = jax.nn.log_sigmoid(h32 @ p["wf"] + p["bf"])      # (B,T,NH)

    if cache is not None and t > 1 and paged is not None:
        slot = paged["slot"]
        c0 = jax.lax.dynamic_slice_in_dim(cache["c"], slot, 1, axis=0)
        n0 = jax.lax.dynamic_slice_in_dim(cache["n"], slot, 1, axis=0)
        m0 = jax.lax.dynamic_slice_in_dim(cache["m"], slot, 1, axis=0)
        tpos = paged["start"] + jnp.arange(t, dtype=jnp.int32)
        valid = (tpos < paged["lengths"][0])[None, :, None]  # (1,T,1)
        logi = jnp.where(valid, logi, -jnp.inf)
        logf = jnp.where(valid, logf, 0.0)
        y, (c1, n1, m1) = _mlstm_chunkwise(
            q, k, v, logi, logf, t,
            init=(c0.astype(jnp.float32), n0.astype(jnp.float32), m0))
        new_cache = dict(cache)
        new_cache["c"] = jax.lax.dynamic_update_slice_in_dim(
            cache["c"], c1.astype(cache["c"].dtype), slot, axis=0)
        new_cache["n"] = jax.lax.dynamic_update_slice_in_dim(
            cache["n"], n1.astype(cache["n"].dtype), slot, axis=0)
        new_cache["m"] = jax.lax.dynamic_update_slice_in_dim(
            cache["m"], m1, slot, axis=0)
    elif cache is None or t > 1:
        chunked = t > MLSTM_CHUNK_THRESHOLD and t % MLSTM_CHUNK == 0
        if chunked:
            from repro.models.layers import SEQ_PAR_ATTN, _dp_only_constrain
            if SEQ_PAR_ATTN:
                # nh=4 < TP ⇒ GSPMD shards head_dim and the chunk scan
                # all-reduces score partials per step (the GQA
                # pathology); the mixer is tiny — replicate it over
                # `model` within each data shard (one gather per layer)
                q = _dp_only_constrain(q)
                k = _dp_only_constrain(k)
                v = _dp_only_constrain(v)
                logi = _dp_only_constrain(logi)
                logf = _dp_only_constrain(logf)
            y, (cT_, nT_, mT_) = _mlstm_chunkwise(
                q, k, v, logi, logf, MLSTM_CHUNK)
        else:
            # parallel form: D_ts = exp(F_t − F_s + logi_s), F = cumsum
            fcum = jnp.cumsum(logf, axis=1)                 # (B,T,NH)
            dmat = (fcum[:, :, None, :] - fcum[:, None, :, :]
                    + logi[:, None, :, :])                  # (B,T,S,NH)
            tri = jnp.tril(jnp.ones((t, t), bool))
            dmat = jnp.where(tri[None, :, :, None], dmat, -jnp.inf)
            m = jnp.max(dmat, axis=2, keepdims=True)        # (B,T,1,NH)
            dstab = jnp.exp(dmat - m)                       # (B,T,S,NH)
            scores = jnp.einsum("bthd,bshd->btsh", q, k) * dstab
            norm = jnp.maximum(
                jnp.abs(jnp.sum(scores, axis=2)), jnp.exp(-m[:, :, 0, :]))
            y = jnp.einsum("btsh,bshd->bthd", scores, v) / norm[..., None]
        new_cache = None
        if cache is not None and chunked:
            new_cache = {"c": cT_.astype(cache["c"].dtype),
                         "n": nT_.astype(cache["n"].dtype), "m": mT_}
        elif cache is not None:
            # prefill: summarize the prompt into the recurrent state
            dlast = fcum[:, -1:, :] - fcum + logi           # (B,T,NH)
            mT = jnp.max(dlast, axis=1)                     # (B,NH)
            wgt = jnp.exp(dlast - mT[:, None, :])           # (B,T,NH)
            cT = jnp.einsum("bth,bthd,bthe->bhde", wgt, k, v)
            nT = jnp.einsum("bth,bthd->bhd", wgt, k)
            new_cache = {"c": cT.astype(cache["c"].dtype),
                         "n": nT.astype(cache["n"].dtype), "m": mT}
    else:
        c0 = cache["c"].astype(jnp.float32)                 # (B,NH,hd,hd)
        n0 = cache["n"].astype(jnp.float32)                 # (B,NH,hd)
        m0 = cache["m"]                                     # (B,NH)
        lf, li = logf[:, 0], logi[:, 0]                     # (B,NH)
        m1 = jnp.maximum(lf + m0, li)
        fw = jnp.exp(lf + m0 - m1)[..., None]
        iw = jnp.exp(li - m1)[..., None]
        k1, v1, q1 = k[:, 0], v[:, 0], q[:, 0]              # (B,NH,hd)
        c1 = fw[..., None] * c0 + iw[..., None] * (
            k1[..., :, None] * v1[..., None, :])            # (B,NH,hd,hd)
        n1 = fw * n0 + iw * k1
        num = jnp.einsum("bhde,bhd->bhe", c1, q1)
        den = jnp.maximum(
            jnp.abs(jnp.einsum("bhd,bhd->bh", n1, q1)), jnp.exp(-m1))
        y = (num / den[..., None])[:, None]                 # (B,1,NH,hd)
        c1 = c1.astype(cache["c"].dtype)
        n1 = n1.astype(cache["n"].dtype)
        if paged is not None:
            # continuous batching: freeze idle slot rows (pos < 0)
            act = pos >= 0
            c1 = jnp.where(act[:, None, None, None], c1, cache["c"])
            n1 = jnp.where(act[:, None, None], n1, cache["n"])
            m1 = jnp.where(act[:, None], m1, cache["m"])
        new_cache = {"c": c1, "n": n1, "m": m1}

    y = y.reshape(bsz, t, di).astype(h.dtype)
    out = linear(y, p["wo"], caps=caps, name=f"{prefix}wo")
    return h + out, new_cache


def mlstm_cache_init(cfg: ArchConfig, batch, dtype):
    di = cfg.mlstm_proj * cfg.d_model
    nh = cfg.num_heads
    hd = di // nh
    return {
        "c": jnp.zeros((batch, nh, hd, hd), jnp.float32),
        "n": jnp.zeros((batch, nh, hd), jnp.float32),
        "m": jnp.full((batch, nh), -1e30, jnp.float32),
    }


# ======================================================================
# xLSTM sLSTM (scalar memory, sequential recurrence)
# ======================================================================
def slstm_init(key, cfg: ArchConfig, dtype) -> Params:
    d = cfg.d_model
    nh = cfg.num_heads
    hd = d // nh
    ks = jax.random.split(key, 9)
    rscale = 1.0 / math.sqrt(hd)

    def rmat(k):
        return (jax.random.normal(k, (nh, hd, hd), jnp.float32)
                * rscale).astype(dtype)

    return {
        "ln": rmsnorm_init(d, dtype),
        "wz": _dense_init(ks[0], d, d, dtype),
        "wi": _dense_init(ks[1], d, d, dtype),
        "wf": _dense_init(ks[2], d, d, dtype),
        "wo_gate": _dense_init(ks[3], d, d, dtype),
        "r_z": rmat(ks[4]),
        "r_i": rmat(ks[5]),
        "r_f": rmat(ks[6]),
        "r_o": rmat(ks[7]),
        "bf": jnp.full((d,), 3.0, jnp.float32),
        "wo": _dense_init(
            ks[8], d, d, dtype, scale=1.0 / math.sqrt(d * 2 * cfg.num_layers)),
    }


def _slstm_cell(p, zx, ix, fx, ox, state, nh, hd):
    """One sLSTM step. zx/ix/fx/ox: (B, D) pre-activations from inputs;
    state = (c, n, hprev, m), each (B, D) f32."""
    c0, n0, h0, m0 = state
    hh = h0.reshape(h0.shape[0], nh, hd)

    def rec(r):
        return jnp.einsum("bhd,hde->bhe", hh, r.astype(jnp.float32)).reshape(
            h0.shape[0], nh * hd)

    z = jnp.tanh(zx + rec(p["r_z"]))
    logi = ix + rec(p["r_i"])
    logf = jax.nn.log_sigmoid(fx + rec(p["r_f"]) + p["bf"][None])
    o = jax.nn.sigmoid(ox + rec(p["r_o"]))
    m1 = jnp.maximum(logf + m0, logi)
    iw = jnp.exp(logi - m1)
    fw = jnp.exp(logf + m0 - m1)
    c1 = fw * c0 + iw * z
    n1 = jnp.maximum(fw * n0 + iw, 1.0)
    h1 = o * c1 / n1
    return (c1, n1, h1, m1)


def slstm_apply(
    p: Params,
    h: jax.Array,
    cfg: ArchConfig,
    *,
    caps=None,
    cache: Optional[Params] = None,
    pos=None,
    prefix: str = "slstm.",
    paged: Optional[Params] = None,
) -> Tuple[jax.Array, Optional[Params]]:
    """Sequential sLSTM over T (lax.scan); decode consumes/updates cache
    {"c","n","h","m"} each (B, D) f32.

    Chunked prefill (``paged`` given, T>1): the scan carries on from
    slot ``paged["slot"]``'s pooled state; padded tail steps keep the
    state unchanged (per-step where-select), so the carry-out is the
    state at the last valid token."""
    d = cfg.d_model
    nh = cfg.num_heads
    hd = d // nh
    bsz, t, _ = h.shape
    h_in = rmsnorm(p["ln"], h, cfg.norm_eps)
    zx = linear(h_in, p["wz"], caps=caps, name=f"{prefix}wz").astype(jnp.float32)
    ix = linear(h_in, p["wi"], caps=caps, name=f"{prefix}wi").astype(jnp.float32)
    fx = linear(h_in, p["wf"], caps=caps, name=f"{prefix}wf").astype(jnp.float32)
    ox = linear(h_in, p["wo_gate"], caps=caps,
                name=f"{prefix}wo_gate").astype(jnp.float32)

    if cache is not None and t > 1 and paged is not None:
        slot = paged["slot"]
        state = tuple(
            jax.lax.dynamic_slice_in_dim(cache[k_], slot, 1, axis=0)
            for k_ in "cnhm")
        tpos = paged["start"] + jnp.arange(t, dtype=jnp.int32)
        valid_t = tpos < paged["lengths"][0]                # (T,)

        def step(state, xs):
            *gates, ok = xs
            st = _slstm_cell(p, *gates, state, nh, hd)
            st = tuple(jnp.where(ok, n_, o_) for n_, o_ in zip(st, state))
            return st, st[2]

        final, ys = jax.lax.scan(
            step, state,
            (zx.swapaxes(0, 1), ix.swapaxes(0, 1),
             fx.swapaxes(0, 1), ox.swapaxes(0, 1), valid_t))
        y = ys.swapaxes(0, 1)                               # (B,T,D)
        new_cache = dict(cache)
        for i, k_ in enumerate("cnhm"):
            new_cache[k_] = jax.lax.dynamic_update_slice_in_dim(
                cache[k_], final[i].astype(cache[k_].dtype), slot, axis=0)
    elif cache is None or t > 1:
        if cache is None:
            state = tuple(
                jnp.zeros((bsz, d), jnp.float32) if i != 3
                else jnp.full((bsz, d), -1e30, jnp.float32) for i in range(4))
        else:
            state = (cache["c"], cache["n"], cache["h"], cache["m"])

        def step(state, xs):
            st = _slstm_cell(p, *xs, state, nh, hd)
            return st, st[2]

        final, ys = jax.lax.scan(
            step, state,
            (zx.swapaxes(0, 1), ix.swapaxes(0, 1),
             fx.swapaxes(0, 1), ox.swapaxes(0, 1)))
        y = ys.swapaxes(0, 1)                               # (B,T,D)
        new_cache = None
        if cache is not None:
            new_cache = {"c": final[0], "n": final[1],
                         "h": final[2], "m": final[3]}
    else:
        state = (cache["c"], cache["n"], cache["h"], cache["m"])
        st = _slstm_cell(p, zx[:, 0], ix[:, 0], fx[:, 0], ox[:, 0],
                         state, nh, hd)
        y = st[2][:, None]
        if paged is not None:
            # continuous batching: freeze idle slot rows (pos < 0)
            act = (pos >= 0)[:, None]
            st = tuple(jnp.where(act, n_, o_) for n_, o_ in zip(st, state))
        new_cache = {"c": st[0], "n": st[1], "h": st[2], "m": st[3]}

    out = linear(y.astype(h.dtype), p["wo"], caps=caps, name=f"{prefix}wo")
    return h + out, new_cache


def slstm_cache_init(cfg: ArchConfig, batch, dtype):
    d = cfg.d_model
    return {
        "c": jnp.zeros((batch, d), jnp.float32),
        "n": jnp.zeros((batch, d), jnp.float32),
        "h": jnp.zeros((batch, d), jnp.float32),
        "m": jnp.full((batch, d), -1e30, jnp.float32),
    }
