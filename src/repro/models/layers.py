"""Layer library: norms, rope, GQA attention, gated MLPs, embeddings.

Conventions:
  - params are plain nested dicts; linear kernels are stored (in, out);
  - every apply takes ``caps``: ``None`` for the fast path, or a dict that
    collects each linear's INPUT under the linear's name (the pruning
    engine's calibration capture — see core.calibration);
  - hidden states are (B, T, D); attention caches are (B, S_max, KV, hd).
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.base import ArchConfig

Params = Dict[str, Any]


# ----------------------------------------------------------------------
# Param init helpers
# ----------------------------------------------------------------------
def _dense_init(key, d_in, d_out, dtype, scale=None):
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale).astype(dtype)


def linear(x, w, b=None, *, caps=None, name="", activation=None):
    """y = act(x @ w + b), recording the input under ``name`` when
    capturing.

    ``w`` may be a 2:4-packed dict {"vals", "idx"} (serve.sparse) — then
    the matmul dispatches through kernels.ops.nm_matmul: the jnp
    decompress-oracle on CPU, the nm_spmm Pallas kernel on TPU (which
    decompresses in VMEM and runs a dense MXU matmul off half the weight
    HBM traffic); ``b``/``activation`` ride along as the kernel's fused
    decode epilogue instead of separate HBM-round-trip ops.
    """
    if caps is not None and name:
        caps[name] = x
    if isinstance(w, dict):
        from repro.kernels import ops as _kops
        return _kops.nm_matmul(x, w["vals"], w["idx"], b,
                               activation=activation, out_dtype=x.dtype)
    y = x @ w.astype(x.dtype)
    if b is not None:
        y = y + b.astype(y.dtype)
    if activation is not None:
        from repro.kernels.ref import activate
        y = activate(y, activation)
    return y


# ----------------------------------------------------------------------
# Norms / rope
# ----------------------------------------------------------------------
def rmsnorm_init(d, dtype):
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(p, x, eps=1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)


def rope(x, positions, theta):
    """Rotary embedding. x: (..., T, n, hd); positions: (..., T)."""
    hd = x.shape[-1]
    half = hd // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., :, None].astype(jnp.float32) * freq  # (..., T, half)
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    sin = sin[..., :, None, :]  # broadcast over heads
    cos = cos[..., :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    return jnp.concatenate([y1, y2], axis=-1).astype(x.dtype)


# ----------------------------------------------------------------------
# Attention block (GQA, optional qk-norm / bias / sliding window)
# ----------------------------------------------------------------------
def attn_init(key, cfg: ArchConfig, dtype) -> Params:
    hd, h, kv, d = cfg.hd, cfg.num_heads, cfg.num_kv_heads, cfg.d_model
    ks = jax.random.split(key, 6)
    p = {
        "ln": rmsnorm_init(d, dtype),
        "wq": _dense_init(ks[0], d, h * hd, dtype),
        "wk": _dense_init(ks[1], d, kv * hd, dtype),
        "wv": _dense_init(ks[2], d, kv * hd, dtype),
        "wo": _dense_init(ks[3], h * hd, d, dtype,
                          scale=1.0 / math.sqrt(h * hd * 2 * cfg.num_layers)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), dtype)
        p["bk"] = jnp.zeros((kv * hd,), dtype)
        p["bv"] = jnp.zeros((kv * hd,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = rmsnorm_init(hd, dtype)
        p["k_norm"] = rmsnorm_init(hd, dtype)
    return p


def _qkv(p, h_in, cfg: ArchConfig, positions, caps, prefix,
         seq_par_ok: bool = True):
    b, t, _ = h_in.shape
    hd, nh, kv = cfg.hd, cfg.num_heads, cfg.num_kv_heads
    q = linear(h_in, p["wq"], p.get("bq"), caps=caps, name=f"{prefix}wq")
    k = linear(h_in, p["wk"], p.get("bk"), caps=caps, name=f"{prefix}wk")
    v = linear(h_in, p["wv"], p.get("bv"), caps=caps, name=f"{prefix}wv")
    q = q.reshape(b, t, nh, hd)
    k = k.reshape(b, t, kv, hd)
    v = v.reshape(b, t, kv, hd)
    if SEQ_PAR_ATTN and seq_par_ok and t >= SEQ_PAR_MIN_T:
        # reshard head→sequence parallelism BEFORE rope/qk-norm, so the
        # per-position elementwise ops never touch head-sharded tensors
        q = _seq_constrain(q)
        k = _seq_constrain(k)
        v = _seq_constrain(v)
    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q, cfg.norm_eps)
        k = rmsnorm(p["k_norm"], k, cfg.norm_eps)
    if positions is not None:  # rope
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def _sdpa(q, k, v, mask, nh, kv):
    """Grouped scaled-dot-product attention.

    q: (B,T,H,hd), k/v: (B,S,KV,hd), mask: broadcastable to (B,KV,G,T,S).
    """
    b, t, _, hd = q.shape
    g = nh // kv
    qg = q.reshape(b, t, kv, g, hd)
    scores = jnp.einsum("btkgd,bskd->bkgts", qg, k).astype(jnp.float32)
    scores = scores / math.sqrt(hd)
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgts,bskd->btkgd", probs, v)
    return out.reshape(b, t, nh * hd)


# full-sequence attention switches to the online-softmax path when the
# score matrix would exceed this many elements per (T,S) pair — 32k
# prefill would otherwise materialize T² scores (flash-attention
# algorithm, expressed as a lax.scan over KV chunks so it stays
# SPMD-partitionable in the dry-run; the Pallas kernel is the TPU
# drop-in for the same math).
ONLINE_ATTN_THRESHOLD = 8192
ONLINE_ATTN_CHUNK = 1024
# §Perf iteration (beyond-paper): sliding-window layers compute only the
# (chunk, chunk+window) band instead of the full (T, S) score matrix —
# T·(chunk+w) score work, a ~16× cut for gemma3's 1024-window locals at
# 32k. Off by default so the baseline roofline reflects the naive path.
BANDED_LOCAL_ATTN = False

# §Perf iteration (beyond-paper): sequence-parallel long attention.
# With GQA kv-heads < TP degree, GSPMD shards q/k/v on head_dim and the
# score contraction emits an all-reduce INSIDE the KV-chunk scan —
# ×(chunks × layers) on the wire (the dominant baseline cost at 32k
# prefill). Constraining q/k/v to be sharded on the SEQUENCE dim over
# the model axis makes every score matmul local; the only traffic is
# streaming each (small) KV chunk to all shards.
SEQ_PAR_ATTN = False
SEQ_PAR_MIN_T = 2048      # apply to train-length sequences too


def _seq_constrain(x, seq_dim=1):
    """Shard dim0 over the data axes and ``seq_dim`` over model (active
    mesh only — no-op in single-device tests)."""
    from repro.dist.api import constrain, current_ctx
    ctx = current_ctx()
    if ctx is None:
        return x
    tp = ctx.mesh.shape[ctx.tp_axis]
    if x.shape[seq_dim] % tp or x.shape[0] % ctx.dp:
        return x
    spec = [None] * x.ndim
    spec[0] = ctx.dp_axes if len(ctx.dp_axes) > 1 else ctx.dp_axes[0]
    spec[seq_dim] = ctx.tp_axis
    return constrain(x, *spec)


def _dp_only_constrain(x):
    """Batch-sharded, replicated over model — one explicit all-gather."""
    from repro.dist.api import constrain, current_ctx
    ctx = current_ctx()
    if ctx is None or x.shape[0] % ctx.dp:
        return x
    spec = [None] * x.ndim
    spec[0] = ctx.dp_axes if len(ctx.dp_axes) > 1 else ctx.dp_axes[0]
    return constrain(x, *spec)


def _sdpa_banded(q, k, v, nh, kv, window, chunk=ONLINE_ATTN_CHUNK):
    """Windowed causal grouped attention over the diagonal band only."""
    b, t, _, hd = q.shape
    g = nh // kv
    assert t % chunk == 0, f"T={t} % chunk={chunk}"
    nq = t // chunk
    band = chunk + window
    if band >= t:            # window covers everything — no banding win
        mask = causal_mask(t, t, window)
        return _sdpa(q, k, v, mask, nh, kv)
    # banded layers: leave sharding entirely to GSPMD — q seq-sharded
    # re-gathers all of q per chunk step (5×275GB measured), and even
    # explicit once-per-layer K/V gathers cost 45×537MB; head-parallel
    # banded attention needs neither (gemma3: 16 q-heads = TP)
    qg = (q.reshape(b, t, kv, g, hd).astype(jnp.float32)
          / math.sqrt(hd))
    qs = jnp.moveaxis(qg.reshape(b, nq, chunk, kv, g, hd), 1, 0)

    def body(_, xs):
        qc, ci = xs                       # qc: (b, chunk, kv, g, hd)
        start = jnp.maximum(ci * chunk - window, 0)
        kc = jax.lax.dynamic_slice_in_dim(k, start, band, axis=1)
        vc = jax.lax.dynamic_slice_in_dim(v, start, band, axis=1)
        kpos = start + jnp.arange(band, dtype=jnp.int32)
        qpos = ci * chunk + jnp.arange(chunk, dtype=jnp.int32)
        ok = ((kpos[None, :] <= qpos[:, None])
              & (kpos[None, :] > qpos[:, None] - window))
        sc = jnp.einsum("bckgd,bskd->bkgcs", qc, kc.astype(jnp.float32))
        sc = jnp.where(ok[None, None, None], sc, -1e30)
        p = jax.nn.softmax(sc, axis=-1)
        oc = jnp.einsum("bkgcs,bskd->bckgd", p, vc.astype(jnp.float32))
        return None, oc

    _, outs = jax.lax.scan(
        body, None, (qs, jnp.arange(nq, dtype=jnp.int32)))
    out = jnp.moveaxis(outs, 0, 1)        # (b, nq, chunk, kv, g, hd)
    return out.reshape(b, t, nh * hd).astype(v.dtype)


def _sdpa_online(q, k, v, nh, kv, *, window=None, prefix_len=None,
                 chunk=ONLINE_ATTN_CHUNK):
    """Causal grouped attention via online softmax over KV chunks.

    Same semantics as _sdpa with a causal (+window/prefix) mask, but
    peak memory is O(T·chunk) instead of O(T·S).
    """
    b, t, _, hd = q.shape
    g = nh // kv
    s = k.shape[1]
    assert s % chunk == 0, f"S={s} not divisible by chunk={chunk}"
    nck = s // chunk
    if SEQ_PAR_ATTN:
        q = _seq_constrain(q)
        # gather K/V across the model axis ONCE per layer (explicit AG);
        # otherwise the chunk scan's dynamic-slice over a seq-sharded
        # operand re-gathers the full K/V every iteration (measured:
        # 2×268MB × chunks × layers — the dominant baseline wire cost)
        k = _dp_only_constrain(k)
        v = _dp_only_constrain(v)
    qg = (q.reshape(b, t, kv, g, hd).astype(jnp.float32)
          / math.sqrt(hd))
    ks = jnp.moveaxis(k.reshape(b, nck, chunk, kv, hd), 1, 0)
    vs = jnp.moveaxis(v.reshape(b, nck, chunk, kv, hd), 1, 0)
    qpos = jnp.arange(t, dtype=jnp.int32)

    def body(carry, xs):
        m, lsum, acc = carry
        kc, vc, ci = xs
        kpos = ci * chunk + jnp.arange(chunk, dtype=jnp.int32)
        ok = kpos[None, :] <= qpos[:, None]                  # (t, chunk)
        if window is not None:
            ok &= kpos[None, :] > qpos[:, None] - window
        if prefix_len is not None:
            ok |= kpos[None, :] < prefix_len
        sc = jnp.einsum("btkgd,bckd->bkgtc", qg,
                        kc.astype(jnp.float32))              # (b,kv,g,t,c)
        sc = jnp.where(ok[None, None, None], sc, -jnp.inf)
        m_cur = jnp.max(sc, axis=-1)
        m_new = jnp.maximum(m, m_cur)
        # avoid NaN from (-inf) - (-inf) on fully-masked rows
        msafe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(sc - msafe[..., None])
        alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - msafe), 0.0)
        lsum = lsum * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bkgtc,bckd->bkgtd", p, vc.astype(jnp.float32))
        return (m_new, lsum, acc), None

    m0 = jnp.full((b, kv, g, t), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, kv, g, t), jnp.float32)
    a0 = jnp.zeros((b, kv, g, t, hd), jnp.float32)
    if SEQ_PAR_ATTN:
        # keep the online-softmax carries sequence-sharded too, or XLA
        # reshards (b,kv,g,t[,hd]) between chunk steps inside the scan
        m0 = _seq_constrain(m0, seq_dim=3)
        l0 = _seq_constrain(l0, seq_dim=3)
        a0 = _seq_constrain(a0, seq_dim=3)
    (m, lsum, acc), _ = jax.lax.scan(
        body, (m0, l0, a0),
        (ks, vs, jnp.arange(nck, dtype=jnp.int32)))
    out = acc / jnp.maximum(lsum, 1e-30)[..., None]
    out = jnp.moveaxis(out, 3, 1)                            # (b,t,kv,g,hd)
    return out.reshape(b, t, nh * hd).astype(v.dtype)


def causal_mask(t, s, window: Optional[int] = None, offset: int = 0,
                prefix_len: Optional[int] = None):
    """(T,S) boolean mask. offset = absolute position of query 0;
    prefix_len = leading bidirectional prefix (VLM prefix-LM)."""
    qpos = jnp.arange(t)[:, None] + offset
    kpos = jnp.arange(s)[None, :]
    ok = kpos <= qpos
    if window is not None:
        ok &= kpos > qpos - window
    if prefix_len is not None:
        ok |= kpos < prefix_len
    return ok[None, None, None]  # (1,1,1,T,S)


def _paged_write(pages: jax.Array, vals: jax.Array,
                 flat_idx: jax.Array) -> jax.Array:
    """Scatter K/V rows into a paged pool.

    pages: (P, page_size, KV, hd); vals: (..., KV, hd) with leading dims
    matching flat_idx: (...,) flat token slots (page*page_size+offset).
    Duplicate indices (everything clamped to the scrap page 0) are
    garbage-on-garbage — never read back because attention masks by
    length.
    """
    p_, ps_, kvh, hd = pages.shape
    flat = pages.reshape(p_ * ps_, kvh, hd)
    flat = flat.at[flat_idx.reshape(-1)].set(
        vals.reshape(-1, kvh, hd).astype(pages.dtype))
    return flat.reshape(p_, ps_, kvh, hd)


def _paged_write_q8(pages: jax.Array, scales: jax.Array, vals: jax.Array,
                    flat_idx: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Quantizing twin of :func:`_paged_write` for int8 KV pages
    (ServeConfig.kv_dtype="int8"): each written row quantizes per
    (token, kv-head) — scale = amax(|row|)/127 over head_dim — and the
    scale scatters into the pool's (P, page_size, KV) f32 scale leaf at
    the same flat slot, so dequant at the gather needs no second
    lookup structure."""
    p_, ps_, kvh, hd = pages.shape
    rows = vals.reshape(-1, kvh, hd).astype(jnp.float32)
    s = jnp.max(jnp.abs(rows), axis=-1) / 127.0            # (R, KV)
    q = jnp.round(rows / jnp.maximum(s, 1e-8)[..., None]).astype(jnp.int8)
    flat = pages.reshape(p_ * ps_, kvh, hd)
    flat = flat.at[flat_idx.reshape(-1)].set(q)
    sflat = scales.reshape(p_ * ps_, kvh)
    sflat = sflat.at[flat_idx.reshape(-1)].set(s)
    return flat.reshape(p_, ps_, kvh, hd), sflat.reshape(p_, ps_, kvh)


def _paged_scatter(cache: Params, k: jax.Array, v: jax.Array,
                   flat: jax.Array) -> Params:
    """Scatter K/V rows into the paged pool leaves, quantizing on write
    when the cache carries scale leaves (int8 KV pages).  Returns the
    dict of updated leaves."""
    if "k_scale" not in cache:
        return {"k": _paged_write(cache["k"], k, flat),
                "v": _paged_write(cache["v"], v, flat)}
    kq, ks = _paged_write_q8(cache["k"], cache["k_scale"], k, flat)
    vq, vs = _paged_write_q8(cache["v"], cache["v_scale"], v, flat)
    return {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}


def attn_paged_cache_init(cfg: ArchConfig, num_pages: int, page_size: int,
                          dtype) -> Params:
    """Paged pool leaves.  int8 adds per-row f32 scale leaves alongside
    the quantized pages (quantize at the scatter, dequantize at the
    gather); any other dtype keeps the two-leaf layout byte-identical
    to the pre-ISSUE-9 tree."""
    kv, hd = cfg.num_kv_heads, cfg.hd
    cache = {
        "k": jnp.zeros((num_pages, page_size, kv, hd), dtype),
        "v": jnp.zeros((num_pages, page_size, kv, hd), dtype),
    }
    if jnp.dtype(dtype) == jnp.int8:
        cache["k_scale"] = jnp.zeros((num_pages, page_size, kv), jnp.float32)
        cache["v_scale"] = jnp.zeros((num_pages, page_size, kv), jnp.float32)
    return cache


def attn_apply(
    p: Params,
    h: jax.Array,
    cfg: ArchConfig,
    *,
    kind: str = "attn",
    caps=None,
    cache: Optional[Params] = None,
    pos=None,
    prefix: str = "attn.",
    cross_kv: Optional[Tuple[jax.Array, jax.Array]] = None,
    causal: bool = True,
    prefix_len: Optional[int] = None,
    paged: Optional[Params] = None,
    page_size: Optional[int] = None,
) -> Tuple[jax.Array, Optional[Params]]:
    """Pre-norm attention with residual. Returns (h_out, new_cache).

    Modes:
      full-sequence (cache=None): causal over T (optionally windowed);
                     ``causal=False`` = encoder self-attention;
                     ``prefix_len`` = bidirectional prefix (VLM prefix-LM);
      decode        (cache given): h is (B,1,D), writes K/V at ``pos`` and
                     attends over positions <= pos;
      cross         (cross_kv given): encoder-decoder cross attention —
                     no cache update, no rope, full visibility.

    Paged modes (``paged`` given — the continuous-batching serve runtime,
    docs/serving.md): ``cache`` holds (num_pages, page_size, KV, hd)
    pool leaves; ``paged["block_tables"]`` (B, P_max) maps each
    request's logical positions to physical pages.  Prefill additionally
    takes ``paged["lengths"]`` (padded prompt tails write to the scrap
    page 0); a chunked prefill (``paged["start"]`` given) writes the
    chunk's K/V at absolute positions ``start + arange(T)`` and attends
    over the *gathered slot context* — earlier chunks' keys read back
    from the pages — so a prompt of any length runs as fixed-size
    chunks through one jitted shape; decode takes per-request ``pos``
    (B,), -1 marking idle slots.
    """
    window = cfg.window if kind == "attn_local" else None
    b, t, _ = h.shape
    nh, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    h_in = rmsnorm(p["ln"], h, cfg.norm_eps)

    if cross_kv is not None:
        q = linear(h_in, p["wq"], p.get("bq"), caps=caps, name=f"{prefix}wq")
        q = q.reshape(b, t, nh, hd)
        k, v = cross_kv
        s = k.shape[1]
        mask = jnp.ones((1, 1, 1, t, s), bool)
        out = _sdpa(q, k, v, mask, nh, kv)
        y = linear(out, p["wo"], caps=caps, name=f"{prefix}wo")
        return h + y, cache

    if cache is not None and t > 1 and paged is not None \
            and paged.get("start") is not None:
        # chunked paged prefill (B=1 per request): one fixed-size token
        # chunk at absolute positions start..start+T-1.  The chunk's
        # K/V is scattered into the pages FIRST, then attention runs
        # over the gathered slot context — intra-chunk keys and earlier
        # chunks' keys both read back from the pool, so every chunk of
        # every prompt shares one jitted shape.
        lengths = paged["lengths"]                           # (B,)
        bt = paged["block_tables"]                           # (B, P_max)
        tpos = paged["start"] + jnp.arange(t, dtype=jnp.int32)
        positions = tpos[None, :]
        q, k, v = _qkv(p, h_in, cfg, positions, caps, prefix,
                       seq_par_ok=False)
        page = jnp.take_along_axis(
            bt, tpos[None, :] // page_size, axis=1)          # (B, T)
        flat = page * page_size + tpos[None, :] % page_size
        flat = jnp.where(tpos[None, :] < lengths[:, None], flat, 0)
        upd = _paged_scatter(cache, k, v, flat)
        s_len = bt.shape[1] * page_size
        kc = upd["k"][bt].reshape(b, s_len, kv, hd)
        vc = upd["v"][bt].reshape(b, s_len, kv, hd)
        if "k_scale" in upd:
            # int8 pages: dequantize the gathered slot context row-wise
            kc = kc.astype(jnp.float32) * upd["k_scale"][bt].reshape(
                b, s_len, kv)[..., None]
            vc = vc.astype(jnp.float32) * upd["v_scale"][bt].reshape(
                b, s_len, kv)[..., None]
        kpos = jnp.arange(s_len, dtype=jnp.int32)
        ok = kpos[None, None, :] <= positions[:, :, None]    # (B, T, S)
        if window is not None:
            ok &= kpos[None, None, :] > positions[:, :, None] - window
        out = _sdpa(q, kc, vc, ok[:, None, None], nh, kv)
        y = linear(out, p["wo"], caps=caps, name=f"{prefix}wo")
        new_cache = dict(cache)
        new_cache.update(upd)
        return h + y, new_cache

    if cache is None or t > 1:
        positions = jnp.arange(t)[None, :]
        banded = (BANDED_LOCAL_ATTN and causal and window is not None
                  and prefix_len is None and t > ONLINE_ATTN_THRESHOLD)
        q, k, v = _qkv(p, h_in, cfg, positions, caps, prefix,
                       seq_par_ok=not banded)
        if (BANDED_LOCAL_ATTN and causal and window is not None
                and prefix_len is None and t > ONLINE_ATTN_THRESHOLD):
            out = _sdpa_banded(q, k, v, nh, kv, window)
        elif causal and t > ONLINE_ATTN_THRESHOLD:
            out = _sdpa_online(q, k, v, nh, kv, window=window,
                               prefix_len=prefix_len)
        else:
            if SEQ_PAR_ATTN and t >= SEQ_PAR_MIN_T:
                # q rows stay seq-sharded; K/V gathered once per layer
                k = _dp_only_constrain(k)
                v = _dp_only_constrain(v)
            if causal:
                mask = causal_mask(t, t, window, prefix_len=prefix_len)
            else:
                mask = jnp.ones((1, 1, 1, t, t), bool)
            out = _sdpa(q, k, v, mask, nh, kv)
        y = linear(out, p["wo"], caps=caps, name=f"{prefix}wo")
        if cache is None:
            return h + y, None
        if paged is not None:
            # paged prefill: scatter the prompt's K/V into this request's
            # pages; padded tail positions (>= lengths) go to scrap page 0
            lengths = paged["lengths"]                       # (B,)
            bt = paged["block_tables"]                       # (B, P_max)
            tpos = jnp.arange(t, dtype=jnp.int32)
            page = jnp.take_along_axis(
                bt, tpos[None, :] // page_size, axis=1)      # (B, T)
            flat = page * page_size + tpos[None, :] % page_size
            flat = jnp.where(tpos[None, :] < lengths[:, None], flat, 0)
            new_cache = dict(cache)
            new_cache.update(_paged_scatter(cache, k, v, flat))
            return h + y, new_cache
        # prefill: write the prompt's K/V into cache[0:t]
        new_cache = dict(cache)
        new_cache["k"] = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, 0, 0, 0))
        new_cache["v"] = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, 0, 0, 0))
        return h + y, new_cache

    # decode: t == 1
    if paged is not None:
        # paged decode: per-request write position (pos (B,), -1 = idle
        # slot); block-table attention over the page pool
        from repro.kernels import ops as _kops

        bt = paged["block_tables"]                           # (B, P_max)
        wpos = jnp.maximum(pos, 0)
        positions = wpos[:, None]
        q, k1, v1 = _qkv(p, h_in, cfg, positions, caps, prefix)
        page = jnp.take_along_axis(
            bt, (wpos // page_size)[:, None], axis=1)[:, 0]  # (B,)
        flat = page * page_size + wpos % page_size
        flat = jnp.where(pos >= 0, flat, 0)                  # idle → scrap
        upd = _paged_scatter(cache, k1[:, 0], v1[:, 0], flat)
        lengths = jnp.maximum(pos + 1, 0)                    # idle → 0
        qg = q[:, 0].reshape(b, kv, nh // kv, hd)
        out = _kops.paged_attention(qg, upd["k"], upd["v"], bt, lengths,
                                    window=window,
                                    k_scale=upd.get("k_scale"),
                                    v_scale=upd.get("v_scale"))
        out = out.reshape(b, 1, nh * hd)
        y = linear(out, p["wo"], caps=caps, name=f"{prefix}wo")
        new_cache = dict(cache)
        new_cache.update(upd)
        return h + y, new_cache

    positions = jnp.full((b, t), pos, dtype=jnp.int32)
    q, k1, v1 = _qkv(p, h_in, cfg, positions, caps, prefix)
    k_cache = jax.lax.dynamic_update_slice(
        cache["k"], k1.astype(cache["k"].dtype), (0, pos, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(
        cache["v"], v1.astype(cache["v"].dtype), (0, pos, 0, 0))
    s = k_cache.shape[1]
    kpos = jnp.arange(s)[None, :]
    ok = kpos <= pos
    if window is not None:
        ok &= kpos > pos - window
    if prefix_len is not None:
        ok |= kpos < prefix_len
    mask = ok[:, None, None, None, :]  # (1,1,1,1,S) broadcast over T=1
    out = _sdpa(q, k_cache, v_cache, mask, nh, kv)
    y = linear(out, p["wo"], caps=caps, name=f"{prefix}wo")
    new_cache = dict(cache)
    new_cache["k"] = k_cache
    new_cache["v"] = v_cache
    return h + y, new_cache


def attn_cache_init(cfg: ArchConfig, batch, max_len, dtype):
    kv, hd = cfg.num_kv_heads, cfg.hd
    return {
        "k": jnp.zeros((batch, max_len, kv, hd), dtype),
        "v": jnp.zeros((batch, max_len, kv, hd), dtype),
    }


# ----------------------------------------------------------------------
# Dense MLP (swiglu / geglu / gelu)
# ----------------------------------------------------------------------
def mlp_init(key, cfg: ArchConfig, dtype, d_ff=None) -> Params:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    p = {
        "ln": rmsnorm_init(d, dtype),
        "wi": _dense_init(ks[0], d, f, dtype),
        "wo": _dense_init(ks[1], f, d, dtype,
                          scale=1.0 / math.sqrt(f * 2 * cfg.num_layers)),
    }
    if cfg.mlp_kind in ("swiglu", "geglu"):
        p["wg"] = _dense_init(ks[2], d, f, dtype)
    return p


def mlp_apply(p, h, cfg: ArchConfig, *, caps=None, prefix="mlp."):
    h_in = rmsnorm(p["ln"], h, cfg.norm_eps)
    # glu gates fuse their activation into the projection epilogue (a
    # no-op for dense weights, a true in-kernel epilogue for 2:4-packed
    # ones); jax.nn.gelu's default approximate=True matches the fused
    # "gelu" epilogue in kernels.ref.activate
    if cfg.mlp_kind == "swiglu":
        up = linear(h_in, p["wi"], caps=caps, name=f"{prefix}wi")
        act = linear(h_in, p["wg"], caps=caps, name=f"{prefix}wg",
                     activation="silu") * up
    elif cfg.mlp_kind == "geglu":
        up = linear(h_in, p["wi"], caps=caps, name=f"{prefix}wi")
        act = linear(h_in, p["wg"], caps=caps, name=f"{prefix}wg",
                     activation="gelu") * up
    else:
        act = linear(h_in, p["wi"], caps=caps, name=f"{prefix}wi",
                     activation="gelu")
    y = linear(act, p["wo"], caps=caps, name=f"{prefix}wo")
    return h + y


# ----------------------------------------------------------------------
# Embedding / unembedding
# ----------------------------------------------------------------------
def embed_init(key, cfg: ArchConfig, dtype) -> Params:
    p = {"tok": (jax.random.normal(key, (cfg.vocab_size, cfg.d_model),
                                   jnp.float32) * 0.02).astype(dtype)}
    if cfg.frontend is not None:
        k2 = jax.random.fold_in(key, 1)
        p["frontend_proj"] = _dense_init(k2, cfg.frontend_dim, cfg.d_model, dtype)
    return p


def embed_apply(p, tokens, cfg: ArchConfig):
    h = p["tok"][tokens]
    if cfg.embed_scale:
        h = h * jnp.asarray(math.sqrt(cfg.d_model), h.dtype)
    if cfg.residual_in_fp32:
        h = h.astype(jnp.float32)
    return h


def frontend_apply(p, feats, cfg: ArchConfig):
    """Stub modality frontend: project precomputed patch/frame embeddings."""
    return feats.astype(p["frontend_proj"].dtype) @ p["frontend_proj"]


def unembed_init(key, cfg: ArchConfig, dtype) -> Params:
    p = {"ln": rmsnorm_init(cfg.d_model, dtype)}
    if not cfg.tie_embeddings:
        p["head"] = _dense_init(key, cfg.d_model, cfg.vocab_size, dtype)
    return p


# §Perf iteration 1b: GSPMD leaves h feature-sharded entering the LM
# head, so the vocab-parallel matmul contracts a sharded dim and
# all-reduces the full f32 LOGITS (40GB/dev at 4k×256×152k) — plus the
# mirrored all-gather in the backward. Gathering h (bf16, ~0.5GB) first
# makes the head a clean column-parallel matmul. Off by default
# (baseline faithfulness); enabled by OptFlags.fsdp_embed_fix.
HEAD_GATHER = False


def unembed_apply(p, embed_p, h, cfg: ArchConfig):
    h = rmsnorm(p["ln"], h, cfg.norm_eps)
    if cfg.residual_in_fp32:
        h = h.astype(p["ln"]["scale"].dtype)
    if HEAD_GATHER:
        h = _dp_only_constrain(h)
    if cfg.tie_embeddings:
        return h @ embed_p["tok"].T.astype(h.dtype)
    return h @ p["head"].astype(h.dtype)
