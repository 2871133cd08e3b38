"""Public jit'd wrappers around the Pallas kernels.

Dispatch is resolved per call through :func:`dispatch_mode` (no module
globals to mutate — ISSUE-7 api_redesign):

- ``interpret`` auto-detects the backend: on this CPU container every
  kernel runs in interpret mode (Python-level execution of the kernel
  body — bit-faithful to the TPU program structure); on TPU they
  compile to Mosaic.  All wrappers handle padding to tile multiples.
- ``force_pallas`` (env ``JAX_PALLAS_INTERPRET=1`` — the CI tier-1
  kernel step) forces the Pallas kernel BODIES, in interpret mode,
  through every dispatch that would otherwise take a jnp-oracle
  shortcut off-TPU (``paged_attention``, ``nm_matmul`` and
  ``selective_scan`` below), so the kernels' logic is exercised on
  CPU-only runners.

Tests and callers that need a specific mode use the
:func:`override_dispatch` context manager instead of monkeypatching:

    with ops.override_dispatch(force_pallas=True):
        ops.paged_attention(...)        # kernel body, interpreted
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
from typing import Iterator, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attn import flash_attn as _flash
from repro.kernels.paged_attn import paged_attn as _paged_attn
from repro.kernels.hessian_accum import hessian_accum as _hessian
from repro.kernels.nm_select import nm_select as _nm_select
from repro.kernels.nm_spmm import nm_spmm as _nm_spmm
from repro.kernels.nm_spmm import nm_spmm_decode as _nm_spmm_decode
from repro.kernels.selective_scan import selective_scan as _selective_scan
from repro.kernels.selective_scan import selective_scan_chunked


@dataclasses.dataclass(frozen=True)
class DispatchMode:
    """How the wrappers run their kernels right now (immutable —
    replace via :func:`override_dispatch`, never mutate)."""

    interpret: bool       # Pallas interpret mode (off-TPU default)
    force_pallas: bool    # kernel bodies even where a jnp oracle exists


_OVERRIDE: list = []      # override stack (innermost last)


def dispatch_mode() -> DispatchMode:
    """The active kernel dispatch mode: the innermost
    :func:`override_dispatch` if one is active, else resolved from the
    backend and the ``JAX_PALLAS_INTERPRET`` env var at call time."""
    if _OVERRIDE:
        return _OVERRIDE[-1]
    return DispatchMode(
        interpret=jax.default_backend() != "tpu",
        force_pallas=os.environ.get("JAX_PALLAS_INTERPRET", "")
        not in ("", "0"))


@contextlib.contextmanager
def override_dispatch(interpret: Optional[bool] = None,
                      force_pallas: Optional[bool] = None
                      ) -> Iterator[DispatchMode]:
    """Scoped dispatch override (replaces the old pattern of tests
    mutating ``ops.INTERPRET``/``ops.FORCE_PALLAS`` module globals).
    Unspecified fields inherit the currently active mode; overrides
    nest."""
    base = dispatch_mode()
    mode = DispatchMode(
        interpret=base.interpret if interpret is None else interpret,
        force_pallas=(base.force_pallas if force_pallas is None
                      else force_pallas))
    _OVERRIDE.append(mode)
    try:
        yield mode
    finally:
        _OVERRIDE.pop()


def _pad_to(x: jax.Array, mults: Tuple[int, ...]) -> jax.Array:
    pads = [(0, (-s) % m) for s, m in zip(x.shape, mults)]
    if any(p[1] for p in pads):
        return jnp.pad(x, pads)
    return x


# ----------------------------------------------------------------------
def compress_24(w: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Dense 2:4-sparse (K, N) → packed (vals, idx). See ref.compress_24."""
    return ref.compress_24(w)


def nm_matmul(x: jax.Array, vals: jax.Array, idx: jax.Array,
              bias: Optional[jax.Array] = None, *,
              activation: Optional[str] = None,
              out_dtype=None, block: int = 128,
              use_kernel: Optional[bool] = None) -> jax.Array:
    """y = act(x @ w_sparse + bias) for packed 2:4 weights.

    x: (..., K); vals/idx: (K/2, N) → (..., N).  ``bias`` ((N,) or
    (1, N)) and ``activation`` (None | "silu" | "gelu") form the fused
    decode epilogue.

    Dispatch mirrors :func:`paged_attention`: the Pallas kernel on TPU
    (or forced via ``JAX_PALLAS_INTERPRET=1`` / ``override_dispatch``);
    the jnp decompress-oracle otherwise — this wrapper sits inside the
    jitted serve decode burst, where interpret-mode execution would
    dominate the step.  The oracle decompress is an exact inverse of
    :func:`compress_24`, so f32 packed serving is bit-identical to the
    dense path.  On the kernel side, skinny M (≤ ``block`` rows — every
    decode burst) takes the single-M-block :func:`nm_spmm_decode`
    variant with the epilogue fused into the accumulator tile; larger M
    (prefill/calibration shapes) takes the tiled kernel with the
    epilogue applied on the sliced result.
    """
    mode = dispatch_mode()
    if use_kernel is None:
        use_kernel = mode.force_pallas or not mode.interpret
    if not use_kernel:
        y = ref.nm_spmm_ref(x, vals, idx, bias=bias, activation=activation)
        return y.astype(out_dtype or x.dtype)
    lead = x.shape[:-1]
    k = x.shape[-1]
    n = vals.shape[-1]
    x2 = x.reshape(-1, k)
    m = x2.shape[0]
    valsp = _pad_to(vals, (block // 2, block))
    idxp = _pad_to(idx, (block // 2, block))
    if m <= block:
        # decode shape: one M block (padded to the f32 sublane tile),
        # bias + activation fused into the kernel epilogue
        b2 = (jnp.zeros((1, n), jnp.float32) if bias is None
              else jnp.reshape(bias, (1, n)).astype(jnp.float32))
        mp = max(8, -(-m // 8) * 8)
        y = _nm_spmm_decode(
            _pad_to(x2, (mp, block)), valsp, idxp, _pad_to(b2, (1, block)),
            bn=block, bk=block, activation=activation,
            interpret=mode.interpret)
        return y[:m, :n].reshape(*lead, n).astype(out_dtype or x.dtype)
    bm = min(block, max(8, m))
    x2p = _pad_to(x2, (bm, block))
    y = _nm_spmm(x2p, valsp, idxp, bm=bm, bn=block, bk=block,
                 interpret=mode.interpret)
    y = y[:m, :n]
    if bias is not None:
        y = y + jnp.reshape(bias, (1, n)).astype(jnp.float32)
    y = ref.activate(y, activation).reshape(*lead, n)
    return y.astype(out_dtype or x.dtype)


def hessian_xxt(x: jax.Array, block: int = 128) -> jax.Array:
    """H = 2·x·xᵀ for x (m, T) via the streaming kernel (f32)."""
    m, t = x.shape
    xp = _pad_to(x, (block, block))
    h = _hessian(xp, bi=block, bj=block, bt=block,
                 interpret=dispatch_mode().interpret)
    return h[:m, :m]


def nm_select_mask(w: jax.Array, hinv: jax.Array,
                   br: int = 128, bg: int = 32) -> jax.Array:
    """Solution 𝔐 2:4 mask (bool, True = pruned) for paper-orientation w.

    Extracts the (G, 4, 4) group diagonal blocks of Hinv host-side-cheap
    (O(m) gather) and runs the combo-scoring kernel.
    """
    r, c = w.shape
    g = c // 4
    cols = (jnp.arange(g) * 4)[:, None] + jnp.arange(4)[None, :]
    hg = hinv[cols[:, :, None], cols[:, None, :]].reshape(g, 16)
    brr = min(br, max(8, r))
    wp = _pad_to(w, (brr, 4 * bg))
    gp = wp.shape[1] // 4
    hgp = _pad_to(hg, (bg, 16))
    # padding groups get identity A (det=1) — harmless, rows sliced off
    if gp > g:
        eye = jnp.tile(jnp.eye(4).reshape(1, 16), (gp - g, 1))
        hgp = hgp.at[g:].set(eye)
    mask = _nm_select(wp, hgp, br=brr, bg=bg,
                      interpret=dispatch_mode().interpret)
    return mask[:r, :c].astype(bool)


def paged_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    block_tables: jax.Array, lengths: jax.Array,
                    window: Optional[int] = None,
                    use_kernel: Optional[bool] = None,
                    k_scale: Optional[jax.Array] = None,
                    v_scale: Optional[jax.Array] = None) -> jax.Array:
    """Paged GQA decode attention over block-table pages.

    q: (B, KV, G, hd); k/v_pages: (P, page_size, KV, hd); block_tables:
    (B, P_max) int32; lengths: (B,). Returns (B, KV, G, hd) in v.dtype
    (f32 when ``k_scale``/``v_scale`` engage the int8 KV-page path —
    pages dequantize row-wise at the gather, see serve/kvpool.py).

    Dispatch: the Pallas kernel on TPU (block-table scalar prefetch, no
    gather materialization); the jnp oracle otherwise — unlike the other
    wrappers this does NOT default to interpret mode on CPU, because it
    sits inside the jitted serve decode step and interpret execution
    would dominate the step; ref.paged_attn_ref is the same math and is
    bit-identical to the dense-cache decode path (use_kernel=True forces
    the kernel, under interpret off-TPU — the parity tests, and
    ``dispatch_mode().force_pallas`` — env JAX_PALLAS_INTERPRET=1 or an
    ``override_dispatch(force_pallas=True)`` scope — forces it for
    every default dispatch: the CI kernel-logic step).
    """
    mode = dispatch_mode()
    if use_kernel is None:
        use_kernel = mode.force_pallas or not mode.interpret
    if not use_kernel:
        return ref.paged_attn_ref(q, k_pages, v_pages, block_tables,
                                  lengths, window=window,
                                  k_scale=k_scale, v_scale=v_scale)
    out = _paged_attn(q, k_pages, v_pages, block_tables, lengths,
                      window=window, interpret=mode.interpret,
                      k_scale=k_scale, v_scale=v_scale)
    if k_scale is not None:
        return out                       # dequantized compute — f32 out
    return out.astype(v_pages.dtype)


def attention(q: jax.Array, k: jax.Array, v: jax.Array,
              causal: bool = True, bq: int = 128, bk: int = 128
              ) -> jax.Array:
    """Flash attention on (BH, T, D); T padded to tile multiples."""
    bh, t, d = q.shape
    bq = min(bq, t) if t % bq == 0 or t < bq else bq
    tpad = (-t) % max(bq, bk)
    if tpad:
        qp = jnp.pad(q, ((0, 0), (0, tpad), (0, 0)))
        kp = jnp.pad(k, ((0, 0), (0, tpad), (0, 0)))
        vp = jnp.pad(v, ((0, 0), (0, tpad), (0, 0)))
    else:
        qp, kp, vp = q, k, v
    if qp.shape[1] < bq:
        bq = bk = qp.shape[1]
    o = _flash(qp, kp, vp, bq=bq, bk=bk, causal=causal,
               interpret=dispatch_mode().interpret)
    return o[:, :t, :]


def selective_scan(dt: jax.Array, x: jax.Array, b: jax.Array, c: jax.Array,
                   a: jax.Array, init: Optional[jax.Array] = None
                   ) -> Tuple[jax.Array, jax.Array]:
    """Mamba-1 selective scan: (y (B, T, Di), last state (B, Di, N)) in
    f32 from dt, x (B, T, Di), b, c (B, T, N), a (Di, N) and an optional
    carry-in state (see ``kernels/selective_scan.py``).

    Dispatch: the Pallas kernel on TPU (or forced, interpreted, by
    ``JAX_PALLAS_INTERPRET=1`` / ``override_dispatch``); the chunked jnp
    scan otherwise, and also inside a multi-device mesh, which cannot
    partition a Mosaic kernel.  Each trace counts its path into
    ``ssm_scan_path_total{path}`` (``repro.obs.note_scan_path``).
    """
    from repro.dist import current_ctx
    from repro.obs import note_scan_path

    mode = dispatch_mode()
    ctx = current_ctx()
    use_kernel = mode.force_pallas or (
        not mode.interpret and (ctx is None or ctx.mesh.size == 1))
    note_scan_path("pallas" if use_kernel else "chunked")
    if not use_kernel:
        return selective_scan_chunked(dt, x, b, c, a, init)
    return _selective_scan(dt, x, b, c, a, init, interpret=mode.interpret)
