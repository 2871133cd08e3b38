"""Chip smoke: prune -> pack -> serve Qwen1.5-0.5B on one TPU chip.

    python3 chip_smoke.py               # one chip: every phase below
    python3 chip_smoke.py --four-chips  # 1x4-mesh prune + serve vs one device

One process, which starts no other.  It drives the main path through the
entry points a user calls, at Qwen1.5-0.5B's published widths and full
depth (``repro/configs/qwen1_5_0_5b.py``, bf16), with weights made from a
fixed seed:

1. device  JAX must find a TPU (``jax_platforms="tpu"``); without one the
           script fails and prints no result.
2. model   the published config in its own dtype.
3. prune   ``PruningEngine`` as ``repro.launch.prune`` drives it: 2:4 with
           the paper's SM solver, 16 calibration sequences of 256 tokens,
           every block.  Checks: every pruned linear is 2:4, every weight
           is finite, and block 0's up projection agrees with a float64
           NumPy run of the same sweep.
4. serve   pack the 2:4 weights, then ``repro.launch.serve.make_router``
           in continuous mode: 8 requests of a few hundred prompt tokens,
           32 new tokens each.  Checks: the engine stayed continuous,
           every response ended by length or stop, and the compiled
           decode burst runs the ``paged_attn`` and ``nm_spmm_decode``
           kernels.
5. parity  the paged-attention kernel against its jnp reference at full
           width (f32 and int8 pages), and the served path's first-token
           and first-decode logits against ``LM.prefill``'s full forward.

``--four-chips`` runs instead a one-block prune on a 1x4 (data, model)
mesh and a 1x4 serve of the same requests, each compared with the same
run on one device.

Phase wall times and peak device memory are printed as set-up
information.  Any failed check raises, so the exit code is non-zero; the
last line of stdout is ``{"ok": true, "device": {...}}`` only on success.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro import configs as cfglib  # noqa: E402
from repro.core import PruningEngine  # noqa: E402
from repro.core.hessian import dampened_inverse_np  # noqa: E402
from repro.core.mrp import mrp_row_reference  # noqa: E402
from repro.data import calibration_batches  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.launch.serve import make_router  # noqa: E402
from repro.models import LM  # noqa: E402
from repro.obs import Obs  # noqa: E402
from repro.serve import ServeConfig, sparsify_params  # noqa: E402
from repro.serve.frontend import CompletionRequest  # noqa: E402
from repro.serve.fused import init_burst_state  # noqa: E402
from repro.serve.sparse import count_packed  # noqa: E402
from repro.utils.compile_cache import enable_compile_cache  # noqa: E402
from repro.utils.hlo import tpu_kernel_names  # noqa: E402

SEED = 0
ARCH = "qwen1_5_0_5b"
SPARSITY, METHOD, GAMMA = "2:4", "SM", 0.01
N_CALIB, CALIB_LEN = 16, 256
# the widest column block that divides both input widths (1024, 2816)
BLOCKSIZE = 256
PROMPT_LENS = (192, 256, 320, 384, 224, 288, 352, 416)
MAX_NEW = 32
MAX_LEN, MAX_BATCH, PAGE_SIZE = 1024, 8, 16
REF_ROWS = 32           # rows of the float64 reference sweep

# The engine solves in f32 and stores bf16 weights between column
# blocks; float64 does neither, so a few near-tied 2:4 groups may pick
# the other pair and the reconstruction error moves by a little.
MASK_AGREE_MIN = 0.99
RECON_REL_TOL = 0.02
# Kernel and reference compute the same f32 attention (the reference at
# full matmul precision); they differ only in summation order.
PAGED_ATOL = 1e-4
# The served path (packed weights, paged KV, chunked prefill) and the full
# forward round bf16 activations at different points; over 24 layers that
# moves logits by a few bf16 ulps of their range.
LOGIT_REL_TOL = 0.05
LOGIT_COS_MIN = 0.999


@contextlib.contextmanager
def phase(name: str, times: dict):
    print(f"[{name}] start", flush=True)
    t0 = time.perf_counter()
    yield
    times[name] = time.perf_counter() - t0
    print(f"[{name}] set-up time {times[name]:.1f}s", flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)
    print(f"  ok: {what}", flush=True)


def make_requests(vocab: int, prompt_lens=PROMPT_LENS, max_new=MAX_NEW):
    rng = np.random.default_rng(SEED)
    return [CompletionRequest(
        prompt=rng.integers(0, vocab, size=n, dtype=np.int32).tolist(),
        max_tokens=max_new, uid=i) for i, n in enumerate(prompt_lens)]


# ----------------------------------------------------------------- prune
def sm_reference(w0, h, blocksize, gamma, n_prune=2, group=4):
    """Float64 NumPy twin of the engine's SM sweep (Algorithm 1) on the
    rows of ``w0``: per column block, the Eq. (14) score picks the
    ``n_prune`` lowest of each group, then every row is re-solved
    (Eq. 13) against its whole accumulated mask."""
    hinv = dampened_inverse_np(h, gamma)
    diag = np.diag(hinv)
    w = w0.astype(np.float64).copy()
    n, m = w.shape
    blocksize = min(blocksize, m)
    mask = np.zeros((n, m), bool)
    for c0 in range(0, m, blocksize):
        cols = slice(c0, c0 + blocksize)
        score = (w[:, cols] ** 2 / (2.0 * diag[cols])).reshape(
            n, -1, group)
        low = np.argsort(score, axis=-1)[..., :n_prune]
        blk = np.zeros(score.shape, bool)
        np.put_along_axis(blk, low, True, axis=-1)
        mask[:, cols] = blk.reshape(n, blocksize)
        for q in range(n):
            w[q], _ = mrp_row_reference(w[q], hinv, np.nonzero(mask[q])[0])
    return w, mask


def prune(model, params, *, n_calib=N_CALIB, calib_len=CALIB_LEN,
          blocksize=BLOCKSIZE, ref_rows=REF_ROWS):
    """Prune every block as ``repro.launch.prune`` does, then check the
    result.  Returns the pruned params."""
    calib = calibration_batches(model.cfg, n_samples=n_calib,
                                seq_len=calib_len)
    engine = PruningEngine(model, SPARSITY, method=METHOD,
                           blocksize=blocksize, gamma=GAMMA)
    engine.obs = Obs.create(metrics=True, trace=False)
    pruned, reports = engine.run(params, calib)
    jax.block_until_ready(pruned)
    segs = model.prunable_segments()
    n_lin = sum(len(s.linears) for s in segs)
    check(len(reports) == n_lin and all(r.sparsity == 0.5 for r in reports),
          f"{len(reports)} linears pruned to exactly 50%")

    @jax.jit
    def max_nonzero(w):                      # (n, m): groups of 4 along m
        n, m = w.shape
        return jnp.max(jnp.sum(w.reshape(n, m // 4, 4) != 0, axis=-1))

    worst = max(int(max_nonzero(lin.get(s.get_params(pruned))))
                for s in segs for lin in s.linears)
    check(worst <= 2, f"every group of 4 holds <= 2 nonzeros ({worst})")
    finite = jax.jit(lambda t: jnp.all(jnp.stack([
        jnp.all(jnp.isfinite(x)) for x in jax.tree.leaves(t)])))(pruned)
    check(bool(finite), "every pruned weight is finite")

    # block 0's up projection against float64 on the host: the same
    # calibration inputs (captured through the dense block), the same sweep
    seg = segs[0]
    lin = next(x for x in seg.linears if x.name.endswith("mlp.wi"))
    dense0 = seg.get_params(params)
    capture = jax.jit(lambda p, b: seg.apply(
        p, model.first_hidden(params, b), capture=True)[1][lin.name])
    x = np.concatenate([np.asarray(capture(dense0, b), np.float64)
                        .reshape(-1, model.cfg.d_model) for b in calib])
    h = 2.0 * (x.T @ x) / x.shape[0]
    w0 = np.asarray(lin.get(dense0), np.float64)
    w1 = np.asarray(lin.get(seg.get_params(pruned)), np.float64)
    rows = np.linspace(0, w0.shape[0] - 1, ref_rows).astype(int)
    w_ref, mask_ref = sm_reference(w0[rows], h, blocksize, GAMMA)

    def recon(w):
        d = w - w0[rows]
        return 0.5 * float(np.einsum("ij,jk,ik->", d, h, d))

    agree = float(np.mean((w1[rows] == 0) == mask_ref))
    e_eng, e_ref = recon(w1[rows]), recon(w_ref)
    rel = abs(e_eng - e_ref) / e_ref
    print(f"  {lin.name} ({w0.shape[0]}x{w0.shape[1]}), {ref_rows} rows: "
          f"mask agreement {agree:.6f}, recon error engine {e_eng:.6g} "
          f"float64 {e_ref:.6g} (rel diff {rel:.3e})", flush=True)
    check(agree >= MASK_AGREE_MIN,
          f"mask agrees with float64 on >= {MASK_AGREE_MIN:.0%}")
    check(rel <= RECON_REL_TOL,
          f"recon error within {RECON_REL_TOL:.0%} of float64")
    return pruned


# ----------------------------------------------------------------- serve
def serve(model, packed, creqs, *, max_len=MAX_LEN, max_batch=MAX_BATCH,
          page_size=PAGE_SIZE, want_kernels=True):
    """Continuous serving through the router a user builds.  Returns the
    responses (uid order)."""
    config = ServeConfig(mode="continuous", max_len=max_len,
                         max_batch=max_batch, page_size=page_size)
    router = make_router(model, packed, config)
    try:
        eng = router.replicas[0].engine
        check(eng.mode == "continuous", "engine serves in continuous mode")
        check(eng.n_sparse_leaves > 0,
              f"{eng.n_sparse_leaves} packed 2:4 weight leaves served")
        out = router.complete(creqs)
        reasons = sorted({r.finish_reason for r in out})
        check(len(out) == len(creqs)
              and set(reasons) <= {"length", "stop"},
              f"{len(out)} responses, finish reasons {reasons}")
        print(f"  first tokens: {[r.tokens[:4] for r in out]}", flush=True)
        if want_kernels:
            state = init_burst_state(eng.max_batch, eng._ring)
            hlo = eng._burst.lower(
                eng.params, eng.pool.kv, eng.pool.tables_device(), state,
                jax.random.key(0)).compile().as_text()
            names = tpu_kernel_names(hlo)
            check({"paged_attn", "nm_spmm_decode"} <= set(names),
                  f"decode burst kernels: {sorted(set(names))}")
    finally:
        router.close()
    return out


# ---------------------------------------------------------------- parity
def paged_parity(cfg, *, batch=MAX_BATCH, max_len=MAX_LEN,
                 page_size=PAGE_SIZE):
    """The paged-attention kernel against ``ref.paged_attn_ref`` at the
    model's head layout, f32 and int8 pages."""
    kvh, g, hd = cfg.num_kv_heads, cfg.num_heads // cfg.num_kv_heads, cfg.hd
    p_max = max_len // page_size
    n_pages = batch * p_max + 1
    ks = jax.random.split(jax.random.key(SEED), 4)
    q = jax.random.normal(ks[0], (batch, kvh, g, hd), jnp.float32)
    kp = jax.random.normal(ks[1], (n_pages, page_size, kvh, hd), jnp.float32)
    vp = jax.random.normal(ks[2], (n_pages, page_size, kvh, hd), jnp.float32)
    bt = (1 + jax.random.permutation(ks[3], n_pages - 1)[:batch * p_max]
          ).reshape(batch, p_max).astype(jnp.int32)
    lengths = jnp.asarray(np.linspace(1, max_len, batch).astype(np.int32))

    def quant(x):
        s = jnp.max(jnp.abs(x), axis=-1) / 127.0
        return jnp.round(x / s[..., None]).astype(jnp.int8), s

    kq, kscale = quant(kp)
    vq, vscale = quant(vp)
    for name, args, kw in (
            ("f32", (q, kp, vp, bt, lengths), {}),
            ("int8", (q, kq, vq, bt, lengths),
             {"k_scale": kscale, "v_scale": vscale})):
        got = ops.paged_attention(*args, use_kernel=True, **kw)
        with jax.default_matmul_precision("highest"):
            want = ref.paged_attn_ref(*args, **kw)
        err = float(jnp.max(jnp.abs(got - want)))
        check(err <= PAGED_ATOL,
              f"paged_attn {name} pages vs reference: max |diff| {err:.3e}")


def logit_parity(model, dense, packed, prompt, *, page_size=PAGE_SIZE,
                 chunk=None, max_len=MAX_LEN):
    """The served path's logits — packed weights, chunked paged prefill,
    one paged decode step — against ``LM.prefill``'s full forward of the
    same tokens with the dense pruned weights."""
    chunk = chunk or ServeConfig().prefill_chunk
    n = len(prompt)
    p_max = max_len // page_size
    kv = model.init_paged_cache(p_max + 1, page_size)
    bt = jnp.asarray(np.arange(1, p_max + 1, dtype=np.int32)[None])
    step = jax.jit(model.prefill_chunk, static_argnames=("page_size",))
    for start in range(0, n, chunk):
        piece = np.zeros((1, chunk), np.int32)
        piece[0, :len(prompt[start:start + chunk])] = prompt[start:start + chunk]
        served0, kv = step(packed, {"tokens": jnp.asarray(piece)}, kv,
                           jnp.int32(start), jnp.int32(n), jnp.int32(0), bt,
                           page_size=page_size)
    tok0 = int(jnp.argmax(served0[0]))
    decode = jax.jit(model.decode_step, static_argnames=("page_size",))
    served1, _ = decode(packed, jnp.asarray([tok0], jnp.int32), kv,
                        jnp.asarray([n], jnp.int32),
                        paged={"block_tables": bt}, page_size=page_size)
    full = jax.jit(model.prefill)
    for name, served, toks in (("first token", served0, prompt),
                               ("first decode", served1, [*prompt, tok0])):
        want, _ = full(dense, {"tokens": jnp.asarray([toks], jnp.int32)},
                       model.init_cache(1, len(toks)))
        a = np.asarray(served[0], np.float64)
        b = np.asarray(want[0], np.float64)
        rel = float(np.max(np.abs(a - b)) / np.max(np.abs(b)))
        cos = float(a @ b / (np.linalg.norm(a) * np.linalg.norm(b)))
        check(rel <= LOGIT_REL_TOL and cos >= LOGIT_COS_MIN,
              f"{name} logits vs full forward: max |diff|/max|ref| "
              f"{rel:.3e}, cosine {cos:.6f}")


# ------------------------------------------------------------------ main
def device_check(n_chips: int):
    jax.config.update("jax_platforms", "tpu")
    devs = jax.devices()
    check(devs[0].platform == "tpu" and len(devs) >= n_chips,
          f"jax {jax.__version__}: {len(devs)} x {devs[0].device_kind}")
    print(f"  compile cache: {enable_compile_cache()}", flush=True)
    return devs


def build_model(cfg):
    model = LM(cfg)
    params = jax.jit(model.init)(jax.random.key(SEED))
    jax.block_until_ready(params)
    return model, params


def one_chip(times: dict) -> None:
    cfg = cfglib.get_config(ARCH)
    with phase("model", times):
        model, params = build_model(cfg)
        print(f"  {cfg.name}: {cfg.num_layers} layers, d {cfg.d_model}, "
              f"{cfg.num_heads}/{cfg.num_kv_heads} heads, d_ff {cfg.d_ff}, "
              f"vocab {cfg.vocab_size}, {cfg.dtype}", flush=True)
    with phase("prune", times):
        pruned = prune(model, params)
    del params
    creqs = make_requests(cfg.vocab_size)
    with phase("serve", times):
        packed = sparsify_params(pruned)
        check(count_packed(packed) == 7, "7 stacked linears packed 2:4")
        serve(model, packed, creqs)
    with phase("parity", times):
        paged_parity(cfg)
        logit_parity(model, pruned, packed, np.asarray(creqs[0].prompt))


def four_chips(times: dict) -> None:
    """One block, pruned and then served on a 1x4 mesh, each compared
    with one device.  A failed mesh prune does not stop the mesh serve;
    the first failure is raised at the end."""
    from repro.dist import make_mesh, use_mesh

    cfg = dataclasses.replace(cfglib.get_config(ARCH), num_layers=1)
    mesh = make_mesh((1, 4), ("data", "model"))
    # one column block per linear: the mesh path's faults do not depend
    # on the block count, and each block count is a program to compile
    blocksize = cfg.d_ff
    failures = []
    with phase("model", times):
        model, params = build_model(cfg)
    with phase("prune 1 device", times):
        single = prune(model, params, blocksize=blocksize)
    try:
        with phase("prune 1x4 mesh", times), use_mesh(mesh):
            sharded = prune(model, params, blocksize=blocksize)
        a = [np.asarray(x, np.float32) for x in jax.tree.leaves(single)]
        b = [np.asarray(x, np.float32) for x in jax.tree.leaves(sharded)]
        zeros = float(np.mean(np.concatenate(
            [((x == 0) == (y == 0)).ravel() for x, y in zip(a, b)])))
        diff = max(float(np.max(np.abs(x - y))) for x, y in zip(a, b))
        check(zeros >= MASK_AGREE_MIN,
              f"1x4 prune vs one device: mask agreement {zeros:.6f}, "
              f"max |diff| {diff:.3e}")
    except Exception as e:  # noqa: BLE001 — reported, raised at the end
        print(f"  FAILED: 1x4 prune: {type(e).__name__}: {e}", flush=True)
        failures.append(e)
    creqs = make_requests(cfg.vocab_size)
    packed = sparsify_params(single)
    try:
        with phase("serve 1x4 mesh", times), use_mesh(mesh):
            got = serve(model, packed, creqs, want_kernels=False)
        with phase("serve 1 device", times):
            want = serve(model, packed, creqs, want_kernels=False)
        first = float(np.mean([x.tokens[:1] == y.tokens[:1]
                               for x, y in zip(want, got)]))
        same = float(np.mean([x.tokens == y.tokens
                              for x, y in zip(want, got)]))
        check(first >= 0.75, f"1x4 serve vs one device: first tokens "
                             f"agree {first:.0%}, whole streams {same:.0%}")
    except Exception as e:  # noqa: BLE001
        print(f"  FAILED: 1x4 serve: {type(e).__name__}: {e}", flush=True)
        failures.append(e)
    if failures:
        raise failures[0]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="only the 1x4-mesh prune and serve, each against "
                         "one device")
    args = ap.parse_args(argv)
    n_chips = 4 if args.four_chips else 1
    times: dict = {}
    t0 = time.perf_counter()
    with phase("device", times):
        devs = device_check(n_chips)
    (four_chips if args.four_chips else one_chip)(times)
    peak = devs[0].memory_stats().get("peak_bytes_in_use")
    print(f"set-up times (s): "
          f"{json.dumps({k: round(v, 1) for k, v in times.items()})}; "
          f"total {time.perf_counter() - t0:.1f}s; device 0 "
          f"peak_bytes_in_use {peak}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
