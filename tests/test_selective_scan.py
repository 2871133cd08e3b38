"""The Mamba selective scan: the Pallas kernel (interpreted) and the
chunked jnp scan against the plain sequential oracle, ``mamba_apply``
against the whole-sequence associative scan it replaced, the
``ssm_scan_path_total`` counter, ``residual_in_fp32``, and the pruning
engine on ``mamba_2_8b``'s smoke config against the benchmark's plain
references."""

import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config, get_smoke
from repro.kernels import ops, ref
from repro.kernels.selective_scan import selective_scan, selective_scan_chunked
from repro.models import LM
from repro.models import ssm as ssm_lib
from repro.obs import MetricsRegistry, counting_traces

CHIP = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"

# Every path computes the same f32 recurrence; they differ only in the
# order of the products and sums (sequential, blocked associative scan,
# a VMEM loop), which moves results by a few f32 ulps per step.  Relative
# to the output's largest magnitude, 2e-5 holds that with room over
# 300 steps and is far below any real fault (a wrong step, a lost carry,
# a misplaced b or c column moves results by O(1)).
RTOL = 2e-5


def _inputs(b=2, t=300, d=200, n=16, seed=0, dtype=jnp.float32):
    k = jax.random.split(jax.random.key(seed), 6)
    dt = jax.nn.softplus(jax.random.normal(k[0], (b, t, d)) - 2.0)
    x = jax.random.normal(k[1], (b, t, d))
    bb = jax.random.normal(k[2], (b, t, n))
    cc = jax.random.normal(k[3], (b, t, n))
    a = -jnp.exp(jax.random.normal(k[4], (d, n)))
    s0 = jax.random.normal(k[5], (b, d, n))
    cast = (lambda v: v.astype(dtype))
    return cast(dt), cast(x), cast(bb), cast(cc), a, s0


def _close(got, want):
    err = float(jnp.max(jnp.abs(got - want)))
    return err <= RTOL * float(jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("path", ["kernel", "chunked"])
@pytest.mark.parametrize("case", ["ragged_t", "init", "bf16"])
def test_scan_matches_sequential_oracle(path, case):
    # ragged_t: T = 300 is a multiple of neither the kernel's T tile
    # (128) nor the chunk (64), and Di = 200 not of the 128-lane tile
    dt, x, b, c, a, s0 = _inputs(
        dtype=jnp.bfloat16 if case == "bf16" else jnp.float32)
    init = s0 if case == "init" else None
    want_y, want_s = ref.selective_scan_ref(dt, x, b, c, a, init)
    if path == "kernel":
        y, s = selective_scan(dt, x, b, c, a, init, bd=128, tc=128,
                              interpret=True)
    else:
        y, s = selective_scan_chunked(dt, x, b, c, a, init, chunk=64)
    assert y.dtype == s.dtype == jnp.float32
    assert y.shape == x.shape and s.shape == (2, 200, 16)
    assert _close(y, want_y) and _close(s, want_s)


def test_kernel_tiles_over_di_and_t():
    # several Di tiles and T tiles: the state carries across T tiles and
    # each Di tile keeps its own
    dt, x, b, c, a, s0 = _inputs(b=1, t=512, d=384, seed=3)
    want_y, want_s = ref.selective_scan_ref(dt, x, b, c, a, s0)
    y, s = selective_scan(dt, x, b, c, a, s0, bd=128, tc=128,
                          interpret=True)
    assert _close(y, want_y) and _close(s, want_s)


def _whole_sequence_scan(dt, x, b, c, a, init=None):
    """The associative scan over the whole sequence that the chunked
    scan replaced (O(B·T·Di·N) memory)."""
    abar = jnp.exp(dt[..., None] * a[None, None])
    bx = (dt * x)[..., None] * b[:, :, None, :]

    def combine(e1, e2):
        return e2[0] * e1[0], e2[0] * e1[1] + e2[1]

    aprod, states = jax.lax.associative_scan(combine, (abar, bx), axis=1)
    if init is not None:
        states = states + aprod * init[:, None]
    return jnp.einsum("btdn,btn->btd", states, c), states[:, -1]


@pytest.mark.parametrize("mode", ["prefill", "chunked_prefill"])
def test_mamba_apply_unchanged(mode, monkeypatch):
    cfg = get_smoke("mamba_2_8b")
    p = LM(cfg).init(jax.random.key(1))["layers"]["s0"]
    p = jax.tree.map(lambda v: v[0], p)["mamba"]
    h = jax.random.normal(jax.random.key(2), (1, 24, cfg.d_model))
    cache = ssm_lib.mamba_cache_init(cfg, 3 if mode != "prefill" else 1,
                                     jnp.float32)
    kw = {}
    if mode == "chunked_prefill":
        # slot 1 carries a state in; 5 padded positions past the prompt
        cache = {"conv": jax.random.normal(jax.random.key(3),
                                           cache["conv"].shape),
                 "ssm": jax.random.normal(jax.random.key(4),
                                          cache["ssm"].shape)}
        kw["paged"] = {"slot": jnp.int32(1), "start": jnp.int32(40),
                       "lengths": jnp.array([59], jnp.int32),
                       "block_tables": jnp.zeros((1, 1), jnp.int32)}

    def apply():
        return ssm_lib.mamba_apply(p, h, cfg, cache=cache, **kw)

    got, got_c = apply()
    monkeypatch.setattr(ssm_lib, "_mamba_ssm_scan", _whole_sequence_scan)
    want, want_c = apply()
    assert _close(got, want)
    for k in ("conv", "ssm"):
        assert _close(got_c[k], want_c[k])


@pytest.mark.parametrize("force", [True, False])
def test_scan_path_counter(force):
    cfg = get_smoke("mamba_2_8b")
    p = jax.tree.map(lambda v: v[0],
                     LM(cfg).init(jax.random.key(1))["layers"]["s0"])
    h = jnp.ones((2, 16, cfg.d_model))
    reg = MetricsRegistry()
    fam = reg.counter("prune_stage_traces_total", labels=("stage",))
    with ops.override_dispatch(force_pallas=force), counting_traces(fam):
        jax.jit(lambda p, h: ssm_lib.mamba_apply(p["mamba"], h, cfg)[0]
                ).lower(p, h)
    paths = {k[0]: c.value
             for k, c in reg.get("ssm_scan_path_total").children()}
    assert paths == {"pallas" if force else "chunked": 1.0}


def test_residual_in_fp32():
    # off in every other configuration; refused where not implemented
    for arch in ARCH_IDS:
        assert get_config(arch).residual_in_fp32 == (arch == "mamba_2_8b")
    with pytest.raises(ValueError, match="residual_in_fp32"):
        dataclasses.replace(get_smoke("qwen1_5_0_5b"), residual_in_fp32=True)
    smoke = get_smoke("mamba_2_8b")
    batch = {"tokens": jnp.arange(32).reshape(2, 16) % smoke.vocab_size}
    params = LM(smoke).init(jax.random.key(0))
    # in float32 the flag changes no arithmetic: bit-identical
    off = dataclasses.replace(smoke, residual_in_fp32=False)
    a, _ = LM(smoke).forward(params, batch)
    b, _ = LM(off).forward(params, batch)
    assert np.array_equal(np.asarray(a), np.asarray(b))
    # in bfloat16 it keeps the residual stream in f32 and nothing else
    bf = dataclasses.replace(smoke, dtype="bfloat16")
    pbf = LM(bf).init(jax.random.key(0))
    for flag, want in ((True, jnp.float32), (False, jnp.bfloat16)):
        m = LM(dataclasses.replace(bf, residual_in_fp32=flag))
        seg = m.prunable_segments()[0]
        h0 = m.calib_init(pbf, batch)
        h1, caps = seg.apply(seg.get_params(pbf), h0, capture=True)
        assert h0.dtype == h1.dtype == want
        assert {v.dtype for v in caps.values()} == {jnp.dtype(jnp.bfloat16)}
    on, _ = LM(bf).forward(pbf, batch)
    off, _ = LM(dataclasses.replace(bf, residual_in_fp32=False)).forward(
        pbf, batch)
    assert 0 < float(jnp.max(jnp.abs(on - off))) < 0.1 * float(
        jnp.max(jnp.abs(off)))


# --------------------------------------------------------- the prune path
@pytest.fixture(scope="module")
def pruned_smoke():
    from repro.core import PruningEngine
    from repro.core.pipeline import SegmentScheduler
    cfg = get_smoke("mamba_2_8b")
    model = LM(cfg)
    params = model.init(jax.random.key(5))
    toks = jax.random.randint(jax.random.key(6), (8, 32), 0, cfg.vocab_size)
    calib = [{"tokens": toks[i:i + 4]} for i in (0, 4)]
    eng = PruningEngine(model, "2:4", method="SM", blocksize=32, gamma=0.01)
    pruned, reports = eng.run(params, calib)
    # the program's Hessians: layer 0 on the dense model, layer 1 on
    # layer 0's pruned output, as the pipeline computes them
    sched = SegmentScheduler()
    seg0, seg1 = model.prunable_segments()
    states = sched.shard_states([model.calib_init(params, b) for b in calib])
    h0 = sched.capture(seg0, seg0.get_params(params), states)
    states = sched.propagate(seg0, seg0.get_params(pruned), states)
    h1 = sched.capture(seg1, seg1.get_params(params), states)
    return {"cfg": cfg, "params": params, "pruned": pruned, "toks": toks,
            "reports": reports, "hessians": [h0, h1]}


LINEARS = ("in_proj", "x_proj", "dt_proj", "out_proj")


def _layer(tree, i):
    return jax.tree.map(lambda v: v[i], tree["layers"]["s0"])["mamba"]


def test_engine_prunes_every_mamba_projection_2_4(pruned_smoke):
    reports = pruned_smoke["reports"]
    assert sorted(r.name for r in reports) == sorted(
        f"period{i}.s0.mamba.{w}" for i in (0, 1) for w in LINEARS)
    for i in (0, 1):
        lay = _layer(pruned_smoke["pruned"], i)
        for w in LINEARS:
            kept = (np.asarray(lay[w]).T != 0).reshape(lay[w].shape[1],
                                                        -1, 4)
            assert (kept.sum(-1) == 2).all(), (i, w)


def test_engine_matches_the_benchmark_references(pruned_smoke):
    sys.path.insert(0, str(CHIP))
    from reference import mamba_forward, sm_sweep
    cfg, params = pruned_smoke["cfg"], pruned_smoke["params"]
    toks = pruned_smoke["toks"]
    # layer 1's Hessians from the reference forward through the
    # program's pruned layer 0
    x = mamba_forward.embed(params, toks)
    p1 = mamba_forward.layer_params(pruned_smoke["pruned"], 0)
    x1 = mamba_forward.propagate(p1, x, cfg.norm_eps)
    names = [f"s0.mamba.{w}" for w in LINEARS]
    hs = {n: jnp.zeros((params["layers"]["s0"]["mamba"][n.split(".")[-1]]
                        .shape[1],) * 2) for n in mamba_forward.LINEARS}
    hs = mamba_forward.capture(mamba_forward.layer_params(params, 1), x1,
                               hs, cfg.norm_eps)
    h1 = pruned_smoke["hessians"][1]
    for n, k in zip(names, mamba_forward.LINEARS):
        want = np.asarray(hs[k]) / toks.size
        got = np.asarray(h1.hessian(n))
        assert np.max(np.abs(got - want)) < 1e-4 * np.max(np.abs(want)), n
    # each projection's mask and weights against the float64 SM sweep
    # on the program's own Hessians
    for i in (0, 1):
        dense, out = _layer(params, i), _layer(pruned_smoke["pruned"], i)
        for n, w in zip(names, LINEARS):
            h = np.asarray(pruned_smoke["hessians"][i].hessian(n), np.float64)
            w_ref, mask_ref = sm_sweep.sm_sweep(
                np.asarray(dense[w], np.float64).T, h, 32, 0.01)
            got = np.asarray(out[w], np.float64).T
            assert np.mean((got == 0) != mask_ref) <= 0.01, (i, w)
            agree = ((got == 0) == mask_ref).all(axis=1)
            dev = (np.linalg.norm(got - w_ref, axis=1)
                   / np.linalg.norm(w_ref, axis=1))
            assert agree.mean() > 0.9 and dev[agree].max() < 1e-3, (i, w)
