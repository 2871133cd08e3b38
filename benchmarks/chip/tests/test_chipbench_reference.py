"""The plain references against the program and against the smoke
script's own float64 sweep, at smoke size on the CPU."""

import dataclasses
import sys

import numpy as np

from chipbench_fixtures import CHIP, tiny_config

import harness  # noqa: E402
import weights  # noqa: E402
from reference import forward, sm_device, sm_sweep  # noqa: E402


def test_dense_rebuilds_packed():
    import jax

    from repro.kernels import ref
    from repro.models import LM
    cfg = harness.build_arch(tiny_config())
    p = weights.make(LM(cfg), harness.seed_key(3), packed=True)
    w = jax.tree.map(lambda x: x[0], p["layers"]["s0"]["mlp"]["wi"])
    got = np.asarray(forward.dense(w))
    want = np.asarray(ref.decompress_24(w["vals"], w["idx"]), np.float32)
    assert np.array_equal(got, want)
    groups = (got.reshape(-1, 4, got.shape[1]) != 0).sum(1)
    assert groups.max() <= 2


def _program_logits(cfg, params, tokens):
    import jax.numpy as jnp

    from repro.kernels.ref import decompress_24
    from repro.models import LM
    dense = {**params, "layers": {"s0": {
        mod: {k: (jnp.stack([decompress_24(v["vals"][i], v["idx"][i])
                             for i in range(cfg.num_layers)])
                  if isinstance(v, dict) and "vals" in v else v)
              for k, v in sub.items()}
        for mod, sub in params["layers"]["s0"].items()}}}
    model = LM(cfg)
    logits, _ = model.prefill(dense, {"tokens": jnp.asarray(tokens)[None]},
                              model.init_cache(1, len(tokens)))
    return np.asarray(logits[0], np.float64)


def test_forward_matches_program_f32():
    import jax
    for arch in ("qwen1_5_0_5b", "qwen3_14b"):
        config = tiny_config(arch)
        cfg = harness.build_arch(config)
        from repro.models import LM
        params = weights.make(LM(cfg), harness.seed_key(7), packed=True)
        toks = np.arange(3, 40) % cfg.vocab_size
        rows = np.arange(len(toks))
        with jax.default_matmul_precision("highest"):
            want = _program_logits(cfg, params, toks)
        got = np.asarray(forward.logits_at(params, toks, rows, config))
        # the program returns the last position's logits from prefill
        last = want.reshape(-1, got.shape[-1])[-1]
        assert np.max(np.abs(got[-1] - last)) < 1e-4 * max(
            1.0, np.max(np.abs(last)))


def test_fp8_control_differs():
    from repro.models import LM
    config = tiny_config()
    cfg = harness.build_arch(config)
    params = weights.make(LM(cfg), harness.seed_key(8), packed=True)
    toks = np.arange(5, 30) % cfg.vocab_size
    rows = np.arange(len(toks))
    a = np.asarray(forward.logits_at(params, toks, rows, config))
    b = np.asarray(forward.logits_at(params, toks, rows, config, "fp8"))
    assert 1e-3 < np.max(np.abs(a - b)) < 0.5 * np.max(np.abs(a))


def test_sm_sweep_matches_smoke_script():
    sys.path.insert(0, str(CHIP.parents[1]))
    import chip_smoke
    rng = np.random.default_rng(0)
    x = rng.standard_normal((256, 32))
    h = 2.0 * x.T @ x / 256
    w0 = rng.standard_normal((6, 32))
    got_w, got_m = sm_sweep.sm_sweep(w0, h, 16, 0.01)
    want_w, want_m = chip_smoke.sm_reference(w0, h, 16, 0.01)
    assert np.array_equal(got_m, want_m)
    assert np.allclose(got_w, want_w, rtol=0, atol=1e-12)
    assert sm_sweep.recon_error(got_w, w0, h) > 0


def test_config_dict_is_a_dataclass_free_copy():
    # the reference reads only the configuration file's keys
    c = forward.consts(tiny_config("qwen3_14b"))
    assert c == {"heads": 4, "kv_heads": 2, "head_dim": 16, "eps": 1e-6,
                 "rope_theta": 1e6, "layers": 2, "tied": False}
    assert not dataclasses.is_dataclass(c)


def test_device_sweep_matches_float64_sweep():
    rng = np.random.default_rng(1)
    x = rng.standard_normal((1024, 128)) * rng.uniform(0.2, 2.0, 128)
    h = 2.0 * x.T @ x / 1024
    w0 = rng.standard_normal((24, 128))
    want_w, want_m = sm_sweep.sm_sweep(w0, h, 32, 0.01)
    got = np.asarray(sm_device.sweep(w0, h, 32, 0.01), np.float64)
    assert np.array_equal(got == 0, want_m)
    assert np.max(np.abs(got - want_w)) < 1e-5 * np.max(np.abs(want_w))


def test_projection_is_the_sweep_for_its_own_mask():
    rng = np.random.default_rng(2)
    x = rng.standard_normal((512, 48))
    h = 2.0 * x.T @ x / 512
    w0 = rng.standard_normal((5, 48))
    w, m = sm_sweep.sm_sweep(w0, h, 16, 0.01)
    got = sm_sweep.project(w0, sm_sweep.dampened_inverse(h, 0.01), m)
    assert np.allclose(got, w, rtol=0, atol=1e-10)
    fp8 = sm_sweep.round_fp8(w)
    assert np.array_equal(fp8 == 0, w == 0)
    assert 1e-3 < np.max(np.abs(fp8 - w)) / np.max(np.abs(w)) < 0.1
