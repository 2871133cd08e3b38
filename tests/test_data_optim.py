"""Data pipeline determinism + optimizer/compression numerics."""

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.data import DataPipeline, MarkovCorpus, calibration_batches
from repro.optim import AdamW, ef_init, ef_quantize
from repro.optim.compression import dequantize_int8, quantize_int8
from repro.optim.schedules import warmup_cosine


# ----------------------------------------------------------------------
def test_batches_deterministic_by_step():
    cfg = get_config("paper_tiny_lm")
    a = DataPipeline(cfg, 4, 32, seed=0)
    b = DataPipeline(cfg, 4, 32, seed=0)
    for step in (0, 3, 17):
        np.testing.assert_array_equal(
            np.asarray(a.batch_at(step)["tokens"]),
            np.asarray(b.batch_at(step)["tokens"]))
    # different steps/streams/seeds differ
    assert not np.array_equal(np.asarray(a.batch_at(0)["tokens"]),
                              np.asarray(a.batch_at(1)["tokens"]))
    assert not np.array_equal(np.asarray(a.batch_at(0)["tokens"]),
                              np.asarray(a.eval_batch(0)["tokens"]))
    c = DataPipeline(cfg, 4, 32, seed=1)
    assert not np.array_equal(np.asarray(a.batch_at(0)["tokens"]),
                              np.asarray(c.batch_at(0)["tokens"]))


def test_corpus_markov_structure():
    """Transitions follow the chain: successor distribution concentrated."""
    corpus = MarkovCorpus(128, seed=0)
    toks = np.asarray(corpus.batch_at(0, 0, 64, 256))
    assert toks.shape == (64, 256)
    assert toks.min() >= 0 and toks.max() < 128
    # empirical next-token entropy must be far below uniform
    pairs = {}
    for row in toks:
        for a, b in zip(row[:-1], row[1:]):
            pairs.setdefault(int(a), []).append(int(b))
    ents = []
    for a, succ in pairs.items():
        if len(succ) >= 30:
            _, counts = np.unique(succ, return_counts=True)
            p = counts / counts.sum()
            ents.append(-(p * np.log(p)).sum())
    assert np.mean(ents) < 0.7 * np.log(128)


def test_corpus_rows_without_table():
    """A vocabulary too large for the (V, V) table samples rows built on
    demand: deterministic, in range, and still a concentrated chain."""
    from repro.data.synthetic import TABLE_MAX_ENTRIES

    vocab = 8192
    assert vocab * vocab > TABLE_MAX_ENTRIES
    corpus = MarkovCorpus(vocab, seed=0)
    toks = np.asarray(corpus.batch_at(0, 0, 64, 256))
    np.testing.assert_array_equal(
        toks, np.asarray(MarkovCorpus(vocab, seed=0).batch_at(0, 0, 64, 256)))
    assert toks.min() >= 0 and toks.max() < vocab
    pairs = {}
    for row in toks:
        for a, b in zip(row[:-1], row[1:]):
            pairs.setdefault(int(a), []).append(int(b))
    ents = []
    for a, succ in pairs.items():
        if len(succ) >= 30:
            _, counts = np.unique(succ, return_counts=True)
            p = counts / counts.sum()
            ents.append(-(p * np.log(p)).sum())
    assert ents and np.mean(ents) < 0.7 * np.log(vocab)


def test_calibration_batches_shapes():
    cfg = get_config("paper_tiny_lm")
    batches = calibration_batches(cfg, n_samples=16, seq_len=32, batch=8)
    assert len(batches) == 2
    assert batches[0]["tokens"].shape == (8, 32)


# ----------------------------------------------------------------------
def test_adamw_converges_quadratic():
    """Minimize ||x - target||² — AdamW must get close."""
    target = jnp.asarray([1.0, -2.0, 3.0])
    params = {"x": jnp.zeros(3)}
    opt = AdamW(lr=0.1, weight_decay=0.0, clip_norm=None)
    state = opt.init(params)
    for _ in range(300):
        grads = {"x": 2 * (params["x"] - target)}
        params, state, _ = opt.update(grads, state, params)
    np.testing.assert_allclose(np.asarray(params["x"]), np.asarray(target),
                               atol=1e-2)


def test_adamw_bf16_moments_close_to_f32():
    key = jax.random.key(0)
    params = {"w": jax.random.normal(key, (16, 16))}
    g = {"w": jax.random.normal(jax.random.fold_in(key, 1), (16, 16))}
    o32 = AdamW(lr=1e-2, moment_dtype="float32")
    o16 = AdamW(lr=1e-2, moment_dtype="bfloat16")
    p32, s32 = params, o32.init(params)
    p16, s16 = params, o16.init(params)
    for _ in range(5):
        p32, s32, _ = o32.update(g, s32, p32)
        p16, s16, _ = o16.update(g, s16, p16)
    np.testing.assert_allclose(np.asarray(p32["w"]), np.asarray(p16["w"]),
                               atol=2e-2)
    assert s16.mu["w"].dtype == jnp.bfloat16


def test_grad_clip():
    params = {"x": jnp.zeros(4)}
    opt = AdamW(lr=1.0, clip_norm=1.0, weight_decay=0.0)
    state = opt.init(params)
    _, _, stats = opt.update({"x": jnp.full((4,), 100.0)}, state, params)
    assert float(stats["grad_norm"]) == 200.0  # pre-clip norm reported


def test_schedule_warmup_and_decay():
    lr = warmup_cosine(1.0, 10, 100)
    assert float(lr(jnp.int32(0))) == 0.0
    assert abs(float(lr(jnp.int32(10))) - 1.0) < 1e-6
    assert float(lr(jnp.int32(100))) < 1e-6
    assert 0.4 < float(lr(jnp.int32(55))) < 0.6


# ----------------------------------------------------------------------
def test_int8_quantize_roundtrip_error_bounded():
    x = jax.random.normal(jax.random.key(0), (1000,)) * 5
    q, s = quantize_int8(x)
    err = np.abs(np.asarray(dequantize_int8(q, s)) - np.asarray(x))
    assert err.max() <= float(s) * 0.5 + 1e-6


def test_error_feedback_unbiased_over_time():
    """Constant gradient: EF-compressed mean over T steps → g with error
    ≤ half-quantization-step / T (the residual carries what each round
    dropped, so the *cumulative* emission is exact up to the last
    residual — the whole point of error feedback)."""
    g = {"w": jnp.asarray([1e-4, 5.0, -3.0, 2e-5])}
    res = ef_init(g)
    total = jnp.zeros(4)
    T = 400
    for _ in range(T):
        deq, res = ef_quantize(g, res)
        total = total + deq["w"]
    half_step = 5.0 / 127 / 2
    err = np.abs(np.asarray(total) / T - np.asarray(g["w"]))
    assert err.max() <= half_step / T + 1e-7
    # WITHOUT error feedback the tiny components would be lost entirely:
    zero = ef_init(g)
    deq_nof, _ = ef_quantize(g, zero)
    assert float(deq_nof["w"][0]) == 0.0   # 1e-4 under half-step → dropped
