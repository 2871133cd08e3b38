"""Continuous-batching serve runtime: paged KV pool, scheduler, engine.

Covers the ISSUE-3/ISSUE-4/ISSUE-5 acceptance surface: pool alloc/
release/preemption unit behavior, paged-vs-dense decode and chunked-
prefill bit-parity (greedy, CPU), continuous-vs-static engine
equivalence (attention, Mamba, xLSTM and hybrid archs — no static
fallback; plain, under a mesh, and with 2:4-sparse weights), top-k/
top-p sampling determinism under the per-(uid, step) key scheme, the
recurrent-state slot pool, the Result utilization accounting, and the
device-resident fused decode loop (ISSUE-5): ``steps_per_sync=1`` vs
``=8`` token bit-parity across greedy/top-k/top-p, preemption-
recompute, EOS mid-burst, host-sync accounting, the non-preempting
burst page lookahead, and a 2x4-mesh subprocess run.
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_smoke
from repro.models import LM
from repro.models.base import ArchConfig
from repro.serve import (PagedKVPool, Request, Scheduler, SeqState,
                         ServeEngine, StatePool)

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

# jamba-shaped hybrid (mamba + attention interleave) WITHOUT MoE —
# expert-capacity dropping is what keeps real Jamba on the static path,
# so this pins the hybrid continuous-batching mechanics separately
HYBRID = ArchConfig(
    name="hybrid-serve-test",
    family="hybrid",
    num_layers=4,
    d_model=64,
    num_heads=4,
    num_kv_heads=2,
    head_dim=16,
    d_ff=128,
    vocab_size=256,
    period=("mamba", "attn"),
    mlp_kind="swiglu",
    ssm_mlp=True,
    ssm_state=4,
    ssm_conv=4,
    dtype="float32",
)


def _sharpened(cfg, seed=0):
    """Random-init model with a sharpened head: greedy argmax gaps wide
    enough to be robust to chunked-vs-dense reduction-order rounding."""
    model = LM(cfg)
    params = model.init(jax.random.key(seed))
    if cfg.tie_embeddings:
        params["embed"]["tok"] = params["embed"]["tok"] * 8.0
    else:
        params["unembed"]["head"] = params["unembed"]["head"] * 8.0
    return model, params


# Programs of different shapes (whole-prompt prefill vs fixed-size
# chunks, dense cache vs paged pool) lower to different f32 reduction
# orders, so their logits agree to f32 rounding accumulated over the
# model's depth, not bit for bit: XLA's CPU backend (jax 0.9) differs by
# ~1.4e-5 on these O(0.1-1) logits.
F32_LOGIT_ATOL = 1e-4


@pytest.fixture(scope="module")
def tiny_random():
    """Random-init full tiny LM with a sharpened head: greedy argmax
    gaps are wide enough to be robust to sharding reduction order."""
    cfg = get_config("paper_tiny_lm")
    model = LM(cfg)
    params = model.init(jax.random.key(0))
    params["unembed"]["head"] = params["unembed"]["head"] * 8.0
    return model, params


def _mixed_requests(vocab, n=10):
    rng = np.random.default_rng(0)
    return [
        Request(uid=i,
                prompt=rng.integers(0, vocab, size=(4, 7, 12)[i % 3],
                                    dtype=np.int32),
                max_new_tokens=(2, 5, 9, 14)[i % 4])
        for i in range(n)
    ]


# ======================================================================
# kvpool
# ======================================================================
def test_pool_alloc_release(tiny_random):
    model, _ = tiny_random
    pool = PagedKVPool(model, num_pages=9, page_size=8, max_slots=4,
                       max_len=32)
    assert pool.capacity == 8 and pool.free_pages == 8
    a = pool.alloc(3)
    assert len(a) == 3 and 0 not in a          # page 0 is scrap
    b = pool.alloc(5)
    assert pool.free_pages == 0
    assert pool.alloc(1) is None               # exhausted, all-or-nothing
    pool.release(b)
    assert pool.free_pages == 5
    c = pool.alloc(5)
    assert sorted(c) == sorted(b)
    # n=0 must not touch the free list ([-0:] slices everything)
    assert pool.alloc(0) == []
    assert pool.free_pages == 0


def test_pool_block_tables(tiny_random):
    model, _ = tiny_random
    pool = PagedKVPool(model, num_pages=9, page_size=8, max_slots=2,
                       max_len=32)
    pages = pool.alloc(2)
    pool.assign(0, pages)
    assert pool.slot_page_count(0) == 2
    assert pool.slot_pages(0) == pages
    np.testing.assert_array_equal(pool.block_tables[0, :2], pages)
    pool.clear_slot(0)
    assert pool.slot_page_count(0) == 0
    assert (pool.block_tables[0] == 0).all()
    assert pool.free_pages == 8
    pool.reset()
    assert pool.free_pages == 8


# ======================================================================
# scheduler
# ======================================================================
def _sched(model, num_pages=17, page_size=8, max_slots=2, max_len=64):
    pool = PagedKVPool(model, num_pages=num_pages, page_size=page_size,
                       max_slots=max_slots, max_len=max_len)
    return Scheduler(pool, max_slots), pool


def test_scheduler_admission_and_retire(tiny_random):
    model, _ = tiny_random
    sched, pool = _sched(model)
    seqs = [sched.submit(Request(uid=i, prompt=np.arange(6, dtype=np.int32)))
            for i in range(3)]
    admitted = sched.admit()
    assert [s.req.uid for s in admitted] == [0, 1]   # 2 slots, FIFO
    # admitted requests enter PREFILL; the engine feeds prompt chunks
    assert all(s.state is SeqState.PREFILL for s in admitted)
    assert sched.next_prefill() is admitted[0]       # oldest first
    assert sched.decoding() == []
    assert pool.free_pages == pool.capacity - 2      # 1 prompt page each
    sched.finish(seqs[0])                            # retire-at-EOS
    assert seqs[0].state is SeqState.FINISHED
    assert [s.req.uid for s in sched.admit()] == [2]  # slot recycled
    assert sched.has_work()


def test_scheduler_preempts_youngest(tiny_random):
    model, _ = tiny_random
    # 4 pages: two 1-page prompts admit, then growth exhausts the pool
    sched, pool = _sched(model, num_pages=5, page_size=8)
    a = sched.submit(Request(uid=0, prompt=np.arange(8, dtype=np.int32)))
    b = sched.submit(Request(uid=1, prompt=np.arange(8, dtype=np.int32)))
    assert len(sched.admit()) == 2
    for s, n in ((a, 8), (b, 8)):
        s.state = SeqState.RUNNING                   # prefill done
        s.n_prefilled = n
        s.n_written = n
        s.tokens = [1]
    pool.alloc(pool.free_pages)                      # drain the free list
    sched.ensure_decode_capacity()
    # the OLDEST request got the victim's page; the youngest re-queued
    assert a.state is SeqState.RUNNING
    assert pool.slot_page_count(a.slot) == 2
    assert b.state is SeqState.WAITING
    assert b.preemptions == 1 and b.n_written == 0 and b.tokens == []
    assert b.n_prefilled == 0                        # recompute from scratch
    assert sched.waiting[0] is b                     # front of the queue


def test_scheduler_single_request_exhaustion(tiny_random):
    model, _ = tiny_random
    sched, pool = _sched(model, num_pages=2, page_size=8, max_slots=1)
    a = sched.submit(Request(uid=0, prompt=np.arange(8, dtype=np.int32)))
    assert sched.admit() == [a]
    a.state = SeqState.RUNNING
    a.n_written = 8
    with pytest.raises(RuntimeError, match="exhausted"):
        sched.ensure_decode_capacity()


def test_scheduler_oversized_prompt_raises(tiny_random):
    model, _ = tiny_random
    sched, _ = _sched(model, num_pages=3, page_size=8, max_len=64)
    sched.submit(Request(uid=0, prompt=np.zeros(40, np.int32)))
    with pytest.raises(RuntimeError, match="prompt needs"):
        sched.admit()


# ======================================================================
# engine: paged vs dense equivalence
# ======================================================================
def test_continuous_matches_static_greedy(tiny_random):
    model, params = tiny_random
    reqs = _mixed_requests(model.cfg.vocab_size)
    static = ServeEngine(model, params, max_batch=4, max_len=48,
                         mode="static")
    cont = ServeEngine(model, params, max_batch=4, max_len=48,
                       mode="continuous", page_size=8)
    rs = static.generate(reqs)
    rc = cont.generate(reqs)
    for a, b in zip(rs, rc):
        assert a.uid == b.uid
        np.testing.assert_array_equal(a.tokens, b.tokens)


def test_paged_decode_bit_parity(tiny_random):
    """Model-level: paged prefill+decode logits match the dense cache
    path to f32 rounding (greedy CPU acceptance criterion)."""
    model, params = tiny_random
    ps = 8
    prompt = np.asarray([1, 2, 3, 4, 5], np.int32)
    L = len(prompt)

    cache = model.init_cache(1, 48)
    lg, cache = model.prefill(
        params, {"tokens": jnp.asarray(prompt[None])}, cache)
    dense = [np.asarray(lg[0])]
    tok = jnp.argmax(lg, -1).astype(jnp.int32)
    for step in range(6):
        lg, cache = model.decode_step(params, tok, cache,
                                      jnp.asarray(L + step, jnp.int32))
        dense.append(np.asarray(lg[0]))
        tok = jnp.argmax(lg, -1).astype(jnp.int32)

    kv = model.init_paged_cache(12, ps)
    bt = np.zeros((1, 6), np.int32)
    bt[0, 0] = 3
    toks = np.zeros((1, 8), np.int32)
    toks[0, :L] = prompt
    lg, kv = model.prefill_paged(
        params, {"tokens": jnp.asarray(toks)}, kv,
        lengths=jnp.asarray([L], jnp.int32),
        block_tables=jnp.asarray(bt), page_size=ps)
    paged = [np.asarray(lg[0])]
    tok = jnp.argmax(lg, -1).astype(jnp.int32)
    n = L
    for step in range(6):
        if n // ps >= 1 and bt[0, n // ps] == 0:
            bt[0, n // ps] = 5 + n // ps
        lg, kv = model.decode_step(
            params, tok, kv, jnp.asarray([n], jnp.int32),
            paged={"block_tables": jnp.asarray(bt)}, page_size=ps)
        paged.append(np.asarray(lg[0]))
        tok = jnp.argmax(lg, -1).astype(jnp.int32)
        n += 1

    for d, p in zip(dense, paged):
        np.testing.assert_allclose(d, p, rtol=0, atol=F32_LOGIT_ATOL)


def test_preemption_reproduces_tokens(tiny_random):
    """A pool too small for the full workload forces preemptions; the
    recompute must reproduce the exact static tokens.  (num_pages=6:
    the prefill-fused K=8 bursts retire short requests within one
    interval and recycle their pages at the sync, so an 8-page pool no
    longer comes under enough step-one pressure to preempt.)"""
    model, params = tiny_random
    reqs = _mixed_requests(model.cfg.vocab_size)
    static = ServeEngine(model, params, max_batch=4, max_len=48,
                         mode="static")
    small = ServeEngine(model, params, max_batch=4, max_len=48,
                        mode="continuous", page_size=8, num_pages=6)
    rs = static.generate(reqs)
    rp = small.generate(reqs)
    assert sum(r.preemptions for r in rp) > 0
    for a, b in zip(rs, rp):
        np.testing.assert_array_equal(a.tokens, b.tokens)


def test_continuous_eos_stops_early(tiny_random):
    model, params = tiny_random
    eng = ServeEngine(model, params, max_batch=2, max_len=64, page_size=8)
    probe = eng.generate(
        [Request(uid=0, prompt=np.asarray([3, 1], np.int32),
                 max_new_tokens=1)])
    eos = int(probe[0].tokens[0])
    eng2 = ServeEngine(model, params, max_batch=2, max_len=64,
                       page_size=8, eos_id=eos)
    res = eng2.generate(
        [Request(uid=0, prompt=np.asarray([3, 1], np.int32),
                 max_new_tokens=8)])
    assert len(res[0].tokens) == 1 and int(res[0].tokens[0]) == eos


def test_continuous_temperature_deterministic(tiny_random):
    """Per-(uid, step) sampling keys: the same request sampled alone or
    in a batch draws the same stream."""
    model, params = tiny_random
    eng = ServeEngine(model, params, max_batch=4, max_len=48,
                      temperature=1.0, page_size=8)
    reqs = _mixed_requests(model.cfg.vocab_size, n=4)
    batched = eng.generate(reqs, seed=7)
    solo = eng.generate([reqs[2]], seed=7)
    np.testing.assert_array_equal(batched[2].tokens, solo[0].tokens)


def test_utilization_accounting(tiny_random):
    """Satellite: Result.decode_steps exposes the static scrap waste
    that continuous batching recovers."""
    model, params = tiny_random
    reqs = [Request(uid=0, prompt=np.arange(4, dtype=np.int32),
                    max_new_tokens=2),
            Request(uid=1, prompt=np.arange(4, dtype=np.int32),
                    max_new_tokens=12)]
    rs = ServeEngine(model, params, max_batch=2, max_len=32,
                     mode="static").generate(reqs)
    rc = ServeEngine(model, params, max_batch=2, max_len=32,
                     mode="continuous", page_size=8).generate(reqs)
    # static: the short request holds its slot for all 12 bucket steps
    assert rs[0].decode_steps == 12
    assert rs[0].utilization == pytest.approx(2 / 12)
    assert rs[1].utilization == 1.0
    # continuous: every occupied step emits a token
    assert rc[0].decode_steps == 2 and rc[0].utilization == 1.0
    assert rc[1].utilization == 1.0


def test_zero_max_new_tokens_matches_static(tiny_random):
    model, params = tiny_random
    reqs = [Request(uid=0, prompt=np.arange(4, dtype=np.int32),
                    max_new_tokens=0),
            Request(uid=1, prompt=np.arange(4, dtype=np.int32),
                    max_new_tokens=3)]
    rs = ServeEngine(model, params, max_batch=2, max_len=32,
                     mode="static").generate(reqs)
    rc = ServeEngine(model, params, max_batch=2, max_len=32,
                     mode="continuous", page_size=8).generate(reqs)
    assert len(rs[0].tokens) == 0 and len(rc[0].tokens) == 0
    np.testing.assert_array_equal(rs[1].tokens, rc[1].tokens)


# ======================================================================
# chunked paged prefill
# ======================================================================
def test_prefill_chunk_bit_parity(tiny_random):
    """Model-level: streaming a prompt through fixed-size prefill_chunk
    calls yields final logits equal to the dense prefill's to f32
    rounding."""
    model, params = tiny_random
    prompt = np.asarray([5, 4, 3, 2, 1, 9, 8, 7, 6, 2, 3], np.int32)
    L = len(prompt)
    cache = model.init_cache(1, 48)
    want, _ = model.prefill(
        params, {"tokens": jnp.asarray(prompt[None])}, cache)

    ps, C = 8, 4
    kv = model.init_paged_cache(12, ps)
    bt = np.zeros((1, 6), np.int32)
    bt[0, 0], bt[0, 1] = 3, 5
    step = jax.jit(model.prefill_chunk, static_argnames=("page_size",))
    got = None
    for start in range(0, L, C):
        chunk = np.zeros((1, C), np.int32)
        piece = prompt[start:start + C]
        chunk[0, :len(piece)] = piece
        got, kv = step(
            params, {"tokens": jnp.asarray(chunk)}, kv,
            jnp.asarray(start, jnp.int32), jnp.asarray(L, jnp.int32),
            jnp.asarray(0, jnp.int32), jnp.asarray(bt), page_size=ps)
    np.testing.assert_allclose(np.asarray(want), np.asarray(got), rtol=0,
                               atol=F32_LOGIT_ATOL)


def test_multi_chunk_prefill_matches_static(tiny_random):
    """Engine-level: a chunk smaller than most prompts (every request
    takes 2-3 chunks) still emits the static greedy tokens."""
    model, params = tiny_random
    reqs = _mixed_requests(model.cfg.vocab_size)
    rs = ServeEngine(model, params, max_batch=4, max_len=48,
                     mode="static").generate(reqs)
    rc = ServeEngine(model, params, max_batch=4, max_len=48,
                     mode="continuous", page_size=8,
                     prefill_chunk=4).generate(reqs)
    for a, b in zip(rs, rc):
        np.testing.assert_array_equal(a.tokens, b.tokens)


def test_chunked_prefill_occupies_steps(tiny_random):
    """A multi-chunk prompt holds its slot for every chunk step — the
    utilization accounting stays honest about prefill occupancy."""
    model, params = tiny_random
    reqs = [Request(uid=0, prompt=np.arange(12, dtype=np.int32),
                    max_new_tokens=4)]
    res = ServeEngine(model, params, max_batch=2, max_len=32,
                      mode="continuous", page_size=8,
                      prefill_chunk=4).generate(reqs)
    # 3 prefill chunks (the last samples token 0) + 3 decode steps
    assert res[0].decode_steps == 6
    assert res[0].utilization == pytest.approx(4 / 6)


# ======================================================================
# recurrent-state paging (Mamba / xLSTM / hybrid)
# ======================================================================
@pytest.mark.parametrize("arch", ["mamba", "xlstm", "hybrid"])
def test_recurrent_arch_continuous_matches_static(arch):
    """Mamba/xLSTM/hybrid archs serve through mode="continuous" (no
    static fallback) with greedy tokens identical to the dense-cache
    static path — multi-chunk prefills included."""
    if arch == "mamba":
        from repro.configs.paper_tiny_lm import MAMBA as cfg
    elif arch == "xlstm":
        cfg = get_smoke("xlstm_350m")
    else:
        cfg = HYBRID
    model, params = _sharpened(cfg)
    reqs = _mixed_requests(cfg.vocab_size, n=6)
    rs = ServeEngine(model, params, max_batch=4, max_len=48,
                     mode="static").generate(reqs)
    eng = ServeEngine(model, params, max_batch=4, max_len=48,
                      mode="continuous", page_size=8, prefill_chunk=8)
    assert eng.mode == "continuous"          # no fallback
    rc = eng.generate(reqs)
    for a, b in zip(rs, rc):
        np.testing.assert_array_equal(a.tokens, b.tokens)


def test_recurrent_preemption_reproduces_tokens():
    """Hybrid arch under a starved pool: preemption drops pages AND
    state rows; the recompute (fresh state reset at re-admission)
    reproduces the static tokens exactly."""
    model, params = _sharpened(HYBRID)
    reqs = _mixed_requests(HYBRID.vocab_size, n=8)
    rs = ServeEngine(model, params, max_batch=4, max_len=48,
                     mode="static").generate(reqs)
    small = ServeEngine(model, params, max_batch=4, max_len=48,
                        mode="continuous", page_size=8, prefill_chunk=8,
                        num_pages=6)
    rp = small.generate(reqs)
    assert sum(r.preemptions for r in rp) > 0
    for a, b in zip(rs, rp):
        np.testing.assert_array_equal(a.tokens, b.tokens)


def test_state_pool_resets_slot_rows():
    from repro.configs.paper_tiny_lm import MAMBA

    model = LM(MAMBA)
    pool = StatePool(model, max_slots=3)
    assert pool.has_state
    kv = model.init_paged_cache(4, 8, max_slots=3)
    # dirty every slot row of every state leaf
    dirty = jax.tree.map(lambda x: x + 7.0, kv)
    clean = pool.reset_slot(dirty, 1)
    for leaf, ref in zip(jax.tree.leaves(clean), jax.tree.leaves(kv)):
        # slot 1 restored to init, slots 0/2 still dirty (leading dim is
        # the scan layer stack; slots live on dim 1)
        np.testing.assert_array_equal(np.asarray(leaf[:, 1]),
                                      np.asarray(ref[:, 1]))
        assert not np.array_equal(np.asarray(leaf[:, 0]),
                                  np.asarray(ref[:, 0]))


def test_attention_arch_has_no_state_pool(tiny_random):
    model, _ = tiny_random
    assert not StatePool(model, max_slots=2).has_state


# ======================================================================
# top-k / top-p sampling
# ======================================================================
@pytest.mark.parametrize("kw", [dict(temperature=1.0, top_k=20),
                                dict(temperature=0.8, top_p=0.9)])
def test_topk_topp_deterministic_and_preemption_exact(tiny_random, kw):
    """Per-(uid, step) keys thread through top-k/p filtering: the same
    request draws the same stream alone or batched, and a preempted
    request's recompute replays it bit-exact."""
    model, params = tiny_random
    reqs = _mixed_requests(model.cfg.vocab_size, n=8)
    eng = ServeEngine(model, params, max_batch=4, max_len=48,
                      page_size=8, prefill_chunk=8, **kw)
    batched = eng.generate(reqs, seed=7)
    solo = eng.generate([reqs[2]], seed=7)
    np.testing.assert_array_equal(batched[2].tokens, solo[0].tokens)
    small = ServeEngine(model, params, max_batch=4, max_len=48,
                        page_size=8, prefill_chunk=8, num_pages=6, **kw)
    rp = small.generate(reqs, seed=7)
    assert sum(r.preemptions for r in rp) > 0
    for a, b in zip(batched, rp):
        np.testing.assert_array_equal(a.tokens, b.tokens)


def test_topk_restricts_support(tiny_random):
    """top_k=1 must reduce to greedy regardless of temperature."""
    model, params = tiny_random
    reqs = _mixed_requests(model.cfg.vocab_size, n=4)
    greedy = ServeEngine(model, params, max_batch=4, max_len=48,
                         page_size=8).generate(reqs)
    k1 = ServeEngine(model, params, max_batch=4, max_len=48, page_size=8,
                     temperature=3.0, top_k=1).generate(reqs, seed=11)
    for a, b in zip(greedy, k1):
        np.testing.assert_array_equal(a.tokens, b.tokens)


def test_moe_arch_falls_back_to_static():
    """MoE expert-capacity dropping makes logits batch-dependent, so the
    continuous path's parity guarantees can't hold — must fall back."""
    from repro.configs import get_smoke

    model = LM(get_smoke("phi3_5_moe_42b_a6_6b"))
    params = model.init(jax.random.key(0))
    eng = ServeEngine(model, params, max_batch=2, max_len=32,
                      mode="continuous")
    assert eng.mode == "static"


# ======================================================================
# device-resident fused decode loop (ISSUE-5, serve.fused)
# ======================================================================
def test_fused_burst_parity_greedy(tiny_random):
    """steps_per_sync=1 and =8 emit bit-identical greedy tokens (and
    match static): the burst length is a dynamic field of the state
    blob, so every K runs the same compiled fused body.  The burst
    engine must also sync the host strictly less often per token."""
    model, params = tiny_random
    reqs = _mixed_requests(model.cfg.vocab_size)
    rs = ServeEngine(model, params, max_batch=4, max_len=48,
                     mode="static").generate(reqs)
    stats = {}
    for sps in (1, 8):
        eng = ServeEngine(model, params, max_batch=4, max_len=48,
                          page_size=8, steps_per_sync=sps)
        rc = eng.generate(reqs)
        stats[sps] = dict(eng.stats)
        for a, b in zip(rs, rc):
            assert a.uid == b.uid
            np.testing.assert_array_equal(a.tokens, b.tokens)
    total = sum(len(r.tokens) for r in rs)
    assert stats[1]["tokens"] == stats[8]["tokens"] == total
    # the whole point of the burst: fewer blocking readbacks per token
    assert stats[8]["host_syncs"] < stats[1]["host_syncs"]
    # per-step mode syncs at least once per decode step
    assert stats[1]["host_syncs"] >= stats[1]["device_steps"]


@pytest.mark.parametrize("kw", [dict(temperature=1.0, top_k=20),
                                dict(temperature=0.8, top_p=0.9)])
def test_fused_burst_parity_sampled(tiny_random, kw):
    """top-k / top-p streams are steps_per_sync-independent (the fused
    step draws under the same per-(uid, step) keys), including across
    preemption-recompute under a starved pool."""
    model, params = tiny_random
    reqs = _mixed_requests(model.cfg.vocab_size, n=8)
    base = ServeEngine(model, params, max_batch=4, max_len=48,
                       page_size=8, steps_per_sync=1,
                       **kw).generate(reqs, seed=7)
    burst = ServeEngine(model, params, max_batch=4, max_len=48,
                        page_size=8, steps_per_sync=8,
                        **kw).generate(reqs, seed=7)
    for a, b in zip(base, burst):
        np.testing.assert_array_equal(a.tokens, b.tokens)
    small = ServeEngine(model, params, max_batch=4, max_len=48,
                        page_size=8, num_pages=6, steps_per_sync=8, **kw)
    rp = small.generate(reqs, seed=7)
    assert sum(r.preemptions for r in rp) > 0
    for a, b in zip(base, rp):
        np.testing.assert_array_equal(a.tokens, b.tokens)


def test_fused_burst_eos_mid_burst(tiny_random):
    """A request hitting EOS inside a burst freezes on device (its
    remaining burst steps treat the slot idle) and retires at the next
    sync with exactly the per-step loop's tokens."""
    model, params = tiny_random
    probe = ServeEngine(model, params, max_batch=2, max_len=64,
                        page_size=8).generate(
        [Request(uid=0, prompt=np.asarray([3, 1], np.int32),
                 max_new_tokens=1)])
    eos = int(probe[0].tokens[0])
    reqs = [Request(uid=0, prompt=np.asarray([3, 1], np.int32),
                    max_new_tokens=12),
            Request(uid=1, prompt=np.asarray([5, 2, 4], np.int32),
                    max_new_tokens=12)]
    r1 = ServeEngine(model, params, max_batch=2, max_len=64, page_size=8,
                     eos_id=eos, steps_per_sync=1).generate(reqs)
    r8 = ServeEngine(model, params, max_batch=2, max_len=64, page_size=8,
                     eos_id=eos, steps_per_sync=8).generate(reqs)
    for a, b in zip(r1, r8):
        np.testing.assert_array_equal(a.tokens, b.tokens)
    # uid 0 really stopped at EOS, mid-burst
    assert len(r8[0].tokens) == 1 and int(r8[0].tokens[0]) == eos


def test_fused_burst_recurrent_arch():
    """The jamba-shaped hybrid through 8-step bursts: recurrent-state
    rows advance inside the device loop (idle rows frozen by the pos<0
    mask) with tokens identical to per-step mode.  (Mamba/xLSTM run
    the burst default in test_recurrent_arch_continuous_matches_static
    already — this pins the K-independence explicitly on a hybrid.)"""
    model, params = _sharpened(HYBRID)
    reqs = _mixed_requests(HYBRID.vocab_size, n=6)
    r1 = ServeEngine(model, params, max_batch=4, max_len=48,
                     page_size=8, prefill_chunk=8,
                     steps_per_sync=1).generate(reqs)
    r8 = ServeEngine(model, params, max_batch=4, max_len=48,
                     page_size=8, prefill_chunk=8,
                     steps_per_sync=8).generate(reqs)
    for a, b in zip(r1, r8):
        np.testing.assert_array_equal(a.tokens, b.tokens)


def test_static_fused_early_exit_variants(tiny_random):
    """Static mode: the no-EOS equal-max_new bucket takes the fori
    variant (no done bookkeeping at all — the satellite fast path), the
    mixed bucket the while variant; both match continuous."""
    model, params = tiny_random
    prompts = [np.arange(4, dtype=np.int32) + i for i in range(3)]
    equal = [Request(uid=i, prompt=p, max_new_tokens=6)
             for i, p in enumerate(prompts)]
    eng = ServeEngine(model, params, max_batch=4, max_len=32,
                      mode="static")
    rs = eng.generate(equal)
    assert set(eng._static_bursts) == {False}       # fori path only
    rc = ServeEngine(model, params, max_batch=4, max_len=32,
                     mode="continuous", page_size=8).generate(equal)
    for a, b in zip(rs, rc):
        np.testing.assert_array_equal(a.tokens, b.tokens)
    mixed = [Request(uid=i, prompt=p, max_new_tokens=4 + 3 * i)
             for i, p in enumerate(prompts)]
    rs = eng.generate(mixed)
    assert set(eng._static_bursts) == {False, True}  # while path now too
    rc = ServeEngine(model, params, max_batch=4, max_len=32,
                     mode="continuous", page_size=8).generate(mixed)
    for a, b in zip(rs, rc):
        np.testing.assert_array_equal(a.tokens, b.tokens)


def test_extend_capacity_never_preempts(tiny_random):
    """Burst page lookahead shortens the burst instead of evicting: with
    pages for only 2 more tokens, extend_decode_capacity(8) maps what it
    can, returns the safe burst length, and preempts nobody."""
    model, _ = tiny_random
    # capacity 4: one 1-page prompt + 1 free page after admission
    sched, pool = _sched(model, num_pages=5, page_size=8, max_slots=2,
                         max_len=64)
    a = sched.submit(Request(uid=0, prompt=np.arange(8, dtype=np.int32),
                             max_new_tokens=32))
    b = sched.submit(Request(uid=1, prompt=np.arange(8, dtype=np.int32),
                             max_new_tokens=32))
    assert len(sched.admit()) == 2
    for s in (a, b):
        s.state = SeqState.RUNNING
        s.n_prefilled = s.n_written = 8
        s.tokens = [1]
    # 2 pages free: an 8-step burst needs one more page per seq — fits
    k = sched.extend_decode_capacity(8)
    assert k == 8
    assert pool.slot_page_count(a.slot) == 2
    assert pool.free_pages == 0
    # pool now dry: each seq has 2*8 - 8 = 8 writable positions, so a
    # 24-step burst clamps to 8 — and NOBODY gets preempted
    k = sched.extend_decode_capacity(24)
    assert k == 8
    assert a.state is SeqState.RUNNING and b.state is SeqState.RUNNING
    assert a.preemptions == 0 and b.preemptions == 0
    assert not sched.waiting


def test_tables_device_row_update(tiny_random):
    """The device block-table mirror is resident: mutations scatter only
    the dirty rows (no full re-upload), and the mirror always matches
    the host tables."""
    model, _ = tiny_random
    pool = PagedKVPool(model, num_pages=9, page_size=8, max_slots=3,
                       max_len=32)
    t0 = pool.tables_device()
    np.testing.assert_array_equal(np.asarray(t0), pool.block_tables)
    assert pool.tables_device() is t0                # steady state: reused
    pages = pool.alloc(2)
    pool.assign(1, pages)
    t1 = pool.tables_device()
    assert t1 is not t0
    np.testing.assert_array_equal(np.asarray(t1), pool.block_tables)
    pool.clear_slot(1)
    np.testing.assert_array_equal(np.asarray(pool.tables_device()),
                                  pool.block_tables)


def test_fused_burst_2x4_mesh():
    """The device-resident burst under a real 2x4 mesh (state blob
    placed by dist.sharding.decode_state_specs): steps_per_sync=8
    serving emits the same greedy tokens as single-device per-step mode
    (subprocess, as in test_dist.py)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    code = """
        import jax, numpy as np
        from repro.configs import get_config
        from repro.models import LM
        from repro.dist import use_mesh
        from repro.serve import Request, ServeEngine

        cfg = get_config("paper_tiny_lm")
        model = LM(cfg)
        params = model.init(jax.random.key(0))
        params["unembed"]["head"] = params["unembed"]["head"] * 8.0
        rng = np.random.default_rng(0)
        reqs = [Request(uid=i,
                        prompt=rng.integers(0, cfg.vocab_size,
                                            size=(4, 8)[i % 2],
                                            dtype=np.int32),
                        max_new_tokens=(3, 6, 10)[i % 3])
                for i in range(8)]
        base = ServeEngine(model, params, max_batch=4, max_len=48,
                           mode="continuous", page_size=8,
                           steps_per_sync=1).generate(reqs)
        from repro.dist import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        with use_mesh(mesh):
            eng = ServeEngine(model, params, max_batch=4, max_len=48,
                              mode="continuous", page_size=8,
                              steps_per_sync=8)
            got = eng.generate(reqs)
        assert eng.stats["host_syncs"] < eng.stats["device_steps"] + \\
            len(reqs) + 8, "burst mode must not sync per step"
        for a, b in zip(base, got):
            np.testing.assert_array_equal(a.tokens, b.tokens)
        print("OK")
    """
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    assert "OK" in out.stdout


# ======================================================================
# equivalence under a mesh / with sparse weights
# ======================================================================
def test_continuous_matches_static_host_mesh(tiny_random):
    from repro.dist import make_host_mesh, use_mesh

    model, params = tiny_random
    reqs = _mixed_requests(model.cfg.vocab_size, n=6)
    base = ServeEngine(model, params, max_batch=4, max_len=48,
                       mode="static").generate(reqs)
    with use_mesh(make_host_mesh()):
        got = ServeEngine(model, params, max_batch=4, max_len=48,
                          mode="continuous", page_size=8).generate(reqs)
    for a, b in zip(base, got):
        np.testing.assert_array_equal(a.tokens, b.tokens)


def test_continuous_matches_static_2x4_mesh():
    """Real multi-device equivalence (subprocess: the parent must keep
    its single CPU device, as in test_dist.py)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    code = """
        import jax, numpy as np
        from repro.configs import get_config
        from repro.models import LM
        from repro.dist import use_mesh
        from repro.serve import Request, ServeEngine

        cfg = get_config("paper_tiny_lm")
        model = LM(cfg)
        params = model.init(jax.random.key(0))
        params["unembed"]["head"] = params["unembed"]["head"] * 8.0
        rng = np.random.default_rng(0)
        reqs = [Request(uid=i,
                        prompt=rng.integers(0, cfg.vocab_size,
                                            size=(4, 8)[i % 2],
                                            dtype=np.int32),
                        max_new_tokens=(3, 6, 10)[i % 3])
                for i in range(8)]
        nomesh = ServeEngine(model, params, max_batch=4, max_len=48,
                             mode="continuous", page_size=8).generate(reqs)
        from repro.dist import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        with use_mesh(mesh):
            static = ServeEngine(model, params, max_batch=4, max_len=48,
                                 mode="static").generate(reqs)
            cont = ServeEngine(model, params, max_batch=4, max_len=48,
                               mode="continuous", page_size=8
                               ).generate(reqs)
        for a, b, c in zip(static, cont, nomesh):
            np.testing.assert_array_equal(a.tokens, b.tokens)
            np.testing.assert_array_equal(a.tokens, c.tokens)
        print("OK")
    """
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    assert "OK" in out.stdout


def test_recurrent_continuous_2x4_mesh():
    """State-pool placement (paged_state_block_specs) on a real 2x4
    mesh: Mamba continuous serving emits the same greedy tokens as
    single-device (subprocess, as in test_dist.py)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    code = """
        import jax, numpy as np
        from repro.configs.paper_tiny_lm import MAMBA
        from repro.models import LM
        from repro.dist import use_mesh
        from repro.serve import Request, ServeEngine

        model = LM(MAMBA)
        params = model.init(jax.random.key(0))
        params["unembed"]["head"] = params["unembed"]["head"] * 8.0
        rng = np.random.default_rng(0)
        reqs = [Request(uid=i,
                        prompt=rng.integers(0, MAMBA.vocab_size,
                                            size=(4, 9)[i % 2],
                                            dtype=np.int32),
                        max_new_tokens=(3, 6)[i % 2])
                for i in range(4)]
        base = ServeEngine(model, params, max_batch=2, max_len=32,
                           mode="continuous", page_size=8,
                           prefill_chunk=8).generate(reqs)
        from repro.dist import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        with use_mesh(mesh):
            got = ServeEngine(model, params, max_batch=2, max_len=32,
                              mode="continuous", page_size=8,
                              prefill_chunk=8).generate(reqs)
        for a, b in zip(base, got):
            np.testing.assert_array_equal(a.tokens, b.tokens)
        print("OK")
    """
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    assert "OK" in out.stdout


def test_continuous_with_sparse_weights(tiny_lm):
    """2:4-prune → pack → nm_spmm path through the PAGED runtime emits
    the same greedy tokens as the static engine on the same weights."""
    from repro.core import PruningEngine
    from repro.data import calibration_batches
    from repro.serve import sparsify_params

    model, params, _ = tiny_lm
    calib = calibration_batches(model.cfg, n_samples=8, seq_len=64, batch=8)
    eng = PruningEngine(model, "2:4", method="SM", blocksize=64)
    pruned, _ = eng.run(params, calib)
    packed = sparsify_params(pruned, patterns=(r"mlp/(wi|wg|wo)$",))

    reqs = [Request(uid=i, prompt=np.asarray([2, 4, 6, 8], np.int32),
                    max_new_tokens=4 + i) for i in range(3)]
    rs = ServeEngine(model, packed, max_batch=2, max_len=32,
                     mode="static").generate(reqs)
    rc = ServeEngine(model, packed, max_batch=2, max_len=32,
                     mode="continuous", page_size=8).generate(reqs)
    for a, b in zip(rs, rc):
        np.testing.assert_array_equal(a.tokens, b.tokens)
