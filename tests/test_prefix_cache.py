"""Page-lifecycle tests for the ISSUE-7 refcounted pool: refcount
invariants under admit/share/CoW/retire/preempt interleavings (a
hypothesis state machine over the allocator + a deterministic seeded
random-walk twin through the real engine), prefix-cache match/cap/
divergence/eviction units, copy-on-write content checks, prefix-on
vs -off and swap-vs-recompute token parity, and a 2x4-mesh subprocess
run proving shared-prefix serving is bit-identical to unshared."""

import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from hypothesis_compat import given, settings, st
from repro.configs import get_config
from repro.models import LM
from repro.serve import PagedKVPool, Request, ServeEngine
from repro.serve.kvpool import _tree_get

SRC = os.path.join(os.path.dirname(os.path.dirname(__file__)), "src")


@pytest.fixture(scope="module")
def tiny_random():
    cfg = get_config("paper_tiny_lm")
    model = LM(cfg)
    params = model.init(jax.random.key(0))
    # sharpen the head so greedy decoding is decisive under f32 jitter
    params["unembed"]["head"] = params["unembed"]["head"] * 8.0
    return model, params


def _pool(model, *, num_pages=9, page_size=4, max_slots=3, max_len=32,
          **kw):
    return PagedKVPool(model, num_pages=num_pages, page_size=page_size,
                       max_slots=max_slots, max_len=max_len, **kw)


# ======================================================================
# refcount primitives
# ======================================================================
def test_refcount_alloc_retain_release(tiny_random):
    model, _ = tiny_random
    pool = _pool(model)
    pages = pool.alloc(3)
    assert pages is not None and len(pages) == 3
    assert all(pool.refcount(p) == 1 for p in pages)
    pool.check_invariants()

    pool.retain(pages[0])
    assert pool.refcount(pages[0]) == 2
    pool.release([pages[0]])
    assert pool.refcount(pages[0]) == 1     # still live: one ref left
    assert pool.free_pages == pool.capacity - 3
    pool.release(pages)                      # drops the last refs
    assert pool.free_pages == pool.capacity
    assert all(pool.refcount(p) == 0 for p in pages)
    pool.check_invariants()

    # releasing a freed page is a bug, not a no-op
    with pytest.raises(AssertionError):
        pool.release([pages[0]])
    # so is retaining one (sharing requires a live owner)
    with pytest.raises(AssertionError):
        pool.retain(pages[1])


def test_attach_shares_and_clear_slot_keeps_shared(tiny_random):
    model, _ = tiny_random
    pool = _pool(model)
    pages = pool.alloc(2)
    pool.assign(0, pages)                    # slot 0 owns both
    pool.attach(1, [pages[0]])               # slot 1 shares the first
    assert pool.refcount(pages[0]) == 2
    assert pool.slot_pages(1) == [pages[0]]

    pool.clear_slot(0)                       # slot 0 retires
    # the shared page survives on slot 1's reference; the exclusive
    # page went back to the free list
    assert pool.refcount(pages[0]) == 1
    assert pool.refcount(pages[1]) == 0
    pool.check_invariants()
    pool.clear_slot(1)
    assert pool.free_pages == pool.capacity


def test_ensure_writable_copies_shared_page(tiny_random):
    """CoW data plane: a shared page is copied content-exactly into a
    fresh page, the writer's table repoints, the reader's does not."""
    model, _ = tiny_random
    pool = _pool(model)
    (page,) = pool.alloc(1)
    pool.assign(0, [page])
    pool.attach(1, [page])                   # both slots map the page

    # stamp recognizable contents into every attn leaf of the page
    for path, stacked in pool._attn_paths:
        block = _tree_get(pool.kv, path)
        for k in block:
            v = block[k]
            fill = jax.numpy.full(
                v.shape[1:] if not stacked else (v.shape[0], *v.shape[2:]),
                3.25, v.dtype)
            block[k] = (v.at[page].set(fill) if not stacked
                        else v.at[:, page].set(fill))

    assert pool.ensure_writable(0, 0) is True
    new = pool.slot_pages(0)[0]
    assert new != page and pool.refcount(page) == 1
    assert pool.refcount(new) == 1
    assert pool.slot_pages(1) == [page]      # the reader kept the original
    assert pool.stats["cow_copies"] == 1
    pool.check_invariants()

    # the copy carried the bytes
    for path, stacked in pool._attn_paths:
        block = _tree_get(pool.kv, path)
        for k, v in block.items():
            src = v[page] if not stacked else v[:, page]
            dst = v[new] if not stacked else v[:, new]
            np.testing.assert_array_equal(np.asarray(src), np.asarray(dst))

    # second call: already exclusive, table unchanged, no copy
    assert pool.ensure_writable(0, 0) is True
    assert pool.slot_pages(0)[0] == new
    assert pool.stats["cow_copies"] == 1


def test_ensure_writable_fails_without_pages(tiny_random):
    model, _ = tiny_random
    pool = _pool(model, num_pages=3)         # capacity 2
    (page,) = pool.alloc(1)
    pool.assign(0, [page])
    pool.attach(1, [page])
    pool.alloc(1)                            # drain the free list
    assert pool.ensure_writable(0, 0) is False    # CoW needs a page
    assert pool.slot_pages(0) == [page]           # nothing mutated
    pool.check_invariants()


# ======================================================================
# prefix index: match / cap / divergence / eviction
# ======================================================================
def test_prefix_match_chain_and_cap(tiny_random):
    model, _ = tiny_random
    pool = _pool(model, prefix_cache=True)
    ps = pool.page_size
    toks = np.arange(1, 1 + 3 * ps, dtype=np.int32)     # 3 full pages
    pages = pool.alloc(3)
    pool.prefix.register(toks, pages)
    pool.release(pages)                      # index refs keep them live
    assert all(pool.refcount(p) == 1 for p in pages)

    # full coverage caps at L-1: last matched page becomes the CoW src
    shared, cow, n = pool.prefix.match(toks)
    assert shared == pages[:2] and cow == pages[2] and n == 3 * ps - 1

    # longer prompt with the cached prefix: all 3 pages attach shared
    longer = np.concatenate([toks, [99, 98]]).astype(np.int32)
    shared, cow, n = pool.prefix.match(longer)
    assert shared == pages and cow is None and n == 3 * ps

    # divergence inside page 2 stops the chain after page 1
    div = toks.copy()
    div[ps + 1] = 77
    shared, cow, n = pool.prefix.match(div)
    assert shared == pages[:1] and cow is None and n == ps

    # no match at all
    shared, cow, n = pool.prefix.match(np.asarray([9, 9, 9], np.int32))
    assert shared == [] and cow is None and n == 0


def test_prefix_partial_tail_lcp(tiny_random):
    model, _ = tiny_random
    pool = _pool(model, prefix_cache=True)
    ps = pool.page_size
    # one full page + a 3-token tail, as a retirement would register it
    kv_toks = np.asarray([*range(1, ps + 1), 50, 51, 52], np.int32)
    pages = pool.alloc(2)
    pool.prefix.register(kv_toks, pages, include_partial=True)
    pool.release(pages)

    # prompt sharing 2 of the 3 tail tokens: full page shared, tail
    # page offered as a CoW source covering the LCP
    prompt = np.asarray([*range(1, ps + 1), 50, 51, 60, 61], np.int32)
    shared, cow, n = pool.prefix.match(prompt)
    assert shared == pages[:1] and cow == pages[1] and n == ps + 2

    # LCP is capped at L-1 even through the partial path
    short = np.asarray([*range(1, ps + 1), 50, 51, 52], np.int32)
    shared, cow, n = pool.prefix.match(short)
    assert n <= len(short) - 1


def test_prefix_lru_eviction_feeds_alloc(tiny_random):
    """A short free list evicts index leaves LRU-first from inside
    alloc — and never an entry another chain still hangs off."""
    model, _ = tiny_random
    pool = _pool(model, num_pages=5, prefix_cache=True)   # capacity 4
    ps = pool.page_size
    a = np.arange(1, 1 + 2 * ps, dtype=np.int32)          # chain of 2
    pages = pool.alloc(2)
    pool.prefix.register(a, pages)
    pool.release(pages)
    assert pool.free_pages == 2 and len(pool.prefix) == 2

    # alloc(3) must evict: the LEAF (page 2 of the chain) goes first
    got = pool.alloc(3)
    assert got is not None
    assert pool.stats["prefix_evictions"] >= 1
    pool.check_invariants()
    # the surviving index never references a freed page
    live = [p for p in range(1, pool.num_pages) if pool.refcount(p)]
    shared, cow, n = pool.prefix.match(a)
    for p in shared + ([cow] if cow is not None else []):
        assert p in live


def test_prefix_match_bumps_recency(tiny_random):
    model, _ = tiny_random
    pool = _pool(model, num_pages=6, prefix_cache=True)   # capacity 5
    ps = pool.page_size
    a = np.arange(1, 1 + ps, dtype=np.int32)
    b = np.arange(100, 100 + ps, dtype=np.int32)
    pa = pool.alloc(1)
    pool.prefix.register(a, pa)
    pool.release(pa)
    pb = pool.alloc(1)
    pool.prefix.register(b, pb)
    pool.release(pb)
    # a is older, but matching it makes b the LRU victim
    pool.prefix.match(np.concatenate([a, [7]]).astype(np.int32))
    pool.alloc(4)                     # forces exactly one eviction
    shared, _, _ = pool.prefix.match(np.concatenate([a, [7]]).astype(
        np.int32))
    assert shared == pa               # a survived
    shared, _, _ = pool.prefix.match(np.concatenate([b, [7]]).astype(
        np.int32))
    assert shared == []               # b was evicted


# ======================================================================
# engine integration: parity + savings + preemption flavors
# ======================================================================
def _prefix_requests(vocab, n=8, tail=2, max_new=6):
    shared = np.arange(5, 17, dtype=np.int32)     # 12-token system prefix
    return [
        Request(uid=i,
                prompt=np.concatenate([shared,
                                       np.asarray([20 + i] * tail,
                                                  np.int32)]),
                max_new_tokens=max_new)
        for i in range(n)
    ]


def test_engine_prefix_parity_and_savings(tiny_random):
    """Prefix sharing changes prefill WORK, never tokens: greedy
    streams are bit-identical with the cache on and off, and the stats
    show real savings."""
    model, params = tiny_random
    reqs = _prefix_requests(model.cfg.vocab_size)
    kw = dict(max_batch=4, max_len=64, page_size=8, num_pages=17,
              host_swap_pages=0)
    off = ServeEngine(model, params, prefix_cache=False, **kw)
    base = off.generate(reqs)
    on = ServeEngine(model, params, prefix_cache=True, **kw)
    got = on.generate(reqs)
    for a, b in zip(base, got):
        np.testing.assert_array_equal(a.tokens, b.tokens)
    assert on.stats["prefix_hit_tokens"] > 0
    assert on.stats["prefill_tok"] < off.stats["prefill_tok"]
    assert off.stats["prefix_hit_tokens"] == 0
    on.pool.check_invariants()


def test_engine_prefix_parity_sampled(tiny_random):
    model, params = tiny_random
    reqs = _prefix_requests(model.cfg.vocab_size)
    kw = dict(max_batch=4, max_len=64, page_size=8, num_pages=17,
              temperature=1.0, top_k=5, host_swap_pages=0)
    base = ServeEngine(model, params, prefix_cache=False,
                       **kw).generate(reqs, seed=3)
    on = ServeEngine(model, params, prefix_cache=True, **kw)
    got = on.generate(reqs, seed=3)
    for a, b in zip(base, got):
        np.testing.assert_array_equal(a.tokens, b.tokens)
    assert on.stats["prefix_hit_tokens"] > 0


def _preempt_requests(vocab, n=6):
    rng = np.random.default_rng(0)
    return [
        Request(uid=i,
                prompt=rng.integers(1, vocab,
                                    (4, 9, 13)[i % 3]).astype(np.int32),
                max_new_tokens=(22, 9, 26)[i % 3])
        for i in range(n)
    ]


def test_swap_preemption_bit_identical_to_recompute(tiny_random):
    """The acceptance pin: under a pool tight enough to force
    preemption, preserve-KV swap resumes produce EXACTLY the token
    streams recompute produces — and the stats split shows which
    flavor ran."""
    model, params = tiny_random
    reqs = _preempt_requests(model.cfg.vocab_size)
    kw = dict(max_batch=3, max_len=48, page_size=8, num_pages=8,
              prefix_cache=False, steps_per_sync=4)
    rec = ServeEngine(model, params, host_swap_pages=0, **kw)
    base = rec.generate(reqs)
    swp = ServeEngine(model, params, host_swap_pages=None, **kw)
    got = swp.generate(reqs)
    for a, b in zip(base, got):
        np.testing.assert_array_equal(a.tokens, b.tokens)
    # both runs preempted; only the flavor differs
    assert rec.stats["preempt_recompute"] > 0
    assert rec.stats["preempt_swap"] == 0
    assert swp.stats["preempt_swap"] > 0
    assert swp.stats["preempt_recompute"] == 0
    assert swp.stats["swap_out_pages"] == swp.stats["swap_in_pages"] > 0
    # resume does NOT re-prefill: the swap run prefills fewer tokens
    assert swp.stats["prefill_tok"] < rec.stats["prefill_tok"]
    swp.pool.check_invariants()


def test_swap_disabled_for_recurrent_state(tiny_random):
    """Hybrid/recurrent archs keep recompute preemption: their state
    rows live outside the page pool, so a KV-only swap would resume
    from the wrong state (kvpool.StatePool docstring)."""
    from repro.models.base import ArchConfig

    cfg = ArchConfig(name="hyb-swap-test", family="hybrid", num_layers=4,
                     d_model=64, num_heads=4, num_kv_heads=2, head_dim=16,
                     d_ff=128, vocab_size=256, period=("mamba", "attn"),
                     ssm_state=4, dtype="float32")
    model = LM(cfg)
    params = model.init(jax.random.key(1))
    eng = ServeEngine(model, params, max_batch=2, max_len=32,
                      page_size=8, host_swap_pages=64)
    assert eng.state_pool is not None
    assert eng._swap_ok is False
    # and a tight run still completes via recompute
    reqs = [Request(uid=i, prompt=np.arange(1, 6, dtype=np.int32),
                    max_new_tokens=8) for i in range(3)]
    res = eng.generate(reqs)
    assert all(len(r.tokens) == 8 for r in res)
    assert eng.stats["preempt_swap"] == 0


def test_stats_surface_through_replica(tiny_random):
    """Satellite 3: the preemption-flavor split and prefix counters ride
    ServeEngine.stats into frontend Replica.stats() — the dict /stats
    serializes."""
    from repro.serve.frontend import Replica

    model, params = tiny_random
    eng = ServeEngine(model, params, max_batch=2, max_len=32,
                      page_size=8)
    rep = Replica(eng, name="t0")
    try:
        stats = rep.stats()
        for key in ("preempt_swap", "preempt_recompute",
                    "prefix_hit_tokens", "prefill_tok", "cow_copies",
                    "swap_out_pages", "swap_in_pages"):
            assert key in stats, key
    finally:
        rep.close()


# ======================================================================
# interleaving invariants: hypothesis machine + deterministic twin
# ======================================================================
def _refcount_walk(pool, ops):
    """Interpret an op list against the pool and a shadow refcounter;
    check the accounting invariants after every op."""
    shadow = {}                       # page -> refcount

    def live():
        return sorted(shadow)

    for op in ops:
        kind = op % 3
        arg = op // 3
        if kind == 0:                 # alloc 1..3 pages
            n = arg % 3 + 1
            pages = pool.alloc(n)
            if len(shadow) + n <= pool.capacity:
                assert pages is not None
                for p in pages:
                    assert p not in shadow
                    shadow[p] = 1
            else:
                assert pages is None
        elif kind == 1 and shadow:    # share a live page
            p = live()[arg % len(shadow)]
            pool.retain(p)
            shadow[p] += 1
        elif kind == 2 and shadow:    # drop one reference
            p = live()[arg % len(shadow)]
            pool.release([p])
            shadow[p] -= 1
            if shadow[p] == 0:
                del shadow[p]
        pool.check_invariants()
        for p, r in shadow.items():
            assert pool.refcount(p) == r
    assert pool.free_pages == pool.capacity - len(shadow)


@given(st.lists(st.integers(min_value=0, max_value=300), max_size=60))
@settings(max_examples=25, deadline=None)
def test_refcount_state_machine(ops):
    """Hypothesis drives alloc/retain/release interleavings against a
    shadow refcounter (skipped where hypothesis isn't installed — the
    seeded twin below always runs)."""
    cfg = get_config("paper_tiny_lm")
    _refcount_walk(_pool(LM(cfg), num_pages=7), ops)


def test_refcount_state_machine_seeded(tiny_random):
    """Deterministic twin of the hypothesis machine: 400-op seeded
    random walks over alloc/retain/release."""
    model, _ = tiny_random
    for seed in range(3):
        rng = np.random.default_rng(seed)
        ops = rng.integers(0, 300, 400).tolist()
        _refcount_walk(_pool(model, num_pages=7), ops)


def test_engine_random_walk_invariants(tiny_random):
    """The full lifecycle interleaving — admit / prefix-share / CoW /
    retire / swap-preempt — driven by a seeded walk through a REAL
    session on a tight pool, with pool invariants checked after every
    sync interval and final tokens pinned against a roomy-pool run."""
    model, params = tiny_random
    vocab = model.cfg.vocab_size
    rng = np.random.default_rng(42)
    shared = np.arange(5, 17, dtype=np.int32)

    def make_requests():
        reqs = []
        for i in range(10):
            if i % 2 == 0:            # shared system prefix + short tail
                prompt = np.concatenate(
                    [shared, rng.integers(1, vocab, 2).astype(np.int32)])
            else:                     # unique prompt
                prompt = rng.integers(1, vocab, int(rng.integers(3, 14))
                                      ).astype(np.int32)
            reqs.append(Request(uid=i, prompt=prompt,
                                max_new_tokens=int(rng.integers(1, 18))))
        return reqs

    reqs = make_requests()
    # roomy reference: no preemption, no sharing pressure
    base = ServeEngine(model, params, max_batch=4, max_len=48,
                       page_size=8, num_pages=33, prefix_cache=False,
                       host_swap_pages=0).generate(reqs)

    eng = ServeEngine(model, params, max_batch=3, max_len=48,
                      page_size=8, num_pages=9, prefix_cache=True,
                      steps_per_sync=3)
    session = eng.session(seed=0)
    it = iter(reqs)
    pending = list(reqs)
    results = {}
    while pending or session.has_work():
        # interleave submissions with steps (arrival jitter)
        for _ in range(int(rng.integers(0, 3))):
            if pending:
                session.submit(pending.pop(0))
        if session.has_work():
            for ev in session.step():
                if ev.finished:
                    results[ev.uid] = ev.result
        eng.pool.check_invariants()
    assert len(results) == len(reqs)
    for r in base:
        np.testing.assert_array_equal(r.tokens, results[r.uid].tokens)
    # the tight pool actually exercised the interesting paths
    assert eng.stats["prefix_hit_tokens"] > 0
    assert (eng.stats["preempt_swap"] + eng.stats["preempt_recompute"]
            + eng.stats["prefix_evictions"]) > 0


# ======================================================================
# 2x4 mesh: shared-prefix serving is bit-identical to unshared
# ======================================================================
def test_shared_prefix_2x4_mesh_parity():
    """Acceptance pin: greedy AND sampled parity with prefix sharing +
    swap on under a real 2x4 mesh (subprocess, as in test_dist.py)."""
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
        shared = np.arange(5, 17, dtype=np.int32)
        reqs = [Request(uid=i,
                        prompt=np.concatenate(
                            [shared, np.asarray([20 + i, 21 + i],
                                                np.int32)]),
                        max_new_tokens=6)
                for i in range(8)]
        from repro.dist import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        for sampled in (False, True):
            kw = dict(max_batch=4, max_len=64, page_size=8,
                      num_pages=17, steps_per_sync=4)
            if sampled:
                kw.update(temperature=1.0, top_k=5)
            with use_mesh(mesh):
                off = ServeEngine(model, params, prefix_cache=False,
                                  host_swap_pages=0, **kw)
                base = off.generate(reqs, seed=3)
                on = ServeEngine(model, params, prefix_cache=True, **kw)
                got = on.generate(reqs, seed=3)
            assert on.stats["prefix_hit_tokens"] > 0
            for a, b in zip(base, got):
                np.testing.assert_array_equal(a.tokens, b.tokens)
        print("OK")
    """
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, \
        f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    assert "OK" in out.stdout
