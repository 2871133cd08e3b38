"""Fault tolerance (ISSUE-10): deterministic chaos injection,
cancellation/deadlines, replica supervision and in-flight failover.

Covers the acceptance surface: FaultPlan trigger windows + replica
scoping; cancellation at every phase (waiting / mid-prefill /
mid-decode / swapped-out) releasing pages with ``check_invariants``
holding and sibling streams bit-identical (a hypothesis sweep in CI,
a deterministic slice locally); hard deadlines retiring with
``finish_reason="timeout"``; injected pool/swap failures degrading
without changing any token stream; a replica crash mid-stream recovered
by the supervisor with failed-over streams token-identical to an
uninjected run and the recovery counters ticking; the server's 503 +
``Retry-After`` when every replica is down; and a client disconnect
cancelling its request and returning the pool to its pre-admission
free-page level.
"""

import asyncio
import json
import threading
import time

import jax
import numpy as np
import pytest

from hypothesis_compat import given, settings, st
from repro.configs import get_smoke
from repro.models import LM
from repro.serve import (FaultPlan, FaultSpec, Request, ServeEngine,
                         StreamEvent)
from repro.serve.frontend import (CompletionRequest, Replica, Router,
                                  Server, Supervisor, sse_decode)

SAMPLED = dict(temperature=0.9, top_k=20)   # key contract load-bearing


@pytest.fixture(scope="module")
def tiny():
    cfg = get_smoke("paper_tiny_lm")
    model = LM(cfg)
    params = model.init(jax.random.key(0))
    params["unembed"]["head"] = params["unembed"]["head"] * 8.0
    return model, params


def _engine(tiny, **kw):
    model, params = tiny
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_len", 64)
    kw.setdefault("page_size", 8)
    kw.setdefault("prefill_chunk", 8)
    return ServeEngine(model, params, **kw)


def _reqs(vocab, n=8, max_new=(2, 5, 9, 14), **kw):
    rng = np.random.default_rng(0)
    return [
        Request(uid=i,
                prompt=rng.integers(0, vocab, size=(4, 7, 12)[i % 3],
                                    dtype=np.int32),
                max_new_tokens=max_new[i % len(max_new)], **kw)
        for i in range(n)
    ]


# ======================================================================
# FaultPlan: parsing, trigger windows, replica scoping
# ======================================================================
def test_fault_spec_parse_roundtrip():
    s = FaultSpec.parse("replica_worker:after=3,count=2,replica=r1")
    assert (s.site, s.after, s.count, s.replica) == \
           ("replica_worker", 3, 2, "r1")
    assert FaultSpec.parse("slow_burst:delay_s=0.25").delay_s == 0.25
    with pytest.raises(ValueError):
        FaultPlan([FaultSpec.parse("nonsense")])
    with pytest.raises(ValueError):
        FaultSpec.parse("engine_step:bogus=1")
    with pytest.raises(ValueError):
        FaultPlan([FaultSpec("engine_step", count=0)])


def test_fault_plan_fire_window():
    plan = FaultPlan([FaultSpec("pool_alloc", after=2, count=2)])
    hits = [plan.hit("pool_alloc") is not None for _ in range(6)]
    assert hits == [False, False, True, True, False, False]
    assert plan.fired == {"pool_alloc": 2}
    assert plan.hit("swap_error") is None       # other sites untouched
    assert not FaultPlan()                      # empty plan is falsy


def test_fault_plan_replica_scoping():
    plan = FaultPlan([FaultSpec("replica_worker", after=1, replica="r1")])
    # r0 passes never count toward an r1-scoped spec
    assert all(plan.hit("replica_worker", "r0") is None for _ in range(5))
    assert plan.hit("replica_worker", "r1") is None       # pass 1 = after
    assert plan.hit("replica_worker", "r1") is not None   # pass 2 fires
    assert plan.hit("replica_worker", "r1") is None       # quiet again


# ======================================================================
# cancellation: any phase, zero leaks, siblings untouched
# ======================================================================
def _run_session(eng, reqs, cancel_at=None, seed=0, max_steps=400):
    """Drive a session to completion, cancelling ``cancel_at[uid]`` at
    that step index.  Returns (per-uid token lists, terminal events,
    uids whose cancel actually landed)."""
    cancel_at = dict(cancel_at or {})
    session = eng.session(seed=seed)
    full = eng.pool.free_pages                # post-reset, pre-admission
    for r in reqs:
        session.submit(r)
    toks, final, cancelled = {}, {}, set()
    step = 0
    while session.has_work():
        assert step < max_steps, "session failed to converge"
        for uid, at in list(cancel_at.items()):
            if at <= step:
                ev = session.cancel(uid)
                del cancel_at[uid]
                if ev is not None:
                    cancelled.add(uid)
                    final[uid] = ev
        for ev in session.step():
            toks.setdefault(ev.uid, []).extend(ev.tokens)
            if ev.finished:
                final[ev.uid] = ev
        eng.pool.check_invariants()
        step += 1
    assert eng.pool.free_pages == full, "cancel leaked KV pages"
    return toks, final, cancelled


def test_cancel_every_phase_releases_pages(tiny):
    """Deterministic slice of the sweep: cancel one waiting, one
    mid-prefill and one mid-decode request; invariants hold each step,
    the pool returns to its pre-admission free level, survivors stream
    bit-identically, and the cancelled counter ticks."""
    eng = _engine(tiny, prefix_cache=False, **SAMPLED)
    reqs = _reqs(tiny[0].cfg.vocab_size, n=6)
    base = {r.uid: list(r.tokens) for r in eng.generate(reqs, seed=0)}

    session = eng.session(seed=0)
    full = eng.pool.free_pages
    for r in reqs:
        session.submit(r)
    # uid 5 is still WAITING (4 slots); cancel before any step
    ev = session.cancel(5)
    assert ev.finished and ev.finish_reason == "cancelled"
    assert ev.result.tokens.size == 0
    evs = session.step()                      # uid 2 (12-tok prompt) is
    ev2 = session.cancel(2)                   # mid-prefill/first-decode
    assert ev2 is not None and ev2.finish_reason == "cancelled"
    eng.pool.check_invariants()
    toks = {}
    for e in evs:
        toks.setdefault(e.uid, []).extend(e.tokens)
    for _ in range(3):
        if session.has_work():
            for e in session.step():
                toks.setdefault(e.uid, []).extend(e.tokens)
    ev0 = session.cancel(0)                   # mid-decode (or finished)
    while session.has_work():
        for e in session.step():
            toks.setdefault(e.uid, []).extend(e.tokens)
        eng.pool.check_invariants()
    assert eng.pool.free_pages == full
    assert session.cancel(999) is None        # unknown uid
    survivors = {1, 3, 4} | ({0} if ev0 is None else set())
    for uid in survivors:
        assert toks[uid] == base[uid], f"uid {uid} stream changed"
    n_cancel = 2 + (ev0 is not None)
    assert eng.stats["cancelled"] >= n_cancel


@settings(max_examples=6, deadline=None)
@given(data=st.data())
def test_cancel_sweep_random_phases(tiny, data):
    """Hypothesis sweep (CI): random uids cancelled at random steps —
    including swapped-out victims (a 13-page pool forces preemption) —
    never leak pages, never violate pool invariants, and never change a
    surviving sibling's stream."""
    eng = _engine(tiny, prefix_cache=False, num_pages=13, **SAMPLED)
    reqs = _reqs(tiny[0].cfg.vocab_size, n=6)
    base = {r.uid: list(r.tokens) for r in eng.generate(reqs, seed=0)}
    uids = data.draw(st.lists(st.integers(0, 5), min_size=1, max_size=4,
                              unique=True))
    cancel_at = {u: data.draw(st.integers(0, 14)) for u in uids}
    toks, final, cancelled = _run_session(eng, reqs, cancel_at)
    for uid in set(base) - cancelled:
        assert toks.get(uid, []) == base[uid], f"uid {uid} stream changed"
        assert final[uid].finish_reason in ("stop", "length")
    for uid in cancelled:
        assert final[uid].finish_reason == "cancelled"


def test_hard_deadline_retires_with_timeout(tiny):
    """An expired hard deadline retires at the next sync with
    ``finish_reason="timeout"`` and frees capacity; an ordering-only
    deadline (the pre-ISSUE-10 field) never expires; siblings finish
    with their exact tokens."""
    eng = _engine(tiny, prefix_cache=False, **SAMPLED)
    reqs = _reqs(tiny[0].cfg.vocab_size, n=4)
    base = {r.uid: list(r.tokens) for r in eng.generate(reqs, seed=0)}
    now = time.monotonic()
    reqs = _reqs(tiny[0].cfg.vocab_size, n=4)
    reqs[1].deadline, reqs[1].deadline_hard = now - 0.001, True
    reqs[2].deadline = 100.0                  # ordering-only: tiny abs
    toks, final, _ = _run_session(eng, reqs)  # value, but never expires
    assert final[1].finish_reason == "timeout"
    assert list(final[1].result.tokens) == []
    for uid in (0, 2, 3):
        assert toks[uid] == base[uid]
    assert eng.stats["deadline_exceeded"] == 1


# ======================================================================
# injected pool/swap failures: graceful degrade, identical streams
# ======================================================================
def test_pool_alloc_fault_degrades_without_stream_change(tiny):
    ref = [list(r.tokens) for r in
           _engine(tiny, prefix_cache=False, **SAMPLED).generate(
               _reqs(tiny[0].cfg.vocab_size, n=6), seed=0)]
    plan = FaultPlan([FaultSpec("pool_alloc", after=3, count=3)])
    eng = _engine(tiny, prefix_cache=False, faults=plan, **SAMPLED)
    out = [list(r.tokens) for r in
           eng.generate(_reqs(tiny[0].cfg.vocab_size, n=6), seed=0)]
    assert plan.fired.get("pool_alloc", 0) >= 1
    assert out == ref
    eng.pool.check_invariants()


def test_swap_error_falls_back_to_recompute(tiny):
    """With the arena failing, preemption degrades to recompute —
    streams stay identical (key contract), nothing leaks."""
    ref = [list(r.tokens) for r in
           _engine(tiny, prefix_cache=False, **SAMPLED).generate(
               _reqs(tiny[0].cfg.vocab_size, n=6), seed=0)]
    plan = FaultPlan([FaultSpec("swap_error", count=1000)])
    eng = _engine(tiny, prefix_cache=False, num_pages=13, faults=plan,
                  **SAMPLED)
    out = [list(r.tokens) for r in
           eng.generate(_reqs(tiny[0].cfg.vocab_size, n=6), seed=0)]
    assert out == ref
    eng.pool.check_invariants()


# ======================================================================
# supervisor: crash detection, restart, in-flight failover
# ======================================================================
def test_supervisor_failover_streams_bit_identical(tiny):
    """Mid-stream replica crash (injected engine_step raise on r0's
    third burst): the supervisor restarts the worker and re-submits its
    in-flight requests; every client stream — including the failed-over
    ones, replay-suppressed — is token-identical to an uninjected run,
    and the restart/failover/recovery series tick."""
    kw = dict(steps_per_sync=2, **SAMPLED)
    reqs = _reqs(tiny[0].cfg.vocab_size, n=6, max_new=(6, 9, 12, 14))
    ref = {r.uid: list(r.tokens)
           for r in _engine(tiny, **kw).generate(reqs, seed=0)}

    plan = FaultPlan([FaultSpec("engine_step", after=2)])
    r0 = Replica(_engine(tiny, faults=plan, **kw), name="r0")
    r1 = Replica(_engine(tiny, **kw), name="r1")
    router = Router([r0, r1])
    sup = Supervisor(router, failover_retries=8)
    lock = threading.Lock()
    toks, done = {}, {}

    def make_cb(uid):
        def cb(ev: StreamEvent) -> None:
            with lock:
                toks.setdefault(uid, []).extend(ev.tokens)
                if ev.finished:
                    done[uid] = ev
        return cb

    try:
        for r in reqs:
            router.submit_request(r, make_cb(r.uid))
        deadline = time.monotonic() + 120
        while len(done) < len(reqs):
            assert time.monotonic() < deadline, \
                f"requests stuck: done={sorted(done)} crashed={r0.crashed!r}"
            sup.check_once()
            time.sleep(0.02)
        recovered = r0.crashed is None and r0.healthy
    finally:
        sup.stop()
        router.close()

    assert plan.fired.get("engine_step", 0) >= 1, "fault never fired"
    assert recovered                             # restarted clean
    with lock:
        for uid, want in ref.items():
            assert toks[uid] == want, f"uid {uid} stream changed"
            assert done[uid].finish_reason in ("stop", "length")
    s0 = r0.engine.m.snapshot()
    assert s0["replica_restarts"] >= 1
    assert s0["failed_over"] >= 1
    rec = r0.engine.obs.metrics.get("serve_recovery_seconds")
    assert rec is not None and sum(c.count for _, c in rec.children()) >= 1


def test_replica_worker_fault_and_restart_idle(tiny):
    """A worker killed while idle (replica_worker site) is detected and
    restarted; the replica serves normally afterwards."""
    plan = FaultPlan([FaultSpec("replica_worker")])
    rep = Replica(_engine(tiny, faults=plan), name="r0")
    router = Router([rep])                   # first worker pass kills it
    sup = Supervisor(router)
    try:
        deadline = time.monotonic() + 30
        while rep.healthy and time.monotonic() < deadline:
            time.sleep(0.01)
        assert not rep.healthy
        assert sup.check_once() == ["r0"]
        assert rep.healthy and rep.crashed is None
        out = router.complete([CompletionRequest(prompt=[1, 2, 3],
                                                 max_tokens=3, uid=0)])
        assert len(out[0].tokens) == 3
    finally:
        sup.stop()
        router.close()


def test_batch_crash_raises_instead_of_hanging():
    """Batch mode runs no supervisor: an engine-step fault kills the only
    replica's worker, and ``run_batch`` must end with the crash instead
    of waiting forever on requests nobody will finish."""
    from repro.launch import serve as launch_serve
    from repro.obs import Obs
    from repro.serve import ServeConfig
    from repro.serve.frontend import ReplicaCrashed

    args = launch_serve.build_parser().parse_args(
        ["--smoke", "--requests", "3", "--max-new", "4",
         "--inject-fault", "engine_step:after=1"])
    config = ServeConfig.from_args(args)
    cfg, model, params = launch_serve.load_model(args)
    result = {}

    def run():
        try:
            launch_serve.run_batch(cfg, model, params, args, config,
                                   Obs.create(metrics=True, trace=False))
        except ReplicaCrashed as e:
            result["err"] = e

    t = threading.Thread(target=run, daemon=True)
    t.start()
    t.join(timeout=120)
    assert not t.is_alive(), "run_batch hung on a crashed replica"
    assert "injected" in str(result["err"].__cause__)


# ======================================================================
# HTTP server: 503 + Retry-After, disconnect cancellation, 504
# ======================================================================
async def _post_raw(host, port, obj):
    body = json.dumps(obj).encode()
    r, w = await asyncio.open_connection(host, port)
    w.write(f"POST /v1/completions HTTP/1.1\r\nHost: t\r\n"
            f"Content-Length: {len(body)}\r\n\r\n".encode() + body)
    await w.drain()
    data = await r.read()
    w.close()
    head, _, rest = data.partition(b"\r\n\r\n")
    return int(head.split()[1]), head, rest


def test_server_503_retry_after_when_all_replicas_down(tiny):
    plan = FaultPlan([FaultSpec("replica_worker")])
    rep = Replica(_engine(tiny, faults=plan), name="r0")
    router = Router([rep])

    async def scenario():
        deadline = time.monotonic() + 30
        while rep.healthy and time.monotonic() < deadline:
            await asyncio.sleep(0.01)
        assert not rep.healthy and not rep.draining
        srv = Server(router, port=0)
        host, port = await srv.start()
        status, head, rest = await _post_raw(
            host, port, {"prompt": [1, 2], "max_tokens": 2})
        assert status == 503
        assert b"retry-after:" in head.lower(), head
        if srv._server is not None:
            srv._server.close()
            await srv._server.wait_closed()

    try:
        asyncio.run(scenario())
    finally:
        router.close()


def test_server_504_on_hard_deadline(tiny):
    """A wire ``deadline_ms`` already expired maps to HTTP 504 on the
    non-streaming path."""
    rep = Replica(_engine(tiny), name="r0")
    router = Router([rep])

    async def scenario():
        srv = Server(router, port=0)
        host, port = await srv.start()
        status, head, rest = await _post_raw(
            host, port, {"prompt": [1, 2, 3], "max_tokens": 30,
                         "deadline_ms": 0.0})
        assert status == 504, (status, rest)
        assert b"deadline" in rest
        await srv.shutdown(timeout=30)

    try:
        asyncio.run(scenario())
    finally:
        router.close()


def test_client_disconnect_cancels_and_frees_pages(tiny):
    """Acceptance: a client that vanishes mid-stream triggers
    cancellation — the sequence retires, the cancelled counter ticks,
    and ``free_pages`` returns to its pre-admission level."""
    eng = _engine(tiny, prefix_cache=False, steps_per_sync=1)
    rep = Replica(eng, name="r0")
    router = Router([rep])
    full = eng.pool.free_pages

    async def scenario():
        srv = Server(router, port=0)
        host, port = await srv.start()
        body = json.dumps({"prompt": [1, 2, 3, 4], "max_tokens": 50,
                           "stream": True}).encode()
        r, w = await asyncio.open_connection(host, port)
        w.write(f"POST /v1/completions HTTP/1.1\r\nHost: t\r\n"
                f"Content-Length: {len(body)}\r\n\r\n".encode() + body)
        await w.drain()
        await r.readuntil(b"\n\n")            # headers + first bytes are
        w.close()                             # flowing... then hang up
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            if rep.load == 0 and eng.pool.free_pages == full:
                break
            await asyncio.sleep(0.02)
        assert rep.load == 0, "request not cancelled on disconnect"
        assert eng.pool.free_pages == full, "disconnect leaked pages"
        eng.pool.check_invariants()
        if srv._server is not None:
            srv._server.close()
            await srv._server.wait_closed()

    try:
        asyncio.run(scenario())
        assert eng.stats["cancelled"] >= 1
    finally:
        router.close()


def test_streaming_terminal_chunk_carries_finish_reason(tiny):
    rep = Replica(_engine(tiny), name="r0")
    router = Router([rep])

    async def scenario():
        srv = Server(router, port=0)
        host, port = await srv.start()
        status, head, rest = await _post_raw(
            host, port, {"prompt": [1, 2, 3], "max_tokens": 4,
                         "stream": True})
        assert status == 200
        chunks = sse_decode(rest)
        assert chunks[-1].finished
        assert chunks[-1].finish_reason == "length"
        status, head, rest = await _post_raw(
            host, port, {"prompt": [1, 2, 3], "max_tokens": 4})
        assert json.loads(rest)["finish_reason"] == "length"
        await srv.shutdown(timeout=30)

    try:
        asyncio.run(scenario())
    finally:
        router.close()
