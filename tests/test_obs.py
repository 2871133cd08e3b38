"""ISSUE-8 observability layer: registry, tracer, serve + prune wiring.

Covers the tentpole acceptance surface: metrics-registry unit behavior
(atomic concurrent increments, histogram bucket edges and interpolated
quantiles, the zero-cost disabled mode, get-or-create binding and
kind-mismatch rejection, Prometheus text rendering), Chrome-trace
export, the request-lifecycle span taxonomy through a real engine run
(submit/queue-wait/prefill/decode-burst/first-token/retire, plus both
preemption flavors with swap-resume), the satellite pin that tracing
on vs off produces bit-identical token streams (greedy + sampled,
steps_per_sync 1 vs 8), the legacy ``ServeEngine.stats`` flat-dict
back-compat view, and the prune pipeline's stage counters/spans
flowing through the same registry.
"""

import json
import threading

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import LM
from repro.obs import (COUNT_BUCKETS, LATENCY_BUCKETS, Obs,
                       MetricsRegistry, Tracer, exp_buckets)
from repro.obs.metrics import merge_histograms
from repro.serve import Request, ServeEngine


@pytest.fixture(scope="module")
def tiny_random():
    """Random-init tiny LM with a sharpened head (greedy gaps robust to
    reduction-order rounding) — same recipe as test_serve_paged."""
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
# registry: counters / gauges / histograms
# ======================================================================
def test_counter_concurrent_increments():
    """The satellite fix for the racy /stats dict merge: N threads
    hammering one counter child lose no increments."""
    reg = MetricsRegistry()
    fam = reg.counter("x_total", "t", ("replica",))
    child = fam.labels(replica="r0")
    other = fam.labels(replica="r1")
    n_threads, per = 8, 2000

    def work():
        for _ in range(per):
            child.inc()
            other.inc(2.0)

    ts = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert child.value == n_threads * per
    assert other.value == n_threads * per * 2.0
    assert fam.total() == n_threads * per * 3.0


def test_counter_rejects_negative():
    reg = MetricsRegistry()
    c = reg.counter("x_total")
    with pytest.raises(ValueError, match="only go up"):
        c.inc(-1.0)


def test_histogram_bucket_edges():
    """``le`` is inclusive: a value exactly on a bound lands in that
    bucket; past the last bound lands in +Inf."""
    reg = MetricsRegistry()
    h = reg.histogram("h", buckets=(1.0, 2.0, 4.0))
    for v in (0.5, 1.0, 1.5, 2.0, 4.0, 9.0):
        h.observe(v)
    child = h.labels()
    assert child.cumulative() == [2, 4, 5, 6]
    assert child.count == 6
    assert child.sum == pytest.approx(18.0)
    assert child.mean == pytest.approx(3.0)


def test_histogram_quantile_interpolation():
    reg = MetricsRegistry()
    h = reg.histogram("h", buckets=(1.0, 2.0, 4.0)).labels()
    for _ in range(100):
        h.observe(1.5)                    # all in the (1, 2] bucket
    # linear interpolation inside the bucket the rank lands in
    assert h.quantile(0.5) == pytest.approx(1.5)
    assert h.quantile(1.0) == pytest.approx(2.0)
    h.observe(100.0)                      # +Inf tail clamps to last bound
    assert h.quantile(0.9999) == pytest.approx(4.0)
    with pytest.raises(ValueError):
        h.quantile(1.5)
    empty = reg.histogram("h2", buckets=(1.0,)).labels()
    assert empty.quantile(0.5) == 0.0


def test_exp_buckets():
    b = exp_buckets(1e-4, 1.12, 10)
    assert len(b) == 10 and b[0] == pytest.approx(1e-4)
    assert all(x < y for x, y in zip(b, b[1:]))
    assert all(len(repr(v)) <= 12 for v in b)      # 4-sig-digit labels
    with pytest.raises(ValueError):
        exp_buckets(0.0, 2.0, 4)
    assert len(LATENCY_BUCKETS) == 120
    assert COUNT_BUCKETS[0] == 1.0


def test_gauge_set_fn_and_dead_callback():
    reg = MetricsRegistry()
    g = reg.gauge("g")
    g.set(5.0)
    assert g.value == 5.0
    g.labels().set_fn(lambda: 3.0)        # callback-backed (queue depth)
    assert g.value == 3.0

    def boom():
        raise RuntimeError("replica died")

    g.labels().set_fn(boom)
    assert g.value == 0.0                 # must not kill /metrics
    g.set(7.0)                            # set() clears the callback
    assert g.value == 7.0


def test_get_or_create_and_mismatch():
    reg = MetricsRegistry()
    a = reg.counter("x_total", "help", ("replica",))
    b = reg.counter("x_total", "ignored", ("replica",))
    assert a is b                          # same family, same children
    assert a.labels(replica="r0") is b.labels(replica="r0")
    with pytest.raises(ValueError, match="already bound"):
        reg.gauge("x_total", labels=("replica",))
    with pytest.raises(ValueError, match="label names"):
        reg.counter("x_total", labels=("zone",))
    with pytest.raises(ValueError, match="labels"):
        a.labels(zone="us")                # undeclared label name


def test_disabled_registry_is_noop():
    reg = MetricsRegistry(enabled=False)
    c = reg.counter("x_total", "t", ("replica",))
    h = reg.histogram("h")
    g = reg.gauge("g")
    assert c.labels(replica="r9") is c     # shared null family
    c.inc(100)
    h.observe(1.0)
    g.set(5.0)
    assert c.value == 0.0 and h.count == 0 and g.value == 0.0
    assert reg.render() == ""
    assert c.total() == 0.0 and h.quantile(0.5) == 0.0
    # same shared object across registries — zero allocation per bind
    assert MetricsRegistry(enabled=False).counter("y_total") is c


def test_render_prometheus_text():
    reg = MetricsRegistry()
    reg.counter("serve_tokens_total", "Tokens emitted",
                ("replica",)).labels(replica="r0").inc(42)
    reg.gauge("serve_queue_depth", labels=("replica",)
              ).labels(replica="r0").set(3)
    reg.histogram("serve_ttft_seconds", "TTFT", ("replica",),
                  buckets=(0.1, 1.0)).labels(replica="r0").observe(0.5)
    text = reg.render()
    assert "# HELP serve_tokens_total Tokens emitted" in text
    assert "# TYPE serve_tokens_total counter" in text
    assert 'serve_tokens_total{replica="r0"} 42' in text   # int formatting
    assert 'serve_queue_depth{replica="r0"} 3' in text
    assert "# TYPE serve_ttft_seconds histogram" in text
    assert 'serve_ttft_seconds_bucket{replica="r0",le="0.1"} 0' in text
    assert 'serve_ttft_seconds_bucket{replica="r0",le="1"} 1' in text
    assert 'serve_ttft_seconds_bucket{replica="r0",le="+Inf"} 1' in text
    assert 'serve_ttft_seconds_sum{replica="r0"} 0.5' in text
    assert 'serve_ttft_seconds_count{replica="r0"} 1' in text
    assert text.endswith("\n")


def test_registry_reset_and_collect():
    reg = MetricsRegistry()
    c = reg.counter("x_total")
    c.inc(5)
    h = reg.histogram("h", buckets=(1.0, 2.0))
    h.observe(0.5)
    g = reg.gauge("depth")
    g.set_fn(lambda: 11.0)
    snap = reg.collect()
    assert snap["x_total"]["samples"][""] == 5.0
    assert snap["h"]["samples"][""]["count"] == 1
    assert snap["depth"]["samples"][""] == 11.0
    reg.reset()
    assert c.value == 0.0 and h.hist_count() == 0
    assert g.value == 11.0                 # callback gauges survive reset


def test_merge_histograms_across_registries():
    """One TTFT percentile across independently-built replica
    registries (the multi-replica router summary path)."""
    regs = [MetricsRegistry() for _ in range(2)]
    for i, reg in enumerate(regs):
        fam = reg.histogram("serve_ttft_seconds", buckets=(1.0, 2.0, 4.0),
                            labels=("replica",))
        for _ in range(50):
            fam.labels(replica=f"r{i}").observe(1.5 if i == 0 else 3.0)
    fams = [r.get("serve_ttft_seconds") for r in regs]
    merged = merge_histograms(fams)
    assert merged.count == 100
    assert merged.quantile(0.25) == pytest.approx(1.5)
    assert merged.quantile(0.75) == pytest.approx(3.0)
    assert merge_histograms([]) is None


# ======================================================================
# tracer
# ======================================================================
def test_tracer_events_and_export(tmp_path):
    tr = Tracer()
    t0 = tr.now()
    tr.async_begin("request", 7, args={"prompt_len": 4})
    tr.instant("preempt_swap", track="r0", args={"uid": 7})
    tr.complete("decode_burst", t0, tr.now(), track="r0",
                args={"steps": 8})
    with tr.span("solve", track="prune"):
        pass
    tr.async_end("request", 7)
    assert len(tr.events("request", ph="b")) == 1
    assert tr.events("request", ph="b")[0]["id"] == 7
    assert len(tr.events("preempt_swap", ph="i")) == 1
    burst = tr.events("decode_burst", ph="X")[0]
    assert burst["dur"] >= 0 and burst["args"]["steps"] == 8
    assert len(tr.events("solve", ph="X")) == 1

    path = tmp_path / "trace.json"
    n = tr.export(str(path))
    doc = json.loads(path.read_text())     # loadable Chrome-trace JSON
    assert len(doc["traceEvents"]) == n
    # thread-name metadata gives each track its own lane
    names = {e["args"]["name"] for e in doc["traceEvents"]
             if e["ph"] == "M"}
    assert {"main", "r0", "prune"} <= names
    tr.clear()
    assert tr.events(ph="X") == [] and tr.events(ph="M") != []


def test_disabled_tracer_records_nothing():
    tr = Tracer(enabled=False)
    tr.async_begin("request", 1)
    tr.instant("x")
    tr.complete("y", 0.0, 1.0)
    with tr.span("z"):
        pass
    assert tr.events() == []


# ======================================================================
# serve wiring: lifecycle spans, legacy stats, /metrics content
# ======================================================================
def test_request_lifecycle_spans(tiny_random):
    """A traced engine run emits the full span taxonomy: one async
    request span per uid (balanced b/e), one queue-wait span and one
    first-token instant per request, burst windows, and the latency
    histograms the summaries derive from."""
    model, params = tiny_random
    obs = Obs.create(metrics=True, trace=True)
    eng = ServeEngine(model, params, max_batch=4, max_len=48,
                      page_size=8, prefill_chunk=4, obs=obs)
    reqs = _mixed_requests(model.cfg.vocab_size)
    res = eng.generate(reqs)
    uids = sorted(r.uid for r in reqs)
    tr = obs.tracer
    assert sorted(e["id"] for e in tr.events("request", ph="b")) == uids
    assert sorted(e["id"] for e in tr.events("request", ph="e")) == uids
    assert len(tr.events("queue_wait", ph="X")) == len(reqs)
    assert len(tr.events("first_token", ph="i")) == len(reqs)
    bursts = (tr.events("decode_burst", ph="X")
              + tr.events("prefill_burst", ph="X"))
    assert len(bursts) == eng.stats["host_syncs"] > 0
    assert all(b["dur"] > 0 for b in bursts)
    # histograms observed once per request
    assert eng.m.ttft.count == len(reqs)
    assert eng.m.queue_wait.count == len(reqs)
    assert eng.m.tpot.count == sum(1 for r in res if len(r.tokens) > 1)
    assert eng.m.burst_steps.count == eng.stats["host_syncs"]


def test_preemption_spans_recompute_and_swap_resume(tiny_random):
    """Both preemption flavors show up in the trace, and a swap-resumed
    request still closes its async span after re-admission."""
    model, params = tiny_random
    rng = np.random.default_rng(3)
    reqs = [Request(uid=i,
                    prompt=rng.integers(1, model.cfg.vocab_size,
                                        (4, 9, 13)[i % 3]).astype(np.int32),
                    max_new_tokens=(22, 9, 26)[i % 3])
            for i in range(7)]
    kw = dict(max_batch=3, max_len=48, page_size=8, num_pages=8,
              prefix_cache=False, steps_per_sync=4)
    rec_obs = Obs.create(metrics=True, trace=True)
    rec = ServeEngine(model, params, host_swap_pages=0, obs=rec_obs, **kw)
    rec.generate(reqs)
    assert rec.stats["preempt_recompute"] > 0
    assert (len(rec_obs.tracer.events("preempt_recompute", ph="i"))
            == rec.stats["preempt_recompute"])
    assert rec_obs.tracer.events("preempt_swap", ph="i") == []

    swp_obs = Obs.create(metrics=True, trace=True)
    swp = ServeEngine(model, params, host_swap_pages=None, obs=swp_obs,
                      **kw)
    swp.generate(reqs)
    tr = swp_obs.tracer
    assert swp.stats["preempt_swap"] > 0
    assert (len(tr.events("preempt_swap", ph="i"))
            == swp.stats["preempt_swap"])
    assert len(tr.events("swap_resume", ph="i")) > 0
    assert len(tr.events("swap_in", ph="X")) > 0
    # every preempted request resumed and retired
    uids = sorted(r.uid for r in reqs)
    assert sorted(e["id"] for e in tr.events("request", ph="e")) == uids
    # queue-wait is first-admission only: one span per request even
    # though swap victims re-enter the wait queue
    assert len(tr.events("queue_wait", ph="X")) == len(reqs)


@pytest.mark.parametrize("sps", [1, 8])
def test_tracing_bit_parity_greedy(tiny_random, sps):
    """Acceptance: tracing + metrics on vs fully disabled emits
    bit-identical greedy token streams at both burst lengths."""
    model, params = tiny_random
    reqs = _mixed_requests(model.cfg.vocab_size)
    kw = dict(max_batch=4, max_len=48, page_size=8, steps_per_sync=sps)
    off = ServeEngine(model, params, obs=Obs.disabled(),
                      **kw).generate(reqs)
    obs = Obs.create(metrics=True, trace=True)
    on = ServeEngine(model, params, obs=obs, **kw).generate(reqs)
    for a, b in zip(off, on):
        assert a.uid == b.uid
        np.testing.assert_array_equal(a.tokens, b.tokens)
    assert len(obs.tracer.events("request", ph="e")) == len(reqs)


@pytest.mark.parametrize("sps", [1, 8])
def test_tracing_bit_parity_sampled(tiny_random, sps):
    model, params = tiny_random
    reqs = _mixed_requests(model.cfg.vocab_size, n=8)
    kw = dict(max_batch=4, max_len=48, page_size=8, steps_per_sync=sps,
              temperature=1.0, top_k=20)
    off = ServeEngine(model, params, obs=Obs.disabled(),
                      **kw).generate(reqs, seed=7)
    on = ServeEngine(model, params, obs=Obs.create(metrics=True,
                                                   trace=True),
                     **kw).generate(reqs, seed=7)
    for a, b in zip(off, on):
        np.testing.assert_array_equal(a.tokens, b.tokens)


def test_legacy_stats_view_rebases_per_run(tiny_random):
    """``ServeEngine.stats`` keeps its flat per-run dict shape on top of
    the monotonic registry: a second generate() re-bases the view."""
    model, params = tiny_random
    eng = ServeEngine(model, params, max_batch=4, max_len=48, page_size=8)
    reqs = _mixed_requests(model.cfg.vocab_size)
    res = eng.generate(reqs)
    total = sum(len(r.tokens) for r in res)
    s1 = dict(eng.stats)
    for key in ("host_syncs", "device_steps", "prefill_chunks", "tokens",
                "decode_wall_s", "preempt_swap", "preempt_recompute",
                "prefix_hit_tokens", "prefill_tok", "cow_copies",
                "prefix_evictions", "swap_out_pages", "swap_in_pages",
                "swap_in_wall_s"):
        assert key in s1
    assert s1["tokens"] == total
    assert isinstance(s1["tokens"], int)          # legacy int typing
    assert isinstance(s1["decode_wall_s"], float)
    eng.generate(reqs[:3])
    assert eng.stats["tokens"] == sum(
        len(r.tokens) for r in res if r.uid < 3)  # this run only
    # while the registry itself stayed monotonic across both runs
    fam = eng.obs.metrics.get("serve_tokens_total")
    assert fam.total() == total + eng.stats["tokens"]


def test_metrics_render_after_run(tiny_random):
    model, params = tiny_random
    obs = Obs.create(metrics=True, trace=False, label="r3")
    eng = ServeEngine(model, params, max_batch=4, max_len=48,
                      page_size=8, obs=obs)
    eng.generate(_mixed_requests(model.cfg.vocab_size))
    text = obs.metrics.render()
    for series in ("serve_host_syncs_total", "serve_device_steps_total",
                   "serve_tokens_total", "serve_requests_total",
                   "serve_slot_steps_total"):
        assert f'{series}{{replica="r3"}}' in text
    assert 'serve_ttft_seconds_count{replica="r3"}' in text
    assert 'serve_burst_steps_bucket{replica="r3",le="1"}' in text


def test_utilization_from_registry(tiny_random):
    """serve_tokens_total / serve_slot_steps_total reproduces the
    Result accounting the launcher summary prints."""
    model, params = tiny_random
    eng = ServeEngine(model, params, max_batch=2, max_len=32,
                      page_size=8)
    res = eng.generate(
        [Request(uid=0, prompt=np.arange(4, dtype=np.int32),
                 max_new_tokens=2),
         Request(uid=1, prompt=np.arange(4, dtype=np.int32),
                 max_new_tokens=12)])
    toks = eng.obs.metrics.get("serve_tokens_total").total()
    slots = eng.obs.metrics.get("serve_slot_steps_total").total()
    want = (sum(r.decode_steps for r in res) /
            sum(r.decode_steps / r.utilization for r in res))
    assert toks / slots == pytest.approx(want)


# ======================================================================
# prune pipeline: stage counters + spans through the same registry
# ======================================================================
def test_prune_pipeline_stage_metrics(tiny_lm):
    from repro.core import PruningEngine
    from repro.data import calibration_batches

    model, params, _ = tiny_lm
    calib = calibration_batches(model.cfg, n_samples=8, seq_len=64,
                                batch=8)
    eng = PruningEngine(model, "2:4", method="SM", blocksize=64)
    eng.obs = Obs.create(metrics=True, trace=True)
    _, reports = eng.run(params, calib)
    reg = eng.obs.metrics
    stage = reg.get("prune_stage_seconds_total")
    by_stage = {k[0]: c.value for k, c in stage.children()}
    assert {"capture", "solve", "propagate"} <= set(by_stage)
    assert all(v > 0 for v in by_stage.values())
    assert reg.get("prune_segments_total").total() > 0
    traces = {k[0]: c.value
              for k, c in reg.get("prune_stage_traces_total").children()}
    assert traces["capture"] > 0 and traces["propagate"] > 0
    # registry seconds mirror the engine's own pipeline stats
    ps = eng.last_pipeline_stats
    assert by_stage["solve"] == pytest.approx(ps.solve_s, rel=1e-6)
    for st in ("capture", "solve", "propagate"):
        assert len(eng.obs.tracer.events(f"prune_{st}", ph="X")) > 0
    # the live spans around and inside the stages, with their segment
    tr = eng.obs.tracer
    n_seg = model.cfg.num_layers
    assert len(tr.events("prune_job", ph="X")) == 1
    assert len(tr.events("prune_drain", ph="X")) == 1
    segs = tr.events("prune_segment", ph="X")
    assert [e["args"]["index"] for e in segs] == list(range(n_seg))
    for name in ("prune_capture", "prune_hessian_merge", "prune_solve",
                 "prune_propagate"):
        assert [e["args"]["segment"] for e in tr.events(name, ph="X")] \
            == [e["args"]["segment"] for e in segs]
    assert len(tr.events("prune_hessian_accumulate", ph="X")) >= n_seg
    lin = tr.events("prune_solve_linear", ph="X")
    assert [f"{e['args']['segment']}.{e['args']['linear']}" for e in lin] \
        == [r.name for r in reports]


# ======================================================================
# prune stages on the profiler's clock, and the retrace counter
# ======================================================================
def _profile_host_events(tmp_path, fn):
    """Host events ``(name, stats)`` of a ``jax.profiler`` trace of
    ``fn()``, read back from its ``.xplane.pb``."""
    from jax.profiler import ProfileData
    with jax.profiler.trace(str(tmp_path)):
        fn()
    path, = tmp_path.glob("**/*.xplane.pb")
    data = ProfileData.from_file(str(path))
    return [(e.name, dict(e.stats)) for plane in data.planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]


@pytest.mark.parametrize("enabled", [True, False])
def test_span_is_a_profiler_host_event(tmp_path, enabled):
    """A live span reaches the profiler's trace with its track and args,
    whether or not the tracer records Chrome JSON."""
    tr = Tracer(enabled=enabled)

    def work():
        with tr.span("obs_probe_span", track="prune",
                     args={"segment": "period1"}):
            jax.block_until_ready(jax.numpy.ones(4) * 2)

    evs = [st for n, st in _profile_host_events(tmp_path, work)
           if n == "obs_probe_span"]
    assert evs == [{"track": "prune", "segment": "period1"}]
    assert len(tr.events("obs_probe_span", ph="X")) == (1 if enabled else 0)


def _toy_segment():
    """A one-linear segment: y = x @ w, capturing x."""
    from repro.core.engine import SegmentSpec

    def apply(p, h, capture=False):
        return h @ p, ({"lin": h} if capture else {})
    return SegmentSpec(name="toy0", apply=apply, linears=[],
                       get_params=lambda p: p, set_params=lambda p, s: s)


def test_stage_traces_count_traces_not_calls():
    from repro.core.pipeline import SegmentScheduler

    obs = Obs.create()
    sched = SegmentScheduler(obs=obs, donate=False)
    seg, w = _toy_segment(), jax.numpy.eye(4)
    fam = obs.metrics.get("prune_stage_traces_total")

    def n():
        return {k[0]: c.value for k, c in fam.children()}.get("propagate")

    sched.propagate(seg, w, [jax.numpy.ones((2, 4))])
    sched.propagate(seg, w, [jax.numpy.ones((2, 4))])
    assert n() == 1
    sched.propagate(seg, w, [jax.numpy.ones((3, 4))])
    assert n() == 2


def test_engine_retraces_stage_programs_every_run(tiny_lm):
    """Each run builds a new scheduler, whose capture and propagate jits
    are traced again: 2 traces a run after the first.  The solve and
    Hessian programs are module-level and cached, so they trace once."""
    from repro.core import PruningEngine
    from repro.data import calibration_batches

    model, params, _ = tiny_lm
    calib = calibration_batches(model.cfg, n_samples=8, seq_len=32,
                                batch=8)
    eng = PruningEngine(model, "2:4", method="SM", blocksize=64)
    eng.obs = Obs.create()

    def counts():
        fam = eng.obs.metrics.get("prune_stage_traces_total")
        return {k[0]: c.value for k, c in fam.children()}

    eng.run(params, calib)
    first = counts()
    assert first["capture"] == 1 and first["propagate"] == 1
    eng.run(params, calib)
    second = counts()
    assert {k: v - first.get(k, 0) for k, v in second.items()
            if v != first.get(k, 0)} == {"capture": 1, "propagate": 1}


def _stage_lowerings(tiny_lm):
    """Each stage program lowered at tiny size, by program name."""
    import jax.numpy as jnp

    from repro.core.distributed import _sharded_prune_fn
    from repro.core.engine import _local_solve_fn
    from repro.core.hessian import (_prune_hessian_merge,
                                    _prune_hessian_update)
    from repro.core.pipeline import SegmentScheduler
    from repro.core.sparsity import SparsitySpec
    from repro.dist import make_mesh

    model, params, _ = tiny_lm
    seg = model.prunable_segments()[0]
    sp = seg.get_params(params)
    init = getattr(model, "calib_init", None) or model.first_hidden
    h = init(params, {"tokens": jnp.zeros((2, 16), jnp.int32)})
    sched = SegmentScheduler(donate=False)
    spec, m = SparsitySpec.parse("2:4"), 16
    w, hm = jnp.ones((8, m)), jnp.eye(m)
    return {
        "prune_capture": lambda: sched._fn(seg, True).lower(sp, h),
        "prune_propagate": lambda: sched._fn(seg, False).lower(sp, h),
        "prune_solve": lambda: _local_solve_fn(
            spec, "SM", 8, 0.01, None, None, False).lower(w, hm),
        "prune_solve_rows": lambda: _sharded_prune_fn(
            make_mesh((1,), ("model",)), spec, "SM", 8, 0.01, None, None,
            "model").lower(w, hm),
        "_prune_hessian_update": lambda: _prune_hessian_update.lower(
            jnp.eye(m), jnp.zeros(()), jnp.ones((m, 4))),
        "_prune_hessian_merge": lambda: _prune_hessian_merge.lower(
            jnp.ones((2, m, m)), jnp.ones((2,))),
    }


@pytest.mark.parametrize("program,scope", [
    ("prune_capture", "prune_capture"),
    ("_prune_hessian_update", "prune_hessian"),
    ("_prune_hessian_merge", "prune_hessian"),
    ("prune_solve", "prune_solve"),
    ("prune_solve_rows", "prune_solve"),
    ("prune_propagate", "prune_propagate"),
])
def test_stage_program_carries_its_scope(tiny_lm, program, scope):
    """Every stage program runs as an XLA module named for its stage (the
    "XLA Modules" line of a device trace) and names the stage in its
    ops' metadata."""
    text = _stage_lowerings(tiny_lm)[program]().as_text(debug_info=True)
    assert f"module @jit_{program} " in text
    assert f"/{scope}/" in text
