"""Serving driver: continuous-batching decode off a (optionally
2:4-pruned) checkpoint — batch CLI or a streaming HTTP server.

  # batch: N random-prompt requests through the router, print a summary
  python -m repro.launch.serve --arch paper-tiny-lm \\
      --params /tmp/pruned/pruned_params --sparse --requests 8

  # server: OpenAI-style /v1/completions with SSE streaming
  python -m repro.launch.serve --arch paper-tiny-lm --server --port 8000 \\
      --replicas 2 --queue-depth 64

Both paths go through the SAME serve.frontend request/response objects
(docs/serving_frontend.md): the batch mode builds CompletionRequests
and calls ``Router.complete`` — it is a client of the server's code
path, not parallel plumbing.  ``--serve-mode static`` keeps the legacy
bucketed engine (no sessions/streaming: the batch path lowers the same
wire objects straight onto ``ServeEngine.generate``).

Every runtime knob funnels through ONE :class:`repro.serve.ServeConfig`
built here by ``ServeConfig.from_args`` and handed down whole —
engine, replicas, router (docs/serving.md).  The continuous runtime's
paged-pool knobs include ``--page-size`` / ``--num-pages`` plus the
ISSUE-7 prefix/swap switches ``--prefix-cache/--no-prefix-cache`` and
``--host-swap-pages``.
"""

from __future__ import annotations

import argparse
import asyncio
import signal
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs as cfglib
from repro.ckpt import load_pytree
from repro.dist import add_mesh_argument, mesh_context
from repro.models import LM
from repro.obs import Obs
from repro.serve import ServeConfig, ServeEngine, sparsify_params
from repro.serve.frontend import (CompletionRequest, CompletionResponse,
                                  Replica, Router, Supervisor, run_server,
                                  to_engine_request)
from repro.utils.compile_cache import enable_compile_cache


def install_sigterm_handler() -> None:
    """Route SIGTERM (the orchestrator's stop signal) through the SAME
    KeyboardInterrupt path as Ctrl-C: drain-first shutdown, then the
    ``finally`` trace export — instead of dying mid-step with KV state
    on the floor (ISSUE-10 satellite)."""

    def _raise(signum, frame):
        raise KeyboardInterrupt

    try:
        signal.signal(signal.SIGTERM, _raise)
    except ValueError:
        pass   # not the main thread (tests import and call main())


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="paper_tiny_lm")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--params", default=None,
                    help="pruned_params dir (default: random init)")
    ap.add_argument("--sparse", action="store_true",
                    help="pack 2:4 weights → nm_spmm kernel path")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--max-batch", type=int, default=8,
                    help="serve slots per engine replica")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--sampling", default="greedy",
                    choices=("greedy", "temperature", "top-k", "top-p"),
                    help="decode sampling: greedy argmax, plain "
                         "temperature, or top-k / top-p (nucleus) "
                         "filtering — all keyed per (uid, step) in "
                         "continuous mode, so preemption-recompute "
                         "replays identical tokens")
    ap.add_argument("--top-k", type=int, default=40,
                    help="k for --sampling top-k")
    ap.add_argument("--top-p", type=float, default=0.9,
                    help="nucleus mass for --sampling top-p")
    ap.add_argument("--serve-mode", default="continuous",
                    choices=("continuous", "static"),
                    help="continuous batching (paged KV) or the legacy "
                         "static bucketed path")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page (continuous mode)")
    ap.add_argument("--num-pages", type=int, default=None,
                    help="KV pool size in pages (default: dense-cache "
                         "capacity equivalent)")
    ap.add_argument("--prefill-chunk", type=int, default=32,
                    help="prompt tokens per chunked-prefill step "
                         "(continuous mode; one jitted shape)")
    ap.add_argument("--steps-per-sync", type=int, default=8,
                    help="fused decode steps per host sync (continuous "
                         "mode): the device runs K sample/record/advance "
                         "steps in one burst and the host only wakes for "
                         "scheduler events — tokens are bit-identical "
                         "for every K (docs/serving.md)")
    ap.add_argument("--prefix-cache", default=True,
                    action=argparse.BooleanOptionalAction,
                    help="hash-based prefix reuse over refcounted KV "
                         "pages: cached prompt pages attach shared "
                         "without prefill, copy-on-write on divergence "
                         "(continuous mode; token streams are "
                         "bit-identical either way)")
    ap.add_argument("--host-swap-pages", type=int, default=None,
                    help="host-memory swap arena capacity in pages: "
                         "preemption evicts a victim's exclusive pages "
                         "to the host tier and streams them back on "
                         "resume instead of recomputing (default: "
                         "pool-sized; 0 disables → recompute-only)")
    ap.add_argument("--kv-dtype", default="fp32",
                    choices=("fp32", "int8"),
                    help="KV page storage dtype: int8 quantizes pages "
                         "on write with per-row scales (half the page "
                         "bytes — the default pool sizing then holds "
                         "2x the tokens; greedy streams match fp32 "
                         "within a small tolerance, docs/serving.md)")
    # ---------------------------------------------- server front end
    ap.add_argument("--server", action="store_true",
                    help="run the streaming HTTP front end instead of "
                         "a one-shot batch (docs/serving_frontend.md)")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8000)
    ap.add_argument("--replicas", type=int, default=1,
                    help="data-parallel ServeEngine replicas behind the "
                         "least-loaded router (--server / batch "
                         "continuous mode)")
    ap.add_argument("--queue-depth", type=int, default=None,
                    help="per-replica wait-queue cap; a full queue "
                         "answers 429 instead of buffering unboundedly")
    # ---------------------------------------------- observability
    ap.add_argument("--metrics", default=True,
                    action=argparse.BooleanOptionalAction,
                    help="serve metrics registry (counters/gauges/"
                         "histograms behind /metrics, /stats and the "
                         "end-of-run report); --no-metrics turns every "
                         "instrumentation point into a zero-cost no-op "
                         "(docs/observability.md)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="record request-lifecycle spans (admit wait, "
                         "prefill chunks, decode bursts, preemption/"
                         "swap/CoW events) and write Chrome-trace JSON "
                         "here on exit — load in chrome://tracing or "
                         "Perfetto; token streams are bit-identical "
                         "with tracing on or off")
    # ---------------------------------------------- chaos injection
    ap.add_argument("--inject-fault", action="append", default=None,
                    metavar="SITE[:K=V,...]",
                    help="deterministic fault injection for chaos "
                         "testing (repeatable). SITE is one of "
                         "engine_step|replica_worker|pool_alloc|"
                         "slow_burst|swap_error; keys: after=N (skip N "
                         "passes), count=N (fire N times), delay_s=S "
                         "(slow_burst stall), replica=rK (scope to one "
                         "replica). e.g. "
                         "--inject-fault replica_worker:after=2,replica=r0")
    add_mesh_argument(ap)
    return ap


def load_model(args):
    cfg = (cfglib.get_smoke(args.arch) if args.smoke
           else cfglib.get_config(args.arch))
    model = LM(cfg)
    if args.params:
        tpl = jax.tree.map(lambda s: np.zeros(s.shape, s.dtype),
                           jax.eval_shape(model.init, jax.random.key(0)))
        params, extra = load_pytree(args.params, tpl)
        params = jax.tree.map(jnp.asarray, params)
        print(f"loaded params ({extra})")
    else:
        params = model.init(jax.random.key(0))
    if args.sparse:
        params = sparsify_params(params)
        print("packed 2:4-sparse weights (nm_spmm path)")
    return cfg, model, params


def make_engine(model, params, config: ServeConfig,
                obs: Obs = None) -> ServeEngine:
    # the engine resolves the active mesh: params go resident
    # tensor-parallel, the paged pool / bucket batches shard by the
    # dist rules
    return ServeEngine(model, params, config, obs=obs)


def make_router(model, params, config: ServeConfig,
                obs: Obs = None) -> Router:
    # every replica shares one seed: a request's stream is identical
    # regardless of which replica serves it (per-(uid, step) keys).
    # Replica reads its wait-queue cap off engine.config.queue_depth.
    #
    # One obs bundle is shared by every replica — each writes its own
    # ``replica``-labelled series into the single registry, which is
    # what /metrics scrapes and the end-of-run report reads.
    if obs is None:
        obs = Obs.create(metrics=config.metrics, trace=config.trace)
    reps = [Replica(make_engine(model, params, config,
                                obs=obs.labelled(f"r{i}")),
                    name=f"r{i}", seed=0)
            for i in range(config.replicas)]
    return Router(reps)


def _random_requests(cfg, args):
    rng = np.random.default_rng(0)
    return [
        CompletionRequest(
            uid=i,
            prompt=rng.integers(0, cfg.vocab_size, size=8,
                                dtype=np.int32).tolist(),
            max_tokens=args.max_new)
        for i in range(args.requests)
    ]


def run_batch(cfg, model, params, args, config: ServeConfig,
              obs: Obs) -> None:
    creqs = _random_requests(cfg, args)
    eng = None
    t0 = time.monotonic()
    if config.mode == "continuous":
        router = make_router(model, params, config, obs=obs)
        eng = router.replicas[0].engine
        if eng.mode != "continuous":
            # arch fell back to static: no sessions — drop to the
            # static path below on the already-built engine
            router.close()
        else:
            t0 = time.monotonic()
            try:
                # raises ReplicaCrashed if a worker dies: batch mode
                # runs no supervisor, so nothing would finish the batch
                results = router.complete(creqs)
            except BaseException:
                router.close()
                raise
            dt = time.monotonic() - t0
            router.drain(timeout=30)
            _summary(results, [r.engine for r in router.replicas], dt)
            return
    if eng is None:
        eng = make_engine(model, params, config, obs=obs.labelled("r0"))
    if eng.mode != config.mode:
        print(f"note: {config.mode} unsupported for {cfg.name} — "
              f"fell back to {eng.mode}")
    # static engines have no session/streaming path; same wire objects,
    # lowered straight onto generate()
    t0 = time.monotonic()
    raw = eng.generate([to_engine_request(c, c.uid) for c in creqs])
    dt = time.monotonic() - t0
    _summary([CompletionResponse.from_result(r) for r in raw], [eng], dt)


def _registries(engines):
    regs = []
    for e in engines:
        reg = e.obs.metrics
        if reg.enabled and all(reg is not x for x in regs):
            regs.append(reg)
    return regs


def _summary(results, engines, dt) -> None:
    """End-of-run report, read from the obs registry (ISSUE-8): one
    source of truth with the /metrics endpoint instead of a parallel
    sum over per-engine stat dicts."""
    toks = sum(len(r.tokens) for r in results)
    for r in results[:4]:
        print(f"req {r.uid}: {list(r.tokens)}"
              + (f"  [{r.replica}]" if r.replica else ""))
    preempts = sum(r.preemptions for r in results)
    regs = _registries(engines)

    def total(name: str) -> float:
        return sum(f.total() for f in (reg.get(name) for reg in regs)
                   if f is not None)

    syncs = total("serve_host_syncs_total")
    burst = total("serve_device_steps_total") / syncs if syncs else 0.0
    slot_steps = total("serve_slot_steps_total")
    # aggregate utilization: emitted tokens per slot-step occupied —
    # the registry-level view of Result.utilization
    util = total("serve_tokens_total") / slot_steps if slot_steps else 0.0
    mode = engines[0].mode
    print(f"{toks} tokens in {dt:.2f}s ({toks / dt:.1f} tok/s) "
          f"[{mode}] host-syncs/token {syncs / max(1, toks):.2f} "
          f"burst {burst:.1f} util {util:.2f}"
          + (f" preemptions {preempts}" if preempts else ""))
    from repro.obs.metrics import merge_histograms

    ttft = merge_histograms(
        [f for f in (reg.get("serve_ttft_seconds") for reg in regs)
         if f is not None])
    if ttft is not None and ttft.count:
        print(f"ttft p50 {ttft.quantile(0.5) * 1e3:.1f}ms "
              f"p95 {ttft.quantile(0.95) * 1e3:.1f}ms "
              f"(n={ttft.count})")


def _export_trace(obs: Obs, path) -> None:
    if path and obs.tracer.enabled:
        n = obs.tracer.export(path)
        print(f"wrote {n} trace events -> {path}")


def run_frontend(cfg, model, params, args, config: ServeConfig,
                 obs: Obs) -> None:
    if config.mode != "continuous":
        raise SystemExit("--server needs the continuous runtime "
                         "(streaming sessions); drop --serve-mode static")
    router = make_router(model, params, config, obs=obs)
    if router.replicas[0].engine.mode != "continuous":
        raise SystemExit(f"--server unsupported for {cfg.name}: the arch "
                         f"falls back to the static bucketed engine")
    # supervision (ISSUE-10): restart crashed/stalled workers and fail
    # their in-flight requests over to healthy siblings
    sup = Supervisor(router)
    sup.start()
    try:
        asyncio.run(run_server(router, args.host, args.port))
    except KeyboardInterrupt:
        print("draining...")
        sup.stop()
        router.drain(timeout=30)
    finally:
        sup.stop()


def main() -> None:
    args = build_parser().parse_args()
    install_sigterm_handler()
    enable_compile_cache()
    config = ServeConfig.from_args(args)   # the ONE knob intake point
    # ONE obs bundle for the whole process: every replica labels its
    # series into this registry/tracer (docs/observability.md)
    obs = Obs.create(metrics=config.metrics, trace=config.trace)
    with mesh_context(args.mesh):
        cfg, model, params = load_model(args)
        try:
            if args.server:
                run_frontend(cfg, model, params, args, config, obs)
            else:
                run_batch(cfg, model, params, args, config, obs)
        finally:
            _export_trace(obs, args.trace_out)


if __name__ == "__main__":
    main()
