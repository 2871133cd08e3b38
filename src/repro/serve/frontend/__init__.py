"""Async serving front end over the continuous-batching engine.

  protocol   wire objects: CompletionRequest/Chunk/Response + SSE
             framing — shared by the HTTP server AND the batch CLI
  replica    one ServeEngine session on a worker thread: thread-safe
             submit, callback token delivery, drain/health/load
  router     least-loaded dispatch over N data-parallel replicas,
             QueueFull failover, bounded-backoff retries,
             drain-on-shutdown
  server     stdlib-asyncio HTTP/1.1: POST /v1/completions (JSON or
             SSE streaming), /healthz, /stats; 429 backpressure,
             client-disconnect cancellation, 503 + Retry-After, 504
             deadline mapping
  supervisor replica crash/stall detection, worker restart, and
             in-flight failover with replay suppression

See docs/serving_frontend.md for the API surface and contracts
(including the failure model).
"""

from repro.serve.frontend.protocol import (CompletionChunk,
                                           CompletionRequest,
                                           CompletionResponse, sse_decode,
                                           sse_encode, to_engine_request)
from repro.serve.frontend.replica import Replica, ReplicaDraining
from repro.serve.frontend.router import (NoHealthyReplicas, ReplicaCrashed,
                                         Router)
from repro.serve.frontend.server import Server, run_server
from repro.serve.frontend.supervisor import Supervisor

__all__ = [
    "CompletionChunk",
    "CompletionRequest",
    "CompletionResponse",
    "NoHealthyReplicas",
    "Replica",
    "ReplicaCrashed",
    "ReplicaDraining",
    "Router",
    "Server",
    "Supervisor",
    "run_server",
    "sse_decode",
    "sse_encode",
    "to_engine_request",
]
