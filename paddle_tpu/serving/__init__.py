"""Multi-tenant serving tier: continuous bucketed batching over
warm-compiled predictors (the ROADMAP "millions of users" workload).

Layers (each its own module, composable and separately testable):

- :mod:`request_queue` — the front door: :class:`Request` futures,
  per-tenant :class:`AdmissionController` (sample-denominated queue cap +
  tenant quota, refusal at submit), the FIFO the scheduler drains;
- :mod:`scheduler`     — continuous batch assembly: FIFO prefix →
  ``jit.bucketing`` rung → ONE padded program call → rows scattered back;
  re-assembly between every pair of steps picks up what arrived mid-step;
- :mod:`engine`        — :class:`ServingEngine`: warm-compiles the bucket
  ladder through ``inference.Predictor.run_many``'s shared
  ``_BatchProgram``, clones the predictor per tenant (zero-copy weight
  sharing), runs the scheduler thread, and proves zero steady-state
  retraces (``compiles_after_warmup == 0``, audited by JX330).

Serving phase 2 (ISSUE 13) adds TRUE continuous batching for GPT decode:

- :mod:`kv_cache`      — :class:`KVSlotPool`: ONE device-resident K/V
  buffer pair ([layers, slots+1, seq, heads, dim], allocated once),
  free-list slot alloc/release, functional in-place row updates under
  donation;
- :mod:`decode`        — :class:`DecodePrograms` (functional GPT
  prefill/decode programs, one warm specialization per bucket rung,
  whole set restorable from the persistent compile cache) and
  :class:`DecodeEngine` (the decode front door: priority tiers, TTL,
  per-tenant lanes);
- :class:`DecodeScheduler` (in :mod:`scheduler`) — one
  prefill-or-decode program call per step; requests join freed slots
  mid-flight and leave the step they finish — no batch re-assembly.

A model whose layers keep a recurrent state and no keys and values
(``models.BrumbyForCausalLM``) is served by the same engine over the third
residency: :class:`StateLanePool` (one float32 state array, a lane a
request) and :class:`RetentionPrograms` (a chunked prefill that carries
the state, a decode step that updates it in place). A model with latent
attention and sparse experts (``models.AXK1ForCausalLM``) is served over
the fourth: a :class:`KVPagePool` of ONE array whose row is a token's
latent, K and V at once, and :class:`LatentPrograms` (a prefill chunk
that attends in the expanded form over the pages before its cursor, an
absorbed decode step over the latent pages, an expert layer that is told
which experts it holds), under the paged scheduler with the chunked
prefill's cursor. A model whose layers are of two kinds, window and global
(``models.Cohere2MoEForCausalLM``), is served over the fifth:
:class:`WindowedPagePools`, two page pools under one manager whose pages
have two lifetimes (a window layer's page goes back as soon as the window
has passed it), and :class:`WindowedPrograms` (parallel blocks, a
grouped-query paged decode kernel that knows the window, the same expert
layer), under a scheduler that keeps two tables a request. The residency
follows from the model (``serving_residency``): no option selects it.

Latency accounting (enqueue→admit→dispatch→complete, queue depth,
p50/p99, requests/sec at FLAGS_serving_slo_ms, the prefill-vs-decode
step split and decode tokens/sec) flows through
``profiler.pipeline.serving_stats``.

    engine = serving.ServingEngine("ckpt/model", buckets=[1, 2, 4, 8])
    engine.warmup()
    out, = engine.run("tenant-a", batch_of_3)       # blocks, 3 rows back
    req = engine.submit("tenant-b", batch_of_5)     # future
    ...
    req.result()
    engine.shutdown(drain=True)
"""
from .decode import (DecodeEngine, DecodePrograms, LatentPrograms,
                     RetentionPrograms, WindowedPrograms)
from .engine import EngineBase, ServingEngine
from .kv_cache import (KVPagePool, KVSlotPool, StateLanePool,
                       WindowedPagePools)
from .request_queue import (AdmissionController, AdmissionError,
                            DecodeRequest, RejectedError, Request,
                            RequestQueue)
from .scheduler import (DecodeScheduler, Scheduler, scatter_outputs,
                        stack_requests)

__all__ = [
    "AdmissionController", "AdmissionError", "DecodeEngine",
    "DecodePrograms", "DecodeRequest", "DecodeScheduler", "EngineBase",
    "KVPagePool", "KVSlotPool", "LatentPrograms", "RejectedError", "Request", "RequestQueue",
    "RetentionPrograms", "Scheduler", "ServingEngine", "StateLanePool",
    "WindowedPagePools", "WindowedPrograms", "scatter_outputs",
    "stack_requests",
]
