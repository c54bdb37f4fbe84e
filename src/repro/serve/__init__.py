"""Scheduling-as-a-service: streaming solve server with continuous bucket
batching.

The pipeline (DESIGN.md §11): an asyncio front-end
(:class:`~repro.serve.service.SolveService`) enqueues
:class:`~repro.serve.queue.SolveRequest`s grouped by quantized
launch-shape signature; a deadline/budget-aware
:class:`~repro.serve.batcher.Batcher` cuts same-signature batches; a
warm-pool :class:`~repro.serve.engine.Engine` runs them through
``device_search.solve_instances`` (or per-request numpy solves),
overlapping host batch assembly with device compute, and streams anytime
incumbents back per request.  Every served result is bit-identical to a
solo ``repro.solve()`` at the same seed/budget/backend.
"""
from .batcher import Batcher, BatchPolicy, CutBatch
from .compile_cache import enable_compilation_cache
from .engine import (
    Engine,
    EngineConfig,
    RequestFailure,
    RequestResult,
    WarmSpec,
)
from .queue import RequestQueue, ServiceClosed, SolveRequest, launch_signature
from .resilience import (
    AdmissionPolicy,
    ResilienceController,
    ResiliencePolicy,
    RetryPolicy,
)
from .service import SolveService

__all__ = [
    "AdmissionPolicy",
    "Batcher",
    "BatchPolicy",
    "CutBatch",
    "Engine",
    "EngineConfig",
    "RequestFailure",
    "RequestResult",
    "RequestQueue",
    "ResilienceController",
    "ResiliencePolicy",
    "RetryPolicy",
    "ServiceClosed",
    "SolveRequest",
    "SolveService",
    "WarmSpec",
    "enable_compilation_cache",
    "launch_signature",
]
