"""Continuous-batching serving subsystem (ISSUE r08 tentpole, r09 prefix
caching + chunked prefill).

Composes four pieces:

  * :class:`~paddle_tpu.serving.kv_pool.KVPool` — page-pool KV cache
    allocator with a reserved null page and per-page refcounts
    (PagedAttention, SOSP '23);
  * :class:`~paddle_tpu.serving.prefix_cache.PrefixIndex` — page-aligned
    radix index over token chunks for KV page reuse across requests
    sharing a prompt prefix, with LRU eviction of reclaimable pages
    (RadixAttention / SGLang);
  * :class:`~paddle_tpu.serving.scheduler.FCFSScheduler` — FCFS
    iteration-level admission with a Sarathi-style per-step chunk budget
    (Orca, OSDI '22; Sarathi-Serve, OSDI '24);
  * :class:`~paddle_tpu.serving.engine.ServingEngine` — the host loop
    over TWO reusable jitted programs (chunked prefill-into-pages +
    single decode step over the slot batch), backed by the Pallas
    paged-attention decode and paged-prefill chunk kernels
    (kernels/paged_attention.py, kernels/paged_prefill.py);
  * observability (r11): dependency-free
    :class:`~paddle_tpu.serving.metrics.MetricsRegistry` (counters /
    gauges / exponential-bucket histograms with p50/p90/p99) fed by the
    engine every step, per-request lifecycle tracing to Chrome
    trace-event JSON (:mod:`~paddle_tpu.serving.tracing`, opens in
    Perfetto, unified with ``profiler.RecordEvent`` host spans), and
    TensorBoard + Prometheus file exporters
    (``ServingEngine(metrics=..., trace=...)``,
    ``engine.run(metrics_dir=...)``);
  * multi-tenant serving front end (r12): pluggable
    :class:`~paddle_tpu.serving.tenancy.SchedulerPolicy` over the
    waiting queue — FCFS default, Virtual-Token-Counter weighted fair
    queueing (:class:`~paddle_tpu.serving.tenancy.WFQPolicy`) with
    per-tenant weights/priorities/quotas — and a stdlib-asyncio
    streaming HTTP API
    (:class:`~paddle_tpu.serving.frontend.ServingFrontend`: SSE
    ``/v1/completions`` per engine step via ``on_token``, ``/metrics``
    Prometheus scrape, ``/healthz``, disconnect→cancel, 429/408 SLO
    mapping);
  * speculative decoding (r13): host-side n-gram self-drafting
    (:class:`~paddle_tpu.serving.drafter.NGramDrafter`, prompt-lookup /
    PLD) proposes up to ``spec_k`` tokens per slot, one multi-query
    paged-attention verify dispatch scores every draft position
    (kernels/paged_attention.py ``paged_attention_mq``), and greedy
    rejection sampling accepts the longest agreeing prefix plus one
    corrected token — token-for-token identical to non-speculative
    decode (``ServingEngine(spec_k=...)``);
  * disaggregated multi-replica serving (r15):
    :class:`~paddle_tpu.serving.router.Router` routes each request to
    the replica with the longest cached prefix (read-only
    ``prefix_match_len`` probes, load tie-break), separates prefill
    workers from decode workers (``ServingEngine(role=...)`` + snapshot
    v5 page-payload handoffs, layout-guarded, adopted bit-exactly into
    the destination pool + prefix index), lifts WFQ virtual-token
    counters router-global
    (:class:`~paddle_tpu.serving.tenancy.ClusterWFQState`;
    ``make_cluster`` builds the whole fleet);
  * a step that dispatches decode N+1 before it reads decode N: the carry
    tokens stay on the device and every host read comes after the step's
    last dispatch, so the host's turn overlaps the device's step (every
    engine's one step path; ``stats["decode_ahead"]`` /
    ``["decode_sync_first"]``; ``spec_k`` alone reads first, to draft);
  * cluster-wide observability (r16): replica-namespaced tracing with
    Chrome flow events stitching prefill export → router pump → decode
    ingest into ONE merged Perfetto timeline
    (:func:`~paddle_tpu.serving.tracing.merge_traces` /
    :func:`~paddle_tpu.serving.tracing.validate_trace`), a bounded
    per-step :class:`~paddle_tpu.serving.flight_recorder.FlightRecorder`
    black box on the engine clock (chaos replays dump bit-identically;
    crashes dump before re-raising), per-tenant SLO attainment + fast /
    slow burn-rate gauges (:class:`~paddle_tpu.serving.metrics.
    SLOTracker`, targets on :class:`~paddle_tpu.serving.tenancy.
    TenantConfig`), histogram-merging cluster aggregation
    (:func:`~paddle_tpu.serving.metrics.merge_registries`), and the
    front end's read-only ``/debug`` surface;
  * fault tolerance (r10): on-demand page growth with
    preempt-and-recompute under pool pressure, per-request deadlines /
    ``cancel`` / bounded-queue backpressure,
    :func:`~paddle_tpu.serving.snapshot.snapshot_engine` /
    :func:`~paddle_tpu.serving.snapshot.restore_engine` for exact
    resume, and the deterministic
    :class:`~paddle_tpu.serving.faults.FaultPlan` chaos harness.

See README "Serving" for the architecture and knobs;
``examples/serve_gpt.py`` for the end-to-end loop.
"""

from .kv_pool import KVPool
from .prefix_cache import PrefixIndex
from .scheduler import Admission, FCFSScheduler, Request
from .tenancy import (DEFAULT_TENANT, ClusterWFQState, FCFSPolicy,
                      SchedulerPolicy, TenantConfig, WFQPolicy)
from .metrics import (Counter, Gauge, Histogram, MetricsFileExporter,
                      MetricsRegistry, SLOTracker, aggregate_scalars,
                      cluster_prometheus, merge_registries)
from .tracing import (PID_ENGINE, PID_HOST, PID_REQUESTS, PID_ROUTER,
                      TraceRecorder, attach_profiler, detach_profiler,
                      flow_id, merge_traces, validate_trace)
from .drafter import NGramDrafter
from .flight_recorder import FlightRecorder
from .engine import TERMINAL_REASONS, FinishedRequest, ServingEngine
from .faults import FaultPlan, InjectedFault
from .snapshot import handoff_state, restore_engine, snapshot_engine
from .frontend import ServingFrontend
from .router import Router, make_cluster

__all__ = ["KVPool", "PrefixIndex", "FCFSScheduler", "Request", "Admission",
           "ServingEngine", "FinishedRequest", "TERMINAL_REASONS",
           "FaultPlan", "InjectedFault", "snapshot_engine",
           "restore_engine", "handoff_state", "MetricsRegistry", "Counter",
           "Gauge", "Histogram", "MetricsFileExporter", "TraceRecorder",
           "attach_profiler", "detach_profiler", "PID_ENGINE",
           "PID_REQUESTS", "PID_HOST", "PID_ROUTER",
           "SchedulerPolicy", "FCFSPolicy", "WFQPolicy", "TenantConfig",
           "ClusterWFQState", "DEFAULT_TENANT", "ServingFrontend",
           "NGramDrafter", "Router", "make_cluster",
           "aggregate_scalars", "cluster_prometheus", "merge_registries",
           "SLOTracker", "FlightRecorder", "flow_id", "merge_traces",
           "validate_trace"]
