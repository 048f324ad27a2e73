"""Continuous-batching serving engine over the paged KV pool.

The static-batch decoder (``models/generation.build_generate_fn``) jits
prefill + ``max_new_tokens`` decode steps as ONE program over a fixed
batch: finished sequences keep burning decode steps until the longest
request ends, and a new request cannot join until the whole batch
drains.  This engine instead runs serving as TWO reusable jitted
programs called from a host loop:

  * ``chunk prefill``: up to ``chunk_tokens`` of ONE request's prompt
    per call — embeddings, ``_block_qkv``, the chunk's K/V written
    into the slot's pool pages (whole pages at a time, in place: see
    ``_scatter_kv``), then paged attention of the chunk
    against everything already written (cached prefix pages, earlier
    chunks, itself) via the block table — the Sarathi-Serve chunked
    prefill (kernels/paged_prefill.py).  Chunk widths pad to power-of-two
    buckets so the program retraces per bucket, not per length.  A long
    prompt no longer stalls every in-flight decode for a monolithic
    prefill: each step spends at most the scheduler's token budget on
    prefill (``token_budget`` less the decoding lanes), co-scheduled with
    decode, as several dispatches of this one program: as many chunks as
    requests wait on prefill per decoding lane, one when decodes
    outnumber them (``FCFSScheduler.prefill_budget``).
  * ``decode``: ONE token for EVERY started slot — per-slot paged KV
    write at each slot's own position, paged attention through the block
    table (kernels/paged_attention.py), sampling.  Slot count is static;
    inactive/partially-prefilled lanes are routed to the pool's null page,
    write nothing and are ignored.

Prefix caching (RadixAttention, SGLang) rides on the page pool: at
admission the scheduler matches the prompt against the pool's
token-chunk radix index, the request's block table starts with the
matched pages SHARED (refcounted, read-only), a partial-tail match is
COPY-ON-WRITE cloned into a fresh page, and only the uncached suffix is
chunk-prefilled.  When a prompt finishes prefilling, its full pages are
inserted into the index; a finished request's pages drop their reference
and cached pages park reclaimable (LRU-evicted under pressure) instead
of being eagerly freed — a shared system prompt is computed once and
reused by every later request.

Fault tolerance (r10) — the engine degrades instead of failing:

  * **On-demand page growth + preempt-and-recompute.**  Admission
    reserves pages for the PROMPT only; decode allocates one page the
    step a slot crosses a page boundary.  When growth (or admission)
    meets an empty pool, the engine preempts the YOUNGEST occupied slot
    — pages freed, generated tokens kept on the request, requeued at
    the head of the waiting queue for recompute-restart through the
    chunked-prefill path (vLLM's preempt-by-recompute; the prefix cache
    makes the recompute cheap because the victim's full prompt pages
    park reclaimable and are re-adopted at re-admission).  The OLDEST
    request (admission seq preserved across preemptions) is never a
    victim, so it always progresses — no livelock.  Greedy outputs are
    token-for-token identical to an unpressured run.
  * **Request lifecycle.**  ``deadline_s`` expires a request at
    queue-pop and per-step; ``cancel(rid)`` works in any state (waiting,
    mid-prefill, decoding — pages released the same call); ``max_queue``
    bounds the waiting queue and converts overflow into an explicit
    ``rejected`` terminal (backpressure) instead of unbounded growth.
    Every request ends in EXACTLY one of
    {``eos``, ``length``, ``rejected``, ``expired``, ``cancelled``},
    delivered as a :class:`FinishedRequest` from ``step()``.
  * **Snapshot / restore.**  ``snapshot()`` captures queue + slot
    metadata + pool/prefix state + host mirrors;
    ``ServingEngine.restore`` resumes a killed host loop with
    token-for-token identical output (serving/snapshot.py).
  * **Deterministic fault injection.**  A ``faults=FaultPlan`` scripts
    alloc failures, phase-boundary step exceptions and virtual step
    latency by step index (serving/faults.py); the engine absorbs them
    (``stats["step_faults"]``) and the chaos suite asserts
    terminal-state totality + leak-free drain under any seed.

Every host-loop iteration the FCFS scheduler admits waiting requests
into freed slots, the chunk budget advances partial prefills, exactly
one decode call covers the started slots, and finished requests return —
iteration-level scheduling (Orca) with block-table paging (vLLM),
composed with the int8 W8A8 + int8-KV serving path from the dense
decoder: the per-(layer, batch, head, position) scale layout carries
over to per-page scales unchanged.

The order of a step: every dispatch comes first, every host read of a
device value after the last dispatch, and the decode that is read is the
PREVIOUS step's.  Admit; the chunk dispatches; grow pages and dispatch
decode N+1; then read decode N's tokens and retire them, then read the
first tokens of the prompts this step completed.  The device holds decode
N+1 while the host retires, returns from ``step()``, takes new requests
and plans N+2.  What makes it possible: the carry tokens stay on the
device (the decode program returns them, a completing chunk's sample is
put into its lane there; the host's ``_tok`` is a mirror filled at
retirement), and lengths, ring turns and state positions advance on the
host at DISPATCH by a count that does not depend on the tokens.  Only an
``eos`` is a surprise: its lane rides one more decode, whose token is
dropped at retirement and whose row landed in pages the lane still owned
(``_retire_decode``).  A preemption whose victim has an unread decode
retires first; ``spec_k > 0`` reads before it drafts, every step.

What the host's turn is made of (PR 39), always on: ``stats["steps"]``;
``dispatch_s``, the host seconds inside every program dispatch of a step
(the ``engine.*_dispatch`` spans, filed as they close, like the phases);
``device_drained``, steps whose first dispatch found the decode in flight
already done (device order is dispatch order: the device had run dry and
waited for this dispatch); ``slow_steps`` / ``slow_step_s``, steps of
``SLOW_STEP_S`` or more, each described by a ``slow_step`` flight record
when a recorder is attached (``_note_slow_step``).
"""

from __future__ import annotations

import collections
import dataclasses
import gc
import os
import resource
import time
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from ..models.generation import (
    _block_finish,
    _block_qkv,
    _decoder_setup,
    _embed,
    _lm_head,
    _make_sampler,
    _norm,
    _resolve_kv_bits,
    decoder_layers,
    spec_accept_greedy,
)
from ..models.ssm import ssm_conv, ssm_in, ssm_out, ssm_split
from ..kernels import paged_attention as pa
from ..kernels import paged_prefill as pp
from ..kernels import ssd
from .drafter import NGramDrafter
from .faults import FaultPlan, InjectedFault
from .flight_recorder import FlightRecorder
from .kv_pool import KVPool, StateSlab, WindowRing
from .metrics import MetricsRegistry, SLOTracker
from .scheduler import FCFSScheduler, Request
from .tenancy import normalize_tenants
from .tracing import PID_ENGINE, PID_REQUESTS, TraceRecorder, flow_id

#: Reasons a request leaves the engine.  "eos"/"length" are successful
#: completions; the r10 lifecycle adds the degraded terminals.
TERMINAL_REASONS = ("eos", "length", "rejected", "expired", "cancelled")


@dataclasses.dataclass
class FinishedRequest:
    """One terminal request: the continuation produced (prompt excluded).

    ``finish_reason`` is one of :data:`TERMINAL_REASONS`; ``reason`` is
    the same value under the r10 lifecycle name.  For degraded terminals
    (``rejected``/``expired``/``cancelled``) ``tokens`` holds whatever
    was generated before the request left (possibly empty)."""

    rid: int
    prompt: np.ndarray
    tokens: np.ndarray            # generated continuation, EOS included
    finish_reason: str
    n_steps: int                  # engine steps it was resident

    @property
    def reason(self) -> str:
        return self.finish_reason

    @property
    def ok(self) -> bool:
        """True when the request ran to completion (eos/length)."""
        return self.finish_reason in ("eos", "length")


class MultiGroupUnsupported(NotImplementedError):
    """A feature that a model with more than the one page group does not
    have yet: two page groups (sliding-window layers beside full-attention
    layers, ``serving/kv_pool.WindowRing``), or recurrent state beside its
    pages (``serving/kv_pool.StateSlab``), which cannot be sliced by
    position at all; PERF.md section 7 lists them."""


#: the four phases of a step: their spans also fill ``_phase_s``, which
#: feeds ``stats["<phase>_s"]``, the registry and the TraceRecorder
_PHASE_OF_SPAN = {f"engine.{ph}": ph
                  for ph in ("admit", "prefill", "handoff", "decode")}
#: the host's calls into the runtime: each span appends its duration to
#: its entry of the step's ``_calls_s``; the dispatches' sum is
#: ``stats["dispatch_s"]``, and all three feed the ``slow_step`` record
_CALL_OF_SPAN = {
    **{f"engine.{p}_dispatch": "dispatch" for p in
       ("prefill", "decode", "carry", "cow", "state_reset", "ingest")},
    "engine.decode_sync": "decode_sync",
    "engine.first_token_sync": "first_token_sync"}
_DISPATCH_SPANS = frozenset(n for n, c in _CALL_OF_SPAN.items()
                            if c == "dispatch")
#: a step of this many wall seconds or more is a stall (a step takes 10-40
#: ms in every benchmark cell): counted, and recorded when a flight
#: recorder is attached
SLOW_STEP_S = 0.25
#: the thread's own CPU time, context switches and faults where the
#: platform keeps them per thread
_RUSAGE_WHO = getattr(resource, "RUSAGE_THREAD", resource.RUSAGE_SELF)
#: wall seconds of generation-2 garbage collections in this process so
#: far, and the start of the one under way (``_time_full_gc``)
_gc_full = {"s": 0.0, "t0": 0.0}


def _time_full_gc(phase: str, info: dict) -> None:
    """``gc.callbacks`` hook: times generation-2 collections, returns at
    once for the others.  A step reads ``_gc_full["s"]`` at its start and,
    when it turns out slow, at its end."""
    if info["generation"] != 2:
        return
    if phase == "start":
        _gc_full["t0"] = time.perf_counter()
    else:
        _gc_full["s"] += time.perf_counter() - _gc_full["t0"]


gc.callbacks.append(_time_full_gc)


def _no_calls() -> Dict[str, List[float]]:
    """A step's ``_calls_s``: the durations of each kind of runtime call."""
    return {k: [] for k in dict.fromkeys(_CALL_OF_SPAN.values())}


#: decode dispatches by the prefill chunks dispatched before them in
#: the same step (each chunk is device time in front of the decode)
_DECODE_AFTER = ("decode_calls_after_0_chunks", "decode_calls_after_1_chunk",
                 "decode_calls_after_2plus_chunks")


class _Span:
    """The engine's one span site: a ``jax.profiler.TraceAnnotation``
    (the profiler's clock, next to the device trace; an inert TraceMe
    outside a profiler session) that also keeps its ``perf_counter``
    start ``t0`` and duration ``dur`` for the stats, files a phase's
    pair under ``phases`` and a runtime call's duration under ``calls``.
    Closes under an exception like the ``finally`` it replaces, so an
    aborted phase still records the time it burned."""

    __slots__ = ("_phases", "_phase", "_calls", "_call", "_ann", "t0", "dur")

    def __init__(self, phases: Dict[str, tuple],
                 calls: Dict[str, List[float]], name: str, args: dict):
        self._phases, self._phase = phases, _PHASE_OF_SPAN.get(name)
        self._calls, self._call = calls, _CALL_OF_SPAN.get(name)
        self._ann = jax.profiler.TraceAnnotation(name, **args)

    def __enter__(self) -> "_Span":
        self.t0 = time.perf_counter()
        self._ann.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        self._ann.__exit__(*exc)
        self.dur = time.perf_counter() - self.t0
        if self._phase is not None:
            self._phases[self._phase] = (self.t0, self.dur)
        if self._call is not None:
            self._calls[self._call].append(self.dur)


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


class _Slot:
    """Host-side state of one occupied engine slot."""

    def __init__(self, request: Request, pages: List[int], prefilled: int,
                 seq: int, base_len: int):
        self.request = request
        self.pages = pages            # table order: shared prefix + owned
        # generated tokens live ON THE REQUEST so they survive preemption;
        # the slot aliases the same list
        self.tokens: List[int] = request.generated
        self.born_step = 0
        self.seq = seq                # admission order (FCFS, preserved
        #                               across preemption — oldest is
        #                               never a preemption victim)
        self.base_len = base_len      # work-prompt length at admission
        self.prefilled = prefilled    # work positions with K/V in pages
        # high-water LOGICAL page count — how many block-table entries
        # have ever been populated.  Without a window it always equals
        # len(pages); windowed recycling frees dead leading pages (their
        # table entries become the null page) so len(pages) shrinks while
        # hw_pages keeps marking where the next growth appends
        self.hw_pages = len(pages)
        self.started = False          # first token sampled; decoding
        # tokens sampled for this slot on the device and not read yet: a
        # completed prompt's first token, the rows of a decode in flight
        self.unread = 0
        # speculative draft buffer (r13): host-only, overwritten by every
        # spec step's fresh proposal — reconstructible from the request
        # history, so snapshots never capture it and a step fault between
        # drafting and verify costs nothing but the proposal
        self.draft: List[int] = []


class ServingEngine:
    """Continuous-batching generation over a paged KV cache.

    ``max_slots`` bounds the decode batch (the step's static shape);
    ``page_size`` the pool granularity; ``num_pages`` the pool size
    (default: enough for every slot at ``max_seq_len``, +1 null page);
    ``token_budget`` the scheduler's per-step token budget (decode tokens
    + prefill chunks; ``token_budget=chunk_tokens`` holds a step to one
    chunk); ``chunk_tokens`` the chunk-prefill program width —
    prompts longer than a step's prefill budget prefill across steps,
    co-scheduled with decode; ``prefix_cache`` reuses KV pages across
    requests sharing a page-aligned token prefix.  Sampling knobs mirror
    ``build_generate_fn``; ``int8`` serves W8A8 projections + int8 KV
    pages.  ``use_paged_kernel`` forces the Pallas kernels (or the jnp
    references) instead of auto-dispatch — tests use it to pin the
    interpret-mode kernel path on CPU.

    r10 lifecycle knobs: ``max_queue`` bounds the waiting queue (overflow
    becomes a ``rejected`` terminal); ``faults`` installs a
    :class:`~paddle_tpu.serving.faults.FaultPlan`; ``clock`` overrides
    the deadline clock (a zero-arg callable returning seconds — defaults
    to the fault plan's virtual clock when one is set, else
    ``time.monotonic``).

    r11 observability knobs: ``metrics`` feeds a
    :class:`~paddle_tpu.serving.metrics.MetricsRegistry` every step
    (pass a registry, or ``True`` to create one; ``None`` = off — the
    hot loop then pays zero metric cost); ``trace`` records the
    per-request lifecycle + engine step phases as Chrome trace-event
    JSON (pass a :class:`~paddle_tpu.serving.tracing.TraceRecorder`, or
    ``True`` to create one).  ``run(metrics_dir=...)`` exports both:
    TensorBoard scalars per step, a ``metrics.prom`` Prometheus text
    dump and ``trace.json`` (open in Perfetto) at drain.  Request-time
    observations (queue wait, TTFT, time-between-tokens, e2e latency)
    are measured on the ENGINE clock, so a FaultPlan's virtual clock
    makes their histograms bit-deterministic.

    r12 multi-tenancy/streaming knobs: ``policy`` picks the waiting-
    queue order (``"fcfs"`` default, ``"wfq"`` for weighted fair
    queueing over per-tenant virtual token counters, or a
    :class:`~paddle_tpu.serving.tenancy.SchedulerPolicy` instance);
    ``tenants`` maps tenant name -> weight /
    :class:`~paddle_tpu.serving.tenancy.TenantConfig` (naming tenants
    implies WFQ); ``on_token(rid, token)`` observes every sampled token
    in delivery order — the streaming HTTP front end
    (:class:`~paddle_tpu.serving.frontend.ServingFrontend`) builds SSE
    on it.  Requests carry ``tenant=`` through :meth:`add_request`;
    per-tenant token/terminal counters land in the metrics registry as
    labeled series (``serving_tenant_*{tenant="..."}``).

    r13 speculative-decoding knobs: ``spec_k`` > 0 proposes up to that
    many draft tokens per slot per step from the request's own history
    (:class:`~paddle_tpu.serving.drafter.NGramDrafter` with
    ``spec_ngram`` as the longest n-gram matched; ``drafter=`` injects
    any object with ``draft(history, max_tokens)``), verifies them all
    in ONE multi-query paged-attention dispatch
    (``kernels/paged_attention.paged_attention_mq``) and accepts the
    longest agreeing prefix plus one corrected token — greedy output is
    token-for-token identical to ``spec_k=0``, only faster when drafts
    accept.  Requires greedy sampling, replaces ``decode_block`` fusion,
    and bills WFQ tenants by ACCEPTED tokens only.  Acceptance telemetry:
    ``stats["spec_drafted"/"spec_accepted"/"spec_rejected"]`` and the
    ``serving_spec_acceptance_rate`` per-request histogram.

    r15 disaggregation knobs: ``role`` splits prefill from decode —
    ``"prefill"`` engines run chunked prefill to completion, then export
    every started slot as a handoff record (request + block-table-order
    page payloads + quantization scales, snapshot v5 wire format) via
    :meth:`drain_handoffs`; ``"decode"``/``"both"`` engines adopt the
    pages bit-exactly through :meth:`ingest_handoff` (layout-guarded,
    prompt pages re-indexed for prefix reuse, zero recompute).
    :class:`~paddle_tpu.serving.router.Router` wires replicas together
    with cache-affinity routing and router-global WFQ.

    A step dispatches decode N+1 before it reads decode N (the module
    docstring has the order): a request's tokens reach ``on_token`` and
    ``step()``'s return one step after their dispatch, in order.
    ``stats["decode_ahead"]`` counts the decode dispatches made while the
    previous one was unread, ``stats["decode_sync_first"]`` those that
    had to retire first (a preemption of a lane in flight, a snapshot);
    with ``spec_k`` every step reads first, because drafting needs the
    retired history.
    """

    def __init__(self, model, *, max_slots: int = 8, page_size: int = 32,
                 max_seq_len: Optional[int] = None,
                 num_pages: Optional[int] = None,
                 token_budget: Optional[int] = None,
                 greedy: bool = True, temperature: float = 1.0,
                 top_k: int = 0, top_p: float = 1.0,
                 eos_token_id: Optional[int] = None,
                 int8: Optional[bool] = None, seed: int = 0,
                 decode_block: int = 1,
                 use_paged_kernel: Optional[bool] = None,
                 chunk_tokens: int = 128, prefix_cache: bool = True,
                 max_queue: Optional[int] = None,
                 faults: Optional[FaultPlan] = None,
                 clock: Optional[Callable[[], float]] = None,
                 metrics=None, trace=None, flight=None,
                 policy=None, tenants=None,
                 on_token: Optional[Callable[[int, int], None]] = None,
                 spec_k: int = 0, spec_ngram: int = 3, drafter=None,
                 kv_bits: Optional[int] = None,
                 attn_window: Optional[int] = None,
                 role: str = "both"):
        cfg = model.cfg
        self.cfg = cfg
        # r15 disaggregation: "prefill" engines run chunked prefill to
        # completion and HAND OFF (request + page payload) instead of
        # decoding; "decode" engines adopt handoffs into fresh pages and
        # decode them; "both" (default) is the monolithic r08-r14 engine.
        if role not in ("prefill", "decode", "both"):
            raise ValueError(
                f"role must be 'prefill', 'decode' or 'both', got {role!r}")
        self.role = role
        # decode_block > 1 fuses that many decode steps into ONE dispatched
        # lax.scan (multi-step scheduling): admission/finish granularity
        # coarsens to the block, but the host->device dispatch cost (not
        # measured on the current installation) is paid once per block
        # instead of once per token.  1 = pure admit-every-step continuous
        # batching (the parity-test mode).
        self.decode_block = max(1, int(decode_block))
        # spec_k > 0 turns the decode dispatch SPECULATIVE (r13): a
        # host-side drafter proposes up to spec_k tokens per slot from the
        # request's own history, one verify dispatch scores carry + all
        # draft positions, and the greedy rejection rule accepts the
        # longest agreeing prefix plus the target's correction token —
        # 1..spec_k+1 tokens per dispatch, token-for-token identical to
        # non-speculative greedy decode.
        self.spec_k = max(0, int(spec_k))
        if self.spec_k:
            if not greedy:
                raise ValueError(
                    "speculative decoding (spec_k > 0) requires greedy "
                    "sampling — the longest-agreeing-prefix rule is the "
                    "greedy special case of rejection sampling")
            if self.decode_block > 1:
                raise ValueError(
                    "spec_k > 0 replaces decode_block fusion: the verify "
                    "dispatch already scores spec_k+1 positions per step")
        self._drafter = drafter if drafter is not None else (
            NGramDrafter(self.spec_k, max_ngram=spec_ngram)
            if self.spec_k else None)
        # the model as the programs read it: one LayerSpec a layer (GPT-2's
        # for a GPT) and the parameter tree
        self.layers = decoder_layers(model, attn_window)
        # layers per distinct window (None: full attention), for the
        # pages-walked counter
        self._window_layers = collections.Counter(
            spec.window for spec in self.layers)
        # the state-space mixer's description, if the model has one (one
        # for all its layers that have one: the slab is one shape)
        mixers = {sp.ssm for sp in self.layers if sp.ssm is not None}
        if len(mixers) > 1:
            raise MultiGroupUnsupported(
                "layers with different state-space mixers")
        self._ssm = next(iter(mixers), None)
        if hasattr(model, "decoder_params"):
            if int8 and self._ssm is not None:
                raise MultiGroupUnsupported(
                    "not available to a model with recurrent state: int8")
            if int8:
                raise ValueError("int8 projections are GPT's (_decoder_setup)")
            self.params, self.int8 = model.decoder_params(), False
        else:
            self.params, _, self.int8 = _decoder_setup(model, int8=int8)
        self.n_heads = cfg.num_heads
        self.n_kv_heads = getattr(cfg, "num_kv_heads", None) or cfg.num_heads
        self.head_dim = (getattr(cfg, "head_dim", None)
                         or cfg.hidden_size // cfg.num_heads)
        self.eps = cfg.layer_norm_eps
        self._moe = next((sp.moe for sp in self.layers if sp.moe), None)
        # KV-capacity knobs (this PR): kv_bits / attn_window override the
        # model config's defaults; the resolved values fix the pool's page
        # layout and every attention dispatch's masking for the engine's
        # whole lifetime (snapshot v5 records them; restore refuses a
        # mismatched layout)
        self.kv_bits = _resolve_kv_bits(cfg, self.int8, kv_bits)
        # Attention kind is per layer.  Layers of ONE kind share one page
        # group (the pool, recycled behind ``self.window`` if they all
        # slide); a model that mixes sliding and full layers gets two: the
        # pool for the full layers and a fixed ring for the sliding ones.
        windows = {sp.window for sp in self.layers}
        if any(w is not None and int(w) < 1 for w in windows):
            raise ValueError(f"attn_window must be >= 1, got {windows}")
        two_groups = len(windows) > 1
        if two_groups and (None not in windows or len(windows) != 2):
            raise MultiGroupUnsupported(
                f"layers with different windows {sorted(windows)}")
        self.window = None if two_groups else next(iter(windows))
        if self.window is not None:
            self.window = int(self.window)
        if two_groups or self._ssm is not None:
            refused = {"role": role != "both", "spec_k": self.spec_k > 0,
                       "decode_block": self.decode_block > 1,
                       "kv_bits": self.kv_bits is not None}
            if any(refused.values()):
                raise MultiGroupUnsupported(
                    "not available to a model with "
                    + " and ".join(
                        ["two page groups"] * two_groups
                        + ["recurrent state"] * (self._ssm is not None))
                    + ": " + ", ".join(k for k, v in refused.items() if v))
        self.max_slots = max_slots
        self.page_size = page_size
        self.max_seq_len = max_seq_len or cfg.max_seq_len
        if self.max_seq_len > cfg.max_seq_len:
            raise ValueError("max_seq_len exceeds the model's position table")
        self.max_pages = -(-self.max_seq_len // page_size)
        self.eos_token_id = eos_token_id
        self.chunk_tokens = max(1, min(int(chunk_tokens), self.max_seq_len))
        self.max_queue = max_queue
        self.faults = faults
        if clock is not None:
            self._clock = clock
        elif faults is not None:
            self._clock = faults.now
        else:
            # the ONE sanctioned wall-clock binding: when neither an
            # explicit clock nor a FaultPlan is injected, real time is
            # the semantics (production); replay paths always inject
            self._clock = time.monotonic  # graftlint: allow=determinism
        dtype = self.params["wte"].dtype
        n_pages = num_pages or (1 + max_slots * self.max_pages)
        # layer -> (its group, its index among the group's layers); group 0
        # is the pool: every admission, growth and preemption asks it alone
        full = [li for li, sp in enumerate(self.layers)
                if not two_groups or sp.window is None]
        slide = [li for li in range(len(self.layers)) if li not in full]
        self._layer_group = {li: (g, j) for g, ls in enumerate((full, slide))
                             for j, li in enumerate(ls)}
        self.pool = KVPool(len(full), cfg.num_heads, self.head_dim,
                           n_pages, page_size, dtype=dtype,
                           prefix_cache=(prefix_cache and not two_groups
                                         and self._ssm is None),
                           num_kv_heads=self.n_kv_heads,
                           kv_bits=self.kv_bits, window=self.window)
        self.pool.faults = faults
        self.ring: Optional[WindowRing] = None
        if two_groups:
            self.ring = WindowRing(
                len(slide), self.n_kv_heads, self.head_dim, max_slots,
                self.max_pages, page_size,
                next(w for w in windows if w is not None),
                self.chunk_tokens, dtype=dtype)
        self._group_pages = (n_pages,) + (
            (self.ring.num_pages,) if two_groups else ())
        # the state group: each slot's recurrent state, a layer that has a
        # mixer (model layer -> its index among them)
        self.slab: Optional[StateSlab] = None
        self._ssm_layer = {li: j for j, li in enumerate(
            li for li, sp in enumerate(self.layers) if sp.ssm is not None)}
        if self._ssm is not None:
            self.slab = StateSlab(len(self._ssm_layer), max_slots, self._ssm,
                                  conv_dtype=dtype)
        self.scheduler = FCFSScheduler(max_slots, self.pool,
                                       token_budget=token_budget,
                                       policy=policy, tenants=tenants)
        # per-token observer (r12): called as on_token(rid, token) once
        # for every token the engine samples for a live request —
        # prefill-completion samples and decode tokens alike, in exactly
        # the order they land on FinishedRequest.tokens.  The streaming
        # front end (serving/frontend.py) hangs SSE delivery off this.
        # Settable after construction; like faults/clock it is NOT part
        # of a snapshot.
        self.on_token = on_token
        self._sample = _make_sampler(greedy, temperature, top_k, top_p)
        if use_paged_kernel is None:
            self._use_kernel = pa.available() and pa.supported(
                cfg.num_heads, page_size, self.head_dim,
                n_kv_heads=self.n_kv_heads, kv_bits=self.kv_bits)
            self._use_prefill_kernel = pp.available() and pp.supported(
                cfg.num_heads, page_size, self.head_dim, self.chunk_tokens,
                n_kv_heads=self.n_kv_heads, kv_bits=self.kv_bits)
            self._use_spec_kernel = pa.available() and pa.supported_mq(
                cfg.num_heads, page_size, self.head_dim, self.spec_k + 1,
                n_kv_heads=self.n_kv_heads, kv_bits=self.kv_bits)
            m = self._ssm
            self._use_ssm_kernel = (
                m is not None and ssd.available() and ssd.supported(
                    m.n_heads, m.head_dim, m.n_groups, m.d_state,
                    self.chunk_tokens))
        else:
            self._use_kernel = bool(use_paged_kernel)
            self._use_prefill_kernel = bool(use_paged_kernel)
            self._use_spec_kernel = bool(use_paged_kernel)
            self._use_ssm_kernel = bool(use_paged_kernel)
        if self._ssm is not None and self.chunk_tokens > self._ssm.chunk:
            raise ValueError(
                f"chunk_tokens {self.chunk_tokens} is more than the mixer's "
                f"scan chunk {self._ssm.chunk}: an engine chunk is one chunk "
                "of the scan")

        # ctor echo for snapshot/restore (serving/snapshot.py): enough to
        # rebuild an equivalent engine around the captured state.  faults
        # and clock are deliberately NOT part of a snapshot.
        self._config = dict(
            max_slots=max_slots, page_size=page_size,
            max_seq_len=self.max_seq_len, num_pages=n_pages,
            token_budget=self.scheduler.token_budget, greedy=greedy,
            temperature=temperature, top_k=top_k, top_p=top_p,
            eos_token_id=eos_token_id, int8=self.int8, seed=seed,
            decode_block=decode_block, use_paged_kernel=use_paged_kernel,
            chunk_tokens=chunk_tokens, prefix_cache=prefix_cache,
            max_queue=max_queue,
            # resolved KV layout knobs (not the raw ctor args): a restored
            # engine must land on the SAME page layout whatever the model
            # config defaults were at snapshot time
            kv_bits=self.kv_bits, attn_window=self.window,
            # spec_k/spec_ngram rebuild the NGramDrafter at restore; a
            # custom drafter instance is like faults/clock — not
            # snapshot-portable (draft buffers themselves are transient
            # host state, reconstructible from request history)
            spec_k=self.spec_k, spec_ngram=spec_ngram,
            # the POLICY NAME, not the instance: a restored engine
            # rebuilds the named policy and reloads its counters from
            # the snapshot's scheduler state (a custom SchedulerPolicy
            # instance is like faults/clock — not snapshot-portable)
            policy=self.scheduler.policy.name,
            tenants=({t: dataclasses.asdict(c)
                      for t, c in normalize_tenants(tenants).items()}
                     if tenants else None),
            role=role)

        # host mirrors of the decode step's device operands
        self._tokens_this_step = 0
        self._chunks_this_step = 0
        self._budget_chunks = 1
        self._phase_s: Dict[str, tuple] = {}
        self._calls_s: Dict[str, List[float]] = _no_calls()
        # the step's first dispatch is still to come (``_check_drained``),
        # and what the step found of the decode in flight: at that
        # dispatch, and when its sync began (None: nothing in flight)
        self._drain_unchecked = False
        self._drained: Optional[bool] = None
        self._ready_at_sync: Optional[bool] = None
        self._slots: List[Optional[_Slot]] = [None] * max_slots
        # the carry tokens live on the device (``_carry``: what the next
        # decode embeds, lane by lane); ``_tok`` mirrors them as of the
        # last retirement, for a handoff, a snapshot and the verify program
        self._carry = jnp.zeros((max_slots,), jnp.int32)
        self._tok = np.zeros((max_slots,), np.int32)
        # the next position each started slot writes: advanced at dispatch
        self._len = np.zeros((max_slots,), np.int32)
        self._table = np.zeros((max_slots, self.max_pages), np.int32)
        self._key = jax.random.PRNGKey(seed)
        self._step_idx = 0
        self._admit_seq = 0
        # terminals produced OUTSIDE step() (reject at enqueue, cancel,
        # …) park here and are delivered by the next step()
        self._pending: List[FinishedRequest] = []
        # r15 disaggregation queues: a prefill-role engine parks finished
        # handoff records in the OUTBOX (the router pumps them away); a
        # decode/both engine queues ingested records in the INBOX until a
        # slot + pages free up.  Inbox payloads are host numpy — they
        # hold no pool pages, so the leak audits are unaffected.
        self._handoff_out: List[dict] = []
        self._handoff_in: List[dict] = []
        # the decode dispatched and not read yet: ((slot, _Slot) pairs,
        # the program's ``remaining``, device tokens, t_dispatch, its
        # mark among the expert counts)
        self._inflight: Optional[tuple] = None
        # completed prompts whose first token is not read yet:
        # (slot, _Slot, device token, mark among the expert counts)
        self._first_unread: List[tuple] = []
        # a decode was retired ahead of its turn; the next dispatch counts
        self._retired_early = False
        self.stats = {"prefill_calls": 0, "decode_calls": 0,
                      "prefill_traces": 0, "decode_traces": 0,
                      # decode dispatches made while the previous decode
                      # was unread, and those that had to retire it first
                      # (one after idleness is neither)
                      "decode_ahead": 0, "decode_sync_first": 0,
                      "tokens_generated": 0,
                      "prefix_hit_tokens": 0, "prompt_tokens": 0,
                      "pages_in_use": 0, "queue_depth": 0,
                      "step_wall_s": 0.0, "last_step_s": 0.0,
                      # the host's turn (PR 39): steps; host seconds inside
                      # program dispatches; steps whose first dispatch found
                      # the decode in flight done; steps of SLOW_STEP_S or
                      # more and their wall seconds
                      "steps": 0, "dispatch_s": 0.0, "device_drained": 0,
                      "slow_steps": 0, "slow_step_s": 0.0,
                      # per-phase wall time (r11): cumulative + last-step,
                      # so admit/prefill/decode no longer conflate into
                      # one step_wall_s bucket
                      "admit_s": 0.0, "prefill_s": 0.0, "decode_s": 0.0,
                      "handoff_s": 0.0,
                      "last_admit_s": 0.0, "last_prefill_s": 0.0,
                      "last_decode_s": 0.0, "last_handoff_s": 0.0,
                      # host time actually BLOCKED on the decode sync
                      "decode_sync_s": 0.0, "last_decode_sync_s": 0.0,
                      # ... and on a completed prompt's first token
                      "prefill_sync_s": 0.0,
                      # the two waits inside TTFT, engine clock, once per
                      # request: enqueue -> first admission -> first token
                      "admissions": 0, "queue_wait_s": 0.0,
                      "first_tokens": 0, "prefill_wait_s": 0.0,
                      # the step mix (they sum to decode_calls) and the
                      # context lengths the decode dispatches attended
                      **dict.fromkeys(_DECODE_AFTER, 0),
                      "decode_attended_tokens": 0,
                      # whole chunks the prefill budget held, summed over
                      # the steps that went on to decode: over decode_calls
                      # the allowance, as prefill_calls is what was spent
                      "prefill_budget_chunks": 0,
                      # what the decode kernels walked, summed over lanes
                      # and layers (the kernels' own live range), beside
                      # what every table entry of every lane would be
                      "decode_pages_walked": 0, "decode_pages_in_table": 0,
                      # ... and the chunk kernel, summed over layers
                      "prefill_pages_walked": 0, "prefill_pages_in_table": 0,
                      # disaggregation traffic (r15)
                      "handoffs_out": 0, "handoffs_in": 0,
                      "handoff_bytes": 0, "handoff_faults": 0,
                      "preemptions": 0, "recompute_tokens": 0,
                      "rejected": 0, "expired": 0, "cancelled": 0,
                      "step_faults": 0,
                      # speculative decoding (r13): drafted = proposals
                      # scored by verify, accepted + rejected = drafted;
                      # the bonus/correction token is NOT counted (it is
                      # ordinary decode output, speculation or not)
                      "spec_drafted": 0, "spec_accepted": 0,
                      "spec_rejected": 0}
        if self._moe is not None:
            # expert routing, per dispatch and summed over expert layers:
            # rows x top_k assignments in all, those that fell on the held
            # experts (which is also the sum of the held experts' rows), the
            # busiest held expert's rows, the held experts that had a row at
            # all, and the layer passes
            self.stats.update(moe_assignments=0, moe_local_assignments=0,
                              moe_expert_tokens_max=0, moe_experts_active=0,
                              moe_layer_passes=0)
        # (device counts, valid rows) of dispatches not yet synced on, in
        # dispatch order, and how many were folded before them
        self._moe_pending: collections.deque = collections.deque()
        self._moe_folded = 0
        if self.ring is not None or self.slab is not None:
            # prefix_cache=True resolves to no index for two groups, and
            # for state (a page of a prefix has no state to go with it)
            self.stats["prefix_index_refused"] = int(bool(prefix_cache))
        if self.ring is not None:
            self.stats.update(
                pages_in_use_window=0, window_pages_recycled=0)
        if self.slab is not None:
            # the state group: lane-layers the decode step was given and
            # those of them that were live; valid rows through the chunk
            # scan and rows as dispatched (padding too), times layers;
            # zeroings of a slot's state; the slab's bytes
            self.stats.update(
                ssm_lane_steps=0, ssm_live_lane_steps=0, ssm_scan_rows=0,
                ssm_scan_row_passes=0, state_resets=0,
                state_slab_bytes=self.slab.hbm_bytes())
        # observability (r11/r16): all default OFF — the hot loop pays
        # nothing unless asked to measure itself
        self.metrics: Optional[MetricsRegistry] = None
        self._m = None
        self.tracer: Optional[TraceRecorder] = None
        self.flight: Optional[FlightRecorder] = None
        # replica-namespaced trace lanes: module defaults until
        # attach_tracer assigns a replica identity
        self._pid_eng = PID_ENGINE
        self._pid_req = PID_REQUESTS
        # handoff trace context: monotonic per-export sequence carried on
        # the wire record so cross-replica flow arrows get unique ids
        self._span_seq = 0
        # SLO layer (r16): per-tenant budgets from TenantConfig; the
        # tracker registers its series lazily in attach_metrics
        self._tenant_cfg = normalize_tenants(tenants)
        self._slo: Optional[SLOTracker] = None
        # engine-clock stamp of the last completed step — the /healthz
        # staleness probe (a wedged replica stops advancing this)
        self._last_step_at: Optional[float] = None
        # run(metrics_dir=) arms the crash dump: a real exception
        # escaping step() writes the flight buffer here before re-raising
        # (the Router renames the file per replica)
        self._crash_dump_dir: Optional[str] = None
        self._crash_dump_name = "flight_crash.json"
        # identity tests, not truthiness: an EMPTY registry is falsy
        # (len 0) but still a registry the caller wants fed
        if metrics is not None and metrics is not False:
            self.attach_metrics(
                metrics if isinstance(metrics, MetricsRegistry) else None)
        if trace is not None and trace is not False:
            self.attach_tracer(
                trace if isinstance(trace, TraceRecorder) else None)
        if flight is not None and flight is not False:
            self.attach_flight(
                flight if isinstance(flight, FlightRecorder) else None)
        self._decode_fn = self._build_decode()
        self._prefill_fn = self._build_prefill()
        self._cow_fn = self._build_cow()
        self._carry_put_fn = self._build_carry_put()
        self._verify_fn = self._build_verify() if self.spec_k else None
        self._state_reset_fn = (self._build_state_reset()
                                if self.slab is not None else None)

    # -- device programs --------------------------------------------------

    # The programs see the pool as the attention kernels do: every buffer
    # viewed ``(L * P, Hkv, page_size, d)`` (a bitcast of ``KVPool.buffers``,
    # taken on entry and undone on exit), layer ``li``'s pages at ids
    # ``li * P ..``.  It is only ever WRITTEN in whole pages along that
    # page axis (``_scatter_kv``) and READ through ``table + li * P``
    # (``_attend_with``), so the donated buffers keep one layout and are
    # updated in place: an element scatter or a ``[li]`` slice makes XLA
    # re-lay-out or copy the pool on every dispatch (PERF.md, PR 25).

    # A model with two page groups hands the programs a TUPLE of buffer
    # dicts and a tuple of tables, (the pool's, the window ring's); each
    # layer works on its own group's (``_layer_group``), under the same
    # rules.  One group is passed bare, as it always was.

    @staticmethod
    def _flat(bufs):
        return {k: b.reshape((-1,) + b.shape[2:]) for k, b in bufs.items()}

    def _unflat(self, bufs, group: int = 0):
        return {k: b.reshape((-1, self._group_pages[group]) + b.shape[1:])
                for k, b in bufs.items()}

    def _enter(self, bufs, tables):
        """(flat buffers by group, tables by group) of a program's pool
        arguments."""
        if self.ring is None:
            bufs, tables = (bufs,), (tables,)
        return [self._flat(b) for b in bufs], tables

    def _leave(self, groups):
        out = tuple(self._unflat(b, g) for g, b in enumerate(groups))
        return out if self.ring is not None else out[0]

    # A model with recurrent state hands the programs ``{"kv": <the page
    # groups as above>, "state": StateSlab.buffers}``: the slab is donated
    # and advanced in place with the pages (``_take_state`` splits it off
    # on entry, ``_with_state`` puts it back on exit).

    def _device_pool(self):
        """The programs' buffer argument (donated)."""
        kv = (self.pool.buffers if self.ring is None
              else (self.pool.buffers, self.ring.buffers))
        if self.slab is None:
            return kv
        return {"kv": kv, "state": self.slab.buffers}

    def _store_pool(self, bufs) -> None:
        if self.slab is not None:
            bufs, self.slab.buffers = bufs["kv"], bufs["state"]
        if self.ring is None:
            self.pool.buffers = bufs
        else:
            self.pool.buffers, self.ring.buffers = bufs

    def _take_state(self, bufs):
        """(page groups, state buffers or None) of a program's argument."""
        if self.slab is None:
            return bufs, None
        return bufs["kv"], dict(bufs["state"])

    def _with_state(self, kv, state):
        return kv if state is None else {"kv": kv, "state": state}

    def _mix(self, bp, x, spec, state, li, *, slot=None, n_valid=None,
             active=None):
        """The state-space mixer's term of layer ``li``'s residual for the
        rows ``x`` of a program, advancing ``state`` (the slab's buffers,
        updated in this dict) where the rows are real: a chunk's first
        ``n_valid`` rows of ``slot`` (``x`` (1, C, h)), or one row of every
        ``active`` lane (``x`` (S, 1, h)).  Kernel or jnp path."""
        m, lj = spec.ssm, self._ssm_layer[li]
        z, xbc, dt = ssm_in(bp, _norm(bp, "ln1", x, self.eps, spec), m)
        conv, slab = state["conv"], state["ssm"]
        rows = slab.reshape((-1,) + slab.shape[2:])        # (L * S, H, P, N)
        a = -jnp.exp(bp["A_log"].astype(jnp.float32))
        taps = m.d_conv - 1         # a slot's tail: (taps, channels), flat
        if slot is not None:
            tail = conv[lj, slot].reshape(taps, -1)
            xbc, tail = ssm_conv(bp, xbc[0], tail, n_valid)
            state["conv"] = conv.at[lj, slot].set(tail.reshape(-1))
            xs, bm, cm = ssm_split(xbc, m)
            # a padding row has dt = 0: it changes nothing
            dt = jnp.where((jnp.arange(x.shape[1]) < n_valid)[:, None],
                           dt[0], 0.0)
            scan = (ssd.ssd_chunk_scan if self._use_ssm_kernel
                    else ssd.ssd_chunk_scan_ref)
            y, rows = scan(rows, lj * self.max_slots + slot, xs, dt, a, bm,
                           cm, bp["D"])
            y = y[None]
        else:
            old = conv[lj]                                 # (S, taps * ch)
            xbc, tail = ssm_conv(bp, xbc, old.reshape(old.shape[0], taps, -1),
                                 1)
            state["conv"] = conv.at[lj].set(
                jnp.where(active[:, None], tail.reshape(old.shape), old))
            xs, bm, cm = ssm_split(xbc[:, 0], m)
            step = (ssd.ssm_state_step if self._use_ssm_kernel
                    else ssd.ssm_state_step_ref)
            y, rows = step(rows, lj * self.max_slots, xs, dt[:, 0], a, bm,
                           cm, bp["D"], active)
            y = y[:, None]
        state["ssm"] = rows.reshape(slab.shape)
        return ssm_out(bp, y, z, m, self.eps, x.dtype)

    def _device_tables(self, idx: Optional[int] = None):
        """The block tables (of slot ``idx``, or all), one per group: host
        COPIES.  A dispatch is asynchronous and the backend may read (on
        the CPU: alias) the array it was handed after the call returns,
        while the ring's rows are turned in place before the next chunk."""
        def pick(t):
            return jnp.asarray(np.array(t if idx is None else t[idx]))

        if self.ring is None:
            return pick(self._table)
        return (pick(self._table), pick(self.ring.table))

    def _attend_with(self, fn, q, bufs, li, table, at):
        """One attention entry (kernel or jnp reference, same signature)
        for model layer ``li``: its group's flat buffers and tables in,
        the layer's page ids and its own window to the kernel."""
        g, lj = self._layer_group[li]
        bufs, table = bufs[g], table[g]
        n = self._group_pages[g]
        lo, window = lj * n, self.layers[li].window
        if self.kv_bits is None:
            return fn(q, bufs["k"], bufs["v"], table + lo, at,
                      window=window)
        # Quantized pools still read a COPY of the layer's rows.  The
        # kernels take the scales as (pages, Hkv, page_size, 1) fp32, and
        # TPU tiling pads that trailing 1 to 128 lanes: handed the flat
        # planes, every layer would re-lay-out all L * P pages of both
        # (2 x 8 GB at the benchmark's sizes) where the slice bounds it to
        # one layer's.  Goes when the kernels take lane-dense scales
        # (ROADMAP S2, PERF.md section 7).
        k, ks, v, vs = (bufs[x][lo:lo + n] for x in ("k", "ks", "v", "vs"))
        return fn(q, k, v, table, at, window=window,
                  k_scales=ks, v_scales=vs)

    def _attend(self, q, bufs, li, table, lengths):
        """Paged decode attention for layer ``li`` — kernel or jnp ref."""
        fn = pa.paged_attention if self._use_kernel else pa.paged_attention_ref
        return self._attend_with(fn, q, bufs, li, table, lengths)

    def _attend_prefill(self, q, bufs, li, table_row, start):
        """Paged chunk attention for layer ``li`` — kernel or jnp ref."""
        fn = (pp.paged_prefill if self._use_prefill_kernel
              else pp.paged_prefill_ref)
        return self._attend_with(fn, q, bufs, li, table_row, start)

    def _attend_spec(self, q, bufs, li, table, lengths):
        """Multi-query verify attention for layer ``li`` — kernel or jnp
        ref.  ``lengths`` counts the positions valid BEFORE the verify
        block (the paged_attention_mq contract)."""
        fn = (pa.paged_attention_mq if self._use_spec_kernel
              else pa.paged_attention_mq_ref)
        return self._attend_with(fn, q, bufs, li, table, lengths)

    def _page_writes(self, table, pos0, valid):
        """Where one dispatch writes, in whole pages: each of the G rows
        of ``table`` (G, max_pages) takes a block of T consecutive
        positions from ``pos0`` (G,), of which ``valid`` (G, T) are real.
        T positions touch at most W = ceil((T - 1) / page_size) + 1
        logical pages, so the plan is static in size:

          * ``ids`` (G * W,): the pool page behind each, or the null page
            0 where no valid row lands (inactive lanes, padded rows, the
            block's unused last page, positions past the table) — a live
            page id therefore occurs at most once;
          * ``src`` (G, W * page_size): the block row at each offset;
          * ``put`` (G * W, 1, page_size, 1): offsets that take that row.
            The rest keep what the page holds, and nothing is ever put on
            page 0, so its duplicate writes are all the same bytes."""
        ps, maxp = self.page_size, self.max_pages
        g, t = valid.shape
        w = (t + ps - 2) // ps + 1
        logical = (pos0 // ps)[:, None] + jnp.arange(w, dtype=jnp.int32)
        row = (logical[:, :, None] * ps + jnp.arange(ps, dtype=jnp.int32)
               - pos0[:, None, None]).reshape(g, w * ps)
        src = jnp.clip(row, 0, t - 1)
        put = ((row >= 0) & (row < t)
               & jnp.take_along_axis(valid, src, axis=1)).reshape(g, w, ps)
        ids = jnp.take_along_axis(table, jnp.minimum(logical, maxp - 1),
                                  axis=1)
        ids = jnp.where((logical < maxp) & put.any(-1), ids, 0)
        put = put & (ids != 0)[:, :, None]
        return ids.reshape(-1), src, put.reshape(g * w, 1, ps, 1)

    def _scatter_layer(self, bufs, li, writes, k1, v1):
        """:meth:`_scatter_kv` for model layer ``li`` of a program: into
        its group's buffers, by its group's plan."""
        g, lj = self._layer_group[li]
        bufs[g] = self._scatter_kv(bufs[g], lj, writes[g], k1, v1, group=g)

    def _scatter_kv(self, bufs, li, writes, k1, v1, group: int = 0):
        """Write a block's K/V (``k1``/``v1`` (G, Hkv, T, D), as
        ``_block_qkv`` yields them) into layer ``li`` of a group's flat
        buffers by
        the plan ``writes`` of :meth:`_page_writes` — quantizing to int8
        (or nibble-packed int4) rows + fp32 per-token scales when serving
        quantized KV.  Gathers the pages written, merges the new rows in
        by the plan's mask and scatters WHOLE pages back, along the page
        axis only.  The ONE write/quantize sequence shared by the decode,
        verify and chunk-prefill programs, so the exact-parity contract
        cannot fork between them."""
        ids, src, put = writes
        ids = ids + li * self._group_pages[group]
        new = {"k": k1, "v": v1}
        if self.kv_bits is not None:
            from ..ops.quant_ops import (quantize_int4_per_token,
                                         quantize_per_token)

            qf = (quantize_int4_per_token if self.kv_bits == 4
                  else quantize_per_token)
            new["k"], new["ks"] = qf(k1)
            new["v"], new["vs"] = qf(v1)
        out = {}
        for name, buf in bufs.items():
            x = new[name]
            g, n, t, d = x.shape
            # the row at every offset of every page written: a decode's
            # one row needs no gather
            x = (jnp.take_along_axis(x, src[:, None, :, None], axis=2)
                 if t > 1 else jnp.broadcast_to(x, (g, n, src.shape[1], d)))
            # (G, Hkv, W * ps, d) -> one (Hkv, ps, d) page per write
            x = jnp.swapaxes(x.reshape(g, n, -1, self.page_size, d), 1, 2)
            x = x.reshape((-1,) + buf.shape[1:])
            out[name] = buf.at[ids].set(jnp.where(put, x, buf[ids]))
        return out

    def _build_decode(self):
        n_heads, eps = self.n_heads, self.eps
        k_steps, n_kv = self.decode_block, self.n_kv_heads

        def one_step(p, bufs, table, toks, lengths, active, key, state=None):
            s = toks.shape[0]
            x = _embed(p, toks, lengths,
                       self.layers[0])[:, None, :]                # (S, 1, h)
            # exhausted/inactive lanes write nothing
            writes = [self._page_writes(t, lengths, active[:, None])
                      for t in table]
            counts = []                     # (held,) a layer, if experts
            for li, (bp, spec) in enumerate(zip(p["blocks"], self.layers)):
                q, kb, vb = _block_qkv(bp, x, n_heads, eps, n_kv_heads=n_kv,
                                       spec=spec,
                                       pos=lengths[:, None])  # (S, H, 1, D)
                self._scatter_layer(bufs, li, writes, kb, vb)
                out = self._attend(q[:, :, 0], bufs, li, table, lengths + 1)
                out = out.reshape(s, -1)[:, None, :].astype(x.dtype)
                mix = None if spec.ssm is None else self._mix(
                    bp, x, spec, state, li, active=active)
                x = _block_finish(bp, x, out, eps, spec=spec,
                                  valid=active[:, None], counts=counts,
                                  mix=mix)
            logits = _lm_head(p, x[:, 0], eps, self.layers[-1])   # (S, V)
            key, sub = jax.random.split(key)
            nxt = self._sample(logits, sub).astype(jnp.int32)
            return bufs, nxt, ((jnp.stack(counts),) if counts else ())

        def decode(p, bufs, toks, lengths, table, remaining, key):
            """Returns the buffers, the sampled rows (k, S) and the carry
            it leaves: each live lane's last sample, a dead lane's ``toks``
            as it came.  The next decode takes that carry as it is, on the
            device."""
            self.stats["decode_traces"] += 1  # python side effect: per trace
            bufs, state = self._take_state(bufs)
            bufs, table = self._enter(bufs, table)
            if k_steps == 1:
                active = remaining > 0
                bufs, nxt, extra = one_step(p, bufs, table, toks, lengths,
                                            active, key, state)
                return (self._with_state(self._leave(bufs), state),
                        nxt[None],                                 # (1, S)
                        jnp.where(active, nxt, toks)) + extra

            def body(carry, i):
                bufs, toks, lengths, remaining, key = carry
                active = remaining > 0
                key, sub = jax.random.split(key)
                bufs, nxt, _ = one_step(p, bufs, table, toks, lengths,
                                        active, sub)
                toks = jnp.where(active, nxt, toks)
                lengths = jnp.where(active, lengths + 1, lengths)
                remaining = jnp.maximum(remaining - 1, 0)
                return (bufs, toks, lengths, remaining, key), nxt

            (bufs, toks, _, _, _), toks_all = jax.lax.scan(
                body, (bufs, toks, lengths, remaining, key),
                jnp.arange(k_steps))
            return self._leave(bufs), toks_all, toks               # (k, S)

        return jax.jit(decode, donate_argnums=(1,))

    def _build_verify(self):
        """The speculative verify program: ONE dispatch embeds each
        slot's ``[carry, draft_0 .. draft_{k-1}]`` block at positions
        ``len .. len+k``, writes all rows' K/V into the slot's pages
        (same quantize/write as decode — rows past the slot's draft
        count and inactive lanes are written nowhere), runs multi-query
        paged attention (each row sees history + earlier block rows,
        causally), projects every row and samples greedily.  The host
        applies the rejection rule to the returned (S, k+1) predictions.

        Rejected rows leave stale K/V at positions past the accepted
        prefix; that is safe by construction: the next step's write
        REWRITES positions ``len' .. len'+k'`` before attending, and no
        query row ever attends past its own position — the same masking
        argument that makes block-table padding harmless."""
        n_heads, eps = self.n_heads, self.eps
        t, n_kv = self.spec_k + 1, self.n_kv_heads

        def verify(p, bufs, toks, draft, n_draft, lengths, table, key):
            self.stats["decode_traces"] += 1  # python side effect: per trace
            s = toks.shape[0]
            block = jnp.concatenate([toks[:, None], draft], axis=1)  # (S, T)
            pos = lengths[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
            # pad rows of short drafts can index positions past the table;
            # clamp for the position embedding (their outputs are unused)
            x = _embed(p, block, jnp.minimum(
                pos, self.cfg.max_seq_len - 1),
                self.layers[0])                                  # (S, T, h)
            # rows beyond the slot's draft count — and every row of a
            # lane not decoding this step (n_draft == -1) — are written
            # nowhere, exactly like inactive decode lanes
            row_ok = jnp.arange(t, dtype=jnp.int32)[None, :] <= \
                n_draft[:, None]
            bufs, table = self._enter(bufs, table)
            writes = [self._page_writes(tb, lengths, row_ok) for tb in table]
            for li, (bp, spec) in enumerate(zip(p["blocks"], self.layers)):
                q, kb, vb = _block_qkv(bp, x, n_heads, eps, n_kv_heads=n_kv,
                                       spec=spec, pos=pos)  # q (S,H,T,D)
                self._scatter_layer(bufs, li, writes, kb, vb)
                out = self._attend_spec(jnp.swapaxes(q, 1, 2), bufs, li,
                                        table, lengths)
                out = out.reshape(s, t, -1).astype(x.dtype)
                x = _block_finish(bp, x, out, eps, spec=spec)
            logits = _lm_head(p, x, eps, self.layers[-1])    # (S, T, V)
            key, sub = jax.random.split(key)
            pred = self._sample(logits.reshape(s * t, -1), sub)
            return self._leave(bufs), pred.reshape(s, t).astype(jnp.int32)

        return jax.jit(verify, donate_argnums=(1,))

    def _build_prefill(self):
        n_heads, eps, n_kv = self.n_heads, self.eps, self.n_kv_heads

        def prefill(p, bufs, toks, start, n_valid, table_row, sample_idx,
                    key, slot=None):
            """One chunk of one prompt: rows [start, start+n_valid) of the
            sequence.  Writes the chunk's K/V into the slot's pages, then
            attends the chunk against every already-written position (the
            cached/previous pages AND itself) through the block table.
            ``sample_idx`` is the chunk row holding the LAST prompt token;
            its sample is used only by the chunk that completes the
            prompt.  ``slot``: whose recurrent state the chunk advances
            (a model with state only)."""
            self.stats["prefill_traces"] += 1
            c = toks.shape[0]
            pos = start + jnp.arange(c, dtype=jnp.int32)
            x = _embed(p, toks, pos, self.layers[0])[None]    # (1, C, h)
            # padded rows are written nowhere
            valid = (jnp.arange(c) < n_valid)[None]
            bufs, state = self._take_state(bufs)
            bufs, table_row = self._enter(bufs, table_row)
            writes = [self._page_writes(tb[None], start[None], valid)
                      for tb in table_row]
            counts = []                     # (held,) a layer, if experts
            for li, (bp, spec) in enumerate(zip(p["blocks"], self.layers)):
                q, kb, vb = _block_qkv(bp, x, n_heads, eps, n_kv_heads=n_kv,
                                       spec=spec,
                                       pos=pos[None])        # (1, H, C, D)
                self._scatter_layer(bufs, li, writes, kb, vb)
                out = self._attend_prefill(jnp.swapaxes(q[0], 0, 1), bufs,
                                           li, table_row, start)
                out = out.reshape(c, -1)[None].astype(x.dtype)
                mix = None if spec.ssm is None else self._mix(
                    bp, x, spec, state, li, slot=slot, n_valid=n_valid)
                x = _block_finish(bp, x, out, eps, spec=spec, valid=valid,
                                  counts=counts, mix=mix)
            # only the sample row's logits are ever consumed (and only by
            # the chunk completing the prompt): project ONE row, not the
            # whole (C, V) chunk — LN + matmul are row-wise, so the
            # sampled logits are bit-identical to the full projection
            h_row = jnp.take(x[0], sample_idx, axis=0)        # (h,)
            last = _lm_head(p, h_row[None, :], eps,
                            self.layers[-1])                  # (1, V)
            key, sub = jax.random.split(key)
            tok = self._sample(last, sub)[0].astype(jnp.int32)
            return (self._with_state(self._leave(bufs), state), tok) + (
                (jnp.stack(counts),) if counts else ())

        return jax.jit(prefill, donate_argnums=(1,))

    def _build_cow(self):
        def cow(bufs, src, dst):
            """Copy-on-write clone of one pool page across all layers —
            the partial-tail prefix match: the new owner will overwrite
            positions past the matched count and decode masks the rest."""
            return {k: b.at[:, dst].set(b[:, src]) for k, b in bufs.items()}

        return jax.jit(cow, donate_argnums=(0,))

    def _build_carry_put(self):
        def carry_put(carry, slot, tok):
            """One lane of the device's carry tokens takes ``tok``: the
            first token a completing chunk sampled (a device value), or a
            host value when a slot is adopted with its token."""
            return carry.at[slot].set(tok)

        return jax.jit(carry_put)

    def _build_state_reset(self):
        def reset(state, slot):
            """Zero one slot's recurrent state in every layer: what a
            request finds when it takes the slot."""
            return {k: b.at[:, slot].set(0) for k, b in state.items()}

        return jax.jit(reset, donate_argnums=(0,))

    # -- public API -------------------------------------------------------

    def add_request(self, prompt, max_new_tokens: int,
                    arrival: float = 0.0,
                    deadline_s: Optional[float] = None,
                    tenant: Optional[str] = None) -> int:
        """Queue one request; returns its rid.  The prompt + continuation
        must fit ``max_seq_len`` (the slot's block-table width).
        ``deadline_s`` expires the request that many engine-clock seconds
        after enqueue, whatever state it is in.  ``tenant`` names the
        account the request schedules and bills under (WFQ policy;
        ignored by FCFS beyond metric labels)."""
        return self._enqueue(
            Request(prompt=np.asarray(prompt, np.int32).reshape(-1),
                    max_new_tokens=max_new_tokens, arrival=arrival,
                    deadline_s=deadline_s, tenant=tenant))

    def _enqueue(self, req: Request) -> int:
        """Single admission gate for both add_request and run(): every
        request must fit the model's position table / block-table width,
        whichever path it arrives by.  A full waiting queue REJECTS the
        request (backpressure): it still gets a rid and a terminal
        ``rejected`` FinishedRequest from the next step()."""
        if req.total_len > self.max_seq_len:
            raise ValueError(
                f"request needs {req.total_len} positions; engine "
                f"max_seq_len is {self.max_seq_len}")
        req.t_enqueue = self._now()
        if self.metrics is not None:
            self._m["enqueued"].inc()
        if ((self.max_queue is not None
             and self.scheduler.n_waiting >= self.max_queue)
                or self.scheduler.quota_reject(req.tenant)):
            # global queue bound OR the tenant's own max_waiting quota:
            # both are backpressure, both become an explicit terminal
            if self.tracer is not None:
                self.tracer.begin("queued", self._pid_req, req.rid)
            self.stats["rejected"] += 1
            self._pending.append(self._terminal(req, "rejected"))
            return req.rid
        rid = self.scheduler.add(req)
        if self.tracer is not None:
            self.tracer.begin("queued", self._pid_req, req.rid,
                              {"prompt_len": req.prompt_len,
                               "max_new": req.max_new_tokens})
        return rid

    def cancel(self, rid: int) -> bool:
        """Cancel a request in ANY live state — waiting, mid-prefill or
        decoding.  Pages are released immediately (same step); the
        terminal ``cancelled`` FinishedRequest (with any tokens generated
        so far) is delivered by the next step().  Returns False when the
        rid is unknown or already terminal."""
        req = self.scheduler.remove_waiting(rid)
        if req is not None:
            self.stats["cancelled"] += 1
            self._pending.append(self._terminal(req, "cancelled"))
            return True
        for idx, st in enumerate(self._slots):
            if st is not None and st.request.rid == rid:
                self.stats["cancelled"] += 1
                self._pending.append(self._finish(idx, "cancelled"))
                return True
        for i, rec in enumerate(self._handoff_in):
            if rec["request"].rid == rid:
                # queued for handoff admission: no slot, no pages — drop
                # the record, terminalize with whatever was generated
                del self._handoff_in[i]
                self.stats["cancelled"] += 1
                self._pending.append(
                    self._terminal(rec["request"], "cancelled"))
                return True
        return False

    @property
    def has_work(self) -> bool:
        """Work THIS engine can advance by stepping: queue/slots,
        undelivered terminals, queued handoff ingests, or device tokens
        not read yet (the decode in flight; first tokens a fault left
        behind).  The handoff OUTBOX is deliberately excluded — draining
        it is the router's job, not a step's."""
        return (self.scheduler.has_work or bool(self._pending)
                or bool(self._handoff_in) or self._inflight is not None
                or bool(self._first_unread))

    def attention_paths(self) -> Dict[str, str]:
        """Which attention implementation each device program was built
        with: ``"kernel"`` (the Pallas paged kernels) or ``"reference"``
        (the jnp oracles) for ``decode``, ``prefill`` and — when
        speculating — ``verify``.  Auto-dispatch (``use_paged_kernel=None``)
        decides from the backend and the shape gates at construction; this
        is where that decision can be read.  A model with recurrent state
        adds ``ssm_step`` and ``ssm_scan`` (``kernels/ssd.py``)."""
        name = {True: "kernel", False: "reference"}
        paths = {"decode": name[self._use_kernel],
                 "prefill": name[self._use_prefill_kernel]}
        if self.spec_k:
            paths["verify"] = name[self._use_spec_kernel]
        if self.slab is not None:
            # the recurrence of a model with state, in decode and in a chunk
            paths["ssm_step"] = paths["ssm_scan"] = name[self._use_ssm_kernel]
        return paths

    def prefix_hit_rate(self) -> float:
        """Fraction of prompt tokens served from cached KV pages."""
        return self.stats["prefix_hit_tokens"] / max(
            self.stats["prompt_tokens"], 1)

    # -- router probes (r15) ----------------------------------------------

    def prefix_match_len(self, prompt) -> int:
        """Tokens of ``prompt`` this replica's prefix index already holds
        K/V for — the router's cache-affinity key.  Probes the WORK
        prompt (``prompt[:-1]``, matching the scheduler's admission-time
        lookup) and is strictly read-only: no LRU touch, no retain."""
        if self.pool.prefix is None:
            return 0
        work = np.asarray(prompt, np.int32).reshape(-1)[:-1]
        if work.size == 0:
            return 0
        return self.pool.prefix.probe_len(work)

    def load_score(self) -> float:
        """Scalar busyness for the router's tie-break: resident slots +
        queue depth (both per capacity) + pool pressure.  Lower is
        better; an idle replica scores ~0, a saturated one ~3."""
        cap = max(self.max_slots, 1)
        return (self.scheduler.n_active / cap
                + self.scheduler.n_waiting / cap
                + self.pool.utilization())

    def stats_snapshot(self) -> Dict[str, float]:
        """A COPY of the stats ledger at this instant.  ``engine.stats``
        is the live mutable dict — callers that stash it see it keep
        changing under them; read through this instead."""
        return dict(self.stats)

    # -- observability (r11) ----------------------------------------------

    def attach_metrics(self, registry: Optional[MetricsRegistry] = None
                       ) -> MetricsRegistry:
        """Start feeding ``registry`` (fresh one if None) every step.
        Benches attach AFTER their warmup run so compile time never
        pollutes the measured histograms.  The registry must belong to
        THIS engine alone: ``serving_*`` counters mirror this engine's
        stats ledger via set_total, so a second feeding engine would
        overwrite them, not add — aggregate replicas by summing their
        registries' ``scalars()`` instead."""
        self.metrics = registry if registry is not None else MetricsRegistry()
        self._tenant_metrics = {}   # (family, tenant[, reason]) -> metric
        c = self.metrics.counter
        g = self.metrics.gauge
        h = self.metrics.histogram
        self._m = {
            "enqueued": c("serving_requests_enqueued",
                          "requests that arrived (incl. later rejects)"),
            "terminal": {r: c(f"serving_requests_terminal_{r}",
                              f"requests that ended {r}")
                         for r in TERMINAL_REASONS},
            "steps": c("serving_steps", "engine host-loop iterations"),
            "tokens": c("serving_tokens_generated", "sampled tokens"),
            "prefill_calls": c("serving_prefill_calls",
                               "chunk-prefill dispatches"),
            "decode_calls": c("serving_decode_calls", "decode dispatches"),
            "decode_ahead": c("serving_decode_ahead",
                              "decode dispatches made while the previous "
                              "decode was unread"),
            "decode_sync_first": c("serving_decode_sync_first",
                                   "decode dispatches that had to retire "
                                   "the previous decode first"),
            "slow_steps": c("serving_slow_steps",
                            f"engine steps of {SLOW_STEP_S} s or more"),
            "slow_step_s": c("serving_slow_step_s",
                             "wall seconds of those steps"),
            "preemptions": c("serving_preemptions",
                             "slots evicted for recompute"),
            "recompute": c("serving_recompute_tokens",
                           "work-prompt tokens re-prefilled"),
            "prefix_hit": c("serving_prefix_hit_tokens",
                            "prompt tokens served from cached pages"),
            "prompt_tokens": c("serving_prompt_tokens",
                               "admitted work-prompt tokens"),
            "cow": c("serving_cow_clones", "copy-on-write page clones"),
            "step_faults": c("serving_step_faults",
                             "injected mid-step exceptions absorbed"),
            "spec_drafted": c("serving_spec_drafted_tokens",
                              "draft tokens scored by verify dispatches"),
            "spec_accepted": c("serving_spec_accepted_tokens",
                               "draft tokens the verify pass accepted"),
            "spec_rejected": c("serving_spec_rejected_tokens",
                               "draft tokens the verify pass rejected"),
            "spec_accept_rate": h("serving_spec_acceptance_rate",
                                  "per-request accepted/drafted at "
                                  "terminal (requests that drafted)"),
            "alloc_calls": c("serving_alloc_calls",
                             "KVPool.alloc lease attempts"),
            "alloc_failures": c("serving_alloc_failures",
                                "KVPool.alloc calls that returned None"),
            "evictions": c("serving_prefix_evictions",
                           "cached pages LRU-evicted under pressure"),
            "pages_in_use": g("serving_pages_in_use",
                              "pages referenced by live requests"),
            "pages_free": g("serving_pages_free", "free-list pages"),
            "pages_reclaimable": g("serving_pages_reclaimable",
                                   "cached pages with no live reference"),
            "queue_depth": g("serving_queue_depth", "waiting requests"),
            "slots_active": g("serving_slots_active", "occupied slots"),
            "kv_bytes_per_token": g("serving_kv_bytes_per_token",
                                    "pool HBM bytes one token position "
                                    "costs across all layers"),
            "pages_per_slot_p50": g("serving_pages_per_slot_p50",
                                    "median live pages per occupied slot"),
            "hit_rate": g("serving_prefix_hit_rate",
                          "prefix_hit_tokens / prompt_tokens"),
            "budget_util": g("serving_token_budget_utilization",
                             "step tokens / token_budget"),
            "queue_wait": h("serving_queue_wait_s",
                            "enqueue -> first admission (engine clock)"),
            "ttft": h("serving_ttft_s",
                      "enqueue -> first token (engine clock)"),
            "tbt": h("serving_tbt_s",
                     "time between tokens per slot (engine clock)"),
            "e2e": h("serving_e2e_latency_s",
                     "enqueue -> terminal (engine clock)"),
            "step_s": h("serving_step_s", "full step wall time"),
            "admit_s": h("serving_step_admit_s",
                         "expire+admit phase wall time"),
            "prefill_s": h("serving_step_prefill_s",
                           "chunk-prefill phase wall time"),
            "decode_s": h("serving_step_decode_s",
                          "grow+decode phase wall time"),
            "chunk_s": h("serving_prefill_chunk_s",
                         "one chunk-prefill dispatch wall time"),
            "decode_call_s": h("serving_decode_call_s",
                               "one decode dispatch+sync wall time"),
            "handoffs_out": c("serving_handoffs_out",
                              "prefill-complete requests exported to the "
                              "router (prefill-role engines)"),
            "handoffs_in": c("serving_handoffs_in",
                             "handoff records accepted from the router"),
            "handoff_bytes": c("serving_handoff_bytes",
                               "KV payload bytes shipped out (degraded "
                               "transfers ship none)"),
            "handoff_faults": c("serving_handoff_faults",
                                "handoffs degraded by an injected "
                                "transfer fault (payload dropped)"),
            "handoff_inbox": g("serving_handoff_inbox",
                               "ingested records waiting for a slot"),
            "handoff_s": h("serving_step_handoff_s",
                           "handoff export phase wall time"),
            "decode_sync": h("serving_decode_sync_s",
                             "host time blocked on the decode device "
                             "sync"),
        }
        if self.slab is not None:
            # the state group's series, beside the KV pool's; a model
            # without state has none of them
            self._m.update(
                ssm_lane_steps=c("serving_ssm_lane_steps",
                                 "lane-layers the decode state step walked"),
                ssm_live_lane_steps=c("serving_ssm_live_lane_steps",
                                      "lane-layers of live slots among them"),
                ssm_scan_rows=c("serving_ssm_scan_rows",
                                "valid row-layers through the chunk scan"),
                ssm_scan_row_passes=c("serving_ssm_scan_row_passes",
                                      "row-layers through the chunk scan, "
                                      "padding included"),
                state_resets=c("serving_state_resets",
                               "slots whose recurrent state was zeroed for "
                               "a (re-)admitted request"),
                state_slab_bytes=g("serving_state_slab_bytes",
                                   "HBM bytes of the recurrent-state slab"))
        # SLO layer (r16): only tenants that DECLARE budgets cost series
        if any(c.ttft_slo_s is not None or c.e2e_slo_s is not None
               for c in self._tenant_cfg.values()):
            self._slo = SLOTracker(self.metrics)
        return self.metrics

    def attach_tracer(self, tracer: Optional[TraceRecorder] = None,
                      replica: Optional[int] = None,
                      replica_name: Optional[str] = None) -> TraceRecorder:
        """Start recording the request lifecycle + engine phases as
        Chrome trace events (fresh recorder if None).  ``replica``
        namespaces this engine's lanes (pid block + label prefix) so N
        replicas' recorders merge into one cluster timeline without
        colliding (:func:`~paddle_tpu.serving.tracing.merge_traces`)."""
        self.tracer = tracer if tracer is not None else TraceRecorder()
        if replica is not None and self.tracer.replica is None:
            self.tracer.set_replica(replica, name=replica_name)
        self._pid_eng = self.tracer.pid(PID_ENGINE)
        self._pid_req = self.tracer.pid(PID_REQUESTS)
        role = "" if self.role == "both" else f" [{self.role}]"
        self.tracer.process_name(
            self._pid_eng,
            self.tracer.lane_label(f"serving engine{role} (step phases)"))
        self.tracer.process_name(
            self._pid_req,
            self.tracer.lane_label("requests (tid = rid)"))
        return self.tracer

    def attach_flight(self, recorder: Optional[FlightRecorder] = None,
                      capacity: int = 1024) -> FlightRecorder:
        """Start the flight recorder (fresh ring of ``capacity`` records
        if None) — every admission / preemption / handoff / alloc
        failure / recycle / fault / terminal lands in the ring, stamped
        on the ENGINE clock for chaos-replay determinism."""
        self.flight = (recorder if recorder is not None
                       else FlightRecorder(capacity, clock=self._clock))
        return self.flight

    def dump_debug(self) -> dict:
        """Debug snapshot for the /debug surface and crash dumps: step
        counter, invariant verdict (the audit RUNS here — a violated
        invariant reports, it doesn't raise), stats ledger, and the
        flight-recorder ring (None when not attached)."""
        try:
            self.check_invariants()
            verdict = "ok"
        except AssertionError as e:
            verdict = f"violated: {e}"
        return {"step": self._step_idx, "role": self.role,
                "invariants": verdict, "stats": self.stats_snapshot(),
                "flight": (self.flight.to_json()
                           if self.flight is not None else None)}

    def _tr_end(self, rid: int, args: Optional[dict] = None) -> None:
        """Close the request's open span, tolerating a tracer attached
        mid-lifecycle (no span open yet)."""
        if self.tracer.open_span(self._pid_req, rid) is not None:
            self.tracer.end(self._pid_req, rid, args)

    def _tenant_counter(self, family: str, help: str, tenant: str,
                        reason: Optional[str] = None):
        """Lazily-created per-tenant labeled counter (r12).  Tenants are
        an open set (requests name them), so these cannot be
        pre-registered in attach_metrics like the label-free families."""
        key = (family, tenant, reason)
        m = self._tenant_metrics.get(key)
        if m is None:
            labels = {"tenant": tenant}
            if reason is not None:
                labels["reason"] = reason
            m = self.metrics.counter(family, help, labels=labels)
            self._tenant_metrics[key] = m
        return m

    def _emit_token(self, req: Request, tok: int) -> None:
        """One sampled token just landed on ``req`` (the caller already
        appended it) — feed the streaming observer and the per-tenant
        token counter.  Called in delivery order, so an on_token stream
        is token-for-token the eventual FinishedRequest.tokens."""
        if self.on_token is not None:
            self.on_token(req.rid, tok)
        if self.metrics is not None and req.tenant is not None:
            self._tenant_counter("serving_tenant_tokens_generated",
                                 "sampled tokens per tenant",
                                 req.tenant).inc()

    def _charge_service(self, req: Request) -> None:
        """Bill the request's first-time-served token delta to its
        tenant's virtual counter (WFQ; no-op under FCFS).  Safe to call
        at every service point — the delta is 0 when nothing new was
        served (including the whole recompute of a preempted request)."""
        delta = req.uncharged_tokens()
        if delta > 0:
            self.scheduler.charge(req, delta)

    def _observe_terminal(self, req: Request, reason: str) -> None:
        """Single funnel for EVERY FinishedRequest creation: terminal
        counters here are exactly one inc per terminal, which is what
        lets the chaos suite assert registry == observed terminals.
        SLO verdicts (r16) ride the same funnel: every terminal is
        judged against its tenant's declared budgets exactly once —
        degraded terminals (reject/expire/cancel) count as misses, so
        attainment cannot be gamed by shedding load."""
        if self.metrics is not None:
            now = self._now()
            self._m["terminal"][reason].inc()
            self._m["e2e"].observe(now - req.t_enqueue)
            if req.spec_drafted > 0:
                self._m["spec_accept_rate"].observe(
                    req.spec_accepted / req.spec_drafted)
            if req.tenant is not None:
                self._tenant_counter("serving_tenant_requests_terminal",
                                     "per-tenant terminals by reason",
                                     req.tenant, reason).inc()
            if self._slo is not None and req.tenant is not None:
                cfg = self._tenant_cfg.get(req.tenant)
                if cfg is not None:
                    if cfg.ttft_slo_s is not None:
                        ok = (req.t_first_token is not None
                              and req.t_first_token - req.t_enqueue
                              <= cfg.ttft_slo_s)
                        self._slo.observe(req.tenant, "ttft", ok, now,
                                          cfg.slo_objective)
                    if cfg.e2e_slo_s is not None:
                        ok = (reason in ("eos", "length")
                              and now - req.t_enqueue <= cfg.e2e_slo_s)
                        self._slo.observe(req.tenant, "e2e", ok, now,
                                          cfg.slo_objective)
        if self.flight is not None:
            self.flight.record("terminal", self._step_idx, rid=req.rid,
                               reason=reason, tokens=len(req.generated),
                               tenant=req.tenant)
        if self.tracer is not None:
            self._tr_end(req.rid)
            self.tracer.instant(reason, self._pid_req, req.rid,
                                {"rid": req.rid,
                                 "tokens": len(req.generated)})

    def snapshot(self) -> dict:
        """Capture the whole engine state (queue, slots, pool, prefix
        index, host mirrors, RNG) as plain numpy/python — see
        serving/snapshot.py.  ``ServingEngine.restore(model, snap)``
        resumes token-for-token."""
        from .snapshot import snapshot_engine

        if self.ring is not None:
            raise MultiGroupUnsupported(
                "snapshot / restore of a model with two page groups")
        if self.slab is not None:
            raise MultiGroupUnsupported(
                "snapshot / restore of a model with recurrent state")
        return snapshot_engine(self)

    @classmethod
    def restore(cls, model, snap: dict, **overrides) -> "ServingEngine":
        """Rebuild an engine around ``model`` (same weights as the
        snapshotted one) and resume from ``snap``."""
        from .snapshot import restore_engine

        return restore_engine(model, snap, **overrides)

    # -- internals --------------------------------------------------------

    def _now(self) -> float:
        return self._clock()

    def _next_key(self):
        self._key, sub = jax.random.split(self._key)
        return sub

    def _fault_point(self, phase: str) -> None:
        if self.faults is not None:
            self.faults.check_raise(phase)

    def _terminal(self, req: Request, reason: str) -> FinishedRequest:
        """Terminal record for a request that is NOT in a slot (waiting
        or rejected at enqueue) — generated tokens from any earlier
        residency ride along."""
        self._observe_terminal(req, reason)
        return FinishedRequest(
            rid=req.rid, prompt=req.prompt,
            tokens=np.asarray(req.generated, np.int32),
            finish_reason=reason, n_steps=0)

    def _finish(self, idx: int, reason: str) -> FinishedRequest:
        st = self._slots[idx]
        self._slots[idx] = None
        self._table[idx] = 0
        self._tok[idx] = 0
        self._len[idx] = 0
        if self.ring is not None:
            self.ring.release(idx)
        if self.slab is not None:
            self.slab.release(idx)
        self.scheduler.release(idx, st.pages, st.request)
        self._observe_terminal(st.request, reason)
        return FinishedRequest(
            rid=st.request.rid, prompt=st.request.prompt,
            tokens=np.asarray(st.tokens, np.int32), finish_reason=reason,
            n_steps=self._step_idx - st.born_step + 1)

    def _preempt(self, idx: int) -> None:
        """Evict slot ``idx`` to recompute later: pages freed (cached
        prompt pages park reclaimable in the prefix index — the cheap
        part of the recompute), generated tokens kept on the request,
        request requeued at the HEAD of the waiting queue (FCFS: it
        predates everything still waiting)."""
        st = self._slots[idx]
        self._slots[idx] = None
        self._table[idx] = 0
        self._tok[idx] = 0
        self._len[idx] = 0
        if self.ring is not None:
            self.ring.release(idx)
        if self.slab is not None:
            self.slab.release(idx)
        self.scheduler.release(idx, st.pages, st.request)
        st.request.n_preempted += 1
        self.scheduler.requeue(st.request)
        self.stats["preemptions"] += 1
        if self.flight is not None:
            self.flight.record("preempt", self._step_idx,
                               victim=st.request.rid, slot=idx,
                               reason="page_pressure",
                               generated=len(st.request.generated),
                               pages_freed=len(st.pages))
        if self.tracer is not None:
            rid = st.request.rid
            self._tr_end(rid)            # the "resident" span
            self.tracer.instant("preempt", self._pid_req, rid,
                                {"generated": len(st.request.generated)})
            self.tracer.begin("queued", self._pid_req, rid,
                              {"recompute": True})

    def _pick_victim(self) -> Optional[int]:
        """The youngest occupied slot (largest admission seq) — unless it
        is the ONLY one: the oldest request is never preempted, so the
        system always makes forward progress (no livelock)."""
        occ = [(self._slots[i].seq, i) for i in range(self.max_slots)
               if self._slots[i] is not None]
        if len(occ) <= 1:
            return None
        return max(occ)[1]

    def _expire(self, finished: List[FinishedRequest]) -> None:
        """Deadline enforcement, both sides: overdue WAITING requests are
        dropped at queue-pop time (before this step's admissions), and
        overdue SLOTS release their pages mid-flight."""
        now = self._now()
        for req in self.scheduler.pop_expired(now):
            self.stats["expired"] += 1
            finished.append(self._terminal(req, "expired"))
        for idx, st in enumerate(self._slots):
            if st is not None and st.request.expired(now):
                self.stats["expired"] += 1
                finished.append(self._finish(idx, "expired"))
        if self._handoff_in:
            keep = []
            for rec in self._handoff_in:
                if rec["request"].expired(now):
                    self.stats["expired"] += 1
                    finished.append(
                        self._terminal(rec["request"], "expired"))
                else:
                    keep.append(rec)
            self._handoff_in = keep

    def _admit(self, adm) -> None:
        """Apply one scheduling decision: build the slot's block table
        from shared-prefix + owned pages, clone the COW tail page, record
        how much of the prompt needs no recompute."""
        req, idx = adm.request, adm.slot
        pages = list(adm.cached) + list(adm.pages)
        if adm.cow is not None:
            src, _ = adm.cow
            # the first owned page inherits the partial tail's K/V; the
            # source page drops the reference the scheduler pinned for us
            with self._span("engine.cow_dispatch"):
                self.pool.buffers = self._cow_fn(
                    self.pool.buffers, jnp.int32(src),
                    jnp.int32(adm.pages[0]))
            self.pool.release([src])
        if req.seq is None:
            # first admission fixes the request's age; preemption keeps it
            self._admit_seq += 1
            req.seq = self._admit_seq
        st = _Slot(req, pages, prefilled=adm.matched, seq=req.seq,
                   base_len=req.work_len)
        st.born_step = self._step_idx
        self._slots[idx] = st
        row = np.zeros((self.max_pages,), np.int32)
        row[:len(pages)] = pages
        self._table[idx] = row
        if self.slab is not None:
            # whatever the slot's last tenant left is zeroed before this
            # one's first chunk: a first admission and a recompute after
            # preemption alike (nothing of the state was saved)
            with self._span("engine.state_reset_dispatch"):
                self.slab.buffers = self._state_reset_fn(self.slab.buffers,
                                                         jnp.int32(idx))
            self.slab.release(idx)
            self.stats["state_resets"] += 1
        self.stats["prefix_hit_tokens"] += adm.matched
        self.stats["prompt_tokens"] += req.work_len
        if req.n_preempted > 0:
            # the uncached remainder of the work prompt is recomputation
            self.stats["recompute_tokens"] += req.work_len - adm.matched
        if req.t_admitted is None:            # first admission only: a
            # re-admission after preemption is not queue wait
            req.t_admitted = self._now()
            self.stats["admissions"] += 1
            self.stats["queue_wait_s"] += req.t_admitted - req.t_enqueue
            if self.metrics is not None:
                self._m["queue_wait"].observe(req.t_admitted - req.t_enqueue)
        if self.metrics is not None and adm.cow is not None:
            self._m["cow"].inc()
        if self.flight is not None:
            self.flight.record("admit", self._step_idx, rid=req.rid,
                               slot=idx, matched=adm.matched,
                               recompute=req.n_preempted > 0,
                               tenant=req.tenant)
        if self.tracer is not None:
            self._tr_end(req.rid)             # the "queued" span
            if adm.cow is not None:
                self.tracer.instant("cow_clone", self._pid_req, req.rid,
                                    {"matched_tokens": adm.cow[1]})
            self.tracer.begin("resident", self._pid_req, req.rid,
                              {"slot": idx, "matched": adm.matched,
                               "preempted": req.n_preempted})
            if self.slab is not None:
                self.tracer.instant("state_reset", self._pid_req, req.rid,
                                    {"slot": idx})

    def _prefill_chunks(self, finished: List[FinishedRequest]) -> None:
        """Spend the step's prefill budget FCFS over partially-prefilled
        slots: at most ``prefill_budget`` prompt tokens total (several
        chunks while more requests wait on prefill than decode), each call
        at most ``chunk_tokens`` of one slot's work prompt (prompt + any
        preemption-survived tokens), dispatched one after another without
        a sync between them.  A slot whose prompt completes samples its
        next token and joins this step's decode batch: the token goes into
        the slot's lane of the device's carry, and is read and delivered
        after the decode's dispatch (``_deliver_first_tokens``)."""
        # the lanes this step's decode will hold: a lane that reaches its
        # length with its unread tokens takes none of the budget
        n_decoding = sum(1 for s in self._slots
                         if s is not None and s.started
                         and s.request.remaining_new > s.unread)
        partial = sorted(
            (i for i, s in enumerate(self._slots)
             if s is not None and not s.started),
            key=lambda i: self._slots[i].seq)
        budget = self.scheduler.prefill_budget(
            n_decoding, self.chunk_tokens, decode_cost=1 + self.spec_k,
            n_prefilling=len(partial) + self.scheduler.n_waiting)
        self._budget_chunks = max(1, budget // self.chunk_tokens)
        for idx in partial:
            st = self._slots[idx]
            req = st.request
            work = req.work_prompt()
            while budget > 0 and not st.started:
                n = self.scheduler.chunk_rows(
                    st.base_len - st.prefilled, budget,
                    self._tokens_this_step, self.chunk_tokens)
                if n == 0:
                    return
                c_pad = min(_next_pow2(max(n, 8)),
                            max(self.chunk_tokens, n))
                toks = np.zeros((c_pad,), np.int32)
                toks[:n] = work[st.prefilled:st.prefilled + n]
                if self.tracer is not None:
                    self.tracer.begin("prefill_chunk", self._pid_req,
                                      req.rid, {"start": st.prefilled,
                                                "n": n})
                if self.ring is not None:
                    # the ring turns in prefill as in decode: a long prompt
                    # never holds more window pages than a query can see
                    self.ring.advance(idx, st.prefilled, st.prefilled + n)
                with self._span("engine.prefill_dispatch", rid=req.rid,
                                start=st.prefilled, n=n) as sp:
                    bufs, tok, *counts = self._prefill_fn(
                        self.params, self._device_pool(), jnp.asarray(toks),
                        jnp.int32(st.prefilled), jnp.int32(n),
                        self._device_tables(idx), jnp.int32(n - 1),
                        self._next_key(),
                        *(() if self.slab is None else (jnp.int32(idx),)))
                    self._store_pool(bufs)
                    self._moe_pending.extend((c, n) for c in counts)
                if self.metrics is not None:
                    self._m["chunk_s"].observe(sp.dur)
                if self.tracer is not None:
                    self.tracer.end(self._pid_req, req.rid)
                self._note_prefill_dispatch(st.prefilled, c_pad)
                if self.slab is not None:
                    self.slab.advanced[idx] += n
                    self.stats["ssm_scan_rows"] += n * self.slab.num_layers
                    self.stats["ssm_scan_row_passes"] += (
                        c_pad * self.slab.num_layers)
                self._chunks_this_step += 1
                st.prefilled += n
                budget -= n
                self._tokens_this_step += n
                # WFQ accounting: bill first-time prompt positions (a
                # recomputed chunk below the high-water mark bills 0)
                req.note_prefill_progress(st.prefilled)
                self._charge_service(req)
                if st.prefilled < st.base_len:
                    continue
                # prompt complete: next token sampled; its full pages
                # become matchable for every later request
                st.started = True
                if self.pool.prefix is not None:
                    if (self.window is not None
                            and st.base_len > self.window):
                        # the prompt extends past the window boundary:
                        # its leading pages are already invisible to every
                        # future query, and windowed recycling is about to
                        # free them — indexing would pin dead pages in the
                        # cache, so refuse cleanly and count it
                        self.pool.prefix.window_refusals += 1
                    else:
                        nfull = st.base_len // self.page_size
                        self.pool.prefix.insert(work, st.pages[:nfull])
                with self._span("engine.carry_dispatch"):
                    self._carry = self._carry_put_fn(self._carry,
                                                     jnp.int32(idx), tok)
                self._len[idx] = st.base_len
                st.unread = 1
                self._first_unread.append((idx, st, tok, self._moe_mark()))
            if budget <= 0:
                break

    def _deliver_first_tokens(self, finished: List[FinishedRequest]) -> None:
        """Read the first tokens of the prompts completed since the last
        call, and deliver them: after the step's last dispatch (in the
        prefill phase where the engine never decodes; before drafting,
        which reads them).  A slot that went away meanwhile (cancelled,
        expired, preempted: its recompute samples the token again) left
        a dead token: not read."""
        unread, self._first_unread = self._first_unread, []
        for idx, st, tok, mark in unread:
            if self._slots[idx] is not st:
                continue
            req = st.request
            with self._span("engine.first_token_sync", rid=req.rid) as sp:
                tok = int(tok)
            self.stats["prefill_sync_s"] += sp.dur
            self._fold_moe_counts(mark)
            st.unread -= 1
            st.tokens.append(tok)
            self._emit_token(req, tok)
            self._charge_service(req)
            self.stats["tokens_generated"] += 1
            now = self._now()
            if req.t_first_token is None:
                self.stats["first_tokens"] += 1
                self.stats["prefill_wait_s"] += now - req.t_admitted
                if self.metrics is not None:
                    self._m["ttft"].observe(now - req.t_enqueue)
                if self.tracer is not None:
                    self.tracer.instant("first_token", self._pid_req,
                                        req.rid)
                req.t_first_token = now
            elif self.metrics is not None and req.t_last_token is not None:
                # a recomputed request's first post-readmission token:
                # the gap since its last delivered token is real
                # user-visible inter-token stall
                self._m["tbt"].observe(now - req.t_last_token)
            req.t_last_token = now
            self._tok[idx] = tok
            if self.eos_token_id is not None and tok == self.eos_token_id:
                finished.append(self._finish(idx, "eos"))
            elif len(st.tokens) >= req.max_new_tokens:
                finished.append(self._finish(idx, "length"))

    def _grow_pages(self, idx: int, consumed: int,
                    finished: List[FinishedRequest]) -> bool:
        """Ensure slot ``idx`` owns every page its next ``consumed``
        decode writes need (positions ``len .. len+consumed-1``) —
        on-demand growth, one admission no longer pays max_new_tokens
        upfront.  On allocation failure, preempt the youngest occupied
        slot and retry; never the oldest.  A victim with a decode in
        flight has tokens the host has not read: the decode is retired
        first (into ``finished``), so that the victim's recompute prompt
        lacks none, and the next dispatch counts as ``decode_sync_first``.
        Returns True when the slot can decode this step (False: it was
        preempted itself, finished in that retirement, or stalled because
        no victim remains — retried next step)."""
        st = self._slots[idx]
        # grow from the HIGH-WATER page count, not len(pages): windowed
        # recycling shrinks the live page list but table positions keep
        # advancing — logical page i always lives at table column i
        need = self.pool.pages_for(int(self._len[idx]) + consumed) \
            - st.hw_pages
        while need > 0:
            got = self.pool.alloc(need)
            if got is not None:
                row = self._table[idx]
                row[st.hw_pages:st.hw_pages + len(got)] = got
                st.pages.extend(got)
                st.hw_pages += len(got)
                return True
            if self.flight is not None:
                self.flight.record(
                    "alloc_fail", self._step_idx, rid=st.request.rid,
                    need=need, free=self.pool.num_free,
                    reclaimable=self.pool.num_reclaimable)
            if self.pool.num_free + self.pool.num_reclaimable >= need:
                # the pool COULD satisfy the lease, so the failure is a
                # transient allocator fault (fault injection), not real
                # pressure — stall this step rather than evict residents
                # whose pages the retry won't even need
                return False
            victim = self._pick_victim()
            if victim is None:
                return False          # stalled; pool can't shrink further
            if self._inflight is not None and any(
                    s is self._slots[victim] for _, s in self._inflight[0]):
                self._retire_decode(finished, early=True)
                if self._slots[idx] is not st:
                    return False      # the grower's last token was in it
                continue              # what finished may have freed enough
            self._preempt(victim)
            if victim == idx:
                return False          # the grower was the youngest itself
        return True

    def _recycle_window_pages(self, idx: int) -> None:
        """Sliding-window page recycling: once every position of a slot's
        leading logical page has fallen out of the attention window — page
        j is dead iff ``(j+1)*page_size <= len+1-window``, i.e. the next
        query at position ``len`` cannot see any of it — the page goes
        back to the pool and its table entry becomes the null page (safe:
        the window mask already excludes those positions from every
        kernel and reference).  A slot's live footprint becomes a RING of
        ~ceil(window/page_size)+1 pages, so long generations stop
        growing.  Shared (prefix-cached) pages just drop this slot's
        reference; only STARTED slots recycle (prefill still writes the
        whole prompt)."""
        st = self._slots[idx]
        if st is None or self.window is None or not st.started:
            return
        dead = (int(self._len[idx]) + 1 - self.window) // self.page_size
        done = st.hw_pages - len(st.pages)    # leading pages already freed
        if dead <= done:
            return
        victims = st.pages[:dead - done]
        del st.pages[:dead - done]
        self._table[idx, done:dead] = 0
        self.pool.free(victims)
        if self.flight is not None:
            self.flight.record("window_recycle", self._step_idx,
                               rid=st.request.rid, pages=len(victims))

    # -- disaggregated prefill/decode handoff (r15) -----------------------

    def _release_slot(self, idx: int) -> _Slot:
        """Free slot ``idx`` WITHOUT a terminal — the handoff path: the
        request lives on (on another replica), so no FinishedRequest, no
        terminal counter; pages release normally (full prompt pages the
        prefix index adopted park reclaimable for later local hits)."""
        st = self._slots[idx]
        self._slots[idx] = None
        self._table[idx] = 0
        self._tok[idx] = 0
        self._len[idx] = 0
        if self.ring is not None:
            self.ring.release(idx)
        if self.slab is not None:
            self.slab.release(idx)
        self.scheduler.release(idx, st.pages, st.request)
        return st

    def _handoff_started(self) -> None:
        """Prefill-role drain: every STARTED slot (prompt complete, first
        token sampled) serializes into a handoff record and leaves the
        engine.  A scripted "handoff" fault degrades the WHOLE step's
        transfers — records ship without page payloads and the decode
        replica re-prefills them (chunked, prefix-cache-assisted), so a
        dropped fabric costs recompute, never correctness."""
        from .snapshot import handoff_state

        started = sorted((i for i, s in enumerate(self._slots)
                          if s is not None and s.started),
                         key=lambda i: self._slots[i].seq)
        if not started:
            return
        degraded = False
        if self.faults is not None:
            try:
                self.faults.check_raise("handoff")
            except InjectedFault:
                degraded = True
        for idx in started:
            st = self._slots[idx]
            h = handoff_state(self, idx, with_payload=not degraded)
            self.stats["handoffs_out"] += 1
            if degraded:
                self.stats["handoff_faults"] += 1
            else:
                self.stats["handoff_bytes"] += h["nbytes"]
            if self.flight is not None:
                self.flight.record("handoff_out", self._step_idx,
                                   rid=st.request.rid,
                                   nbytes=h["nbytes"],
                                   n_pages=h["n_pages"],
                                   degraded=degraded)
            if self.tracer is not None:
                rid = st.request.rid
                tr = h.get("trace")
                if tr is not None:
                    # INSIDE the resident span (before _tr_end closes
                    # it): the flow arrow leaves from the prefill slice
                    self.tracer.flow_start(
                        "handoff", self._pid_req, rid,
                        flow_id(tr["rid"], tr["seq"]))
                self._tr_end(rid)            # the "resident" span
                self.tracer.instant("handoff", self._pid_req, rid,
                                    {"n_pages": h["n_pages"],
                                     "nbytes": h["nbytes"],
                                     "degraded": degraded})
            self._release_slot(idx)
            self._handoff_out.append(h)

    def drain_handoffs(self) -> List[dict]:
        """Hand the outbox to the caller (the router's pump) — records
        are the caller's to deliver once returned."""
        out, self._handoff_out = self._handoff_out, []
        return out

    def ingest_handoff(self, h: dict) -> int:
        """Accept one prefill-replica handoff record.  Layout-guarded
        EAGERLY (a byte-incompatible payload must fail at the boundary,
        not at admission); timestamps rebase onto this engine's clock
        exactly like snapshot restore.  A payload-bearing record queues
        in the inbox until a slot + pages free up; a DEGRADED record
        (payload None) re-enters the waiting queue at the head — its
        work prompt re-prefills here, recompute-style.  Returns the
        rid."""
        from .snapshot import _request_from_state

        if self.role == "prefill":
            raise ValueError(
                "a prefill-role engine cannot ingest handoffs — route "
                "them to a decode/both replica")
        if self.slab is not None:
            raise MultiGroupUnsupported(
                "handoff to a model with recurrent state: pages carry no "
                "state")
        payload = h["payload"]
        if payload is not None:
            self.pool.check_layout(payload["layout"], what="handoff")
        req = _request_from_state(h["request"])
        delta = self._now() - float(h["clock_now"])
        req.t_enqueue += delta
        for attr in ("t_admitted", "t_first_token", "t_last_token"):
            v = getattr(req, attr)
            if v is not None:
                setattr(req, attr, v + delta)
        self.stats["handoffs_in"] += 1
        if self.flight is not None:
            self.flight.record("handoff_in", self._step_idx, rid=req.rid,
                               nbytes=int(h["nbytes"]),
                               n_pages=int(h["n_pages"]),
                               degraded=payload is None)
        if payload is None:
            # degraded transfer: the request was already accepted and
            # billed, so it bypasses backpressure and requeues at the
            # head — uncharged_tokens()'s monotone high-water mark means
            # the re-prefill bills the tenant nothing.  Accounting-wise
            # this IS a preemption (the work prompt gets recomputed), so
            # the re-admission lands in recompute_tokens like one.
            req.n_preempted += 1
            self.scheduler.requeue(req)
            if self.tracer is not None:
                self.tracer.begin("queued", self._pid_req, req.rid,
                                  {"recompute": True, "handoff": True})
        else:
            self._handoff_in.append(dict(
                request=req, base_len=int(h["base_len"]),
                n_pages=int(h["n_pages"]), payload=payload,
                nbytes=int(h["nbytes"])))
            if self.tracer is not None:
                self.tracer.begin("queued", self._pid_req, req.rid,
                                  {"handoff": True})
        if self.tracer is not None:
            tr = h.get("trace")
            if tr is not None:
                # inside the just-opened "queued" span (bp="e" binds the
                # arrow head to the enclosing slice): the flow lands on
                # the decode replica's lane
                self.tracer.flow_finish("handoff", self._pid_req,
                                        req.rid,
                                        flow_id(tr["rid"], tr["seq"]))
        return req.rid

    def _admit_handoffs(self, finished: List[FinishedRequest]) -> None:
        """Admit queued handoff records FIFO into free slots: lease
        pages, scatter the payload in (bit-exact adoption — no
        recompute), rebuild the slot mirrors as if local prefill had just
        completed, and index the full prompt pages for prefix reuse.
        Head-of-line blocking on slot/page shortage is intentional, same
        as the scheduler's admission loop (a transient alloc fault just
        retries next step — residents drain, so no livelock)."""
        while self._handoff_in:
            rec = self._handoff_in[0]
            if not self._try_admit_handoff(rec):
                break
            self._handoff_in.pop(0)

    def _try_admit_handoff(self, rec: dict) -> bool:
        if not self.scheduler._free_slots:
            return False
        pages = self.pool.alloc(rec["n_pages"])
        if pages is None:
            return False
        req = rec["request"]
        base_len = rec["base_len"]
        with self._span("engine.ingest_dispatch"):
            self.pool.ingest_pages(rec["payload"], pages)
        if req.seq is None:      # carried from the prefill replica's
            self._admit_seq += 1  # admission normally; None only if the
            req.seq = self._admit_seq   # sender predates admission seqs
        st = _Slot(req, pages, prefilled=base_len, seq=req.seq,
                   base_len=base_len)
        st.born_step = self._step_idx
        st.started = True
        slot = self.scheduler._free_slots.pop()
        self.scheduler.note_restored_slot(req)
        self._slots[slot] = st
        row = np.zeros((self.max_pages,), np.int32)
        row[:len(pages)] = pages
        self._table[slot] = row
        # mirrors exactly as local prefill completion leaves them: the
        # carry token is the last sampled one, the device length is the
        # work-prompt length whose K/V the pages hold
        self._tok[slot] = req.generated[-1]
        with self._span("engine.carry_dispatch"):
            self._carry = self._carry_put_fn(self._carry, jnp.int32(slot),
                                             jnp.int32(req.generated[-1]))
        self._len[slot] = base_len
        # adopt the full prompt pages into THIS pool's prefix index —
        # same insert (and same windowed refusal) as local prefill; the
        # indexable tokens are the base_len positions the pages actually
        # hold, i.e. the work prompt minus the carry token
        if self.pool.prefix is not None:
            if self.window is not None and base_len > self.window:
                self.pool.prefix.window_refusals += 1
            else:
                work = req.work_prompt()[:base_len]
                nfull = base_len // self.page_size
                self.pool.prefix.insert(work, st.pages[:nfull])
        if self.flight is not None:
            self.flight.record("admit", self._step_idx, rid=req.rid,
                               slot=slot, handoff=True,
                               adopted_pages=len(pages),
                               tenant=req.tenant)
        if self.tracer is not None:
            self._tr_end(req.rid)            # the "queued" span
            self.tracer.begin("resident", self._pid_req, req.rid,
                              {"slot": slot, "handoff": True,
                               "adopted_pages": len(pages)})
        return True

    def step(self) -> List[FinishedRequest]:
        """One engine iteration: expire deadlines, admit into freed
        slots, advance partial prefills by the chunk budget, grow decode
        pages (preempting under pressure), dispatch one decode step over
        every started slot, THEN read the previous step's decode and this
        step's first tokens.  Returns every request that reached a
        terminal state this step (including rejects/cancels recorded
        since the last step): a decode's finishes one step after its
        dispatch.  Injected faults abort the remainder of the
        iteration at a phase boundary; the next step resumes."""
        t0 = time.perf_counter()
        self._step_idx += 1
        if self.faults is not None:
            self.faults.begin_step(self._step_idx)
        with self._span("engine.step", step=self._step_idx):
            return self._step_in_span(t0)

    def _step_in_span(self, t0: float) -> List[FinishedRequest]:
        finished: List[FinishedRequest] = list(self._pending)
        self._pending.clear()
        self._tokens_this_step = self._chunks_this_step = 0
        # phase -> (start perf-seconds, duration); filled as _run_step's
        # phase spans close, so a fault aborting a phase still records the
        # time it burned before aborting.  Carried on the instance (not a
        # parameter) so _run_step keeps its r10 signature.
        phase = self._phase_s = {}
        calls = self._calls_s = _no_calls()
        self._drain_unchecked = True
        self._drained = self._ready_at_sync = None
        ru0, gc0 = resource.getrusage(_RUSAGE_WHO), _gc_full["s"]
        try:
            self._run_step(finished)
        except InjectedFault as e:
            self.stats["step_faults"] += 1
            if self.flight is not None:
                self.flight.record("injected_fault", self._step_idx,
                                   error=str(e))
        except BaseException as e:
            # a REAL fault escaping mid-step must not swallow terminals
            # already recorded this iteration (their pages are freed) —
            # re-park them so a retrying host loop still delivers every
            # request exactly one terminal state
            self._pending = finished + self._pending
            # black box first (r16): before the exception unwinds the
            # host loop, the flight ring lands next to the metrics
            # artifacts — the postmortem starts with the last N
            # decisions, not just a stack trace
            if self.flight is not None:
                self.flight.record("crash", self._step_idx,
                                   error=f"{type(e).__name__}: {e}")
                if self._crash_dump_dir is not None:
                    try:
                        self.flight.dump(os.path.join(
                            self._crash_dump_dir, self._crash_dump_name))
                    except OSError:
                        pass          # the dump must never mask the fault
            raise
        dt = time.perf_counter() - t0
        self._last_step_at = self._now()
        self.stats["pages_in_use"] = self.pool.pages_in_use
        if self.ring is not None:
            self.stats["pages_in_use_window"] = self.ring.pages_in_use
            self.stats["window_pages_recycled"] = self.ring.recycled
        self.stats["queue_depth"] = self.scheduler.n_waiting
        self.stats["step_wall_s"] += dt
        self.stats["last_step_s"] = dt
        for ph in ("admit", "prefill", "handoff", "decode"):
            start_dur = phase.get(ph)
            v = start_dur[1] if start_dur is not None else 0.0
            self.stats[f"{ph}_s"] += v
            self.stats[f"last_{ph}_s"] = v
        self.stats["steps"] += 1
        self.stats["dispatch_s"] += sum(calls["dispatch"])
        if dt >= SLOW_STEP_S:
            self.stats["slow_steps"] += 1
            self.stats["slow_step_s"] += dt
            if self.flight is not None and self.faults is None:
                self._note_slow_step(dt, phase, calls, ru0, gc0)
        if self.tracer is not None:
            for ph, (start, dur) in phase.items():
                self.tracer.complete(ph, start, dur, self._pid_eng, 0,
                                     {"step": self._step_idx})
        if self.metrics is not None:
            self._sync_metrics(dt, phase)
        return finished

    def _note_slow_step(self, dt: float, phase: Dict[str, tuple],
                        calls: Dict[str, List[float]], ru0, gc0: float
                        ) -> None:
        """The ``slow_step`` flight record: where a stalled step's seconds
        went.  Its phases; the host's seconds in dispatches and in the two
        waits for device values, each split into its calls where there
        were several; whether the decode in flight was done at the step's
        first dispatch and when its sync began; and what the thread went
        through meanwhile: generation-2 garbage collection, its own CPU
        seconds, voluntary and involuntary context switches, major page
        faults.  Not under a FaultPlan, whose replays compare their black
        boxes byte for byte: a stall is wall time."""
        ru1 = resource.getrusage(_RUSAGE_WHO)
        rec = dict(step_s=dt, drained=self._drained,
                   ready_at_sync=self._ready_at_sync,
                   gc_s=_gc_full["s"] - gc0,
                   cpu_s=(ru1.ru_utime + ru1.ru_stime
                          - ru0.ru_utime - ru0.ru_stime),
                   voluntary_switches=ru1.ru_nvcsw - ru0.ru_nvcsw,
                   involuntary_switches=ru1.ru_nivcsw - ru0.ru_nivcsw,
                   major_faults=ru1.ru_majflt - ru0.ru_majflt)
        for ph in ("admit", "prefill", "decode"):
            rec[f"{ph}_s"] = phase[ph][1] if ph in phase else 0.0
        for key, durs in calls.items():
            rec[f"{key}_s"] = sum(durs)
            if len(durs) > 1:
                rec[f"{key}_calls_s"] = list(durs)
        self.flight.record("slow_step", self._step_idx, **rec)

    def _sync_metrics(self, dt: float, phase: Dict[str, tuple]) -> None:
        """End-of-step registry feed: monotonic counters sync from the
        stats ledger (one source of truth — they cannot diverge), gauges
        sample the pool/scheduler, histograms take this step's wall
        times.  Terminal counters and request-time histograms are fed
        inline at their event sites instead."""
        m, s = self._m, self.stats
        m["steps"].inc()
        for stat_key, name in (("tokens_generated", "tokens"),
                               ("prefill_calls", "prefill_calls"),
                               ("decode_calls", "decode_calls"),
                               ("decode_ahead", "decode_ahead"),
                               ("decode_sync_first", "decode_sync_first"),
                               ("slow_steps", "slow_steps"),
                               ("slow_step_s", "slow_step_s"),
                               ("preemptions", "preemptions"),
                               ("recompute_tokens", "recompute"),
                               ("prefix_hit_tokens", "prefix_hit"),
                               ("prompt_tokens", "prompt_tokens"),
                               ("step_faults", "step_faults"),
                               ("spec_drafted", "spec_drafted"),
                               ("spec_accepted", "spec_accepted"),
                               ("spec_rejected", "spec_rejected"),
                               ("handoffs_out", "handoffs_out"),
                               ("handoffs_in", "handoffs_in"),
                               ("handoff_bytes", "handoff_bytes"),
                               ("handoff_faults", "handoff_faults")):
            m[name].set_total(s[stat_key])
        if self.slab is not None:
            for key in ("ssm_lane_steps", "ssm_live_lane_steps",
                        "ssm_scan_rows", "ssm_scan_row_passes",
                        "state_resets"):
                m[key].set_total(s[key])
            m["state_slab_bytes"].set(s["state_slab_bytes"])
        m["handoff_inbox"].set(len(self._handoff_in))
        m["alloc_calls"].set_total(self.pool.alloc_calls)
        m["alloc_failures"].set_total(self.pool.alloc_failures)
        if self.pool.prefix is not None:
            m["evictions"].set_total(self.pool.prefix.evictions)
        m["pages_in_use"].set(self.pool.pages_in_use)
        m["pages_free"].set(self.pool.num_free)
        m["pages_reclaimable"].set(self.pool.num_reclaimable)
        m["queue_depth"].set(self.scheduler.n_waiting)
        m["slots_active"].set(self.scheduler.n_active)
        m["kv_bytes_per_token"].set(self.pool.bytes_per_token())
        held = sorted(len(s.pages) for s in self._slots if s is not None)
        m["pages_per_slot_p50"].set(
            held[len(held) // 2] if held else 0)
        m["hit_rate"].set(self.prefix_hit_rate())
        m["budget_util"].set(self._tokens_this_step
                             / max(self.scheduler.token_budget, 1))
        m["step_s"].observe(dt)
        for ph in ("admit", "prefill", "handoff", "decode"):
            if ph in phase:
                m[f"{ph}_s"].observe(phase[ph][1])
        if self._slo is not None:
            # per step, not per terminal: burn-rate windows must page
            # OUT (and the gauges decay) even when nothing terminates
            self._slo.sync(self._now())

    def _span(self, name: str, **args) -> _Span:
        if self._drain_unchecked and name in _DISPATCH_SPANS:
            self._check_drained()
        return _Span(self._phase_s, self._calls_s, name, args)

    def _check_drained(self) -> None:
        """Just before the step's first dispatch: is the decode in flight
        done already?  The device runs programs in dispatch order, so a
        finished decode N means it ran out of work and waits for this
        dispatch.  A step with nothing in flight is neither case."""
        self._drain_unchecked = False
        if self._inflight is not None:
            self._drained = self._inflight[2].is_ready()
            self.stats["device_drained"] += self._drained

    def _run_step(self, finished: List[FinishedRequest]) -> None:
        with self._span("engine.admit"):
            self._expire(finished)
            # handoff ingests admit FIRST: their prefill is already paid
            # for, so they take priority over raw admissions for the
            # slots/pages this step frees up
            self._admit_handoffs(finished)
            for adm in self.scheduler.schedule_step():
                self._admit(adm)
            self._fault_point("admit")
        with self._span("engine.prefill"):
            self._prefill_chunks(finished)
            if self.role == "prefill":
                self._deliver_first_tokens(finished)
            self._fault_point("prefill")

        if self.role == "prefill":
            # prefill workers never decode: every slot that completed its
            # prompt this step exports (request, block-table order pages,
            # payload + scales) and frees its slot — the router delivers
            # the records to a decode replica
            with self._span("engine.handoff"):
                self._handoff_started()
            return

        with self._span("engine.decode"):
            self._decode_step(finished)
            self._fault_point("decode")

    def _moe_mark(self) -> int:
        """How many expert-count entries were dispatched so far."""
        return self._moe_folded + len(self._moe_pending)

    def _fold_moe_counts(self, mark: int) -> None:
        """Add to the stats the expert-routing counts of the dispatches up
        to ``mark`` (``_moe_mark`` as it stood when a program was
        dispatched).  Called right after the sync on that program's token:
        the programs before it ran before it, so reading their counts waits
        for nothing, and the decode dispatched since is left alone."""
        while self._moe_folded < mark:
            counts, rows = self._moe_pending.popleft()
            self._moe_folded += 1
            per_layer = np.asarray(counts)                # (layers, held)
            st = self.stats
            st["moe_assignments"] += rows * self._moe.top_k * len(per_layer)
            st["moe_local_assignments"] += int(per_layer.sum())
            st["moe_expert_tokens_max"] += int(per_layer.max(axis=1).sum())
            st["moe_experts_active"] += int((per_layer > 0).sum())
            st["moe_layer_passes"] += len(per_layer)

    def _note_prefill_dispatch(self, start: int, width: int) -> None:
        """Counters of one chunk dispatch: ``width`` rows (the bucket's, as
        the kernel sees them) after ``start`` written positions, each layer
        walking its own window's live pages of the slot's one table row."""
        self.stats["prefill_calls"] += 1
        for window, n_layers in self._window_layers.items():
            lo, hi = pa.live_pages(start + 1, self.page_size, window, width,
                                   self.max_pages)
            self.stats["prefill_pages_walked"] += n_layers * int(hi - lo)
        self.stats["prefill_pages_in_table"] += (
            self.max_pages * len(self.layers))

    def _note_decode_dispatch(self, run: List[int],
                              remaining: Optional[np.ndarray] = None) -> None:
        """Counters of one decode (or verify) dispatch over slots ``run``;
        ``remaining`` is a decode program's argument (a verify has none)."""
        self.stats["decode_calls"] += 1
        self.stats[_DECODE_AFTER[min(self._chunks_this_step, 2)]] += 1
        self.stats["prefill_budget_chunks"] += self._budget_chunks
        self.stats["decode_attended_tokens"] += int(self._len[run].sum())
        # the attention kernels see EVERY lane, each layer under its own
        # window: a verify once with spec_k + 1 rows, a decode once per
        # inner step with the lengths as the program advances them
        if remaining is None:
            seen, rows = [self._len + 1], self.spec_k + 1
        else:
            seen, rows = [self._len + np.minimum(i, remaining) + 1
                          for i in range(self.decode_block)], 1
        for window, n_layers in self._window_layers.items():
            lo, hi = pa.live_pages(np.stack(seen), self.page_size, window,
                                   rows, self.max_pages)
            self.stats["decode_pages_walked"] += n_layers * int((hi - lo).sum())
        self.stats["decode_pages_in_table"] += (
            len(seen) * self.max_slots * self.max_pages * len(self.layers))
        if self.slab is not None:
            # the kernel walks the live lanes alone; the jnp path all
            walked = len(run) if self._use_ssm_kernel else self.max_slots
            self.stats["ssm_lane_steps"] += walked * self.slab.num_layers
            self.stats["ssm_live_lane_steps"] += (
                len(run) * self.slab.num_layers)

    def _decode_step(self, finished: List[FinishedRequest]) -> None:
        """Dispatch this step's decode, THEN read the previous step's and
        the first tokens of this step's completed prompts (the module
        docstring has the order and why it is safe)."""
        if self.spec_k:
            # drafting reads the retired history: a synchronous step
            self._deliver_first_tokens(finished)
            return self._spec_decode_step(finished)
        # decode-page growth, oldest first so preemption victims are
        # always younger than the grower.  A lane's budget counts the
        # tokens sampled for it and not read yet: one that reaches its
        # length with them is left out and finishes when they are retired
        order = sorted((i for i, s in enumerate(self._slots)
                        if s is not None and s.started),
                       key=lambda i: self._slots[i].seq)
        run: List[int] = []
        for idx in order:
            st = self._slots[idx]
            if st is None:                    # preempted by an earlier grow
                continue
            left = st.request.remaining_new - st.unread
            if left > 0 and self._grow_pages(
                    idx, min(self.decode_block, left), finished):
                run.append(idx)
        # a growth that retired the decode in flight may have finished a
        # lane picked before it
        run = [idx for idx in run if self._slots[idx] is not None]
        flight = None
        if run:
            remaining = np.zeros((self.max_slots,), np.int32)
            for idx in run:
                st = self._slots[idx]
                remaining[idx] = st.request.remaining_new - st.unread
                if self.ring is not None:
                    self.ring.advance(idx, int(self._len[idx]),
                                      int(self._len[idx]) + 1)
            with self._span("engine.decode_dispatch", slots=len(run)) as sp:
                # ``_len`` goes as a copy, like the tables: it advances
                # below while the program may not have read it yet
                bufs, toks_all, self._carry, *counts = self._decode_fn(
                    self.params, self._device_pool(), self._carry,
                    jnp.asarray(self._len.copy()), self._device_tables(),
                    jnp.asarray(remaining), self._next_key())
                self._store_pool(bufs)
                self._moe_pending.extend((c, len(run)) for c in counts)
            self._note_decode_dispatch(run, remaining)
            if self._inflight is not None:
                self.stats["decode_ahead"] += 1
            elif self._retired_early:
                self.stats["decode_sync_first"] += 1
            self._retired_early = False
            # the host's positions follow the dispatch, not the tokens: the
            # program consumes a count known here, whatever it samples
            for idx in run:
                n = int(min(self.decode_block, remaining[idx]))
                self._slots[idx].unread += n
                self._len[idx] += n
                if self.slab is not None:
                    self.slab.advanced[idx] += n
                self._recycle_window_pages(idx)
            # slot objects ride along so retirement can detect
            # cancel/expire/slot-reuse
            flight = ([(idx, self._slots[idx]) for idx in run], remaining,
                      toks_all, sp.t0, self._moe_mark())
        if self._inflight is not None:
            self._retire_decode(finished)
        self._inflight = flight
        self._deliver_first_tokens(finished)

    def _retire_all(self, finished: List[FinishedRequest]) -> None:
        """Read and apply every device token not read yet, ahead of its
        turn: what a snapshot needs, which cannot carry a device value."""
        if self._inflight is not None:
            self._retire_decode(finished, early=True)
        self._deliver_first_tokens(finished)

    def _retire_decode(self, finished: List[FinishedRequest],
                       early: bool = False) -> None:
        """Sync the decode in flight and apply its results: append tokens,
        bill tenants, finish eos/length, mirror the carry.  It runs one
        step after the dispatch, behind the NEXT decode's dispatch, so
        finishes surface a step later, which greedy outputs
        (schedule-invariant per request) don't observe.  ``early``: ahead
        of that turn (a preemption, a snapshot).

        A lane may have left meanwhile (cancelled, expired, finished on an
        ``eos`` that an earlier retirement or a first token brought while
        this decode was already dispatched): its tokens are dropped.  The
        row such a lane wrote went into pages it still owned at the
        dispatch, at a position past its prompt's full pages (the prefix
        index holds none of it); the pages were freed on the host after
        that dispatch, and whatever a later owner runs on them is
        dispatched later still.  The device runs programs in dispatch
        order, so the stray row is written before the new owner's and
        never after."""
        entries, remaining, toks_all, t_c, mark = self._inflight
        self._inflight = None
        self._retired_early = self._retired_early or early
        self._ready_at_sync = toks_all.is_ready()
        with self._span("engine.decode_sync") as sp:
            toks_all = np.asarray(jax.block_until_ready(toks_all))
        sync_s = sp.dur
        self._fold_moe_counts(mark)
        self.stats["decode_sync_s"] += sync_s
        self.stats["last_decode_sync_s"] = sync_s
        if self.metrics is not None:
            # block_until_ready closed the dispatch, so this is the real
            # device step time, not the async hand-off; sync_s is the
            # part the host actually WAITED
            self._m["decode_call_s"].observe(time.perf_counter() - t_c)
            self._m["decode_sync"].observe(sync_s)
        now = self._now()
        for idx, st_dispatched in entries:
            st = self._slots[idx]
            if st is not st_dispatched:
                continue
            consumed = int(min(self.decode_block, remaining[idx]))
            st.unread -= consumed
            reason = None
            n_new = 0
            req = st.request
            for i in range(consumed):
                tok = int(toks_all[i, idx])
                st.tokens.append(tok)
                self._emit_token(req, tok)
                n_new += 1
                self.stats["tokens_generated"] += 1
                if (self.eos_token_id is not None
                        and tok == self.eos_token_id):
                    reason = "eos"
                    break
            self._tokens_this_step += n_new
            self._charge_service(req)
            if (self.metrics is not None and n_new
                    and req.t_last_token is not None):
                self._m["tbt"].observe((now - req.t_last_token) / n_new)
            req.t_last_token = now
            if reason is None and (len(st.tokens)
                                   >= st.request.max_new_tokens):
                reason = "length"
            if reason is not None:
                finished.append(self._finish(idx, reason))
            else:
                self._tok[idx] = int(toks_all[consumed - 1, idx])

    def _spec_decode_step(self, finished: List[FinishedRequest]) -> None:
        """One speculative iteration over the started slots: draft from
        each request's history, grow pages for the whole verify block
        (carry + drafts — up to spec_k+1 positions, the same on-demand
        growth/preemption path as fused decode), one verify dispatch,
        then the greedy rejection rule advances each slot by
        ``accepted + 1`` tokens.  The draft is capped at
        ``remaining_new - 1`` so even full acceptance plus the bonus
        token lands exactly on ``max_new_tokens``."""
        k = self.spec_k
        order = sorted((i for i, s in enumerate(self._slots)
                        if s is not None and s.started),
                       key=lambda i: self._slots[i].seq)
        # -1 marks a lane not decoding this step (empty slot, mid-prefill,
        # stalled growth): the verify program masks all its rows
        n_draft = np.full((self.max_slots,), -1, np.int32)
        draft = np.zeros((self.max_slots, k), np.int32)
        run: List[int] = []
        for idx in order:
            if self._slots[idx] is None:      # preempted by an earlier grow
                continue
            st = self._slots[idx]
            cap = min(k, st.request.remaining_new - 1)
            if cap > 0:
                prop = np.asarray(
                    self._drafter.draft(st.request.work_prompt(), cap),
                    np.int32).reshape(-1)
                st.draft = [int(v) for v in prop[:cap]]
            else:
                st.draft = []
            if self._grow_pages(idx, len(st.draft) + 1, finished):
                run.append(idx)
                n_draft[idx] = len(st.draft)
                if st.draft:
                    draft[idx, :len(st.draft)] = st.draft
        if not run:
            return
        # mid-verify fault point: drafts proposed + pages grown, dispatch
        # not yet issued — an injected fault here leaves the draft
        # buffers populated; the next step's proposal overwrites them
        # (check_invariants audits their bounds meanwhile)
        self._fault_point("verify")
        with self._span("engine.decode_dispatch", slots=len(run)) as sp:
            bufs, pred = self._verify_fn(
                self.params, self._device_pool(), jnp.asarray(self._tok),
                jnp.asarray(draft), jnp.asarray(n_draft),
                jnp.asarray(self._len), self._device_tables(),
                self._next_key())
            self._store_pool(bufs)
        self._note_decode_dispatch(run)
        with self._span("engine.decode_sync") as sync:
            pred = np.asarray(pred)                  # (max_slots, k+1)
        self.stats["decode_sync_s"] += sync.dur
        if self.metrics is not None:
            self._m["decode_call_s"].observe(time.perf_counter() - sp.t0)
        now = self._now()
        for idx in run:
            st = self._slots[idx]
            req = st.request
            nd = len(st.draft)
            n_acc, emitted = spec_accept_greedy(pred[idx], st.draft)
            st.draft = []
            self.stats["spec_drafted"] += nd
            self.stats["spec_accepted"] += n_acc
            self.stats["spec_rejected"] += nd - n_acc
            req.spec_drafted += nd
            req.spec_accepted += n_acc
            reason = None
            n_new = 0
            for tok in emitted:
                st.tokens.append(tok)
                self._emit_token(req, tok)
                n_new += 1
                self.stats["tokens_generated"] += 1
                if (self.eos_token_id is not None
                        and tok == self.eos_token_id):
                    reason = "eos"
                    break
            self._tokens_this_step += n_new
            self._charge_service(req)
            if (self.metrics is not None and n_new
                    and req.t_last_token is not None):
                self._m["tbt"].observe((now - req.t_last_token) / n_new)
            req.t_last_token = now
            if reason is None and len(st.tokens) >= req.max_new_tokens:
                reason = "length"
            if reason is not None:
                finished.append(self._finish(idx, reason))
            else:
                # mirror the DEVICE state: positions len .. len+n_new-1
                # now hold the accepted block rows' K/V (the carry token
                # and the accepted drafts — exactly the tokens sequential
                # decode would have written there); the new carry is the
                # bonus/correction token, whose K/V the next step writes
                self._tok[idx] = emitted[n_new - 1]
                self._len[idx] += n_new
                self._recycle_window_pages(idx)

    def _next_positions(self) -> Dict[int, int]:
        """Occupied slot -> the next position it writes."""
        return {i: (int(self._len[i]) if s.started else s.prefilled)
                for i, s in enumerate(self._slots) if s is not None}

    def check_invariants(self) -> None:
        """Page-leak / refcount / scheduler-consistency audit.  The pool's
        internal bookkeeping must balance, the refcount total must equal
        the page references live slots actually hold (so anything waiting
        — including preempted requests — provably holds ZERO pages), no
        rid may be waiting and resident at once, and slot occupancy must
        agree with the scheduler's free-slot list.  The serving tests'
        conftest fixture calls this after every step and cancel."""
        self.pool.check()
        if self.ring is not None:
            # the window group: no slot over its ring, no live page outside
            # it, none given away while a later query still sees it
            self.ring.check(self._next_positions())
        if self.slab is not None:
            # the state group: each occupied slot's state has folded in
            # exactly the positions before its next one, since its reset
            self.slab.check(self._next_positions())
        refs = sum(len(s.pages) for s in self._slots if s is not None)
        held = sum(self.pool.refcount)
        if held != refs:
            raise AssertionError(
                f"refcount sum {held} != {refs} page references held by "
                "live slots — a page reference leaked or double-freed")
        waiting_rids = [r.rid for r in self.scheduler.waiting]
        if len(waiting_rids) != len(set(waiting_rids)):
            raise AssertionError("duplicate rid in the waiting queue")
        slot_rids = {s.request.rid for s in self._slots if s is not None}
        both = set(waiting_rids) & slot_rids
        if both:
            raise AssertionError(
                f"rid(s) {sorted(both)} simultaneously waiting and "
                "resident in a slot")
        # handoff inbox (r15): ingested-but-unadmitted records hold NO
        # pool pages here (their payload is host memory until admission),
        # and their rids must collide with neither queue nor slots
        inbox_rids = [rec["request"].rid for rec in self._handoff_in]
        if len(inbox_rids) != len(set(inbox_rids)):
            raise AssertionError("duplicate rid in the handoff inbox")
        clash = set(inbox_rids) & (set(waiting_rids) | slot_rids)
        if clash:
            raise AssertionError(
                f"rid(s) {sorted(clash)} in the handoff inbox AND "
                "waiting/resident")
        free = set(self.scheduler._free_slots)
        for i, s in enumerate(self._slots):
            if (i in free) == (s is not None):
                raise AssertionError(
                    f"slot {i} occupancy disagrees with the scheduler's "
                    "free-slot list")
        # windowed page arithmetic (KV-capacity PR): recycling must keep
        # every started slot's live footprint a bounded ring — high-water
        # never below the live count, and the live count within one
        # step's growth of ceil(window/page_size)+1 pages.  Without a
        # window the high-water mark and the live list must agree exactly.
        cmax = max(self.decode_block, self.spec_k + 1)
        for i, s in enumerate(self._slots):
            if s is None:
                continue
            if s.hw_pages < len(s.pages):
                raise AssertionError(
                    f"slot {i} high-water {s.hw_pages} below live page "
                    f"count {len(s.pages)}")
            if self.window is None:
                if s.hw_pages != len(s.pages):
                    raise AssertionError(
                        f"slot {i} recycled pages without a window "
                        f"(hw {s.hw_pages}, live {len(s.pages)})")
            elif s.started:
                length = int(self._len[i])
                cap = self.pool.pages_for(
                    min(length + cmax, self.window + cmax)) + 1
                if len(s.pages) > cap:
                    raise AssertionError(
                        f"slot {i} holds {len(s.pages)} pages at len "
                        f"{length} under window {self.window}; ring cap "
                        f"is {cap}")
        # speculative draft buffers (r13): a slot's draft must stay
        # within the engine's spec window and the request's remaining
        # budget, and only DECODING slots may hold one — whatever step
        # fault landed between drafting and verify
        for i, s in enumerate(self._slots):
            if s is None or not s.draft:
                continue
            if len(s.draft) > self.spec_k:
                raise AssertionError(
                    f"slot {i} holds {len(s.draft)} draft tokens; "
                    f"spec_k is {self.spec_k}")
            if not s.started:
                raise AssertionError(
                    f"slot {i} holds draft tokens but is still prefilling")
            if len(s.draft) >= s.request.remaining_new:
                raise AssertionError(
                    f"slot {i} draft of {len(s.draft)} could overshoot "
                    f"the remaining budget {s.request.remaining_new}")
        # policy-side accounting (r12): per-tenant residency counts must
        # match the slots, virtual counters must stay finite/non-negative
        self.scheduler.policy.check(
            [s.request for s in self._slots if s is not None])

    def run(self, requests: Optional[Sequence] = None,
            metrics_dir: Optional[str] = None, flush_every: int = 1
            ) -> Dict[int, FinishedRequest]:
        """Drive the host loop to completion over queued (+ given)
        requests; returns {rid: FinishedRequest} — degraded terminals
        (rejected/expired/cancelled) included.

        ``metrics_dir`` turns the drain into an observed run: every
        ``flush_every`` steps the registry's scalars flush to a
        TensorBoard event file under the dir (auto-attaching metrics —
        and a tracer when none is set — if needed), and at drain the dir
        additionally holds ``metrics.prom`` (Prometheus text exposition)
        and ``trace.json`` (Chrome trace events, open in Perfetto)."""
        from .metrics import MetricsFileExporter

        for r in requests or ():
            if isinstance(r, Request):
                self._enqueue(r)
            else:
                prompt, max_new = r
                self.add_request(prompt, max_new)
        exporter = None
        if metrics_dir is not None:
            if self.metrics is None:
                self.attach_metrics()
            if self.tracer is None:
                self.attach_tracer()
            if self.flight is None:
                self.attach_flight()
            os.makedirs(metrics_dir, exist_ok=True)
            # arm the crash dump: a real exception escaping step()
            # writes flight_crash.json here before re-raising
            self._crash_dump_dir = metrics_dir
            exporter = MetricsFileExporter(self.metrics, metrics_dir)
        done: Dict[int, FinishedRequest] = {}
        try:
            while self.has_work:
                for fin in self.step():
                    done[fin.rid] = fin
                if exporter is not None and \
                        self._step_idx % flush_every == 0:
                    exporter.flush(self._step_idx)
        finally:
            if exporter is not None:
                if exporter.last_step != self._step_idx:
                    # flush_every > 1: the tail steps (or a whole run
                    # shorter than the interval) still reach the file
                    exporter.flush(self._step_idx)
                exporter.close()
                if self.tracer is not None:
                    self.tracer.save(
                        os.path.join(metrics_dir, "trace.json"))
                if self.flight is not None:
                    self.flight.dump(
                        os.path.join(metrics_dir, "flight.json"))
        # teardown: with every request terminal the pool must be back at
        # the cached-prefix-only baseline — any page still referenced by
        # a live slot (there are none) is a leak
        if self.scheduler.n_active or self.pool.pages_in_use:
            raise AssertionError(
                f"page leak after drain: {self.scheduler.n_active} slots "
                f"active, {self.pool.pages_in_use} pages still referenced")
        return done
