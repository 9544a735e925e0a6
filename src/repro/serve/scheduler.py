"""Request scheduler: continuous batching with decode-interleaved chunked
prefill over the engine's slot arena.

Default mode ("continuous"): the batch axis is a SLOT ARENA.  Each loop
iteration first spends at most ``ServeConfig.prefill_token_budget`` tokens
advancing the head-of-queue request's CHUNKED prefill (one fixed-width
compiled chunk HLO per ``engine.prefill_chunk_step``; a request whose
prompt outruns the budget simply resumes next iteration), admitting it into
a free slot the moment its prompt completes (one compiled splice,
``engine.admit``, traced slot index) — then runs ONE ragged decode step for
the whole arena.  Resident sequences therefore never stall behind an
arriving prompt for more than the configured budget (rounded down to whole
chunks, minimum one chunk): long-prompt admission work and decoding
interleave instead of head-of-line blocking.  A request submitted
mid-generation joins the running batch as soon as its chunks are paid for,
a finished request's slot is recycled immediately, and the jitted decode /
chunk / splice HLOs are each compiled once and reused across all
admissions — no recompiles, no cache compaction, no drain barrier.

PAGED mode (ISSUE 5, ``ServeConfig.page_size > 0``): the SALS segments'
backing store is a refcounted page pool (``core/pager.py``) instead of the
dense slot arena, and this scheduler is its MEMORY MANAGER:

  * admission is a PAGE RESERVATION — a request is admitted when the pool
    has pages for its prompt (suffix), not when a slot index frees up; on
    shortfall it stalls at the head of the queue (``admission_stalls``)
    until residents release pages, after LRU prefix-cache entries have
    been evicted;
  * prompts sharing a registered prefix map their leading page-table
    entries to the SAME physical pages (refcount bump, ``prefix_hits``)
    and resume their chunked prefill at the page boundary — N concurrent
    same-system-prompt requests cost one prefill and one stored copy of
    the prefix;
  * decode growth allocates one page per ``page_size`` generated tokens;
    a write landing on a still-shared page triggers copy-on-write
    (``cow_copies``) — structurally the cache is append-only and sharing
    is whole-page, so this is a guarded safety net, not a hot path;
  * pool exhaustion mid-decode evicts the resident that could not map its
    write page back onto the queue (``evictions``; greedy decoding makes
    the re-run deterministic).  SELF-eviction is the anti-livelock policy:
    survivors keep every page they own, so at least one resident always
    runs to completion between evictions — no steal-back ping-pong;
  * every decode step appends a gauge row to ``pool_gauges``
    (pages_in_use / pages_free / cumulative counters) — the capacity
    ledger tests and benchmarks read.

TWO-TIER mode (ISSUE 7, ``ServeConfig.hbm_pages > 0``): the pool is a
:class:`~repro.core.tiering.TieredPagePool` — live-page capacity stays
``pool_pages`` (host RAM) while the device payload pools hold only
``hbm_pages`` hot slots.  The scheduler adds, around the same decode loop:

  * WRITE PINS — each resident's current write page is pinned hot
    (``ensure_write_pin``; the per-token append lands in it through the
    hot table every step);
  * PREFETCH — before each step, every row's PREVIOUS selection is warmed
    host→HBM (``tier_prefetch``; the paper's step-to-step selection
    stability is the hit-rate model, measured by benchmarks/overlap_score);
  * FETCH-AND-RERUN — the decode step collects the selected-page mask; if
    any selected page was cold (its reconstruction read the trash slot),
    the scheduler fetches it hot and RERUNS the identical step — all cache
    writes are idempotent per-position ``.set``s, so the final all-hot run
    is bit-identical to an all-HBM step;
  * gauges ``host_pages`` / ``fetch_hits`` / ``prefetch_hits`` /
    ``cold_misses`` / ``spills`` ride on ``pool_gauges``, and
    ``audit_pager`` extends to tier conservation (hot ⊎ cold ⊎ fresh ⊎
    in-flight == live, slot conservation, pins hot-only);
  * THRASH SHEDDING — when one step's working set (touched pages + write
    pins) exceeds the hot tier (:class:`HotTierThrash`), the scheduler
    sheds LOAD, not the request: the demanding row self-evicts to the
    queue head (``shed_thrash``), dropping the multiprogramming degree so
    the survivors' working set fits — the classic thrash response.  Only
    a SOLE resident that thrashes (its own selection cannot fit hot) fails
    through the per-request retry budget.

FAULT TOLERANCE (ISSUE 6).  Every request carries the terminal state
machine of ``serve/lifecycle.py`` (QUEUED → PREFILLING → DECODING →
{DONE, FAILED, CANCELLED, TIMED_OUT}); all mutations go through
``lifecycle.transition``.  The guarantees:

  * ISOLATION — a fault in any per-request phase (prefill chunk, admission
    splice, page alloc / COW during upkeep, NaN/inf logits or out-of-vocab
    sample on one row) fails THAT request only; its pages, prefix pins and
    slot are released through one idempotent teardown and the remaining
    residents keep decoding.  The engine's injection points fire BEFORE
    each donating jitted call, so an injected fault never strands donated
    buffers — a real fault after donation is unrecoverable by design and
    propagates.
  * BOUNDED RETRY — faults marked ``transient`` requeue the request with
    exponential backoff in scheduler steps (``retry_backoff_steps · 2^i``
    capped at ``retry_backoff_cap_steps``) up to ``max_request_retries``;
    greedy decoding makes every re-run token-exact.  Batch-wide
    ``decode_step`` faults retry the step itself under the same bound.
  * DEADLINES & CANCELLATION — ``request_timeout_steps`` (per-request
    override on ``Request.timeout_steps``) and ``Request.cancel()`` both
    route through the same teardown at the next step boundary, whatever
    phase the request is in.
  * BACKPRESSURE — ``max_queue`` bounds the pending queue; ``submit``
    raises ``QueueFull`` ("reject") or cancels the oldest pending request
    ("shed-oldest").
  * AUDIT — ``audit_serving_state()`` proves page conservation across
    pool / page tables / prefix pins / gauges (``core.pager.audit_pager``)
    plus slot↔state coherence; it runs every ``audit_every`` steps and on
    every teardown when auditing is enabled.

SLO SCHEDULING (ISSUE 8).  The front door is no longer plain FIFO:

  * PRIORITY CLASSES — ``Request.priority`` ∈ [0, priority_classes);
    admission always serves the highest eligible class first, and with
    ``preempt_policy != "none"`` a strictly higher waiting class preempts
    the lowest-priority (most recently admitted) resident when no slot is
    free.  Under ``"park"`` the victim keeps its PAGES: the slot's window
    state snapshots to host (``engine.detach_slot``), the page table moves
    into a parked record (refcounts held — SALS's compressed latents make
    this cheap, the LoRC argument), and resume splices the snapshot back
    into any free slot and continues DECODING token-exact with no
    re-prefill.  Under tiering, parking drops the write pin and spills
    exclusively-parked pages cold, so the preemption actually frees hot
    slots.  ``"evict"`` is the destructive PR 5 baseline.  A page-stalled
    admission may reclaim a strictly-lower-priority parked victim's pages
    (destructive requeue) — parked sunk work never starves a higher class.
  * TENANT FAIRNESS — ``Request.tenant_id`` keys deficit-round-robin
    admission WITHIN a priority class (``tenant_quantum`` tokens per
    rotation turn; a request costs prompt + budget tokens), plus optional
    per-tenant token-rate credits (``tenant_rate``/step, debited at
    admission) and in-flight caps (``tenant_max_inflight``).
    ``tenant_gauges`` exports per-tenant starvation counters.
  * STREAMING — ``Request.on_token`` delivers each token the step it
    commits; mid-stream ``cancel()`` tears down at the next boundary and
    non-DONE teardowns flush the partial stream into a
    ``complete=False`` result.

SPECULATIVE DECODING (ISSUE 9, ``ServeConfig.spec_window > 1``): the
per-arena decode step becomes a VERIFY WINDOW — each resident's pending
token plus ``spec_window−1`` prompt-lookup drafts (``serve/draft.py``,
per-slot state on ``_Slot.drafter``) run through ONE compiled windowed
HLO (``engine._decode_window``: one latent selection amortized over the
window, one reconstruction pass attending every window query), greedy
verify accepts each row's longest matching draft prefix, and the masked
``engine._commit_window`` writes ONLY accepted positions — cache bytes
and the emitted token stream are bit-identical to sequential greedy
decode whatever the drafts were.  ``on_token`` fires once per ACCEPTED
token in commit order with contiguous indices; rejected draft positions
never reach the client.  Requires greedy decoding, an attention family
and the untiered cache (``config.base`` validates); paged rows map every
page the window span can touch before the step.  The ``draft_verify``
fault point fires before the windowed jit call, so an injected fault
retries the whole round like a ``decode_step`` fault.  Counters:
``spec_rounds`` / ``spec_proposed`` / ``spec_accepted`` /
``spec_committed``.

WALL-CLOCK DEADLINES (ISSUE 9): ``ServeConfig.request_timeout_ms`` (per-
request override ``Request.timeout_ms``) arms a monotonic-clock deadline
at submit, swept by the same teardown path as ``request_timeout_steps``
— either deadline may fire first.  The clock source is injectable
(``RequestScheduler(clock=...)``) for deterministic tests.

"static" mode survives as the GPT-fast-style baseline (and the fallback for
recurrent-state families, whose prefill can neither right-pad nor chunk):
fixed-size batches, length-bucketed FIFO (priority/tenant knobs are
continuous-mode only), monolithic prefill → decode-until-drained per batch.

Results are delivered on the ``Request`` objects in both modes; ``run``
returns every request that reached a terminal state during the call, in
completion order — check ``Request.state`` / ``Request.error`` to tell
DONE apart from FAILED / CANCELLED / TIMED_OUT.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import itertools
import time
import warnings
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.pager import (PagePool, PageTable, PagerInvariantError,
                              PrefixIndex, audit_pager)
from repro.core.tiering import HotTierThrash, TieredPagePool
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.obs.metrics import MetricsRegistry
from repro.serve import faults
from repro.serve.draft import NgramDrafter
from repro.serve.engine import GenerationResult, PrefillTask, ServeEngine
from repro.serve.lifecycle import (NanLogitsError, QueueFull,
                                   RequestCancelled, RequestState,
                                   RequestTimeout, transition)

_req_ids = itertools.count()
_NO_SPAN = contextlib.nullcontext()     # tracing off: one shared no-op


@dataclasses.dataclass
class Request:
    prompt: np.ndarray
    max_new_tokens: int = 32
    req_id: int = dataclasses.field(default_factory=lambda: next(_req_ids))
    result: Optional[GenerationResult] = None
    # --- lifecycle (ISSUE 6) ------------------------------------------------
    state: RequestState = RequestState.QUEUED
    error: Optional[BaseException] = None
    timeout_steps: Optional[int] = None   # None = ServeConfig default
    timeout_ms: Optional[float] = None    # None = ServeConfig default
    retries: int = 0                      # transient-fault retries consumed
    deadline_step: Optional[int] = None   # set at submit
    deadline_time: Optional[float] = None  # wall-clock deadline (ISSUE 9)
    not_before_step: int = 0              # retry backoff gate
    cancel_requested: bool = False
    # --- SLO scheduling (ISSUE 8) ------------------------------------------
    priority: int = 0                     # class index; higher = more urgent
    tenant_id: str = "default"            # fairness / rate-limit key
    # Streaming: called as on_token(token_id, index) the step each token
    # commits (index 0 = the first token, emitted at admission).  Delivery
    # is at-least-once across destructive restarts (evict-to-requeue and
    # retry re-runs re-emit from index 0); a park/resume never re-emits.
    # A raising callback fails THIS request (non-transient).
    on_token: Optional[Callable[[int, int], None]] = None
    submit_step: Optional[int] = None     # set at submit (wait gauges)
    attempts: int = 0                     # times prefill started
    parks: int = 0                        # times preempt-parked

    def cancel(self) -> None:
        """Client cancellation: honored at the next scheduler step
        boundary via the same teardown path as faults and timeouts."""
        self.cancel_requested = True

    @property
    def done(self) -> bool:
        """Completed successfully (full budget generated)."""
        return self.state is RequestState.DONE

    @property
    def finished(self) -> bool:
        """Reached ANY terminal state (done / failed / cancelled / timed
        out) — the request owns no serving resources anymore."""
        return self.state.terminal


@dataclasses.dataclass
class _Slot:
    """One resident sequence of the continuous batch."""
    req: Request
    out: List[int]                 # generated token ids so far
    seq: int = 0                   # admission order (preemption tie-break)
    # speculative decoding (ISSUE 9): per-request prompt-lookup draft
    # state.  Rebuilt from prompt + out on every (re)admission and resume,
    # so evictions, retries and park/resume need no extra bookkeeping.
    drafter: Optional[NgramDrafter] = None


@dataclasses.dataclass
class _Admission:
    """Head-of-queue request being chunk-prefilled into a reserved slot.

    Paged mode: ``ptab`` holds the request's reserved page table (shared
    prefix pages + fresh suffix pages — the reservation IS the admission
    criterion) and ``shared_pages`` how many leading pages came from a
    prefix-cache entry (``entry``)."""
    req: Request
    slot: int
    task: PrefillTask
    ptab: Optional[PageTable] = None
    shared_pages: int = 0
    entry: object = None


@dataclasses.dataclass
class _Parked:
    """A preempt-parked resident (ISSUE 8): everything needed to resume
    DECODING token-exact in any free slot.  The record OWNS the request's
    page table — refcounts stay held across the park, which is the whole
    point: resume is a window splice, not a re-prefill."""
    req: Request
    out: List[int]                 # tokens committed before the park
    position: int                  # next decode position
    ptab: PageTable                # held pages (ownership moved from slot)
    snapshot: dict                 # engine.detach_slot host snapshot
    parked_step: int               # FIFO resume order within a class


class _CounterView:
    """ISSUE 10 migration shim: a legacy public int counter
    (``sched.prefix_hits`` et al.) that is now a THIN VIEW over the
    scheduler's :class:`~repro.obs.metrics.MetricsRegistry`.  Existing
    ``+= 1`` sites, tests and benchmarks keep working unchanged; the
    registry is the single store, so exporters can never disagree with
    the public fields."""

    __slots__ = ("metric",)

    def __init__(self, metric: str):
        self.metric = metric

    def __get__(self, obj, objtype=None):
        if obj is None:
            return self
        return int(obj.metrics.counter(self.metric).value())

    def __set__(self, obj, value):
        obj.metrics.counter(self.metric).set_to(float(value))


class RequestScheduler:
    """``mode``: "continuous" (default, from ``engine.scfg.scheduler``) or
    "static".  Recurrent-state families always run static (see engine).

    Observability hooks the scheduler tests assert against:
      ``admissions``     — (decode_step_index, slot, req_id) per admission
                           (join latency, slot recycling, FIFO);
      ``prefill_chunks`` — (decode_step_index, req_id, chunk_index,
                           n_resident) per chunk HLO executed (the
                           interleaving ledger: the number of entries
                           sharing a step index with n_resident > 0 bounds
                           how long residents waited between decode steps).
    """

    # Public counters, migrated onto the metrics registry (ISSUE 10).
    # Reads and ``+= 1`` writes behave exactly as the old plain ints did.
    prefix_hits = _CounterView("serve_prefix_hits_total")
    cow_copies = _CounterView("serve_cow_copies_total")
    admission_stalls = _CounterView("serve_admission_stalls_total")
    evictions = _CounterView("serve_evictions_total")
    failures = _CounterView("serve_requests_failed_total")
    timeouts = _CounterView("serve_requests_timed_out_total")
    cancellations = _CounterView("serve_requests_cancelled_total")
    retries = _CounterView("serve_retries_total")
    step_faults = _CounterView("serve_step_faults_total")
    shed = _CounterView("serve_shed_total")
    fetch_hits = _CounterView("serve_fetch_hits_total")
    prefetch_hits = _CounterView("serve_prefetch_hits_total")
    cold_misses = _CounterView("serve_cold_misses_total")
    spec_rounds = _CounterView("serve_spec_rounds_total")
    spec_proposed = _CounterView("serve_spec_proposed_total")
    spec_accepted = _CounterView("serve_spec_accepted_total")
    spec_committed = _CounterView("serve_spec_committed_total")
    parks = _CounterView("serve_parks_total")
    resumes = _CounterView("serve_resumes_total")
    preemptions = _CounterView("serve_preemptions_total")
    submitted = _CounterView("serve_requests_submitted_total")
    done = _CounterView("serve_requests_done_total")

    def __init__(self, engine: ServeEngine, max_batch: Optional[int] = None,
                 mode: Optional[str] = None,
                 clock: Optional[Callable[[], float]] = None):
        self.engine = engine
        # wall-clock source for request_timeout_ms deadlines (ISSUE 9);
        # injectable so deadline tests are deterministic, monotonic so a
        # system clock step never expires (or revives) a request
        self._clock: Callable[[], float] = clock or time.monotonic
        self.max_batch = max_batch or engine.scfg.max_batch
        mode = mode or engine.scfg.scheduler
        if mode not in ("continuous", "static"):
            raise ValueError(f"unknown scheduler mode {mode!r}")
        if not engine.ragged_ok:
            mode = "static"        # recurrent state can't right-pad or chunk
        if engine.paged and mode != "continuous":
            raise ValueError("the paged latent cache requires the "
                             "continuous scheduler (admission = page "
                             "reservation)")
        self.mode = mode
        # deque, not list (ISSUE 8): admission pops the head and
        # evict-to-requeue pushes it — both O(1); list.pop(0) is O(n)
        # under deep queues
        self.pending: collections.deque = collections.deque()
        self.completed: Dict[int, Request] = {}
        # Observability ledgers become RING BUFFERS when
        # ServeConfig.gauge_history > 0 (ISSUE 8 bugfix: they otherwise
        # grow one row per step/chunk forever in long-running serving);
        # 0 = unbounded full history for the tests that read whole ledgers.
        hist = engine.scfg.gauge_history or None
        self.admissions: collections.deque = \
            collections.deque(maxlen=hist)      # (step, slot, req_id)
        # (step, req_id, chunk_idx, n_resident) — see class docstring
        self.prefill_chunks: collections.deque = collections.deque(
            maxlen=hist)
        self.steps: int = 0                     # decode steps executed
        # --- paged-pool observability (ISSUE 5 satellite) ------------------
        # one gauge row per decode step: the capacity ledger for tests +
        # benchmarks (pages_in_use ≈ prefix + Σ unique suffixes under
        # prefix sharing, high-water = peak live tokens, ...)
        self.pool_gauges: collections.deque = collections.deque(maxlen=hist)
        # --- unified telemetry (ISSUE 10) ----------------------------------
        # The registry is the single store behind every public counter
        # above the class (``_CounterView``): an externally installed
        # registry (``obs.metrics.install``) is adopted so exporters see
        # this scheduler; otherwise a private one backs the views at the
        # same cost.  Label-set growth shares the gauge_history cap.
        self.metrics: MetricsRegistry = (
            obs_metrics.active()
            or MetricsRegistry(max_series=hist or 0))
        # per-step gauge publishing only runs for an INSTALLED registry
        # (someone is scraping); the private fallback registry exists just
        # to back the counter views, so disabled mode stays one-check cheap
        self._metrics_installed = obs_metrics.active() is not None
        self.tracer = obs_trace.active()        # None = spans disabled
        for view in (
                # paged-pool observability (ISSUE 5 satellite)
                "prefix_hits",       # admissions reusing pages
                "cow_copies",        # copy-on-write page dups
                "admission_stalls",  # sweeps blocked on pages
                "evictions",         # evict-to-requeue events
                # fault-tolerance observability (ISSUE 6)
                "failures",          # requests ending FAILED
                "timeouts",          # requests ending TIMED_OUT
                "cancellations",     # requests ending CANCELLED
                "retries",           # transient requeues granted
                "step_faults",       # batch-wide decode retries
                "shed",              # queue-policy sheds
                # two-tier pool observability (ISSUE 7)
                "fetch_hits",        # touched pages already hot
                "prefetch_hits",     # ... warmed by the prefetcher
                "cold_misses",       # demand host→HBM fetches
                # speculative decoding observability (ISSUE 9)
                "spec_rounds",       # verify windows executed
                "spec_proposed",     # draft tokens proposed
                "spec_accepted",     # draft tokens accepted
                "spec_committed",    # tokens committed via windows
                # SLO scheduling (ISSUE 8)
                "parks",             # preempt-park events
                "resumes",           # successful park resumes
                "preemptions",       # park + evict preemptions
                # request conservation (ISSUE 10): submitted must equal
                # done+failures+timeouts+cancellations at drain
                "submitted", "done"):
            setattr(self, view, 0)
        self.parked: List[_Parked] = []         # live parked records
        # per-tenant starvation/fairness gauges (see _tenant_gauge);
        # insertion-ordered so the gauge_history LRU cap can evict the
        # least-recently-touched tenant (ISSUE 10 bugfix)
        self.tenant_gauges: "collections.OrderedDict[str, dict]" = \
            collections.OrderedDict()
        self._drr_rot: Dict[int, List[str]] = {}      # DRR rotation / class
        self._drr_deficit: Dict[int, Dict[str, float]] = {}
        self._rate_credit: Dict[str, float] = {}      # tenant token credit
        self.paged = engine.paged and mode == "continuous"
        self.tiered = engine.tiered and mode == "continuous"
        self.pool: Optional[PagePool] = None
        self.prefix_index: Optional[PrefixIndex] = None
        if self.paged:
            scfg = engine.scfg
            # +1 / n_reserved=1: physical page 0 is the trash page
            if self.tiered:
                self.pool = TieredPagePool(scfg.pool_pages + 1,
                                           scfg.page_size,
                                           hbm_slots=scfg.hbm_pages,
                                           n_reserved=1)
            else:
                self.pool = PagePool(scfg.pool_pages + 1, scfg.page_size,
                                     n_reserved=1)
            if scfg.prefix_cache:
                self.prefix_index = PrefixIndex(self.pool)
        # live loop state, mirrored on self so audit_serving_state can see
        # it mid-run (tests also call it after run: drained == empty)
        self._slots: List[Optional[_Slot]] = []
        self._tables: List[Optional[PageTable]] = []
        self._active: Optional[_Admission] = None

    def submit(self, req: Request) -> int:
        if req.max_new_tokens < 1:
            raise ValueError(f"req {req.req_id}: max_new_tokens must be >= 1 "
                             "(prefill always emits the first token)")
        if len(req.prompt) + req.max_new_tokens > self.engine.scfg.max_seq_len:
            # reject HERE, not mid-run: an oversized request must not abort
            # a running batch and strand its residents
            raise ValueError(
                f"req {req.req_id}: prompt {len(req.prompt)} + new "
                f"{req.max_new_tokens} exceeds max_seq "
                f"{self.engine.scfg.max_seq_len}")
        if self.paged:
            ps = self.engine.scfg.page_size
            need = -(-(len(req.prompt) + req.max_new_tokens) // ps)
            if need > self.engine.scfg.pool_pages:
                raise ValueError(
                    f"req {req.req_id}: needs {need} pages at its longest; "
                    f"the pool has {self.engine.scfg.pool_pages}")
        scfg = self.engine.scfg
        if not 0 <= req.priority < scfg.priority_classes:
            raise ValueError(
                f"req {req.req_id}: priority {req.priority} outside "
                f"[0, {scfg.priority_classes})")
        if scfg.max_queue and len(self.pending) >= scfg.max_queue:
            if scfg.queue_policy == "reject":
                raise QueueFull(
                    f"pending queue at max_queue={scfg.max_queue}")
            # shed-oldest: a pending request makes room — its submitter
            # sees state CANCELLED with a QueueFull error.  Victim choice
            # is _shed_victim_index, NOT blindly pending[0] (ISSUE 8
            # bugfix): already-doomed and never-started requests go first.
            idx = self._shed_victim_index()
            victim = self.pending[idx]
            del self.pending[idx]
            self._terminate(victim, RequestState.CANCELLED,
                            QueueFull("shed for newer request"))
            self.shed += 1
        timeout = (req.timeout_steps if req.timeout_steps is not None
                   else scfg.request_timeout_steps)
        if timeout:
            req.deadline_step = self.steps + timeout
        # wall-clock deadline (ISSUE 9): EITHER deadline may fire — both
        # sweep through the same TIMED_OUT teardown path
        timeout_ms = (req.timeout_ms if req.timeout_ms is not None
                      else scfg.request_timeout_ms)
        if timeout_ms:
            req.deadline_time = self._clock() + timeout_ms / 1000.0
        req.submit_step = self.steps
        self._tenant_gauge(req.tenant_id)["submitted"] += 1
        self.submitted += 1
        if self.tracer is not None:
            self.tracer.begin("queue_wait", f"req{req.req_id}",
                              tenant=req.tenant_id, priority=req.priority)
        self.pending.append(req)
        return req.req_id

    def _shed_victim_index(self) -> int:
        """shed-oldest victim policy (ISSUE 8 bugfix).  Preference order:
        1. the oldest CANCEL-REQUESTED pending request — it is already
           doomed to be swept CANCELLED, so shedding it costs nothing;
        2. the oldest NEVER-STARTED request (no prefill attempt, no retry
           budget consumed) — shedding it discards no work;
        3. the oldest outright.
        The old policy popped pending[0] blindly, which could discard a
        backoff-parked retried request's consumed retry work while a
        cancel-requested request behind it survived to be swept anyway."""
        for idx, r in enumerate(self.pending):
            if r.cancel_requested:
                return idx
        for idx, r in enumerate(self.pending):
            if r.attempts == 0 and r.retries == 0:
                return idx
        return 0

    # ----------------------------------------------------------- lifecycle

    def _trace_phase(self, req: Request, name: str, **args) -> None:
        """Move ``req`` to lifecycle phase ``name`` on its trace track
        (ISSUE 10).  Each request has AT MOST ONE open span — its current
        phase — so closing the previous phase first keeps begin/end
        balanced through every teardown, retry, eviction and park path.
        No-op when tracing is disabled."""
        tr = self.tracer
        if tr is not None:
            track = f"req{req.req_id}"
            tr.end_track(track)
            tr.begin(name, track, **args)

    def _terminate(self, req: Request, state: RequestState,
                   error: Optional[BaseException] = None,
                   issued: Optional[List[Request]] = None,
                   partial: Optional[tuple] = None) -> None:
        """Move ``req`` to a terminal state and record it.  The caller has
        already released every resource the request held.

        ``partial`` (ISSUE 8 streaming): ``(tokens_so_far, prompt_len)``
        from a request dying mid-decode — flushed into a
        ``complete=False`` result on any non-DONE terminal of a STREAMING
        request (``on_token`` set), so the client keeps what it was
        already delivered.  Non-streaming requests keep the pre-existing
        contract: a non-DONE terminal leaves ``result`` None.  Retries and
        evictions never flush (the request is not terminal; its re-run
        re-emits)."""
        transition(req, state, error)
        if state is RequestState.FAILED:
            self.failures += 1
        elif state is RequestState.TIMED_OUT:
            self.timeouts += 1
        elif state is RequestState.CANCELLED:
            self.cancellations += 1
        elif state is RequestState.DONE:
            self.done += 1
        if self.tracer is not None:
            # close whatever lifecycle phase was open (queue_wait /
            # prefill / decode / parked — teardown can arrive from ANY of
            # them) so spans balance on every terminal path, then record
            # the teardown itself
            track = f"req{req.req_id}"
            self.tracer.end_track(track)
            self.tracer.end(self.tracer.begin("teardown", track,
                                              state=state.name))
        if partial is not None and state is not RequestState.DONE \
                and req.on_token is not None \
                and req.result is None and partial[0]:
            toks, plen = partial
            req.result = GenerationResult(np.asarray(toks, np.int32), plen,
                                          len(toks), complete=False)
        self.completed[req.req_id] = req
        if issued is not None:
            issued.append(req)

    def _backoff(self, retries: int) -> int:
        scfg = self.engine.scfg
        return min(scfg.retry_backoff_steps * (2 ** max(0, retries - 1)),
                   scfg.retry_backoff_cap_steps)

    def _fail_or_retry(self, req: Request, exc: BaseException,
                       issued: List[Request],
                       partial: Optional[tuple] = None) -> None:
        """Supervisor policy for one faulted request (resources already
        released): transient faults requeue with exponential backoff in
        scheduler steps; anything else — or an exhausted retry budget —
        terminates the request as FAILED with the fault attached.

        Deadline interaction (ISSUE 8 bugfix): a retry whose backoff gate
        lands at or past the request's deadline could never run again — it
        would sit in pending only to be swept TIMED_OUT later with zero
        re-runs (and no retry budget consumed against a fault that already
        happened).  Policy: FAIL FAST — terminate TIMED_OUT at requeue
        time with the triggering fault chained as ``__cause__``.  The
        deadline is an SLO promise to the client; silently extending it by
        the backoff would lie about it."""
        scfg = self.engine.scfg
        if getattr(exc, "transient", False) \
                and req.retries < scfg.max_request_retries:
            gate = self.steps + self._backoff(req.retries + 1)
            if req.deadline_step is not None and gate >= req.deadline_step:
                err = RequestTimeout(
                    f"req {req.req_id}: retry backoff gate (step {gate}) "
                    f"cannot beat deadline step {req.deadline_step}")
                err.__cause__ = exc
                self._terminate(req, RequestState.TIMED_OUT, err, issued,
                                partial=partial)
                return
            req.retries += 1
            req.not_before_step = gate
            transition(req, RequestState.QUEUED)
            self.retries += 1
            self._trace_phase(req, "queue_wait", retry=req.retries)
            self.pending.append(req)
        else:
            self._terminate(req, RequestState.FAILED, exc, issued,
                            partial=partial)

    # ------------------------------------------- tenant fairness (ISSUE 8)

    @staticmethod
    def _cost(req: Request) -> int:
        """A request's admission cost in tokens: prompt + decode budget —
        what it will pin in pages/slot-time, known at submit."""
        return len(req.prompt) + req.max_new_tokens

    def _tenant_gauge(self, tenant: str) -> dict:
        """Per-tenant starvation/fairness counters (created on first
        touch): submissions, admissions (+tokens), deferrals by cause,
        and the worst admission wait seen, in steps.

        LRU-capped by ``gauge_history`` (ISSUE 10 bugfix; 0 = unbounded,
        the same ring policy as ``pool_gauges``): the old ``setdefault``
        dict grew one entry per unique tenant id FOREVER — a long-running
        front door with per-user tenant ids leaks without bound.  Every
        touch refreshes recency; past the cap the least-recently-touched
        tenant's gauges are dropped (it restarts from zero if it ever
        returns — starvation gauges are ring history, not billing)."""
        g = self.tenant_gauges.get(tenant)
        if g is None:
            g = {"submitted": 0, "admitted": 0, "admitted_tokens": 0,
                 "rate_deferrals": 0, "cap_deferrals": 0,
                 "max_wait_steps": 0}
            self.tenant_gauges[tenant] = g
        else:
            self.tenant_gauges.move_to_end(tenant)
        cap = self.engine.scfg.gauge_history
        while cap and len(self.tenant_gauges) > cap:
            self.tenant_gauges.popitem(last=False)
        return g

    def _tenant_inflight(self, tenant: str) -> int:
        """Requests of ``tenant`` currently holding serving resources:
        residents + parked + the in-flight admission."""
        n = sum(1 for s in self._slots
                if s is not None and s.req.tenant_id == tenant)
        n += sum(1 for rec in self.parked
                 if rec.req.tenant_id == tenant)
        if self._active is not None \
                and self._active.req.tenant_id == tenant:
            n += 1
        return n

    def _refill_rate_credits(self) -> None:
        """Accrue per-tenant admission credit (``tenant_rate`` tokens per
        scheduler iteration) while the tenant has pending work, capped at
        32 iterations' worth so an idle-then-bursty tenant cannot bank
        unbounded credit.  Admission debits the request cost — credit may
        go negative, PACING a burst instead of rejecting it."""
        rate = self.engine.scfg.tenant_rate
        if not rate:
            return
        for t in {r.tenant_id for r in self.pending}:
            self._rate_credit[t] = min(
                self._rate_credit.get(t, 0.0) + rate, rate * 32)

    def _eligible(self, r: Request, count: bool = False) -> bool:
        """Admission gates for one pending request: retry backoff elapsed,
        tenant in-flight cap, tenant rate credit.  ``count=True`` records
        deferrals in the tenant gauges (admission-sweep probes only, so
        the counters track real deferred admission attempts)."""
        if r.not_before_step > self.steps:
            return False
        scfg = self.engine.scfg
        if scfg.tenant_max_inflight and self._tenant_inflight(r.tenant_id) \
                >= scfg.tenant_max_inflight:
            if count:
                self._tenant_gauge(r.tenant_id)["cap_deferrals"] += 1
            return False
        if scfg.tenant_rate \
                and self._rate_credit.get(r.tenant_id, 0.0) <= 0.0:
            if count:
                self._tenant_gauge(r.tenant_id)["rate_deferrals"] += 1
            return False
        return True

    def _note_admission(self, req: Request) -> None:
        """Fairness bookkeeping for a popped (about-to-admit) request:
        rate-credit debit + tenant gauges."""
        g = self._tenant_gauge(req.tenant_id)
        g["admitted"] += 1
        g["admitted_tokens"] += self._cost(req)
        if req.submit_step is not None:
            g["max_wait_steps"] = max(g["max_wait_steps"],
                                      self.steps - req.submit_step)
        if self.engine.scfg.tenant_rate:
            self._rate_credit[req.tenant_id] = \
                self._rate_credit.get(req.tenant_id, 0.0) - self._cost(req)

    def _drr_pick(self, prio: int, heads: Dict[str, int]) -> int:
        """Deficit round robin within priority class ``prio``.  ``heads``
        maps tenant -> pending index of that tenant's FIFO head.  Each
        rotation turn banks ``tenant_quantum`` tokens of deficit for the
        tenant at the rotation head; the first tenant whose head request
        costs <= its deficit is served and debited.  Tenants rotate in
        first-seen order; a tenant with no eligible work loses its bank
        (classic DRR — credit does not survive idleness).  Returns the
        chosen pending index."""
        rot = self._drr_rot.setdefault(prio, [])
        for t in heads:
            if t not in rot:
                rot.append(t)
        defc = self._drr_deficit.setdefault(prio, {})
        q = self.engine.scfg.tenant_quantum
        costs = {t: self._cost(self.pending[i]) for t, i in heads.items()}
        # enough turns that the costliest head MUST accumulate its cost
        turns = len(rot) * (max(costs.values()) // q + 2)
        for _ in range(turns):
            t = rot.pop(0)
            rot.append(t)
            if t not in heads:
                defc[t] = 0.0
                continue
            defc[t] = defc.get(t, 0.0) + q
            if costs[t] <= defc[t]:
                defc[t] -= costs[t]
                return heads[t]
        return min(heads.values())    # unreachable bound: FIFO head

    # ------------------------------------------------------------------ run

    def run(self, on_batch: Optional[Callable[[List[Request]], None]] = None,
            on_step: Optional[Callable[["RequestScheduler", int], None]] = None
            ) -> List[Request]:
        """Drain the queue; returns terminal requests in completion order.

        ``on_step`` (continuous mode) fires after every decode step — tests
        and clients use it to submit requests mid-generation; their prefill
        chunks start within the very next iteration's budget.  ``on_batch``
        (static mode) fires after each drained batch.
        """
        if self.mode == "static":
            return self._run_static(on_batch)
        try:
            return self._run_continuous(on_step)
        finally:
            if self.tracer is not None:   # the last iteration, or what
                self.tracer.end_track("scheduler")   # an exception left open

    def _span(self, name: str, **args):
        """A span on the scheduler track (mirrored into the profiler as
        ``serve.<name>``); a shared no-op context when tracing is off."""
        if self.tracer is None:
            return _NO_SPAN
        return self.tracer.span(name, "scheduler", **args)

    # ------------------------------------------------------------ continuous

    def _run_continuous(self, on_step) -> List[Request]:
        eng = self.engine
        if self.max_batch != eng.scfg.max_batch:
            raise ValueError("continuous mode uses the engine's slot arena: "
                             f"max_batch {self.max_batch} != "
                             f"engine {eng.scfg.max_batch}")
        b = self.max_batch
        chunk = eng.scfg.prefill_chunk
        ps = eng.scfg.page_size
        mp = eng.scfg.max_seq_len // ps if self.paged else 0
        chunks_per_sweep = max(1, eng.scfg.prefill_token_budget // chunk)
        audit_on = bool(eng.scfg.audit_every)
        with self._span("run_start"):      # the arena and the sampling key
            cache = eng.init_slot_cache()
            key = jax.random.PRNGKey(eng.scfg.seed)
        slots: List[Optional[_Slot]] = [None] * b
        self._slots = slots
        self._active = None        # in-flight admission; its slot reserved
        tokens = np.zeros((b,), np.int32)
        positions = np.zeros((b,), np.int32)
        issued: List[Request] = []
        admit_seq = itertools.count()   # admission order (preempt tie-break)
        prio_on = (eng.scfg.priority_classes > 1
                   and eng.scfg.preempt_policy != "none")
        # paged state: per-slot page tables + the host mirror of the device
        # table (pushed when dirty — decode writes need the page mapped)
        tables: List[Optional[PageTable]] = [None] * b
        self._tables = tables
        host_table = np.zeros((b, mp), np.int32) if self.paged else None
        dirty = [False]
        fault_streak = 0           # consecutive batch-wide decode faults
        # speculative decoding (ISSUE 9): width of the verify window the
        # decode step runs through the windowed kernels; 0/1 = sequential
        spec_q = eng.scfg.spec_window if eng.scfg.spec_window > 1 else 0
        # tiered state (ISSUE 7): the host mirror of the device hot-slot
        # table, each row's pinned-hot write page, and each row's previous
        # selection (the prefetch oracle)
        pool = self.pool
        host_hot = np.zeros((b, mp), np.int32) if self.tiered else None
        hot_dirty = [False]
        write_pin: List[Optional[int]] = [None] * b
        prev_selected: List[set] = [set() for _ in range(b)]

        def release_pages(i: int):
            nonlocal cache
            if not self.paged:
                return
            if self.tiered:
                # unpin BEFORE release_all: freeing a pinned page is an
                # invariant violation by design (catches leaked pins)
                if write_pin[i] is not None:
                    pool.unpin(write_pin[i])
                    write_pin[i] = None
                prev_selected[i] = set()
                host_hot[i] = 0
                hot_dirty[0] = True
            if tables[i] is not None:
                tables[i].release_all()
                tables[i] = None
            host_table[i] = 0
            dirty[0] = True
            cache = eng.release_slot(cache, i)   # metadata-only (lengths/pt)

        def clear_slot(i: int):
            """The idempotent slot teardown every exit path shares: frees
            the row (parked at position 0 — paged: the trash page — so its
            idle writes stay harmless), its pages, and its table entry.
            Request-state bookkeeping is the CALLER's job."""
            slots[i] = None        # recycled on the next admission sweep
            tokens[i] = 0
            positions[i] = 0
            release_pages(i)
            if audit_on:
                self.audit_serving_state()

        def teardown_admission(adm: _Admission):
            """Release an in-flight admission's reservation (pages incl.
            shared-prefix refcounts).  Idempotent: a torn reservation has
            ptab=None already, release_all on an empty table is a no-op,
            and no prefix entry exists yet (registration happens strictly
            after a successful splice), so nothing can leak a pin."""
            if adm.ptab is not None:
                adm.ptab.release_all()
                adm.ptab = None
            if audit_on:
                self.audit_serving_state()

        def finish(i: int):
            slot = slots[i]
            slot.req.result = GenerationResult(
                np.asarray(slot.out, np.int32), len(slot.req.prompt),
                len(slot.out))
            clear_slot(i)
            self._terminate(slot.req, RequestState.DONE, issued=issued)

        def fail_resident(i: int, exc: BaseException):
            """Per-request fault isolation: row ``i`` alone pays for its
            fault — teardown, then retry-or-fail; every other resident
            keeps decoding untouched.  Tokens committed before the fault
            ride along as the partial-stream flush (used only if the
            request terminates)."""
            req = slots[i].req
            out = list(slots[i].out)
            clear_slot(i)
            self._fail_or_retry(req, exc, issued,
                                partial=(out, len(req.prompt)))

        def drop_entries(n_needed: int, protect_entry=None) -> bool:
            """Evict least-recently-USED prefix-cache entries until
            >= n_needed pages are free (``protect_entry`` shields the
            entry an in-flight reservation is about to share — and a hot
            system-prompt entry naturally outlives one-shot prefixes).
            Entries are pure caches — always droppable, never
            correctness-bearing."""
            while self.pool.pages_free < n_needed and self.prefix_index:
                victim_e = self.prefix_index.lru_entry(exclude=protect_entry)
                if victim_e is None:
                    break
                self.prefix_index.evict(victim_e)
            return self.pool.pages_free >= n_needed

        def evict_to_requeue(i: int):
            """Pool exhausted and row ``i`` cannot map its next write page:
            evict THE ROW ITSELF back onto the queue head (releasing its
            pages) and let it restart later — greedy decoding makes the
            re-run produce identical tokens.  Self-eviction is what makes
            exhaustion livelock-free: the surviving residents keep every
            page they own, so at least one request always runs to
            completion between evictions (monotonic progress, no
            steal-back ping-pong)."""
            if eng.scfg.temperature > 0.0:
                # sampled decoding: the restart draws from an advanced key
                # stream, so the regenerated completion WILL differ — size
                # the pool for the workload (or run greedy) if that matters
                warnings.warn(
                    "paged pool exhausted: evicting a resident under "
                    "temperature > 0 — its re-run resamples and may "
                    "produce different tokens", RuntimeWarning,
                    stacklevel=2)
            req = slots[i].req
            clear_slot(i)
            transition(req, RequestState.QUEUED)   # eviction != a retry:
            req.not_before_step = 0                # no fault, no backoff
            self._trace_phase(req, "queue_wait", evicted=True)
            self.pending.appendleft(req)           # restarts from scratch
            self.evictions += 1

        def pop_eligible() -> Optional[Request]:
            """Pop the next request to admit: the highest eligible
            PRIORITY class first; within the class, deficit-round-robin
            across tenants (FIFO within one tenant, so plain FIFO falls
            out when every request shares a class and tenant — the
            pre-ISSUE-8 behavior).  Eligibility = retry backoff elapsed +
            tenant rate credit + tenant in-flight cap (_eligible)."""
            heads: Dict[str, int] = {}
            prio: Optional[int] = None
            for idx, r in enumerate(self.pending):
                if not self._eligible(r, count=True):
                    continue
                if prio is None or r.priority > prio:
                    prio, heads = r.priority, {}
                if r.priority == prio and r.tenant_id not in heads:
                    heads[r.tenant_id] = idx
            if prio is None:
                return None
            idx = next(iter(heads.values())) if len(heads) == 1 \
                else self._drr_pick(prio, heads)
            req = self.pending[idx]
            del self.pending[idx]
            self._note_admission(req)
            return req

        def try_reserve(req: Request) -> Optional[_Admission]:
            """Paged admission = page reservation: shared prefix pages +
            fresh suffix pages, or None (stall) if the pool can't cover
            the suffix right now.  The caller has POPPED ``req`` already —
            eviction-to-requeue inserts victims at the queue head, so the
            request being reserved must not still occupy that position.
            A fault mid-reservation (page_alloc, prefix_resume) releases
            the partial table before propagating — reservation is
            all-or-nothing."""
            prompt = np.asarray(req.prompt, np.int32)
            plen = len(prompt)
            entry, shared = (None, 0)
            if self.prefix_index is not None:
                entry, shared = self.prefix_index.match(prompt)
                # always leave >= 1 suffix token (the resumed chunk loop
                # must produce the prompt's next-token logits itself), and
                # never deeper than the boundary-ring snapshot cap
                shared = min(shared, (plen - 1) // ps,
                             self.engine.scfg.prefix_share_pages)
            n_new = -(-plen // ps) - shared
            if self.pool.pages_free < n_new and \
                    not drop_entries(n_new, protect_entry=entry):
                if entry is not None:
                    # sharing is an optimization, never an obligation: if
                    # protecting the matched entry is what starves the
                    # reservation, retry UNSHARED so that entry becomes
                    # evictable too — otherwise an entry pinning the pool
                    # with no residents left would stall admission forever
                    entry, shared = None, 0
                    n_new = -(-plen // ps)
                if self.pool.pages_free < n_new and not drop_entries(n_new):
                    # a new request never steals pages from running
                    # residents: it stalls at the queue head until they
                    # release pages
                    self.admission_stalls += 1
                    return None
            free = next(i for i in range(b) if slots[i] is None)
            ptab = PageTable(self.pool, mp)
            try:
                for j in range(shared):
                    ptab.append_shared(entry.page_ids[j])
                for _ in range(n_new):
                    ptab.append_page()
                if shared:
                    task = eng.start_prefill(prompt, resume=(entry, shared))
                else:
                    task = eng.start_prefill(prompt)
            except BaseException:
                ptab.release_all()         # all-or-nothing reservation
                raise
            if shared:
                self.prefix_hits += 1
                self.prefix_index.touch(entry)
            return _Admission(req, free, task, ptab=ptab,
                              shared_pages=shared, entry=entry)

        # ---- two-tier helpers (ISSUE 7) -----------------------------------

        def shed_thrash(i: int, exc: "HotTierThrash"):
            """Hot-tier thrash on row ``i``: shed load, not the request.
            With other residents live, evict row i to the queue head —
            its pins and hot pages free immediately, the survivors'
            working set shrinks, and the evicted request restarts later
            at a lower multiprogramming degree (greedy decode keeps the
            re-run token-identical).  A SOLE thrashing resident is a hard
            capacity misfit — its own per-step selection cannot fit the
            hot tier — and self-eviction would livelock, so that one goes
            through the transient retry budget and fails with the thrash
            attached."""
            if sum(s is not None for s in slots) > 1:
                evict_to_requeue(i)
            else:
                fail_resident(i, exc)

        def claim_slot(exclude) -> int:
            """A free hot payload slot, spilling the LRU unpinned hot page
            if none is free.  ``exclude``: pids that must stay hot (about
            to be read/written this step).  Raises HotTierThrash when
            every hot page is pinned or excluded (transient, per-row)."""
            nonlocal cache
            slot = pool.take_slot()
            if slot is None:
                victim = pool.spill_victim(exclude)
                if victim is None:
                    raise HotTierThrash(
                        f"no spillable hot page among {len(pool.hot)} "
                        f"({len(pool.pins)} pinned)")
                vslot = pool.begin_spill(victim)   # fires "spill" first
                sid = None if self.tracer is None else self.tracer.begin(
                    "tier_spill", "scheduler", page=victim)
                mirror = eng.read_page_payload(cache, vslot)
                pool.finish_spill(victim, mirror)
                self._note_transfer(mirror, sid)
                hot_dirty[0] = True
                slot = pool.take_slot()
            return slot

        def fetch_page(pid: int, exclude) -> None:
            """Host→HBM demand/prefetch fetch of cold page ``pid``.  The
            fault points fire before any state change or transfer, so an
            injected host_fetch/spill fault leaves both tiers intact — the
            caller fails only the row that demanded the page."""
            nonlocal cache
            slot = claim_slot(exclude)
            try:
                payload = pool.begin_fetch(pid)    # fires "host_fetch" first
            except BaseException:
                pool.give_slot(slot)
                raise
            sid = None if self.tracer is None else self.tracer.begin(
                "tier_fetch", "scheduler", page=pid)
            try:
                cache = eng.load_page(cache, slot, payload)
            except BaseException:
                if sid is not None:
                    self.tracer.end(sid, aborted=True)
                pool.abort_fetch(pid)
                pool.give_slot(slot)
                raise
            pool.finish_fetch(pid, slot)
            self._note_transfer(payload, sid)
            hot_dirty[0] = True

        def ensure_write_pin(i: int):
            """Pin row i's current write page hot: the per-token decode
            write lands in it through the hot table every step, so it must
            hold a device slot for as long as writes target it.  Growth
            pages become hot IMMEDIATELY with garbage payload — per-row
            position masks keep unwritten rows unselectable, exactly the
            PR 5 recycled-page story."""
            nonlocal cache
            ptab = tables[i]
            pid = ptab.pages[int(positions[i]) // ps]
            if write_pin[i] == pid:
                return
            if write_pin[i] is not None:
                pool.unpin(write_pin[i])
                write_pin[i] = None
            if pid in pool.fresh:          # growth page: slot, no transfer
                pool.set_hot(pid, claim_slot({pid}))
            elif pid in pool.cold:         # write into a spilled page
                fetch_page(pid, {pid})
                self.cold_misses += 1
            pool.pin(pid)
            write_pin[i] = pid
            hot_dirty[0] = True

        def push_tables():
            """Push the host page table — and, tiered, the hot-slot table
            rebuilt from the pool's residency — to the device cache in one
            leaf swap."""
            nonlocal cache
            if self.tiered and (dirty[0] or hot_dirty[0]):
                slot_of = np.zeros((pool.n_pages,), np.int32)
                for pid, s in pool.hot.items():
                    slot_of[pid] = s
                host_hot[:] = slot_of[host_table]
                cache = eng.with_page_tables(cache, host_table, host_hot)
                dirty[0] = hot_dirty[0] = False
            elif dirty[0]:
                cache = eng.with_page_tables(cache, host_table)
                dirty[0] = False

        def assign_residency(adm: _Admission) -> List[int]:
            """First residency for an admission's FRESH pages: hot while
            free slots last, overflow cold (mirror extracted from the
            task's dense cache — those pages never touch the device pools).
            Admission never spills residents.  Returns the hot-slot row
            aligned to the reservation (shared pages keep the residency
            their registrant gave them)."""
            hot_row = []
            for j, pid in enumerate(adm.ptab.pages):
                if pid in pool.fresh:
                    slot = pool.take_slot()
                    if slot is not None:
                        pool.set_hot(pid, slot)
                    else:
                        pool.set_cold(pid, eng.extract_page_payload_dense(
                            adm.task.cache, j))
                hot_row.append(pool.hot.get(pid, 0))
            hot_dirty[0] = True
            return hot_row

        def tiered_decode(prefetched: set):
            """The tiered decode step: FETCH-AND-RERUN.  Run the selection-
            collecting decode; if any selected page was cold, its
            reconstruction read the trash slot — fetch the cold pages hot
            and rerun the SAME step on the returned cache.  Every cache
            write is an idempotent ``.set`` at a deterministic position, so
            the final run (all touched pages hot) is bit-identical to an
            all-HBM step.  Converges because the score pool is always
            true: run N's selection at the first miss-affected layer is
            final, so each round fixes at least one more layer — bounded
            by the layer count.  Returns the final logits, or None if
            every resident was torn down by injected fetch faults."""
            nonlocal cache
            rounds = 0
            while True:
                with self._span("decode_dispatch"):
                    logits, cache, touched = eng._decode_sel(
                        jnp.asarray(tokens), cache, jnp.asarray(positions))
                with self._span("device_wait"):
                    tnp = np.asarray(touched)
                touched_all: set = set()
                new_prev: Dict[int, set] = {}
                demand: List[tuple] = []
                for i in range(b):
                    if slots[i] is None:
                        continue
                    pids = {int(host_table[i, j])
                            for j in np.nonzero(tnp[i])[0]}
                    pids.discard(0)
                    new_prev[i] = pids
                    touched_all |= pids
                    for pid in sorted(pids):
                        if pid in pool.cold:
                            demand.append((i, pid))
                pool.touch(p for p in sorted(touched_all) if p in pool.hot)
                if rounds == 0:
                    self.fetch_hits += sum(
                        1 for p in touched_all if p in pool.hot)
                    self.prefetch_hits += len(touched_all & prefetched)
                if not demand:
                    for i, pids in new_prev.items():
                        prev_selected[i] = pids
                    return logits
                rounds += 1
                if rounds > eng.cfg.n_layers + 2:
                    raise PagerInvariantError(
                        "tiered fetch-and-rerun did not converge in "
                        f"{rounds} rounds (selection unstable?)")
                for i, pid in demand:
                    if slots[i] is None:       # row died earlier this pass
                        continue
                    if pid not in pool.cold:   # fetched for an earlier row
                        continue
                    try:
                        fetch_page(pid, touched_all)
                        self.cold_misses += 1
                    except faults.InjectedFault as exc:
                        fail_resident(i, exc)
                    except HotTierThrash as exc:
                        shed_thrash(i, exc)
                if not any(s is not None for s in slots):
                    return None
                push_tables()

        def ensure_writable(i: int, span: int = 1):
            """Pre-decode page upkeep for resident row i: map every page
            its next ``span`` writes can land in (allocating on page
            crossings; a speculative verify window commits up to
            spec_window tokens in one step, so its span covers the whole
            window) and COW any still-shared target (structurally
            unreachable — sharing is whole-page and the cache append-only —
            but guarded so a future sharing policy cannot silently corrupt
            a shared page).  If the pool is exhausted even after dropping
            cache entries, the row evicts ITSELF to the queue (see
            evict_to_requeue).  Tiered: also pins the write page hot
            (ensure_write_pin)."""
            nonlocal cache
            ptab = tables[i]
            lo = int(positions[i]) // ps
            hi = (int(positions[i]) + span - 1) // ps
            for p in range(lo, hi + 1):
                if p >= ptab.n_pages:
                    if self.pool.pages_free < 1 and not drop_entries(1):
                        evict_to_requeue(i)
                        return
                    ptab.ensure_for_position(p * ps)
                    host_table[i, :ptab.n_pages] = ptab.pages
                    dirty[0] = True
                elif self.pool.refcount(ptab.pages[p]) > 1:
                    if self.pool.pages_free < 1 and not drop_entries(1):
                        evict_to_requeue(i)
                        return
                    old, new = ptab.ensure_exclusive(p)
                    if self.tiered:
                        # score page: physical-id copy, device-resident
                        cache = eng.copy_score_page(cache, old, new)
                        if old in pool.hot:
                            slot = claim_slot({old, new})
                            cache = eng.copy_page(cache, pool.hot[old], slot)
                            pool.set_hot(new, slot)
                        else:          # cold source: host-mirror duplicate
                            faults.maybe_fault("cow_copy")
                            pool.set_cold(new, {
                                seg: {f: v.copy() for f, v in fl.items()}
                                for seg, fl in pool.cold[old].items()})
                        hot_dirty[0] = True
                    else:
                        cache = eng.copy_page(cache, old, new)
                    host_table[i, p] = new
                    dirty[0] = True
                    self.cow_copies += 1
            if self.tiered:
                ensure_write_pin(i)

        def emit_tokens(i: int, n_new: int) -> bool:
            """Stream row ``i``'s ``n_new`` newest committed tokens through
            its request's ``on_token`` callback (ISSUE 8), in COMMIT ORDER
            with contiguous indices.  ISSUE 9 bugfix: a verify window that
            accepts k > 1 tokens fires the callback k times — once per
            accepted token, never for rejected draft positions — so the
            index sequence a client observes is exactly 0, 1, 2, ...
            whatever mix of sequential and speculative steps committed
            them.  A raising callback is the client's failure signal: it
            fails (non-transiently, unless the raised error says
            otherwise) THAT request alone — tokens already delivered stay
            delivered.  Returns False when the row was torn down."""
            req = slots[i].req
            if req.on_token is None:
                return True
            base = len(slots[i].out) - n_new
            for k in range(n_new):
                try:
                    req.on_token(int(slots[i].out[base + k]), base + k)
                except Exception as exc:
                    fail_resident(i, exc)
                    return False
            return True

        # ---- preempt-park machinery (ISSUE 8) -----------------------------

        def spill_parked_cold():
            """Hot-tier liveness half of a park: spill every page whose
            ONLY owners are parked tables (hot, unpinned, refcount ==
            parked multiplicity) to the host mirror, so the preemption
            actually frees device slots.  Pages shared with a live
            resident, an in-flight admission or a prefix entry keep their
            residency.  Runs after each park AND once per loop iteration:
            an injected ``spill`` fault just leaves the page hot until the
            next sweep retries (the tier auditor only enforces the safety
            rules — never pinned, never fresh)."""
            nonlocal cache
            if not (self.tiered and self.parked):
                return
            counts = collections.Counter()
            for rec in self.parked:
                counts.update(rec.ptab.pages)
            for pid, n in sorted(counts.items()):
                if pid in pool.hot and not pool.pins.get(pid) \
                        and pool.refcount(pid) == n:
                    try:
                        vslot = pool.begin_spill(pid)  # fires "spill" first
                    except faults.InjectedFault:
                        return         # retried next iteration
                    sid = None if self.tracer is None else self.tracer.begin(
                        "tier_spill", "scheduler", page=pid, parked=True)
                    mirror = eng.read_page_payload(cache, vslot)
                    pool.finish_spill(pid, mirror)
                    self._note_transfer(mirror, sid)
                    hot_dirty[0] = True

        def park_resident(i: int):
            """Preempt-PARK resident row ``i``: snapshot its per-slot
            window state to host (engine.detach_slot — fires the ``park``
            fault point before any read, so an injected fault leaves the
            victim resident), move page-table ownership into a parked
            record WITHOUT releasing any page, free the batch slot.  Under
            tiering the write pin drops and exclusively-parked pages spill
            cold (see spill_parked_cold)."""
            nonlocal cache
            req = slots[i].req
            snap = eng.detach_slot(cache, i)
            rec = _Parked(req=req, out=slots[i].out,
                          position=int(positions[i]), ptab=tables[i],
                          snapshot=snap, parked_step=self.steps)
            slots[i] = None
            tokens[i] = 0
            positions[i] = 0
            tables[i] = None        # ownership moved to rec — NOT released
            host_table[i] = 0
            dirty[0] = True
            if self.tiered:
                if write_pin[i] is not None:
                    pool.unpin(write_pin[i])
                    write_pin[i] = None
                prev_selected[i] = set()
                host_hot[i] = 0
                hot_dirty[0] = True
            cache = eng.release_slot(cache, i)     # metadata-only
            transition(req, RequestState.PARKED)
            self._trace_phase(req, "parked")
            self.parked.append(rec)
            req.parks += 1
            self.parks += 1
            spill_parked_cold()
            if audit_on:
                self.audit_serving_state()

        def resume_parked(rec: _Parked, i: int) -> bool:
            """Resume a parked record into free slot ``i``: splice the
            window snapshot back (engine.attach_slot — fires the
            ``resume`` fault point before the donating call), reinstall
            the table row, continue DECODING token-exact.  On a resume
            fault the snapshot is still whole but the park is abandoned:
            held pages release and the request restarts from scratch
            through the standard retry policy (PARKED -> QUEUED/FAILED)."""
            nonlocal cache
            try:
                cache = eng.attach_slot(cache, i, rec.snapshot)
            except Exception as exc:
                rec.ptab.release_all()
                self._fail_or_retry(rec.req, exc, issued,
                                    partial=(rec.out, len(rec.req.prompt)))
                if audit_on:
                    self.audit_serving_state()
                return False
            tables[i] = rec.ptab
            host_table[i] = 0
            host_table[i, :rec.ptab.n_pages] = rec.ptab.pages
            dirty[0] = True
            if self.tiered:
                hot_dirty[0] = True  # hot rows rebuild from pool residency
            slots[i] = _Slot(rec.req, out=rec.out, seq=next(admit_seq),
                             drafter=NgramDrafter(
                                 list(rec.req.prompt) + rec.out)
                             if spec_q else None)
            tokens[i] = rec.out[-1]
            positions[i] = rec.position
            transition(rec.req, RequestState.DECODING)
            self._trace_phase(rec.req, "decode", resumed=True)
            self.resumes += 1
            if audit_on:
                self.audit_serving_state()
            return True

        def best_incoming_priority() -> Optional[int]:
            """Highest priority among eligible pending + parked requests —
            what a resident must be strictly below to be preempted."""
            best = None
            for r in self.pending:
                if self._eligible(r) and (best is None or r.priority > best):
                    best = r.priority
            for rec in self.parked:
                if best is None or rec.req.priority > best:
                    best = rec.req.priority
            return best

        def preempt_for_priority():
            """Park (or evict, per preempt_policy) the lowest-priority,
            most-recently-admitted resident while a strictly higher
            eligible class is waiting and no slot is free.  Never preempts
            below the incoming class (no same-priority churn) and never
            while an admission is in flight (it owns the next free slot;
            inversion is bounded by one prompt's chunks)."""
            if self._active is not None:
                return
            while True:
                if any(s is None for s in slots):
                    return
                best = best_incoming_priority()
                if best is None:
                    return
                vict = None
                for i in range(b):
                    s = slots[i]
                    if s is None or s.req.priority >= best:
                        continue
                    if vict is None or (s.req.priority, -s.seq) < \
                            (slots[vict].req.priority, -slots[vict].seq):
                        vict = i
                if vict is None:
                    return
                if eng.scfg.preempt_policy == "evict":
                    evict_to_requeue(vict)
                else:
                    try:
                        park_resident(vict)
                    except faults.InjectedFault:
                        return   # victim stays resident; retry next iter
                self.preemptions += 1

        def resume_ready_parked():
            """Fill free slots with parked records, highest priority first
            (earliest-parked within a class), unless a STRICTLY higher
            pending class is eligible — parked work outranks new
            admissions of its own class (it is sunk work: pages held,
            tokens committed).  The slot an in-flight admission reserved
            is not up for grabs."""
            while self.parked:
                reserved = self._active.slot \
                    if self._active is not None else -1
                free = next((i for i in range(b)
                             if slots[i] is None and i != reserved), None)
                if free is None:
                    return
                best_pend = None
                for r in self.pending:
                    if self._eligible(r) and (best_pend is None
                                              or r.priority > best_pend):
                        best_pend = r.priority
                rec = min(self.parked,
                          key=lambda rc: (-rc.req.priority, rc.parked_step))
                if best_pend is not None and best_pend > rec.req.priority:
                    return
                self.parked.remove(rec)
                resume_parked(rec, free)

        def reclaim_parked_pages(req: Request) -> bool:
            """A page-stalled admission may reclaim pages from a PARKED
            victim of strictly lower priority — parked sunk work never
            starves a waiting higher class — or from ANY parked record
            when nothing is resident (held pages with an empty arena
            would otherwise deadlock the queue).  Destructive: the
            victim's pages release and it requeues from scratch, exactly
            an evict-to-requeue."""
            none_resident = not any(s is not None for s in slots)
            cands = [rec for rec in self.parked
                     if rec.req.priority < req.priority or none_resident]
            if not cands:
                return False
            rec = min(cands, key=lambda rc: (rc.req.priority,
                                             -rc.parked_step))
            self.parked.remove(rec)
            rec.ptab.release_all()
            transition(rec.req, RequestState.QUEUED)
            rec.req.not_before_step = 0
            self.pending.append(rec.req)
            self.evictions += 1
            if audit_on:
                self.audit_serving_state()
            return True

        def sweep_deadlines_and_cancels():
            """Honor cancel() and expired deadlines in EVERY phase through
            the one teardown path.  Runs at each iteration boundary — a
            request is never torn down mid-splice."""
            for idx in range(len(self.pending) - 1, -1, -1):
                req = self.pending[idx]
                state = _overdue(req)
                if state is not None:
                    del self.pending[idx]
                    self._terminate(req, state, _overdue_error(req, state),
                                    issued)
            adm = self._active
            if adm is not None:
                state = _overdue(adm.req)
                if state is not None:
                    teardown_admission(adm)
                    self._active = None
                    self._terminate(adm.req, state,
                                    _overdue_error(adm.req, state), issued)
            # parked requests honor cancel/deadline too: release the held
            # pages (that IS the whole teardown — no slot, no pins) and
            # flush the partial stream
            for idx in range(len(self.parked) - 1, -1, -1):
                rec = self.parked[idx]
                state = _overdue(rec.req)
                if state is not None:
                    del self.parked[idx]
                    rec.ptab.release_all()
                    self._terminate(rec.req, state,
                                    _overdue_error(rec.req, state), issued,
                                    partial=(rec.out, len(rec.req.prompt)))
                    if audit_on:
                        self.audit_serving_state()
            for i in range(b):
                if slots[i] is None:
                    continue
                req = slots[i].req
                state = _overdue(req)
                if state is not None:
                    out = list(slots[i].out)
                    clear_slot(i)
                    self._terminate(req, state, _overdue_error(req, state),
                                    issued, partial=(out, len(req.prompt)))

        def _overdue(req: Request) -> Optional[RequestState]:
            if req.cancel_requested:
                return RequestState.CANCELLED
            if req.deadline_step is not None \
                    and self.steps >= req.deadline_step:
                return RequestState.TIMED_OUT
            if req.deadline_time is not None \
                    and self._clock() >= req.deadline_time:
                return RequestState.TIMED_OUT
            return None

        def _overdue_error(req: Request, state: RequestState):
            if state is RequestState.CANCELLED:
                return RequestCancelled(f"req {req.req_id} cancelled")
            if req.deadline_step is not None \
                    and self.steps >= req.deadline_step:
                return RequestTimeout(
                    f"req {req.req_id} missed deadline step "
                    f"{req.deadline_step}")
            ms = (req.timeout_ms if req.timeout_ms is not None
                  else self.engine.scfg.request_timeout_ms)
            return RequestTimeout(
                f"req {req.req_id} missed wall-clock deadline ({ms:g} ms)")

        while self.pending or self._active or self.parked \
                or any(s is not None for s in slots):
            if self.tracer is not None:   # one span per loop turn
                self.tracer.end_track("scheduler")
                self.tracer.begin("iteration", "scheduler", step=self.steps,
                                  n_live=sum(s is not None for s in slots))
            with self._span("upkeep"):
                sweep_deadlines_and_cancels()
                self._refill_rate_credits()
                if prio_on:
                    preempt_for_priority()
                resume_ready_parked()
                spill_parked_cold()

            # ---- prefill sweep: ≤ budget tokens of chunk work; priority
            # classes first, DRR across tenants within a class ----------
            spent = 0
            while spent < chunks_per_sweep:
                if self._active is None:
                    with self._span("admit"):
                        free = next(
                            (i for i in range(b) if slots[i] is None), None)
                        if free is None:
                            break
                        req = pop_eligible()
                        if req is None:
                            break
                        if self.paged:
                            try:
                                self._active = try_reserve(req)
                            except Exception as exc:   # torn reservation
                                self._fail_or_retry(req, exc, issued)
                                continue
                            if self._active is None:
                                # stalled on pages, not slots: back to the
                                # head, BEFORE any evicted victims — after
                                # trying to reclaim pages from a
                                # lower-priority parked victim
                                self.pending.appendleft(req)
                                if reclaim_parked_pages(req):
                                    continue   # pages freed: retry at once
                                break
                        else:
                            self._active = _Admission(req, free,
                                                      eng.start_prefill(
                                                          req.prompt))
                        req.attempts += 1
                        transition(req, RequestState.PREFILLING)
                        self._trace_phase(req, "prefill",
                                          attempt=req.attempts)
                active = self._active
                self.prefill_chunks.append(
                    (self.steps, active.req.req_id, active.task.next_chunk,
                     sum(s is not None for s in slots)))
                csid = None if self.tracer is None else self.tracer.begin(
                    "prefill_chunk", "scheduler", req=active.req.req_id,
                    chunk=active.task.next_chunk)
                try:
                    eng.prefill_chunk_step(active.task)
                except Exception as exc:
                    # the task's own cache/scratch are lost (donated or
                    # torn) but the ARENA is untouched: release the
                    # reservation, retry-or-fail this request alone
                    if csid is not None:
                        self.tracer.end(csid, faulted=True)
                    teardown_admission(active)
                    self._active = None
                    self._fail_or_retry(active.req, exc, issued)
                    spent += 1
                    continue
                if csid is not None:
                    self.tracer.end(csid)
                spent += 1
                if active.task.done:
                    with self._span("admit"):
                        i = active.slot
                        try:
                            if self.tiered:
                                # residency first: the cold mirrors read the
                                # task's dense cache, which the splice leaves
                                # alive (only the ARENA is donated)
                                hot_row = assign_residency(active)
                                cache = eng.admit_tiered(
                                    cache, active.task.cache, i,
                                    active.ptab.pages, hot_row,
                                    active.shared_pages,
                                    active.task.prompt_len)
                            elif self.paged:
                                cache = eng.admit_paged(
                                    cache, active.task.cache, i,
                                    active.ptab.pages, active.shared_pages,
                                    active.task.prompt_len)
                            else:
                                cache = eng.admit(cache, active.task.cache,
                                                  i)
                        except Exception as exc:  # torn splice (pre-donate)
                            teardown_admission(active)
                            self._active = None
                            self._fail_or_retry(active.req, exc, issued)
                            continue
                        if self.paged:
                            tables[i] = active.ptab
                            host_table[i] = 0
                            host_table[i, :active.ptab.n_pages] = \
                                active.ptab.pages
                            dirty[0] = True
                            self._register_prefix(active)
                        # ownership of ptab just moved to tables[i]: drop
                        # the admission NOW so a teardown audit below
                        # cannot count the same table twice (resident +
                        # in-flight)
                        self._active = None
                        transition(active.req, RequestState.DECODING)
                        self._trace_phase(active.req, "decode")
                        key, sub = jax.random.split(key)
                        tok_arr, ok = jax.device_get(eng.sample_checked(
                            active.task.logits, sub))
                        if not ok[0]:
                            # poisoned prompt logits: this request alone
                            # fails
                            slots[i] = _Slot(active.req, out=[],
                                             seq=next(admit_seq))
                            fail_resident(i, NanLogitsError(
                                f"req {active.req.req_id}: non-finite "
                                "prefill logits"))
                            continue
                        tok0 = int(tok_arr[0])
                        slots[i] = _Slot(active.req, out=[tok0],
                                         seq=next(admit_seq),
                                         drafter=NgramDrafter(
                                             list(active.req.prompt) + [tok0])
                                         if spec_q else None)
                        tokens[i] = tok0
                        positions[i] = len(active.req.prompt)
                        self.admissions.append(
                            (self.steps, i, active.req.req_id))
                        if not emit_tokens(i, 1):
                            continue
                        if len(slots[i].out) >= active.req.max_new_tokens:
                            finish(i)

            if not any(s is not None for s in slots):
                if not (self.pending or self._active or self.parked):
                    break
                if self._active is None and self.pending:
                    # arena idle and every pending request is backing off:
                    # fast-forward the step clock to the earliest gate so
                    # retry waits cannot busy-livelock an empty arena
                    nxt = min(r.not_before_step for r in self.pending)
                    if nxt > self.steps:
                        self.steps = nxt
                continue            # nothing resident yet: keep prefilling

            # ---- paged upkeep: map/COW every row's write page, then push
            # the host table to the device cache in one leaf swap ----------
            if self.paged:
                with self._span("upkeep"):
                    for i in range(b):
                        if slots[i] is not None:
                            # speculative window: the verify step may
                            # commit up to min(spec_window, remaining
                            # budget) tokens in one shot — map every page
                            # that span can touch
                            span = 1 if not spec_q else \
                                min(spec_q, slots[i].req.max_new_tokens
                                    - len(slots[i].out))
                            try:
                                ensure_writable(i, span)
                            except HotTierThrash as exc:
                                shed_thrash(i, exc)  # load, not the request
                            except Exception as exc:  # alloc/COW fault:
                                fail_resident(i, exc)  # only row i pays
                    # ---- selection-driven prefetch: warm each row's
                    # PREVIOUS step's selected pages — the paper's stability
                    # insight says the next selection mostly repeats it
                    # (measured: benchmarks/overlap_score.py) ---------------
                    prefetched: set = set()
                    if self.tiered and eng.scfg.tier_prefetch:
                        for i in range(b):
                            if slots[i] is None:
                                continue
                            try:
                                for pid in sorted(prev_selected[i]):
                                    if pid in pool.cold:
                                        fetch_page(pid, prev_selected[i])
                                        prefetched.add(pid)
                            except HotTierThrash:
                                break   # hot tier saturated: best-effort
                            except faults.InjectedFault as exc:
                                fail_resident(i, exc)   # prefetch radius
                    push_tables()
                    if not any(s is not None for s in slots):
                        continue   # upkeep evicted/failed every resident

            # ---- one ragged decode step for the whole arena ---------------
            # (empty slots idle at position 0, harmlessly rewriting their
            # own row's slot-0 cache line — paged: the trash page; the SAME
            # compiled HLO serves every step and every admission pattern)
            try:
                # batch-wide fault point; BEFORE _decode donates the cache
                faults.maybe_fault("decode_step")
                if spec_q:
                    # draft-verify fault point (ISSUE 9): fires before the
                    # windowed jit call, while the cache is still whole —
                    # the whole window round retries like a decode_step
                    # fault (drafting is pure host work, re-proposing is
                    # free and deterministic)
                    faults.maybe_fault("draft_verify")
            except faults.InjectedFault:
                # nothing ran: retry the whole step, bounded so a rate-1.0
                # schedule cannot spin forever
                self.step_faults += 1
                fault_streak += 1
                if fault_streak > self.engine.scfg.max_request_retries:
                    raise
                continue
            fault_streak = 0
            tr = self.tracer
            dsid = None if tr is None else tr.begin(
                "verify_window" if spec_q else "decode_step", "scheduler",
                step=self.steps,
                n_live=sum(s is not None for s in slots))
            if spec_q:
                # ---- speculative verify window (ISSUE 9): ONE latent
                # selection + ONE windowed reconstruction serves the
                # pending token plus spec_q-1 prompt-lookup drafts; greedy
                # verify accepts the longest matching prefix and the
                # masked commit writes ONLY accepted positions, so cache
                # bytes and the token stream stay bit-identical to
                # sequential decode whatever the drafts were --------------
                wt = np.zeros((b, spec_q), np.int32)
                for i in range(b):
                    if slots[i] is not None:
                        wt[i, 0] = tokens[i]
                        wt[i, 1:] = slots[i].drafter.propose(spec_q - 1)
                with self._span("decode_dispatch"):
                    win_logits, aux = eng._decode_window(
                        jnp.asarray(wt), cache, jnp.asarray(positions))
                live = [i for i in range(b) if slots[i] is not None]
                pick = faults.maybe_pick("nan_logits", len(live))
                if pick is not None:
                    # poison ONE live row's window logits — the finiteness
                    # verdict must confine the blast radius to that row
                    win_logits = win_logits.at[live[pick]].set(jnp.nan)
                with self._span("device_wait"):
                    wl = np.asarray(win_logits)               # (B, Q, V)
                preds = wl.argmax(axis=-1).astype(np.int32)   # (B, Q)
                finite = np.isfinite(wl).all(axis=(1, 2))
                n_matched = np.cumprod(
                    wt[:, 1:] == preds[:, :-1], axis=1).sum(axis=1)
                n_commit = np.zeros((b,), np.int32)
                emitted: List[List[int]] = [[] for _ in range(b)]
                for i in range(b):
                    if slots[i] is None or not finite[i]:
                        continue
                    left = slots[i].req.max_new_tokens - len(slots[i].out)
                    n_emit = int(min(n_matched[i] + 1, left))
                    row = [int(wt[i, 1 + k]) for k in range(n_emit - 1)]
                    row.append(int(preds[i, n_emit - 1]))
                    emitted[i] = row
                    n_commit[i] = n_emit
                # the committed window slots are the PENDING token plus the
                # accepted drafts; the last emitted token becomes the new
                # pending token (its KV lands next round).  Rejected and
                # idle rows commit nothing (OOB-drop scatters).
                cache = eng._commit_window(cache, aux,
                                           jnp.asarray(positions),
                                           jnp.asarray(n_commit))
                self.steps += 1
                self.spec_rounds += 1
                with self._span("emit"):
                    for i in range(b):
                        if slots[i] is None:
                            continue
                        if not finite[i]:
                            fail_resident(i, NanLogitsError(
                                f"req {slots[i].req.req_id}: non-finite "
                                f"window logits at step {self.steps}"))
                            continue
                        row = emitted[i]
                        self.spec_proposed += spec_q - 1
                        self.spec_accepted += min(int(n_matched[i]),
                                                  len(row) - 1)
                        self.spec_committed += len(row)
                        slots[i].out.extend(row)
                        slots[i].drafter.extend(row)
                        tokens[i] = row[-1]
                        positions[i] += len(row)
                        if not emit_tokens(i, len(row)):
                            continue
                        if len(slots[i].out) >= slots[i].req.max_new_tokens:
                            finish(i)
            else:
                if self.tiered:
                    logits = tiered_decode(prefetched)
                    if logits is None:  # fetch faults tore every row down
                        if dsid is not None:
                            tr.end(dsid, aborted=True)
                        continue
                else:
                    with self._span("decode_dispatch"):
                        logits, cache = eng._decode(
                            jnp.asarray(tokens), cache,
                            jnp.asarray(positions))
                live = [i for i in range(b) if slots[i] is not None]
                pick = faults.maybe_pick("nan_logits", len(live))
                if pick is not None:
                    # poison ONE live row's logits — the blast radius the
                    # sample_checked verdict must confine to that row
                    logits = logits.at[live[pick]].set(jnp.nan)
                with self._span("sample"):
                    key, sub = jax.random.split(key)
                    tok_arr, ok = eng.sample_checked(logits, sub)
                # the step's one blocking readback: tokens and verdicts
                with self._span("device_wait"):
                    new_toks, ok = jax.device_get((tok_arr, ok))
                self.steps += 1
                with self._span("emit"):
                    for i in range(b):
                        if slots[i] is None:
                            continue
                        if not ok[i]:
                            fail_resident(i, NanLogitsError(
                                f"req {slots[i].req.req_id}: non-finite "
                                f"logits or out-of-vocab token at step "
                                f"{self.steps}"))
                            continue
                        slots[i].out.append(int(new_toks[i]))
                        tokens[i] = new_toks[i]
                        positions[i] += 1
                        if not emit_tokens(i, 1):
                            continue
                        if len(slots[i].out) >= slots[i].req.max_new_tokens:
                            finish(i)
            if dsid is not None:
                tr.end(dsid)
            if self.paged:
                row = {
                    "step": self.steps,
                    "pages_in_use": self.pool.pages_in_use,
                    "pages_free": self.pool.pages_free,
                    "prefix_hits": self.prefix_hits,
                    "cow_copies": self.cow_copies,
                    "admission_stalls": self.admission_stalls,
                    "evictions": self.evictions,
                    "parked": len(self.parked),
                    "parks": self.parks,
                    "resumes": self.resumes,
                    "preemptions": self.preemptions,
                    "prefix_entries": len(self.prefix_index.entries)
                    if self.prefix_index else 0,
                }
                if self.tiered:
                    row.update({
                        "host_pages": pool.host_pages,
                        "fetch_hits": self.fetch_hits,
                        "prefetch_hits": self.prefetch_hits,
                        "cold_misses": self.cold_misses,
                        "spills": pool.spills,
                    })
                self.pool_gauges.append(row)
            # gauges are point-in-time samples: publish at 1/4 step rate
            # (scrape intervals dwarf 4 steps) — the unconditional publish
            # at drain below keeps end-state reads exact
            if self._metrics_installed and self.steps % 4 == 0:
                self._publish_gauges()
            if audit_on and self.steps % self.engine.scfg.audit_every == 0:
                self.audit_serving_state(
                    self.pool_gauges[-1] if self.pool_gauges else None)
            if on_step:
                with self._span("on_step"):
                    on_step(self, self.steps)
        self._publish_gauges()
        return issued

    # ------------------------------------------------------ telemetry (10)

    @staticmethod
    def _mirror_nbytes(mirror: dict) -> int:
        """ACTUAL bytes of a host page mirror ({seg: {field: np array}}) —
        the measured side of the tiered PCIe ledger term."""
        return sum(int(a.nbytes)
                   for seg in mirror.values() for a in seg.values())

    def _note_transfer(self, mirror: dict, sid: Optional[int]) -> None:
        """Close one host↔HBM page transfer's span with its measured byte
        count."""
        if sid is not None:
            self.tracer.end(sid, bytes=self._mirror_nbytes(mirror))

    def _publish_gauges(self) -> None:
        """Refresh the registry's point-in-time gauges (occupancy, queue
        depths, tenant fairness).  Cumulative counts live in the counters
        behind the ``_CounterView`` fields and never pass through here."""
        g = self.metrics
        g.gauge("serve_steps", "decode steps executed").set(self.steps)
        g.gauge("serve_pending", "requests waiting in queue").set(
            len(self.pending))
        g.gauge("serve_residents", "slots running decode").set(
            sum(s is not None for s in self._slots))
        g.gauge("serve_parked_requests", "preempt-parked residents").set(
            len(self.parked))
        if self.pool is not None:
            g.gauge("serve_pages_in_use", "refcounted pool pages").set(
                self.pool.pages_in_use)
            g.gauge("serve_pages_free", "allocatable pool pages").set(
                self.pool.pages_free)
            if self.tiered:
                g.gauge("serve_host_pages", "cold (host-mirror) pages").set(
                    self.pool.host_pages)
                g.counter("serve_tier_spills_total",
                          "HBM→host page spills").set_to(self.pool.spills)
                g.counter("serve_tier_fetches_total",
                          "host→HBM page fetches").set_to(self.pool.fetches)
        if self.prefix_index is not None:
            g.gauge("serve_prefix_entries", "live prefix-cache entries").set(
                len(self.prefix_index.entries))
        if self.tenant_gauges:
            tg = g.gauge("serve_tenant_stat",
                         "per-tenant fairness/starvation gauges",
                         labelnames=("tenant", "stat"))
            for t, gd in self.tenant_gauges.items():
                for k, v in gd.items():
                    tg.set(v, tenant=t, stat=k)

    # ---------------------------------------------------------------- audit

    def audit_serving_state(self, gauges: Optional[dict] = None) -> None:
        """Cross-structure invariant audit (ISSUE 6): prove the pool, every
        live page table (residents + in-flight admission), the prefix
        index's pins, and the exported gauges agree — conservation of
        pages, no use-after-free, no leak — plus slot↔request-state
        coherence.  Raises :class:`PagerInvariantError`.  Host-side only:
        O(pages + residents), no device sync."""
        for i, s in enumerate(self._slots):
            if s is not None and s.req.state is not RequestState.DECODING:
                raise PagerInvariantError(
                    f"slot {i} resident req {s.req.req_id} in state "
                    f"{s.req.state.value}, expected decoding")
            if self.paged and s is None and i < len(self._tables) \
                    and self._tables[i] is not None:
                raise PagerInvariantError(
                    f"slot {i} is empty but still owns a page table")
        for rec in self.parked:
            if rec.req.state is not RequestState.PARKED:
                raise PagerInvariantError(
                    f"parked req {rec.req.req_id} in state "
                    f"{rec.req.state.value}, expected parked")
            if rec.ptab is None or rec.ptab.n_pages == 0:
                raise PagerInvariantError(
                    f"parked req {rec.req.req_id} holds no pages — a park "
                    "is only legal for a paged resident")
        if not self.paged:
            return
        # parked tables join the census: a park HOLDS pages, it does not
        # hide them from conservation (ISSUE 8)
        tables = [t for t in self._tables if t is not None]
        parked_pids: List[int] = []
        for rec in self.parked:
            tables.append(rec.ptab)
            parked_pids.extend(rec.ptab.pages)
        adm = self._active
        if adm is not None and adm.ptab is not None:
            tables.append(adm.ptab)
        entries = self.prefix_index.entries if self.prefix_index else []
        audit_pager(self.pool, tables, entries, gauges=gauges,
                    parked=parked_pids)

    def _register_prefix(self, adm: _Admission) -> None:
        """Register a finished prefill's whole-page prefix for sharing.

        The entry retains the task's final cache/scratch (append-only
        resume state) and its page-boundary ring snapshots; a resumed
        registrant inherits the boundary rings it skipped from ITS entry
        (same tokens, same rings)."""
        if self.prefix_index is None:
            return
        task = adm.task
        if task.prompt_len < self.engine.scfg.page_size:
            return
        rings = dict(task.boundary_rings or {})
        if adm.entry is not None:
            for d, snap in adm.entry.boundary_rings.items():
                if d <= adm.shared_pages:
                    rings.setdefault(d, snap)
        prompt = np.asarray(task.tokens[0, :task.prompt_len], np.int32)
        entry = self.prefix_index.insert(prompt, list(adm.ptab.pages), rings,
                                         task.cache, task.scratch)
        if entry is None:
            return                # duplicate / sub-page: nothing to cap
        # entry cap: each entry retains a dense (L, 1, max_seq, ·) resume
        # snapshot beyond its pinned pages — LRU-evict past the budget so
        # entry HBM stays bounded however many distinct prompts arrive.
        # Cap AFTER the (possibly no-op) insert: a duplicate registration
        # must never cost an unrelated live entry its cache slot.
        cap = max(1, self.engine.scfg.prefix_cache_entries)
        while len(self.prefix_index.entries) > cap:
            self.prefix_index.evict(self.prefix_index.lru_entry(
                exclude=entry))

    # ---------------------------------------------------------------- static

    def _run_static(self, on_batch) -> List[Request]:
        """GPT-fast-style: drain fixed batches back to back.  Lifecycle
        support is minimal but honest: cancellations requested before a
        batch starts are honored; states move QUEUED → PREFILLING →
        DECODING → DONE around each monolithic generate."""
        issued: List[Request] = []
        # length-bucket inside the admission window (deque has no sort)
        self.pending = collections.deque(
            sorted(self.pending, key=lambda r: len(r.prompt)))
        while self.pending:
            for idx in range(len(self.pending) - 1, -1, -1):
                req = self.pending[idx]
                if req.cancel_requested:
                    del self.pending[idx]
                    self._terminate(req, RequestState.CANCELLED,
                                    RequestCancelled(
                                        f"req {req.req_id} cancelled"),
                                    issued)
            batch: List[Request] = []
            while self.pending and len(batch) < self.max_batch:
                batch.append(self.pending.popleft())
            if not batch:
                break
            for req in batch:
                req.attempts += 1
                transition(req, RequestState.PREFILLING)
                transition(req, RequestState.DECODING)
            mnt = max(r.max_new_tokens for r in batch)
            if self.engine.scfg.spec_window > 1:
                results = self.engine.generate_speculative(
                    [r.prompt for r in batch], max_new_tokens=mnt)
            else:
                results = self.engine.generate(
                    [r.prompt for r in batch], max_new_tokens=mnt)
            for req, res in zip(batch, results):
                req.result = GenerationResult(
                    res.tokens[:req.max_new_tokens], res.prompt_len,
                    min(res.steps, req.max_new_tokens))
                self._terminate(req, RequestState.DONE, issued=issued)
            if on_batch:
                on_batch(batch)
        return issued
