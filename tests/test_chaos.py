"""Fault-tolerant serving (ISSUE 6): chaos tests.

Under seeded fault schedules (exact-occurrence regressions + randomized
sweeps) the serving tier must satisfy three properties:

  (a) ``audit_serving_state()`` passes after every scheduler step — page
      conservation across pool / page tables / prefix pins / gauges, no
      use-after-free, slot↔state coherence;
  (b) every NON-faulted request completes token-exact vs the fault-free
      greedy run (isolation: a fault's blast radius is its own request),
      and retried requests also end token-exact (greedy re-runs are
      deterministic);
  (c) no deadlock/livelock: every run drains within a step bound and every
      request reaches a terminal state.

Fault hooks must be true no-ops when disabled (identical outputs, no
schedule installed).  Hypothesis drives a randomized arrival × fault-rate
sweep when installed; deterministic parametrized seeds always run.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:                       # optional dev extra (pip install .[dev]) — guarded
    from hypothesis import given, settings, strategies as st
    from hypothesis import stateful
    HAVE_HYPOTHESIS = True
except ImportError:        # property tests skip; everything else still runs
    from conftest import given, settings, st  # noqa: F401
    HAVE_HYPOTHESIS = False

from repro.config import SALSConfig, ServeConfig
from repro.configs import get_config
from repro.core import calibration as cal
from repro.core.pager import (PagePool, PageTable, PagerInvariantError,
                              PrefixIndex, audit_pager)
from repro.models import transformer as tf
from repro.serve import (NanLogitsError, QueueFull, Request, RequestScheduler,
                         RequestState, ServeEngine, faults)
from repro.serve.lifecycle import LifecycleError, transition

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def model():
    cfg = get_config("qwen2-1.5b").reduced(n_layers=3, vocab_size=128)
    params = tf.init_params(KEY, cfg, jnp.float32)
    sals = SALSConfig(rank_ratio=0.5, score_ratio=0.5, n_critical=16,
                      n_sink=2, n_recent=8, v_bits=8, v_group=32,
                      skip_layers_front=1, skip_layers_back=1)
    proj = cal.random_layer_projectors(KEY, cfg, sals, cfg.n_layers)
    return cfg, params, sals, proj


@pytest.fixture(scope="module")
def eng(model):
    """ONE paged engine shared by most tests (compiled HLOs amortize);
    auditing every step so property (a) is checked implicitly — any
    violation raises PagerInvariantError out of run()."""
    cfg, params, sals, proj = model
    scfg = ServeConfig(max_seq_len=128, max_new_tokens=8, max_batch=3,
                       sals=sals, prefill_chunk=8, page_size=16,
                       prefill_token_budget=8,   # 1 chunk/sweep: prefill
                       audit_every=1)            # stays observable mid-flight
    return ServeEngine(params, proj, cfg, scfg)


def _reqs(prompts, mnt=4, **kw):
    return [Request(np.asarray(p, np.int32), max_new_tokens=mnt, **kw)
            for p in prompts]


def _run(eng, reqs, schedule=None, on_step=None):
    sched = RequestScheduler(eng, mode="continuous")
    for r in reqs:
        sched.submit(r)
    if schedule is None:
        sched.run(on_step=on_step)
    else:
        with faults.injected(schedule):
            sched.run(on_step=on_step)
    return sched


def _drain_check(sched):
    """No leak at drain: audit passes, and once the prefix-cache entries
    release their pins the pool holds zero live pages."""
    sched.audit_serving_state()
    if sched.prefix_index is not None:
        for e in sched.prefix_index.entries:
            sched.prefix_index.evict(e)
    if sched.pool is not None:
        assert sched.pool.pages_in_use == 0
        sched.pool.check()


PROMPTS = None


def _workload(model):
    """Fixed request stream incl. a shared 2-page prefix (exercises the
    prefix-resume and pin paths under faults)."""
    global PROMPTS
    if PROMPTS is None:
        rng = np.random.default_rng(42)
        head = rng.integers(1, 128, size=32).astype(np.int32)
        PROMPTS = [
            rng.integers(1, 128, size=11).astype(np.int32),
            np.concatenate([head,
                            rng.integers(1, 128, size=7).astype(np.int32)]),
            rng.integers(1, 128, size=26).astype(np.int32),
            np.concatenate([head,
                            rng.integers(1, 128, size=13).astype(np.int32)]),
            rng.integers(1, 128, size=18).astype(np.int32),
        ]
    return PROMPTS


REFERENCE = {}


def _reference(eng, model):
    """Fault-free greedy outputs of the fixed workload (computed once)."""
    if "tokens" not in REFERENCE:
        reqs = _reqs(_workload(model))
        sched = _run(eng, reqs)
        assert all(r.done for r in reqs)
        REFERENCE["tokens"] = [r.result.tokens.copy() for r in reqs]
        _drain_check(sched)
    return REFERENCE["tokens"]


# ---------------------------------------------------------------------------
# hooks are no-ops when disabled
# ---------------------------------------------------------------------------

def test_fault_hooks_noop_when_disabled(eng, model):
    """Acceptance: with no schedule installed the hooks change nothing —
    same tokens, same ledgers, and the pager hook stays unwired."""
    from repro.core import pager
    assert faults.active() is None and pager._fault_hook is None
    ref = _reference(eng, model)
    # an installed-but-empty schedule must also change nothing
    reqs = _reqs(_workload(model))
    sched = _run(eng, reqs, schedule=faults.FaultSchedule(seed=1))
    for r, want in zip(reqs, ref):
        assert r.state is RequestState.DONE
        np.testing.assert_array_equal(r.result.tokens, want)
    assert sched.failures == sched.retries == sched.step_faults == 0
    assert faults.active() is None and pager._fault_hook is None
    _drain_check(sched)


# ---------------------------------------------------------------------------
# per-point regressions: isolation + retry + teardown
# ---------------------------------------------------------------------------

def test_nan_logits_fails_only_victim(eng, model):
    """One poisoned decode row: the victim retries (greedy re-run, token-
    exact) and every other resident never notices."""
    ref = _reference(eng, model)
    reqs = _reqs(_workload(model))
    sched = _run(eng, reqs,
                 schedule=faults.FaultSchedule(at={"nan_logits": [0]}))
    for r, want in zip(reqs, ref):
        assert r.state is RequestState.DONE, (r.req_id, r.state, r.error)
        np.testing.assert_array_equal(r.result.tokens, want)
    assert sched.retries == 1 and sched.failures == 0
    _drain_check(sched)


def test_nan_logits_exhausts_retries_into_failed(eng, model):
    """A row that poisons on every attempt ends FAILED with the error
    attached — never an infinite retry loop, never a crashed loop."""
    rng = np.random.default_rng(3)
    reqs = _reqs([rng.integers(1, 128, size=10).astype(np.int32)], mnt=6)
    # solo resident: every strike hits this request; 3 strikes > 2 retries
    sched = _run(eng, reqs,
                 schedule=faults.FaultSchedule(at={"nan_logits": [0, 1, 2]}))
    (r,) = reqs
    assert r.state is RequestState.FAILED
    assert isinstance(r.error, NanLogitsError)
    assert r.result is None and r.retries == 2
    assert sched.failures == 1 and sched.retries == 2
    _drain_check(sched)


def test_prefill_chunk_fault_retries_token_exact(eng, model):
    ref = _reference(eng, model)
    reqs = _reqs(_workload(model))
    sched = _run(eng, reqs,
                 schedule=faults.FaultSchedule(at={"prefill_chunk": [1]}))
    for r, want in zip(reqs, ref):
        assert r.state is RequestState.DONE, (r.req_id, r.state, r.error)
        np.testing.assert_array_equal(r.result.tokens, want)
    assert sched.retries == 1
    _drain_check(sched)


def test_admit_fault_releases_reservation(eng, model):
    """A torn admission splice releases the whole reservation (incl.
    shared-prefix refcounts) and the retry still lands token-exact."""
    ref = _reference(eng, model)
    reqs = _reqs(_workload(model))
    sched = _run(eng, reqs,
                 schedule=faults.FaultSchedule(at={"admit": [0, 2]}))
    for r, want in zip(reqs, ref):
        assert r.state is RequestState.DONE, (r.req_id, r.state, r.error)
        np.testing.assert_array_equal(r.result.tokens, want)
    assert sched.retries == 2
    _drain_check(sched)


def test_prefix_resume_fault_no_pin_leak(eng, model):
    """A fault on the prefix-resume branch must not leak the matched
    entry's pins nor the reservation; the retry resumes and matches."""
    ref = _reference(eng, model)
    reqs = _reqs(_workload(model))
    sched = _run(eng, reqs,
                 schedule=faults.FaultSchedule(at={"prefix_resume": [0]}))
    for r, want in zip(reqs, ref):
        assert r.state is RequestState.DONE, (r.req_id, r.state, r.error)
        np.testing.assert_array_equal(r.result.tokens, want)
    assert sched.retries == 1
    _drain_check(sched)


def test_page_alloc_fault_during_reservation(eng, model):
    """An alloc fault mid-reservation tears the PARTIAL page table down
    (all-or-nothing) — audited every step, drains leak-free."""
    ref = _reference(eng, model)
    reqs = _reqs(_workload(model))
    sched = _run(eng, reqs,
                 schedule=faults.FaultSchedule(at={"page_alloc": [2, 9]}))
    for r, want in zip(reqs, ref):
        assert r.state is RequestState.DONE, (r.req_id, r.state, r.error)
        np.testing.assert_array_equal(r.result.tokens, want)
    assert sched.retries >= 1
    _drain_check(sched)


def test_decode_step_fault_retries_step(eng, model):
    """Batch-wide decode faults retry the STEP (no request pays) — bounded
    so a saturated schedule raises instead of spinning."""
    ref = _reference(eng, model)
    reqs = _reqs(_workload(model))
    sched = _run(eng, reqs,
                 schedule=faults.FaultSchedule(at={"decode_step": [1]}))
    for r, want in zip(reqs, ref):
        assert r.state is RequestState.DONE
        np.testing.assert_array_equal(r.result.tokens, want)
    assert sched.step_faults == 1 and sched.failures == 0
    _drain_check(sched)
    # consecutive faults beyond the retry bound must propagate, not spin
    reqs = _reqs(_workload(model)[:1])
    with pytest.raises(faults.InjectedFault):
        _run(eng, reqs,
             schedule=faults.FaultSchedule(at={"decode_step": [0, 1, 2]}))


def test_draft_verify_fault_retries_round_token_exact(model):
    """ISSUE 9: ``draft_verify`` fires BEFORE the windowed verify jit
    call, so an injected fault retries the whole round — drafting is pure
    host work, re-proposing is free — and the run stays token-exact vs
    the fault-free speculative run, cache audited every step."""
    cfg, params, sals, proj = model
    scfg = ServeConfig(max_seq_len=128, max_new_tokens=8, max_batch=2,
                       sals=sals, prefill_chunk=8, page_size=16,
                       prefill_token_budget=8, audit_every=1,
                       spec_window=4, temperature=0.0)
    eng_s = ServeEngine(params, proj, cfg, scfg)
    rng = np.random.default_rng(31)
    base = rng.integers(1, 128, size=8).astype(np.int32)
    prompts = [np.tile(base, 4)[:20], np.tile(base, 4)[:26]]

    def run(schedule=None):
        reqs = _reqs(prompts, mnt=9)
        sched = _run(eng_s, reqs, schedule=schedule)
        for r in reqs:
            assert r.state is RequestState.DONE, (r.req_id, r.state, r.error)
        _drain_check(sched)
        return [r.result.tokens.copy() for r in reqs], sched

    want, s0 = run()
    assert s0.step_faults == 0 and s0.spec_rounds > 0
    got, s1 = run(faults.FaultSchedule(at={"draft_verify": [1]}))
    for w, g in zip(want, got):
        np.testing.assert_array_equal(g, w)
    # the round retried as a STEP fault: no request paid, no row retry
    assert s1.step_faults == 1 and s1.retries == 0 and s1.failures == 0
    # rounds may re-batch (the retry shifts admission interleaving) but
    # every token still commits through a verify round
    assert s1.spec_rounds > 0
    assert s1.spec_committed == sum(len(t) for t in got) - len(got)
    # consecutive strikes beyond the bound must propagate, not spin
    reqs = _reqs(prompts[:1], mnt=6)
    with pytest.raises(faults.InjectedFault):
        _run(eng_s, reqs,
             schedule=faults.FaultSchedule(at={"draft_verify": [0, 1, 2]}))


# ---------------------------------------------------------------------------
# deadlines / cancellation / backpressure
# ---------------------------------------------------------------------------

def test_request_timeout_tears_down(eng, model):
    rng = np.random.default_rng(5)
    slow = Request(rng.integers(1, 128, size=9).astype(np.int32),
                   max_new_tokens=30, timeout_steps=4)
    ok = Request(rng.integers(1, 128, size=9).astype(np.int32),
                 max_new_tokens=6)
    sched = RequestScheduler(eng, mode="continuous")
    sched.submit(slow)
    sched.submit(ok)
    sched.run()
    assert slow.state is RequestState.TIMED_OUT and slow.result is None
    assert ok.state is RequestState.DONE and len(ok.result.tokens) == 6
    assert sched.timeouts == 1
    _drain_check(sched)


def test_cancel_mid_decode_spares_others(eng, model):
    ref = _reference(eng, model)
    reqs = _reqs(_workload(model))
    victim = reqs[0]

    def on_step(s, step):
        if step == 2:
            victim.cancel()

    sched = _run(eng, reqs, on_step=on_step)
    assert victim.state is RequestState.CANCELLED and victim.result is None
    for r, want in zip(reqs[1:], ref[1:]):
        assert r.state is RequestState.DONE
        np.testing.assert_array_equal(r.result.tokens, want)
    assert sched.cancellations == 1
    _drain_check(sched)


def test_cancel_mid_prefill_no_pinned_entry_leak(eng, model):
    """ISSUE 6 satellite: cancelling a request whose prefix-hit prefill is
    still chunking must release its shared-page refcounts and register NO
    entry — the index and pool drain clean."""
    rng = np.random.default_rng(7)
    head = rng.integers(1, 128, size=32).astype(np.int32)     # 2 pages
    first = Request(np.concatenate(
        [head, rng.integers(1, 128, size=6).astype(np.int32)]),
        max_new_tokens=20)
    # long suffix: many chunks -> still PREFILLING when step 1 fires
    follower = Request(np.concatenate(
        [head, rng.integers(1, 128, size=80).astype(np.int32)]),
        max_new_tokens=4)
    sched = RequestScheduler(eng, mode="continuous")
    sched.submit(first)
    sched.submit(follower)
    cancelled_in = {}

    def on_step(s, step):
        if s._active is not None and s._active.req is follower:
            cancelled_in["state"] = follower.state
            follower.cancel()

    sched.run(on_step=on_step)
    assert cancelled_in.get("state") is RequestState.PREFILLING
    assert follower.state is RequestState.CANCELLED
    assert first.state is RequestState.DONE
    # only the FIRST request registered an entry; the follower's shared
    # refcounts are gone: entries pin exactly their own pages
    entries = sched.prefix_index.entries
    assert len(entries) == 1
    _drain_check(sched)


def test_bounded_queue_reject_and_shed(model):
    """submit() backpressure is typed and immediate — no engine compile,
    no silent drop."""
    cfg, params, sals, proj = model
    rng = np.random.default_rng(9)
    prompts = [rng.integers(1, 128, size=8).astype(np.int32)
               for _ in range(4)]
    scfg = ServeConfig(max_seq_len=128, max_batch=2, sals=sals,
                       prefill_chunk=8, max_queue=2, queue_policy="reject")
    sched = RequestScheduler(ServeEngine(params, proj, cfg, scfg))
    r1, r2, r3, _ = _reqs(prompts)
    sched.submit(r1)
    sched.submit(r2)
    with pytest.raises(QueueFull):
        sched.submit(r3)
    assert r3.state is RequestState.QUEUED      # caller still owns it
    scfg = ServeConfig(max_seq_len=128, max_batch=2, sals=sals,
                       prefill_chunk=8, max_queue=2,
                       queue_policy="shed-oldest")
    sched = RequestScheduler(ServeEngine(params, proj, cfg, scfg))
    q1, q2, q3, q4 = _reqs(prompts)
    sched.submit(q1)
    sched.submit(q2)
    sched.submit(q3)                            # sheds q1
    sched.submit(q4)                            # sheds q2
    assert q1.state is RequestState.CANCELLED
    assert isinstance(q1.error, QueueFull)
    assert q2.state is RequestState.CANCELLED
    assert [r.req_id for r in sched.pending] == [q3.req_id, q4.req_id]
    assert sched.shed == 2 and sched.cancellations == 2


# ---------------------------------------------------------------------------
# prefix-pin accounting (ISSUE 6 satellite)
# ---------------------------------------------------------------------------

def test_entry_eviction_with_live_sharer_keeps_pages():
    """Evicting an entry whose pages a live resident still shares must
    drop only the ENTRY's refcounts — the resident's pages survive and the
    audit stays clean throughout."""
    pool = PagePool(8, 4, n_reserved=1)
    idx = PrefixIndex(pool)
    reg = PageTable(pool, 4)                   # the registrant's table
    reg.append_page()
    reg.append_page()
    toks = np.arange(8, dtype=np.int32)
    entry = idx.insert(toks, list(reg.pages), {1: None, 2: None}, None, None)
    live = PageTable(pool, 4)                  # a follower shares both pages
    live.append_shared(entry.page_ids[0])
    live.append_shared(entry.page_ids[1])
    reg.release_all()                          # registrant finished
    audit_pager(pool, [live], idx.entries)
    idx.evict(entry)                           # entry evicted under pressure
    audit_pager(pool, [live], [])
    for pid in live.pages:                     # sharer's pages still live
        assert pool.refcount(pid) == 1
    live.release_all()
    assert pool.pages_in_use == 0
    pool.check()


class _Census:
    """Pool + tables + prefix index driven by named ops, audited after
    every op — shared body of the hypothesis state machine and its
    deterministic fallback."""

    def __init__(self):
        self.pool = PagePool(16, 4, n_reserved=1)
        self.tables = [PageTable(self.pool, 8) for _ in range(3)]
        self.idx = PrefixIndex(self.pool)
        self.serial = 0

    def grow(self, t):
        tab = self.tables[t]
        if self.pool.pages_free and tab.n_pages < tab.max_pages:
            tab.append_page()

    def share(self, src, dst):
        ts, td = self.tables[src], self.tables[dst]
        if ts.pages and td.n_pages < td.max_pages:
            td.append_shared(ts.pages[-1])

    def register(self, t):
        # a finished prefill registers its whole-page prefix (the entry
        # takes its OWN pins — the registrant may release later)
        tab = self.tables[t]
        if tab.n_pages == 0:
            return
        self.serial += 1
        toks = np.arange(self.serial * 1000,
                         self.serial * 1000 + tab.n_pages * 4, dtype=np.int32)
        self.idx.insert(toks, list(tab.pages), {}, None, None)

    def evict(self, k):
        entries = self.idx.entries
        if entries:
            self.idx.evict(entries[k % len(entries)])

    def release(self, t):
        self.tables[t].release_all()

    def audit(self):
        audit_pager(self.pool, self.tables, self.idx.entries)

    def drain(self):
        for e in self.idx.entries:
            self.idx.evict(e)
        for t in self.tables:
            t.release_all()
        self.audit()
        assert self.pool.pages_in_use == 0


@pytest.mark.skipif(not HAVE_HYPOTHESIS, reason="hypothesis not installed")
def test_chaos_pager_state_machine():
    """ISSUE 6 tentpole: random alloc/share/register/evict/release
    interleavings with the cross-structure audit as the invariant after
    EVERY rule — the eviction/COW/prefix edge cases cannot leak."""

    class AuditMachine(stateful.RuleBasedStateMachine):
        def __init__(self):
            super().__init__()
            self.c = _Census()

        @stateful.rule(t=st.integers(0, 2))
        def grow(self, t):
            self.c.grow(t)

        @stateful.rule(src=st.integers(0, 2), dst=st.integers(0, 2))
        def share(self, src, dst):
            self.c.share(src, dst)

        @stateful.rule(t=st.integers(0, 2))
        def register(self, t):
            self.c.register(t)

        @stateful.rule(k=st.integers(0, 7))
        def evict(self, k):
            self.c.evict(k)

        @stateful.rule(t=st.integers(0, 2))
        def release(self, t):
            self.c.release(t)

        @stateful.invariant()
        def audited(self):
            self.c.audit()

    stateful.run_state_machine_as_test(
        AuditMachine, settings=settings(max_examples=25,
                                        stateful_step_count=50,
                                        deadline=None))


def test_chaos_pager_census_deterministic():
    """Seeded replay of the state-machine rules (always runs, hypothesis
    or not), ending in a full drain to zero live pages."""
    rng = np.random.default_rng(13)
    c = _Census()
    ops = [lambda: c.grow(int(rng.integers(3))),
           lambda: c.share(int(rng.integers(3)), int(rng.integers(3))),
           lambda: c.register(int(rng.integers(3))),
           lambda: c.evict(int(rng.integers(8))),
           lambda: c.release(int(rng.integers(3)))]
    for _ in range(300):
        ops[int(rng.integers(len(ops)))]()
        c.audit()
    c.drain()


# ---------------------------------------------------------------------------
# lifecycle + auditor units
# ---------------------------------------------------------------------------

def test_lifecycle_transition_table():
    r = Request(np.array([1], np.int32))
    assert r.state is RequestState.QUEUED and not r.finished
    transition(r, RequestState.PREFILLING)
    transition(r, RequestState.QUEUED)         # retry requeue
    transition(r, RequestState.PREFILLING)
    transition(r, RequestState.DECODING)
    transition(r, RequestState.DONE)
    assert r.done and r.finished
    for bad in (RequestState.QUEUED, RequestState.DONE,
                RequestState.FAILED):          # terminal states are frozen
        with pytest.raises(LifecycleError):
            transition(r, bad)
    f = Request(np.array([1], np.int32))
    boom = RuntimeError("boom")
    transition(f, RequestState.FAILED, boom)
    assert f.error is boom and f.finished and not f.done
    with pytest.raises(LifecycleError):
        transition(f, RequestState.DECODING)   # no resurrection


def test_auditor_detects_hand_corruption():
    """The auditor raises TYPED errors (python -O safe) for each broken
    conservation invariant."""
    pool = PagePool(6, 4, n_reserved=1)
    t = PageTable(pool, 4)
    t.append_page()
    t.append_page()
    audit_pager(pool, [t], [])
    # 1) orphaned pool ref (leak)
    pool._ref[t.pages[0]] += 1
    with pytest.raises(PagerInvariantError, match="leaked"):
        audit_pager(pool, [t], [])
    pool._ref[t.pages[0]] -= 1
    # 2) owner without pool ref (table maps a freed page)
    ghost = PageTable(pool, 4)
    ghost.pages = [t.pages[1]]                 # duplicate claim, no share()
    with pytest.raises(PagerInvariantError, match="over-referenced"):
        audit_pager(pool, [t, ghost], [])
    ghost.pages = []
    # 3) table maps the reserved trash page
    ghost.pages = [0]
    with pytest.raises(PagerInvariantError, match="reserved"):
        audit_pager(pool, [t, ghost], [])
    ghost.pages = []
    # 4) gauge drift
    with pytest.raises(PagerInvariantError, match="gauge"):
        audit_pager(pool, [t], [], gauges={"pages_in_use": 99})
    # 5) free-stack corruption through PagePool.check (typed, not assert)
    pid = t.pages[0]
    pool._free.append(pid)                     # live page on the free stack
    with pytest.raises(PagerInvariantError):
        pool.check()
    pool._free.pop()
    t.release_all()
    audit_pager(pool, [], [])


def test_scheduler_audit_catches_external_corruption(eng, model):
    """End-to-end: corrupting the pool mid-run makes the NEXT step's audit
    raise PagerInvariantError out of run() — the auditor is live, not
    decorative."""
    rng = np.random.default_rng(11)
    reqs = _reqs([rng.integers(1, 128, size=10).astype(np.int32)], mnt=8)

    def on_step(s, step):
        if step == 2:
            # simulate a lost free: drop a live table ref behind the
            # pool's back
            pid = next(t for t in s._tables if t is not None).pages[0]
            s.pool._ref[pid] += 1

    sched = RequestScheduler(eng, mode="continuous")
    for r in reqs:
        sched.submit(r)
    with pytest.raises(PagerInvariantError):
        sched.run(on_step=on_step)


# ---------------------------------------------------------------------------
# randomized arrival × fault sweep (deterministic seeds always run;
# hypothesis widens the seed space when installed)
# ---------------------------------------------------------------------------

RATES = {"page_alloc": 0.04, "prefill_chunk": 0.04, "admit": 0.04,
         "decode_step": 0.02, "nan_logits": 0.03, "prefix_resume": 0.1,
         "cow_copy": 0.02}
STEP_BOUND = 400


def _chaos_run(eng, model, seed):
    """One randomized chaos episode.  Asserts the three acceptance
    properties; audit_every=1 on the engine makes (a) implicit."""
    ref = _reference(eng, model)
    reqs = _reqs(_workload(model))
    schedule = faults.FaultSchedule(seed=seed, rates=RATES)
    try:
        sched = _run(eng, reqs, schedule=schedule)
    except faults.InjectedFault:
        # only legal escape: a decode_step streak beyond the retry bound
        # (rate-scheduled runs can roll one); anything else must be handled
        assert schedule.log[-1][0] == "decode_step"
        return
    assert sched.steps <= STEP_BOUND, "livelock: step bound exceeded"
    for r, want in zip(reqs, ref):
        assert r.finished, (r.req_id, r.state)
        if r.state is RequestState.DONE:       # (b) token-exactness
            np.testing.assert_array_equal(r.result.tokens, want)
        else:
            assert r.state is RequestState.FAILED
            assert r.error is not None
    _drain_check(sched)                        # no leak at drain


# CI extends the committed seeds with run-number-derived ones (replayable:
# the parametrize id in the failure log IS the seed to rerun locally)
_EXTRA_SEEDS = [int(s) for s in
                os.environ.get("SALS_CHAOS_SEEDS", "").split(",") if s]


@pytest.mark.parametrize("seed", [0, 1, 2, 3] + _EXTRA_SEEDS)
def test_chaos_sweep_deterministic(eng, model, seed):
    _chaos_run(eng, model, seed)


@pytest.mark.skipif(not HAVE_HYPOTHESIS, reason="hypothesis not installed")
@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 2 ** 16))
def test_chaos_sweep_randomized(eng, model, seed):
    _chaos_run(eng, model, seed)


# ---------------------------------------------------------------------------
# two-tier pool chaos (ISSUE 7): the tier-transfer fault points ride the
# same schedules; tier conservation is audited every step
# ---------------------------------------------------------------------------

TIERED_RATES = dict(RATES, host_fetch=0.05, spill=0.05)


@pytest.fixture(scope="module")
def eng_tiered(model):
    """Paged engine with the hot tier at its FLOOR (max_batch + 1 = 4):
    the fixed workload's live pages far exceed HBM, so every episode
    sees demand fetches, spills, prefetches, and thrash shedding — and
    their fault points."""
    cfg, params, sals, proj = model
    scfg = ServeConfig(max_seq_len=128, max_new_tokens=8, max_batch=3,
                       sals=sals, prefill_chunk=8, page_size=16,
                       prefill_token_budget=8, hbm_pages=4, audit_every=1)
    return ServeEngine(params, proj, cfg, scfg)


def _drain_check_tiered(sched):
    """PR 7 drain: on top of zero live pages, BOTH tiers are empty,
    nothing is mid-transfer, and every hot slot is back on the free
    list."""
    _drain_check(sched)
    pool = sched.pool
    assert not pool.in_flight
    assert not pool.hot and pool.host_pages == 0 and not pool.fresh
    assert pool.slots_free == pool.hbm_slots
    pool.audit_tiers()


def test_tiered_transfer_faults_retry_token_exact(eng, eng_tiered, model):
    """One injected fault on each tier-transfer point: the page stays in
    its prior tier (the hook fires BEFORE any state change), only the
    demanding row pays a transient retry, and the run ends token-exact
    vs the UNTIERED fault-free reference."""
    ref = _reference(eng, model)
    for point in ("host_fetch", "spill"):
        reqs = _reqs(_workload(model))
        schedule = faults.FaultSchedule(at={point: [0]})
        sched = _run(eng_tiered, reqs, schedule=schedule)
        assert schedule.log == [(point, 0)], f"{point} never fired"
        for r, want in zip(reqs, ref):
            assert r.state is RequestState.DONE, \
                (point, r.req_id, r.state, r.error)
            np.testing.assert_array_equal(r.result.tokens, want)
        _drain_check_tiered(sched)


def _chaos_run_tiered(eng, eng_tiered, model, seed):
    """One randomized episode over the TIERED pool: same three acceptance
    properties as :func:`_chaos_run` (audit_every=1 now also proves tier
    conservation via ``audit_tiers``), with DONE rows token-exact vs the
    UNTIERED fault-free reference — faults and placement both invisible."""
    ref = _reference(eng, model)
    reqs = _reqs(_workload(model))
    schedule = faults.FaultSchedule(seed=seed, rates=TIERED_RATES)
    try:
        sched = _run(eng_tiered, reqs, schedule=schedule)
    except faults.InjectedFault:
        assert schedule.log[-1][0] == "decode_step"
        return
    assert sched.steps <= STEP_BOUND, "livelock: step bound exceeded"
    for r, want in zip(reqs, ref):
        assert r.finished, (r.req_id, r.state)
        if r.state is RequestState.DONE:
            np.testing.assert_array_equal(r.result.tokens, want)
        else:
            assert r.state is RequestState.FAILED
            assert r.error is not None
    _drain_check_tiered(sched)


@pytest.mark.parametrize("seed", [0, 1, 2, 3] + _EXTRA_SEEDS)
def test_tiered_chaos_sweep_deterministic(eng, eng_tiered, model, seed):
    _chaos_run_tiered(eng, eng_tiered, model, seed)


@pytest.mark.skipif(not HAVE_HYPOTHESIS, reason="hypothesis not installed")
@settings(max_examples=4, deadline=None)
@given(seed=st.integers(0, 2 ** 16))
def test_tiered_chaos_sweep_randomized(eng, eng_tiered, model, seed):
    _chaos_run_tiered(eng, eng_tiered, model, seed)


# ---------------------------------------------------------------------------
# preempt-park chaos (ISSUE 8): park/resume fault points under mixed-
# priority arrivals; parked-page conservation is audited every step
# ---------------------------------------------------------------------------

PARK_RATES = dict(RATES, park=0.1, resume=0.1)
TIERED_PARK_RATES = dict(PARK_RATES, host_fetch=0.05, spill=0.05)


@pytest.fixture(scope="module")
def eng_prio(model):
    """2-slot prioritized paged engine: every high-priority arrival finds
    a full arena, so parks/resumes are routine, not exceptional."""
    cfg, params, sals, proj = model
    scfg = ServeConfig(max_seq_len=128, max_new_tokens=8, max_batch=2,
                       sals=sals, prefill_chunk=8, page_size=16,
                       prefill_token_budget=8, audit_every=1,
                       priority_classes=2, preempt_policy="park")
    return ServeEngine(params, proj, cfg, scfg)


@pytest.fixture(scope="module")
def eng_prio_tiered(model):
    """The prioritized engine with a small hot tier on top: parked pages
    must additionally drain cold and never hold write pins."""
    cfg, params, sals, proj = model
    scfg = ServeConfig(max_seq_len=128, max_new_tokens=8, max_batch=2,
                       sals=sals, prefill_chunk=8, page_size=16,
                       prefill_token_budget=8, audit_every=1, hbm_pages=6,
                       priority_classes=2, preempt_policy="park")
    return ServeEngine(params, proj, cfg, scfg)


def _park_reqs(model, priorities=False):
    """The fixed workload split into two long low-priority residents and
    three short high-priority arrivals (priority 0 everywhere for the
    single-class reference engine)."""
    ps = _workload(model)
    lo, hi = (0, 1) if priorities else (0, 0)
    return ([Request(p, max_new_tokens=8, priority=lo) for p in ps[:2]]
            + [Request(p, max_new_tokens=4, priority=hi) for p in ps[2:]])


PARK_REFERENCE = {}


def _park_reference(eng, model):
    """Fault-free FIFO outputs of the park workload (computed once)."""
    if "tokens" not in PARK_REFERENCE:
        reqs = _park_reqs(model)
        sched = _run(eng, reqs)
        assert all(r.done for r in reqs)
        PARK_REFERENCE["tokens"] = [r.result.tokens.copy() for r in reqs]
        _drain_check(sched)
    return PARK_REFERENCE["tokens"]


def _staged_park_run(eng_p, reqs, schedule):
    """Submit the two low-priority requests up front, drop the three
    high-priority ones mid-generation (>= trigger steps, robust to the
    backoff fast-forward skipping exact step values)."""
    sched = RequestScheduler(eng_p)
    for r in reqs[:2]:
        sched.submit(r)
    arrivals = [(2, reqs[2]), (4, reqs[3]), (6, reqs[4])]

    def on_step(sch, step):
        while arrivals and step >= arrivals[0][0]:
            sch.submit(arrivals.pop(0)[1])

    if schedule is None:
        sched.run(on_step=on_step)
    else:
        with faults.injected(schedule):
            sched.run(on_step=on_step)
    assert not arrivals
    return sched


def test_park_fault_leaves_victim_resident(eng, eng_prio, model):
    """An injected ``park`` fault fires BEFORE the snapshot read: the
    preemption is simply abandoned for that iteration (victim stays
    resident, keeps decoding) and retried later — every request still
    lands token-exact."""
    ref = _park_reference(eng, model)
    reqs = _park_reqs(model, priorities=True)
    schedule = faults.FaultSchedule(at={"park": [0]})
    sched = _staged_park_run(eng_prio, reqs, schedule)
    assert ("park", 0) in schedule.log, "park point never exercised"
    for r, want in zip(reqs, ref):
        assert r.state is RequestState.DONE, (r.req_id, r.state, r.error)
        np.testing.assert_array_equal(r.result.tokens, want)
    _drain_check(sched)


def test_resume_fault_restarts_parked_request(eng, eng_prio, model):
    """An injected ``resume`` fault fires BEFORE the splice: the parked
    record is still whole, its pages are released, and the request
    re-runs from scratch through the standard retry policy — greedy
    decoding makes the restart invisible in the final tokens."""
    ref = _park_reference(eng, model)
    reqs = _park_reqs(model, priorities=True)
    schedule = faults.FaultSchedule(at={"resume": [0]})
    sched = _staged_park_run(eng_prio, reqs, schedule)
    assert ("resume", 0) in schedule.log, "resume point never exercised"
    assert sched.parks >= 1 and sched.retries >= 1
    for r, want in zip(reqs, ref):
        assert r.state is RequestState.DONE, (r.req_id, r.state, r.error)
        np.testing.assert_array_equal(r.result.tokens, want)
    _drain_check(sched)


def test_park_round_trip_under_no_faults(eng, eng_prio, model):
    """Fault-free contended episode: parks AND resumes both happen, and
    every request (victims included) matches the FIFO reference."""
    ref = _park_reference(eng, model)
    reqs = _park_reqs(model, priorities=True)
    sched = _staged_park_run(eng_prio, reqs, None)
    assert sched.parks >= 1 and sched.resumes >= 1
    for r, want in zip(reqs, ref):
        assert r.state is RequestState.DONE, (r.req_id, r.state, r.error)
        np.testing.assert_array_equal(r.result.tokens, want)
    _drain_check(sched)


def _park_chaos_run(eng, eng_p, model, seed, rates, tiered=False):
    """One randomized park episode: same acceptance contract as
    :func:`_chaos_run` — audit_every=1 additionally proves, every step,
    that parked page tables stay inside the pager census (and cold /
    unpinned under tiering) while faults hammer every point."""
    ref = _park_reference(eng, model)
    reqs = _park_reqs(model, priorities=True)
    schedule = faults.FaultSchedule(seed=seed, rates=rates)
    try:
        sched = _staged_park_run(eng_p, reqs, schedule)
    except faults.InjectedFault:
        assert schedule.log[-1][0] == "decode_step"
        return
    assert sched.steps <= STEP_BOUND, "livelock: step bound exceeded"
    for r, want in zip(reqs, ref):
        assert r.finished, (r.req_id, r.state)
        if r.state is RequestState.DONE:
            np.testing.assert_array_equal(r.result.tokens, want)
        else:
            assert r.state is RequestState.FAILED
            assert r.error is not None
    (_drain_check_tiered if tiered else _drain_check)(sched)


@pytest.mark.parametrize("seed", [0, 1, 2, 3] + _EXTRA_SEEDS)
def test_park_chaos_sweep_deterministic(eng, eng_prio, model, seed):
    _park_chaos_run(eng, eng_prio, model, seed, PARK_RATES)


@pytest.mark.skipif(not HAVE_HYPOTHESIS, reason="hypothesis not installed")
@settings(max_examples=4, deadline=None)
@given(seed=st.integers(0, 2 ** 16))
def test_park_chaos_sweep_randomized(eng, eng_prio, model, seed):
    _park_chaos_run(eng, eng_prio, model, seed, PARK_RATES)


@pytest.mark.parametrize("seed", [0, 1])
def test_tiered_park_chaos_sweep(eng, eng_prio_tiered, model, seed):
    _park_chaos_run(eng, eng_prio_tiered, model, seed, TIERED_PARK_RATES,
                    tiered=True)


# ---------------------------------------------------------------------------
# unified telemetry conservation (ISSUE 10): re-run the chaos episodes with
# the FULL obs stack installed (registry + tracer, audit_every=1 on the
# engines) and assert the metrics themselves conserve: submitted == Σ
# terminal-state counters, every span balanced with zero dangling tracks at
# drain, exporters schema-valid
# ---------------------------------------------------------------------------

from repro import obs                                        # noqa: E402


def _obs_conservation_check(sched, handles):
    assert sched.submitted == (sched.done + sched.failures + sched.timeouts
                               + sched.cancellations), \
        "a request left the system without hitting a terminal counter"
    reg = handles["registry"]
    assert reg.counter("serve_requests_submitted_total").value() == \
        sched.submitted
    tr = handles["tracer"]
    assert tr.balanced(), (tr.begun, tr.ended, tr.open_tracks())
    assert tr.open_tracks() == []                # zero dangling at drain
    assert obs.trace.validate_chrome_trace(tr.chrome_trace()) == []
    from repro.obs.metrics import validate_prometheus, validate_snapshot
    assert validate_snapshot(reg.snapshot()) == []
    assert validate_prometheus(reg.to_prometheus()) == []


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_obs_chaos_conservation(eng, model, seed):
    """The randomized fault sweep with telemetry on: the same acceptance
    properties as test_chaos_sweep_deterministic PLUS metric conservation
    — and the telemetry itself must not perturb tokens (DONE rows stay
    exact vs the obs-off reference)."""
    cfg, params, sals, proj = model
    ref = _reference(eng, model)
    reqs = _reqs(_workload(model))
    schedule = faults.FaultSchedule(seed=seed, rates=RATES)
    with obs.enabled() as handles:
        try:
            sched = _run(eng, reqs, schedule=schedule)
        except faults.InjectedFault:
            assert schedule.log[-1][0] == "decode_step"
            return
        _obs_conservation_check(sched, handles)
    for r, want in zip(reqs, ref):
        assert r.finished, (r.req_id, r.state)
        if r.state is RequestState.DONE:
            np.testing.assert_array_equal(r.result.tokens, want)
    _drain_check(sched)


@pytest.mark.parametrize("seed", [0, 1])
def test_obs_tiered_chaos_conservation(eng, eng_tiered, model, seed):
    """Tiered flavor: fetch/spill fault points fire mid-transfer — spans
    for aborted transfers must still close."""
    cfg, params, sals, proj = model
    _reference(eng, model)
    reqs = _reqs(_workload(model))
    schedule = faults.FaultSchedule(seed=seed, rates=TIERED_RATES)
    with obs.enabled() as handles:
        try:
            sched = _run(eng_tiered, reqs, schedule=schedule)
        except faults.InjectedFault:
            assert schedule.log[-1][0] == "decode_step"
            return
        _obs_conservation_check(sched, handles)
    _drain_check_tiered(sched)


@pytest.mark.parametrize("seed", [0, 1])
def test_obs_park_chaos_conservation(eng, eng_prio, model, seed):
    """Park/resume flavor: parked requests hold an open 'parked' phase
    span while off the arena; every park/resume/retry path must hand the
    span back before drain."""
    cfg, params, sals, proj = model
    _park_reference(eng, model)
    reqs = _park_reqs(model, priorities=True)
    schedule = faults.FaultSchedule(seed=seed, rates=PARK_RATES)
    with obs.enabled() as handles:
        try:
            sched = _staged_park_run(eng_prio, reqs, schedule)
        except faults.InjectedFault:
            assert schedule.log[-1][0] == "decode_step"
            return
        _obs_conservation_check(sched, handles)
    _drain_check(sched)
