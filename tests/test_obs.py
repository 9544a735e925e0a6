"""Unified telemetry: metrics registry and span tracer.

Four contracts under test:

  (a) the registry's instruments are typed, labeled, LRU-bounded by the
      ``gauge_history`` policy, and both exporters (Prometheus text, JSON
      snapshot) emit schema-valid output;
  (b) spans balance — through every teardown/retry path, park/evict/fault
      episodes included — and the Chrome-trace export stays valid;
  (c) under ``jax.profiler`` the scheduler's spans land in the profile as
      nested ``serve.*`` host events, one ``serve.decode_dispatch`` per
      decode step, and the jitted steps keep their names while their ops
      carry the model's named scopes (``weights``, ``seg*``,
      ``cache_carry``, ``head``);
  (d) telemetry is invisible when disabled — the core hook stays None,
      no ``serve.*`` event reaches a profile, and scheduler/engine
      behavior is unchanged.
"""
import glob
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.config import SALSConfig, ServeConfig
from repro.configs import get_config
from repro.core import calibration as cal
from repro.models import transformer as tf
from repro.obs.metrics import (MetricsRegistry, validate_prometheus,
                               validate_snapshot)
from repro.obs.trace import RequestTimeline, SpanTracer, validate_chrome_trace
from repro.serve import Request, RequestScheduler, RequestState, ServeEngine
from repro.serve import faults

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def model():
    """The chaos proxy config: every layer between the skip margins is a
    SALS layer, so the §4.5 ledger has substance."""
    cfg = get_config("qwen2-1.5b").reduced(n_layers=3, vocab_size=128)
    params = tf.init_params(KEY, cfg, jnp.float32)
    sals = SALSConfig(rank_ratio=0.5, score_ratio=0.5, n_critical=16,
                      n_sink=2, n_recent=8, v_bits=8, v_group=32,
                      skip_layers_front=1, skip_layers_back=1)
    proj = cal.random_layer_projectors(KEY, cfg, sals, cfg.n_layers)
    return cfg, params, sals, proj


def _engine(model, **kw):
    cfg, params, sals, proj = model
    base = dict(max_seq_len=128, max_new_tokens=8, max_batch=3, sals=sals,
                prefill_chunk=8, prefill_token_budget=8)
    base.update(kw)
    return ServeEngine(params, proj, cfg, ServeConfig(**base))


@pytest.fixture(scope="module")
def eng_dense(model):
    return _engine(model)


@pytest.fixture(scope="module")
def eng_paged(model):
    return _engine(model, page_size=16, audit_every=1)


@pytest.fixture(scope="module")
def eng_tiered(model):
    return _engine(model, page_size=16, hbm_pages=4, audit_every=1)


@pytest.fixture(scope="module")
def eng_spec(model):
    return _engine(model, page_size=16, audit_every=1, spec_window=4,
                   max_batch=2, temperature=0.0)


def _prompts(seed=42, n=4):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 128, size=int(rng.integers(10, 30)))
            .astype(np.int32) for _ in range(n)]


def _drain(eng, reqs, schedule=None, on_step=None):
    sched = RequestScheduler(eng, mode="continuous")
    for r in reqs:
        sched.submit(r)
    if schedule is None:
        sched.run(on_step=on_step)
    else:
        with faults.injected(schedule):
            sched.run(on_step=on_step)
    return sched


# ---------------------------------------------------------------------------
# (a) registry + exporters
# ---------------------------------------------------------------------------

def test_registry_typed_instruments():
    reg = MetricsRegistry()
    c = reg.counter("req_total", "requests", labelnames=("tenant",))
    c.inc(tenant="a")
    c.inc(2.0, tenant="b")
    assert c.value(tenant="a") == 1.0 and c.value(tenant="b") == 2.0
    with pytest.raises(ValueError):
        c.inc(-1.0, tenant="a")                 # counters are monotonic
    with pytest.raises(ValueError):
        c.inc(tenant="a", extra="x")            # undeclared label
    g = reg.gauge("depth")
    g.set(5.0)
    g.dec(2.0)
    assert g.value() == 3.0
    h = reg.histogram("lat_ms", buckets=(1.0, 10.0, 100.0))
    for v in (0.5, 5.0, 50.0, 500.0):
        h.observe(v)
    assert h.count() == 4 and h.sum() == 555.5
    # idempotent re-registration returns the same instrument; a type or
    # label mismatch is a bug, not a merge
    assert reg.counter("req_total", labelnames=("tenant",)) is c
    with pytest.raises(ValueError):
        reg.gauge("req_total")
    with pytest.raises(ValueError):
        reg.counter("req_total", labelnames=("other",))
    with pytest.raises(ValueError):
        reg.counter("bad name!")


def test_registry_series_lru_cap():
    """max_series is the registry-side twin of the gauge_history ring:
    the least-recently-touched label set is evicted past the cap."""
    reg = MetricsRegistry(max_series=3)
    c = reg.counter("x_total", labelnames=("t",))
    for t in "abcd":
        c.inc(t=t)
    c.inc(t="b")                                # refresh b
    kept = {s["labels"]["t"] for s in reg.snapshot()["metrics"][0]["series"]}
    assert kept == {"b", "c", "d"}              # a was LRU
    assert len(kept) == 3


def test_exporters_validate():
    reg = MetricsRegistry()
    reg.counter("a_total", "help text", labelnames=("k",)).inc(k='q"uote')
    reg.gauge("b").set(-1.5)
    reg.histogram("c_ms").observe(3.0)
    snap = reg.snapshot()
    assert validate_snapshot(snap) == []
    assert validate_snapshot(json.loads(json.dumps(snap))) == []
    assert validate_prometheus(reg.to_prometheus()) == []
    # the validators actually reject garbage
    assert validate_snapshot({"schema": "nope", "metrics": 3})
    assert validate_prometheus('bad{-}line 1\n')


def test_core_hook_contract(model):
    """core.pager._metrics_hook follows the _fault_hook contract: None
    when disabled (zero-cost), wired by install(), counting page events
    under core_events_total when enabled — core never imports obs."""
    from repro.core import pager
    assert pager._metrics_hook is None
    pool = pager.PagePool(4, 4, n_reserved=1)
    pid = pool.alloc()
    pool.free(pid)                              # no registry: nothing breaks
    with obs.metrics.installed(MetricsRegistry()) as reg:
        assert pager._metrics_hook is not None
        pid = pool.alloc()
        pool.share(pid)
        pool.free(pid)
        pool.free(pid)
        ev = reg.counter("core_events_total", labelnames=("point",))
        assert ev.value(point="page_alloc") == 1
        assert ev.value(point="page_share") == 1
        assert ev.value(point="page_free") == 1  # on refcount -> 0 only
    assert pager._metrics_hook is None


# ---------------------------------------------------------------------------
# (b) span tracer
# ---------------------------------------------------------------------------

def test_tracer_balance_and_ring_cap():
    t = [0.0]
    tr = SpanTracer(max_events=2, clock=lambda: t.__setitem__(0, t[0] + 1)
                    or t[0])
    sids = [tr.begin("a", "r1"), tr.begin("b", "r1"), tr.begin("c", "r2")]
    assert tr.open_count == 3 and tr.open_tracks() == ["r1", "r2"]
    assert tr.end(sids[2]) > 0
    with pytest.raises(ValueError):
        tr.end(sids[2])                         # double close is the bug
    assert tr.end_track("r1") == 2              # newest-first unwind
    assert tr.balanced()
    # ring kept only 2 completed events but the CUMULATIVE counters
    # survive eviction — balance checks stay exact
    assert len(tr.events) == 2 and tr.begun == tr.ended == 3
    tr.instant("marker", "r1")
    payload = tr.chrome_trace()
    assert validate_chrome_trace(payload) == []
    names = {e["name"] for e in payload["traceEvents"]}
    assert "marker" in names and "thread_name" in names


def test_tracer_span_ctx_tolerates_end_track():
    tr = SpanTracer()
    with tr.span("outer", "req1"):
        tr.begin("inner", "req1")
        tr.end_track("req1")                    # teardown closed everything
    assert tr.balanced()


def test_request_timeline_feeds_histograms():
    t = [0.0]

    def clock():
        t[0] += 0.010
        return t[0]

    reg = MetricsRegistry()
    tl = RequestTimeline(clock=clock, registry=reg)
    tl.submitted(7)
    tl.stamp(7)                                 # first token -> ttft
    tl.stamp(7)                                 # second -> inter-token
    assert tl.ttft_ms(7) == pytest.approx(10.0)
    assert tl.gaps_ms(7) == [pytest.approx(10.0)]
    assert reg.get("obs_ttft_ms").count() == 1
    assert reg.get("obs_inter_token_ms").count() == 1
    s = tl.summary()
    assert s["n"] == 1 and s["ttft_p50_ms"] == pytest.approx(10.0)


def test_timeline_attach_chains_two_arg_callback():
    """Scheduler emit_tokens calls on_token(tok, idx): the chained
    wrapper must forward BOTH args to the client callback."""
    tl = RequestTimeline()
    seen = []
    req = Request(np.array([1, 2], np.int32))
    req.on_token = lambda tok, idx: seen.append((tok, idx))
    tl.submitted(req.req_id)
    tl.attach(req)
    req.on_token(5, 0)
    req.on_token(6, 1)
    assert seen == [(5, 0), (6, 1)]
    assert len(tl.stamps[req.req_id]) == 3      # submit + 2 tokens


# ---------------------------------------------------------------------------
# (c) the profiler mirror and the named scopes
# ---------------------------------------------------------------------------

def _serve_events(profile_dir) -> list:
    """The ``serve.*`` host events of the profile written under
    ``profile_dir``: [(name, start_ns, end_ns, stats)], by start."""
    from jax.profiler import ProfileData
    out = []
    for path in glob.glob(f"{profile_dir}/**/*.xplane.pb", recursive=True):
        for plane in ProfileData.from_file(path).planes:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("serve."):
                        out.append((ev.name, ev.start_ns,
                                    ev.start_ns + ev.duration_ns,
                                    dict(ev.stats)))
    return sorted(out, key=lambda e: (e[1], -e[2]))


@pytest.mark.parametrize("which", ["dense", "paged"])
def test_scheduler_spans_reach_the_profile_nested(eng_dense, eng_paged,
                                                  which, tmp_path):
    eng = {"dense": eng_dense, "paged": eng_paged}[which]
    reqs = [Request(p, max_new_tokens=5) for p in _prompts(51, n=3)]
    tr = SpanTracer()
    with obs.trace.installed(tr), jax.profiler.trace(str(tmp_path)):
        sched = _drain(eng, reqs)
    assert tr.balanced() and all(r.done for r in reqs)
    evs = _serve_events(tmp_path)
    names = [e[0] for e in evs]
    for want in ("serve.run_start", "serve.iteration", "serve.upkeep",
                 "serve.admit", "serve.prefill_chunk", "serve.decode_step",
                 "serve.decode_dispatch", "serve.sample", "serve.device_wait",
                 "serve.emit"):
        assert want in names, want
    # one dispatch, one sample and one readback per decode step
    for one in ("decode_dispatch", "sample", "device_wait", "decode_step"):
        assert names.count(f"serve.{one}") == sched.steps, one
    iters = [e for e in evs if e[0] == "serve.iteration"]
    assert {"step", "n_live"} <= set(iters[0][3])
    # iterations tile the loop without overlapping; every other span but
    # run_start lies inside one of them
    for a, b in zip(iters, iters[1:]):
        assert a[2] <= b[1]
    for name, t0, t1, _ in evs:
        if name in ("serve.iteration", "serve.run_start"):
            continue
        assert any(i[1] <= t0 and t1 <= i[2] for i in iters), name
    # the device work of a step sits inside its decode_step span
    steps = [e for e in evs if e[0] == "serve.decode_step"]
    for name in ("serve.decode_dispatch", "serve.device_wait", "serve.emit"):
        for e in (e for e in evs if e[0] == name):
            assert any(d[1] <= e[1] and e[2] <= d[2] for d in steps), name


@pytest.mark.parametrize("which", ["tiered", "spec"])
def test_tiered_and_speculative_steps_keep_their_spans_nested(
        eng_tiered, eng_spec, which, tmp_path):
    """The fetch-and-rerun loop dispatches and reads back its selection
    once per round, then reads back the step's tokens; the verify window
    dispatches and reads back once a round.  Every dispatch and readback
    sits inside its step's span, and the step inside an iteration."""
    eng = {"tiered": eng_tiered, "spec": eng_spec}[which]
    rng = np.random.default_rng(53)
    base = rng.integers(1, 128, size=8).astype(np.int32)
    reqs = [Request(np.tile(base, 4)[:20 + 6 * i], max_new_tokens=8)
            for i in range(3 if which == "tiered" else 2)]
    tr = SpanTracer()
    with obs.trace.installed(tr), jax.profiler.trace(str(tmp_path)):
        sched = _drain(eng, reqs)
    assert tr.balanced() and all(r.done for r in reqs)
    evs = _serve_events(tmp_path)
    step = "serve.verify_window" if which == "spec" else "serve.decode_step"
    steps = [e for e in evs if e[0] == step]
    assert len(steps) == sched.steps
    iters = [e for e in evs if e[0] == "serve.iteration"]
    for e in steps:
        assert any(i[1] <= e[1] and e[2] <= i[2] for i in iters)
    disp = [e for e in evs if e[0] == "serve.decode_dispatch"]
    waits = [e for e in evs if e[0] == "serve.device_wait"]
    assert len(disp) >= sched.steps
    assert len(waits) == len(disp) + (sched.steps if which == "tiered" else 0)
    for e in disp + waits:
        assert any(d[1] <= e[1] and e[2] <= d[2] for d in steps)


@pytest.mark.parametrize("how", ["cancel", "deadline", "fault_raises"])
def test_scheduler_track_balanced_on_every_exit(eng_dense, how):
    """Whatever ends the run — a cancel, a missed deadline, or a fault
    that escapes run() — no scheduler span (and so no profiler
    annotation) is left open."""
    reqs = [Request(p, max_new_tokens=12,
                    timeout_steps=3 if how == "deadline" else None)
            for p in _prompts(52, n=2)]
    tr = SpanTracer()

    def on_step(s, step):
        if how == "cancel" and step == 2:
            reqs[1].cancel()

    with obs.trace.installed(tr):
        if how == "fault_raises":
            schedule = faults.FaultSchedule(at={"decode_step": range(50)})
            with pytest.raises(faults.InjectedFault):
                _drain(eng_dense, reqs, schedule=schedule)
        else:
            _drain(eng_dense, reqs, on_step=on_step)
    assert obs.trace.SCHEDULER_TRACK not in tr.open_tracks()
    assert not any("ann" in sp for sp in tr._open.values())
    if how != "fault_raises":
        assert tr.balanced() and all(r.finished for r in reqs)


def _lowered_step(eng, step):
    """(lowered program, function name) of one of the engine's jitted
    steps, from shapes alone."""
    b = eng.scfg.max_batch
    if step == "decode":
        cache = jax.eval_shape(eng._init_slots_impl)
        return eng._decode.lower(jnp.zeros((b,), jnp.int32), cache,
                                 jnp.zeros((b,), jnp.int32)), "_decode_impl"
    cache, scratch = jax.eval_shape(eng._init_prefill_impl)
    c = eng.scfg.prefill_chunk
    return eng._prefill_chunk.lower(
        jnp.zeros((1, c), jnp.int32), cache, scratch, jnp.int32(0),
        jnp.ones((1,), jnp.int32)), "_prefill_chunk_impl"


@pytest.mark.parametrize("step", ["decode", "prefill_chunk"])
def test_jitted_steps_keep_names_and_carry_scopes(eng_dense, step):
    """The benchmark finds the programs by name and attributes their ops
    by scope: both must survive.  (What a chip's compiler keeps of them
    is checked in tests/test_tpu_compile.py.)"""
    lowered, fn = _lowered_step(eng_dense, step)
    assert lowered.as_text().startswith(f"module @jit_{fn}")
    locs = set(re.findall(r'loc\("([^"]+)"',
                          lowered.as_text(debug_info=True)))
    for scope in ("weights", "seg0.full", "seg1.sals", "seg2.full",
                  "cache_carry", "head"):
        assert any(f"{scope}/" in n for n in locs), scope
    assert f"jit({fn})/weights/slice" in locs
    for op in ("dynamic_slice", "dynamic_update_slice"):
        assert f"cache_carry/{op}" in locs, op


def test_scan_layers_matches_lax_scan():
    """The named cache carry is lax.scan's own xs/ys plumbing."""
    x0 = jnp.arange(6.0).reshape(2, 3)
    w = jnp.linspace(0.5, 1.5, 4 * 3 * 3).reshape(4, 3, 3)
    state = {"k": jnp.arange(4 * 2 * 5.0).reshape(4, 2, 5),
             "n": jnp.arange(4, dtype=jnp.int32)}

    def step(x, w_l, st):
        x = jnp.tanh(x @ w_l)
        st = {"k": st["k"].at[:, 0].add(x[:, 0]), "n": st["n"] + 1}
        return x, st, x.sum()

    def ref_body(x, w_st):
        w_l, st = w_st
        x, st, y = step(x, w_l, st)
        return x, (st, y)

    x_r, (st_r, y_r) = jax.lax.scan(ref_body, x0, (w, state))
    x_s, st_s, y_s = jax.jit(tf._scan_layers, static_argnums=0)(
        step, x0, w, state)
    np.testing.assert_array_equal(x_s, x_r)
    np.testing.assert_array_equal(y_s, y_r)
    for k in state:
        np.testing.assert_array_equal(st_s[k], st_r[k])
        assert st_s[k].dtype == state[k].dtype


# ---------------------------------------------------------------------------
# scheduler integration: views, conservation, LRU bugfix, lifecycle spans
# ---------------------------------------------------------------------------

def test_counter_views_are_registry_backed(eng_dense, model):
    """Legacy public fields (prefix_hits, failures, ...) stay readable /
    writable but the registry is the single store."""
    cfg, params, sals, proj = model
    with obs.enabled() as h:
        sched = RequestScheduler(eng_dense, mode="continuous")
        assert sched.metrics is h["registry"]
        sched.prefix_hits += 3
        assert sched.prefix_hits == 3
        assert h["registry"].counter(
            "serve_prefix_hits_total").value() == 3.0


def test_metrics_conservation_and_terminal_counters(eng_dense, model):
    """submitted == done + failures + timeouts + cancellations at drain,
    in the public views AND the registry series they proxy."""
    rng = np.random.default_rng(47)
    cfg, params, sals, proj = model
    with obs.enabled() as h:
        reqs = [Request(rng.integers(1, 128, size=12).astype(np.int32),
                        max_new_tokens=6) for _ in range(3)]
        reqs.append(Request(rng.integers(1, 128, size=12).astype(np.int32),
                            max_new_tokens=30, timeout_steps=3))
        victim = Request(rng.integers(1, 128, size=12).astype(np.int32),
                         max_new_tokens=30)
        reqs.append(victim)

        def on_step(s, step):
            if step == 2:
                victim.cancel()

        sched = _drain(eng_dense, reqs, on_step=on_step)
        assert all(r.finished for r in reqs)
        assert sched.submitted == 5
        assert sched.submitted == (sched.done + sched.failures
                                   + sched.timeouts + sched.cancellations)
        assert sched.timeouts == 1 and sched.cancellations == 1
        reg = h["registry"]
        assert reg.counter("serve_requests_submitted_total").value() == 5.0
        assert reg.counter("serve_requests_done_total").value() == 3.0
        # gauges published at drain: nothing pending, nothing resident
        assert reg.gauge("serve_pending").value() == 0
        assert reg.gauge("serve_residents").value() == 0


def test_tenant_gauges_lru_capped(eng_dense, model):
    """ISSUE 10 satellite bugfix: the per-tenant setdefault dict grew
    forever on a long-lived scheduler; it now follows the gauge_history
    ring policy (0 = unbounded)."""
    cfg, params, sals, proj = model
    import dataclasses
    scfg = dataclasses.replace(eng_dense.scfg, gauge_history=4)
    eng2 = ServeEngine.__new__(ServeEngine)
    eng2.__dict__.update(eng_dense.__dict__)
    eng2.scfg = scfg
    sched = RequestScheduler(eng2, mode="continuous")
    for i in range(10):
        sched._tenant_gauge(f"tenant{i}")
    assert len(sched.tenant_gauges) == 4
    assert set(sched.tenant_gauges) == {f"tenant{i}" for i in range(6, 10)}
    sched._tenant_gauge("tenant6")              # refresh 6
    sched._tenant_gauge("tenant99")             # evicts 7 (LRU), not 6
    assert "tenant6" in sched.tenant_gauges
    assert "tenant7" not in sched.tenant_gauges
    # unbounded default keeps the pre-fix behavior
    sched0 = RequestScheduler(eng_dense, mode="continuous")
    for i in range(10):
        sched0._tenant_gauge(f"t{i}")
    assert len(sched0.tenant_gauges) == 10


def test_spans_balance_park_evict_fault_episode(model):
    """Acceptance: a park + evict + fault episode ends with every span
    closed and a valid Chrome-trace export covering the full lifecycle
    vocabulary."""
    cfg, params, sals, proj = model
    eng_p = _engine(model, page_size=16, audit_every=1, max_batch=2,
                    priority_classes=2, preempt_policy="park")
    prompts = _prompts(48, n=5)
    with obs.enabled() as h:
        sched = RequestScheduler(eng_p, mode="continuous")
        lo = [Request(p, max_new_tokens=8, priority=0) for p in prompts[:2]]
        hi = [Request(p, max_new_tokens=4, priority=1) for p in prompts[2:]]
        for r in lo:
            sched.submit(r)
        arrivals = [(2, hi[0]), (4, hi[1]), (6, hi[2])]

        def on_step(s, step):
            while arrivals and step >= arrivals[0][0]:
                s.submit(arrivals.pop(0)[1])

        schedule = faults.FaultSchedule(at={"nan_logits": [1]})
        with faults.injected(schedule):
            sched.run(on_step=on_step)
        assert sched.parks >= 1, "park never exercised"
        assert sched.retries >= 1, "fault retry never exercised"
        assert all(r.finished for r in lo + hi)
        tr = h["tracer"]
        assert tr.balanced(), (tr.open_tracks(), tr.begun, tr.ended)
        payload = tr.chrome_trace()
        assert validate_chrome_trace(payload) == []
        names = {e["name"] for e in payload["traceEvents"]}
        for want in ("queue_wait", "prefill", "prefill_chunk", "decode",
                     "decode_step", "parked", "teardown"):
            assert want in names, f"missing lifecycle span {want!r}"
    # evict flavor of the same episode
    eng_e = _engine(model, page_size=16, audit_every=1, max_batch=2,
                    priority_classes=2, preempt_policy="evict")
    with obs.enabled() as h:
        sched = RequestScheduler(eng_e, mode="continuous")
        lo = [Request(p, max_new_tokens=8, priority=0) for p in prompts[:2]]
        hi = [Request(p, max_new_tokens=4, priority=1) for p in prompts[2:]]
        for r in lo:
            sched.submit(r)
        arrivals = [(2, hi[0]), (4, hi[1]), (6, hi[2])]

        def on_step2(s, step):
            while arrivals and step >= arrivals[0][0]:
                s.submit(arrivals.pop(0)[1])

        sched.run(on_step=on_step2)
        assert sched.preemptions >= 1
        assert h["tracer"].balanced()
        assert validate_chrome_trace(h["tracer"].chrome_trace()) == []


def test_disabled_mode_is_invisible(eng_dense, model, tmp_path):
    """(d) With nothing installed the scheduler runs exactly as before:
    no tracer, public views still count, no ``serve.*`` event reaches a
    profile, same tokens as an enabled run (telemetry must never perturb
    decoding)."""
    from repro.core import pager
    prompts = _prompts(49, n=2)

    def run():
        reqs = [Request(p, max_new_tokens=4) for p in prompts]
        sched = _drain(eng_dense, reqs)
        return sched, [r.result.tokens.copy() for r in reqs]

    assert obs.metrics.active() is None and pager._metrics_hook is None
    with jax.profiler.trace(str(tmp_path)):
        sched_off, toks_off = run()
    assert _serve_events(tmp_path) == []
    assert sched_off.tracer is None
    assert sched_off.done == 2                  # local registry backs views
    with obs.enabled():
        sched_on, toks_on = run()
    for a, b in zip(toks_off, toks_on):
        np.testing.assert_array_equal(a, b)
    assert obs.metrics.active() is None and pager._metrics_hook is None


def test_engine_decode_throughput_on_tracer(eng_dense, model):
    """Satellite 2: the hand-rolled perf_counter in decode_throughput now
    rides the tracer and publishes a gauge when telemetry is on."""
    cfg, params, sals, proj = model
    tput = eng_dense.decode_throughput(2, 16, n_steps=2)   # disabled path
    assert tput > 0
    with obs.enabled() as h:
        tput = eng_dense.decode_throughput(2, 16, n_steps=2)
        assert tput > 0
        g = h["registry"].gauge("engine_decode_tokens_per_s",
                                labelnames=("batch", "context"))
        assert g.value(batch="2", context="16") == pytest.approx(tput)
        spans = [e for e in h["tracer"].events
                 if e["name"] == "decode_throughput"]
        assert spans and h["tracer"].balanced()


def test_launcher_style_export_roundtrip(eng_dense, model, tmp_path):
    """The --metrics-out/--trace-out shapes: both files written at drain
    validate, and the JSON snapshot round-trips."""
    cfg, params, sals, proj = model
    with obs.enabled() as h:
        reqs = [Request(p, max_new_tokens=4) for p in _prompts(50, n=2)]
        _drain(eng_dense, reqs)
        prom = tmp_path / "metrics.prom"
        prom.write_text(h["registry"].to_prometheus())
        snap = tmp_path / "metrics.json"
        snap.write_text(obs.metrics.snapshot_to_json(h["registry"]))
        trace = tmp_path / "trace.json"
        h["tracer"].dump(trace)
    assert validate_prometheus(prom.read_text()) == []
    assert validate_snapshot(json.loads(snap.read_text())) == []
    assert validate_chrome_trace(json.loads(trace.read_text())) == []
