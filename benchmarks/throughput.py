"""Paper Table 7 — end-to-end decode throughput (tokens/s), SALS engine vs
full-cache engine (the GPT-fast role), measured on the reduced model on CPU
+ v5e projection at the paper's (bs, seq) grid."""
from __future__ import annotations

import numpy as np

import time

from repro.analysis.roofline import HBM_BW
from repro.config import SALSConfig, ServeConfig
from repro.configs import get_config
from repro.serve import Request, RequestScheduler, ServeEngine
from benchmarks import common
from benchmarks.memory_access import traffic_ratio


def measured_rows():
    cfg, params, corpus = common.trained_model()
    rows = []
    for bs, ctx in [(2, 256), (4, 256)]:
        eng_full = ServeEngine(params, None, cfg,
                               ServeConfig(max_seq_len=ctx + 64,
                                           sals=SALSConfig(enabled=False)))
        tput_full = eng_full.decode_throughput(bs, ctx, n_steps=16)
        sals = common.sals_settings(cfg, "25")
        proj = common.projectors_for(cfg, params, corpus, sals)
        eng_sals = ServeEngine(params, proj, cfg,
                               ServeConfig(max_seq_len=ctx + 64, sals=sals))
        tput_sals = eng_sals.decode_throughput(bs, ctx, n_steps=16)
        rows.append(("table7-cpu", bs, ctx, round(tput_full, 1),
                     round(tput_sals, 1), round(tput_sals / tput_full, 2)))
    return rows


def projected_rows():
    """v5e projection: decode step latency ≈ (weights + KV traffic)/HBM_bw;
    SALS shrinks only the KV term (paper's observation that the weight
    stream dominates short contexts — hence 1.4x @4k but 4.5x @32k)."""
    cfg = get_config("paper-llama2-7b")
    w_bytes = cfg.param_count() * 2
    rows = []
    for bs, seq in [(8, 4096), (8, 8192), (8, 16384), (8, 32768),
                    (4, 65536)]:
        kv_full = bs * 2 * seq * cfg.kv_dim * 2 * cfg.n_layers
        t_full = (w_bytes + kv_full) / HBM_BW
        for variant in ("25", "12.5"):
            sals = SALSConfig(rank_ratio=0.25 if variant == "25" else 0.125,
                              v_bits=8 if variant == "25" else 4,
                              n_critical=1024, n_sink=16, n_recent=128,
                              v_group=64)
            ratio = traffic_ratio(cfg, sals, seq)
            t_sals = (w_bytes + kv_full * ratio) / HBM_BW
            rows.append((f"table7-v5e-SALS{variant}", bs, seq,
                         round(bs / t_full, 1), round(bs / t_sals, 1),
                         round(t_full / t_sals, 2)))
    return rows


def scheduler_rows():
    """Continuous vs static batching (ISSUE 3): wall-clock to drain a
    mixed-length request stream through the SAME SALS engine.  Continuous
    admits into freed slots between ragged decode steps; static drains
    whole batches.  The win grows with max_new_tokens variance (static pads
    every batch to its slowest member)."""
    cfg, params, corpus = common.trained_model()
    sals = common.sals_settings(cfg, "25")
    proj = common.projectors_for(cfg, params, corpus, sals)
    eng = ServeEngine(params, proj, cfg,
                      ServeConfig(max_seq_len=256, max_batch=4, sals=sals))
    rows = []
    for n_req, mnt_spread in [(8, (4, 24)), (12, (2, 12))]:
        def workload():
            # fresh rng per call: both modes drain the IDENTICAL stream
            rng = np.random.default_rng(n_req)
            return [Request(corpus.batch(70_000 + i, 1,
                                         int(rng.integers(16, 48)))
                            ["tokens"][0],
                            max_new_tokens=max(1, int(rng.integers(
                                *mnt_spread))))
                    for i in range(n_req)]
        out = {}
        for mode in ("static", "continuous"):
            sched = RequestScheduler(eng, mode=mode)
            reqs = workload()
            for r in reqs:
                sched.submit(r)
            t0 = time.perf_counter()
            done = sched.run()
            dt = time.perf_counter() - t0
            toks = sum(r.result.steps for r in done)
            out[mode] = toks / dt
        rows.append(("scheduler-cpu", n_req, f"mnt{mnt_spread}",
                     round(out["static"], 1), round(out["continuous"], 1),
                     round(out["continuous"] / out["static"], 2)))
    return rows


def prefill_interleave_rows():
    """ISSUE 4: inter-token latency and TTFT when a LONG prompt arrives
    mid-decode.  "blocking" prefills the whole prompt in one sweep (chunk =
    max_seq — the pre-chunking behavior: residents stall for the full
    prompt).  "interleaved" spends prefill_token_budget tokens of chunk
    work between decode steps, so the residents' p99 inter-token gap is
    bounded by one budget's worth of chunk HLOs while the long prompt's
    TTFT stretches only modestly."""
    cfg, params, corpus = common.trained_model()
    sals = common.sals_settings(cfg, "25")
    proj = common.projectors_for(cfg, params, corpus, sals)
    long_prompt = corpus.batch(90_000, 1, 160)["tokens"][0]
    rows = []
    for label, chunk, budget in (("blocking", 256, 256),
                                 ("interleaved", 16, 16)):
        eng = ServeEngine(params, proj, cfg,
                          ServeConfig(max_seq_len=256, max_batch=2,
                                      sals=sals, prefill_chunk=chunk,
                                      prefill_token_budget=budget))
        sched = RequestScheduler(eng, mode="continuous")
        # staggered budgets: the second short request stays RESIDENT through
        # the whole long-prompt prefill, so every on_step gap is a genuine
        # resident inter-token stall (no no-resident idle spans pollute p99)
        short = [Request(corpus.batch(91_000 + i, 1, 24)["tokens"][0],
                         max_new_tokens=mnt)
                 for i, mnt in enumerate((24, 96))]
        long_req = Request(long_prompt, max_new_tokens=4)
        for r in short:
            sched.submit(r)
        times = []
        state = {}

        def on_step(s, step):
            times.append(time.perf_counter())
            if step == 4 and "t_submit" not in state:
                state["t_submit"] = time.perf_counter()
                s.submit(long_req)
            if "t_first" not in state and long_req.req_id in {
                    a[2] for a in s.admissions}:
                state["t_first"] = time.perf_counter()

        sched.run(on_step=on_step)
        gaps = np.diff(np.asarray(times)) * 1e3              # ms
        ttft = (state["t_first"] - state["t_submit"]) * 1e3
        # max gap is the robust discriminator on this tiny CPU model (the
        # blocking mode's single whole-prompt sweep); p99 needs enough
        # decode steps to register it
        rows.append(("prefill-interleave-cpu", label,
                     f"chunk{chunk}/budget{budget}", round(ttft, 1),
                     round(float(np.max(gaps)), 1),
                     round(float(np.percentile(gaps, 99)), 1),
                     round(float(np.median(gaps)), 1)))
    return rows


def prefix_sharing_rows():
    """ISSUE 5: N requests sharing a long prompt prefix through the PAGED
    engine, prefix cache on vs off.  Shared admission skips the shared
    pages' chunk HLOs and stores the prefix once (pages high-water ≈
    prefix + Σ unique suffixes, not N·prompt).  On this tiny CPU model the
    wall-clock TTFT is jit-compile-dominated — the structural columns
    (chunk_hlos, pages_high_water, prefix_hits) are the discriminator; on
    real hardware the skipped chunk HLOs ARE the follower-TTFT win."""
    cfg, params, corpus = common.trained_model()
    sals = common.sals_settings(cfg, "25")
    proj = common.projectors_for(cfg, params, corpus, sals)
    ps, n_req = 32, 4
    prefix = corpus.batch(95_000, 1, 96)["tokens"][0]
    prompts = [np.concatenate([prefix,
                               corpus.batch(95_100 + i, 1, 16)["tokens"][0]])
               for i in range(n_req)]
    rows = []
    for label, share in (("shared", True), ("unshared", False)):
        eng = ServeEngine(params, proj, cfg,
                          ServeConfig(max_seq_len=256, max_batch=n_req,
                                      sals=sals, prefill_chunk=16,
                                      page_size=ps, prefix_cache=share))
        sched = RequestScheduler(eng, mode="continuous")
        reqs = [Request(p, max_new_tokens=8) for p in prompts]
        t_submit = time.perf_counter()
        admit_t = {}

        def on_step(s, step):
            for _, slot, rid in s.admissions:
                admit_t.setdefault(rid, time.perf_counter())

        for r in reqs:
            sched.submit(r)
        t0 = time.perf_counter()
        sched.run(on_step=on_step)
        dt = time.perf_counter() - t0
        # follower TTFT: time to admission of the LAST same-prefix request
        last_ttft = (max(admit_t.values()) - t_submit) * 1e3 if admit_t \
            else float("nan")
        hw = max(g["pages_in_use"] for g in sched.pool_gauges)
        toks = sum(r.result.steps for r in reqs)
        rows.append(("prefix-sharing-cpu", label, n_req,
                     round(last_ttft, 1), hw, sched.prefix_hits,
                     len(sched.prefill_chunks), round(toks / dt, 1)))
    return rows


def fault_degradation_rows():
    """ISSUE 6: measured graceful degradation — drain the same request
    stream through the PAGED engine under seeded fault schedules at
    increasing per-step rates (page-alloc + decode-step + one-row NaN
    faults).  Columns: goodput (committed tok/s counting only DONE
    requests), p99 inter-token gap for surviving residents, and the
    retry/failure ledger.  The deterministic counterpart (closed-form
    attempts/goodput at the same rates) is
    ``benchmarks/memory_access.py::fault_degradation_model`` in
    ``BENCH_attention.json["fault_degradation_model"]``."""
    from repro.serve import faults
    cfg, params, corpus = common.trained_model()
    sals = common.sals_settings(cfg, "25")
    proj = common.projectors_for(cfg, params, corpus, sals)
    eng = ServeEngine(params, proj, cfg,
                      ServeConfig(max_seq_len=256, max_batch=4, sals=sals,
                                  prefill_chunk=16, page_size=32,
                                  max_request_retries=2))
    rows = []
    for rate in (0.0, 0.01, 0.05):
        sched = RequestScheduler(eng, mode="continuous")
        rng = np.random.default_rng(17)
        reqs = [Request(corpus.batch(97_000 + i, 1,
                                     int(rng.integers(16, 48)))["tokens"][0],
                        max_new_tokens=int(rng.integers(8, 24)))
                for i in range(8)]
        for r in reqs:
            sched.submit(r)
        times = []
        schedule = faults.FaultSchedule(
            seed=17, rates={"page_alloc": rate, "decode_step": rate,
                            "nan_logits": rate / 2})
        t0 = time.perf_counter()
        with faults.injected(schedule):
            sched.run(on_step=lambda s, step: times.append(
                time.perf_counter()))
        dt = time.perf_counter() - t0
        done = [r for r in reqs if r.done]
        toks = sum(r.result.steps for r in done)
        gaps = np.diff(np.asarray(times)) * 1e3 if len(times) > 1 else \
            np.zeros(1)
        rows.append(("fault-degradation-cpu", rate, f"{len(done)}/8",
                     round(toks / dt, 1),
                     round(float(np.percentile(gaps, 99)), 1),
                     sched.retries, sched.step_faults, sched.failures))
    return rows


def slo_rows():
    """ISSUE 8: per-class SLO report under a mixed-priority workload —
    FIFO (priority off) vs evict-requeue vs preempt-park on the same
    PAGED engine shape.  Three long batch-class requests occupy a 2-slot
    arena; three short interactive-class requests arrive mid-generation.

    Streaming latency is measured at the client's on_token callback over
    the event sequence [submit, tok0, tok1, ...] — so queueing/preemption
    delay lands in BOTH the TTFT column and the p99 inter-event gap (what
    a streaming client actually experiences).  Under FIFO the interactive
    class waits for a drained slot (TTFT ≈ a long request's remaining
    budget); evict frees a slot immediately but re-prefills the victim
    (batch-class tokens are repaid); park frees a slot immediately AND
    keeps the victim's pages — interactive p99 drops without giving up
    goodput (DONE tokens/s over the whole episode).  Each policy runs the
    episode twice and reports the second (HLOs warm)."""
    cfg, params, corpus = common.trained_model()
    sals = common.sals_settings(cfg, "25")
    proj = common.projectors_for(cfg, params, corpus, sals)
    # long batch prompts: what park holds (and evict re-prefills) is six
    # chunks' worth of pages per victim — enough for held state to matter
    # even on the tiny CPU model
    lo_prompts = [corpus.batch(98_000 + i, 1, 96)["tokens"][0]
                  for i in range(3)]
    hi_prompts = [corpus.batch(98_100 + i, 1, 16)["tokens"][0]
                  for i in range(3)]
    rows = []
    for policy in ("fifo", "evict", "park"):
        kw = {} if policy == "fifo" else dict(priority_classes=2,
                                              preempt_policy=policy)
        eng = ServeEngine(params, proj, cfg,
                          ServeConfig(max_seq_len=256, max_batch=2,
                                      sals=sals, prefill_chunk=16,
                                      page_size=32, prefill_token_budget=16,
                                      **kw))

        def episode():
            from repro.obs.trace import RequestTimeline
            sched = RequestScheduler(eng, mode="continuous")
            # the ONE stamping path (obs/trace.py): stamps[rid] is the
            # event sequence [t_submit, t_tok0, t_tok1, ...]
            timeline = RequestTimeline()

            def make(prompt, mnt, prio, tenant):
                req = Request(prompt, max_new_tokens=mnt, priority=prio,
                              tenant_id=tenant)
                timeline.attach(req)
                return req

            hi_prio = 1 if policy != "fifo" else 0
            lo = [make(p, 32, 0, "batch") for p in lo_prompts]
            hi = [make(p, 8, hi_prio, "interactive") for p in hi_prompts]
            for r in lo:
                timeline.submitted(r.req_id)
                sched.submit(r)
            arrivals = [(2, hi[0]), (4, hi[1]), (6, hi[2])]

            def on_step(s, step):
                while arrivals and step >= arrivals[0][0]:
                    _, r = arrivals.pop(0)
                    timeline.submitted(r.req_id)
                    s.submit(r)

            t0 = time.perf_counter()
            sched.run(on_step=on_step)
            dt = time.perf_counter() - t0
            done = [r for r in lo + hi if r.done]
            good = sum(r.result.steps for r in done) / dt
            out = {}
            for label, grp in (("interactive", hi), ("batch", lo)):
                ttfts, gaps = [], []
                for r in grp:
                    ts = timeline.stamps.get(r.req_id, [])
                    if len(ts) > 1:
                        # diff over [submit, tok0, ...]: queueing delay
                        # lands in BOTH ttft and the p99 gap (docstring)
                        ttfts.append((ts[1] - ts[0]) * 1e3)
                        gaps.extend(np.diff(np.asarray(ts)) * 1e3)
                out[label] = (float(np.mean(ttfts)),
                              float(np.percentile(gaps, 99)),
                              float(np.median(gaps)))
            return sched, out, good

        episode()                           # warm every HLO this policy hits
        sched, out, good = episode()
        for label in ("interactive", "batch"):
            ttft, p99, med = out[label]
            rows.append(("slo-cpu", policy, label, round(ttft, 1),
                         round(p99, 1), round(med, 1), round(good, 1),
                         sched.parks, sched.preemptions, sched.evictions))
    return rows


def speculative_rows():
    """ISSUE 9: measured speculative decode through the fused SALS path —
    one latent selection amortized over a q_len=4 verify window, on the
    same engine shape sequential runs.  Two workloads bracket the n-gram
    drafter: "repetitive" prompts (tiled token loops, the structured-output
    proxy) accept nearly every draft; "novel" corpus text sits near the
    drafter's floor.  Both runs are greedy and the episode stays inside the
    exact regime — ``n_critical`` covers every position's selectable range,
    so the window's single stale selection is the full selection and the
    speculative output is token-exact vs sequential (the ``exact`` column
    asserts it; shrinking ``n_critical`` below the range would make the
    amortized selection an approximation, like SALS itself).  Each variant
    runs twice and reports the second (HLOs warm).  The closed-form
    counterpart (bytes/accepted-token at swept acceptance) is
    ``BENCH_attention.json["speculative_traffic_model"]``."""
    import dataclasses
    cfg, params, corpus = common.trained_model()
    sals = dataclasses.replace(common.sals_settings(cfg, "25"),
                               n_critical=96)
    proj = common.projectors_for(cfg, params, corpus, sals)
    base = corpus.batch(99_000, 1, 12)["tokens"][0]
    workloads = {
        "repetitive": [np.tile(base, 6)[:32 + 8 * i].astype(base.dtype)
                       for i in range(4)],
        "novel": [corpus.batch(99_100 + i, 1, 32)["tokens"][0]
                  for i in range(4)],
    }
    mnt, q = 24, 4
    rows = []
    for label, prompts in workloads.items():
        eng_seq = ServeEngine(params, proj, cfg,
                              ServeConfig(max_seq_len=256, max_batch=4,
                                          sals=sals))
        eng_spec = ServeEngine(params, proj, cfg,
                               ServeConfig(max_seq_len=256, max_batch=4,
                                           sals=sals, spec_window=q))
        out = {}
        for mode, eng in (("sequential", eng_seq), ("speculative",
                                                    eng_spec)):
            gen = eng.generate_speculative if mode == "speculative" \
                else eng.generate
            gen(prompts, max_new_tokens=mnt)            # warm
            t0 = time.perf_counter()
            res = gen(prompts, max_new_tokens=mnt)
            dt = time.perf_counter() - t0
            toks = sum(len(r.tokens) for r in res)
            out[mode] = (toks / dt, [r.tokens for r in res])
        stats = eng_spec.spec_stats
        acc = stats["accepted_drafts"] / max(1, stats["proposed"])
        exact = all(np.array_equal(a, b) for a, b in
                    zip(out["sequential"][1], out["speculative"][1]))
        rows.append(("speculative-cpu", label, q, round(acc, 3),
                     round(stats["committed"] / max(1, stats["rounds"]), 2),
                     round(out["sequential"][0], 1),
                     round(out["speculative"][0], 1),
                     round(out["speculative"][0] / out["sequential"][0], 2),
                     exact))
    return rows


# telemetry seams the scheduler/engine hot loop consults per decode step
# when NOTHING is installed: tracer-is-None at the span sites (lifecycle,
# decode_step, transfers), _metrics_installed, the pager
# hook, engine decode_throughput's tracer check.  Counted generously (the
# real loop visits fewer on most steps).
_OBS_SEAMS_PER_STEP = 16


def obs_overhead_rows():
    """ISSUE 10: telemetry cost on the serving hot path.

    Differential wall-clock (off-vs-on drains, interleaved) was the first
    design and it cannot work here: per-drain throughput on this shared
    CPU box swings ±10-20% (scheduler preemption + frequency drift;
    ``process_time`` is worse because the multi-threaded CPU backend's
    contention shows up as extra CPU seconds), so a ≤1% bound would need
    hundreds of trials.  Both cells therefore measure ATTRIBUTION inside
    one drain, where numerator and denominator share the same noise:

    * "enabled": the full stack (registry + tracer) runs while every
      telemetry entry point the scheduler/engine calls (span begin/end,
      gauge publishing) is wrapped with a
      ``perf_counter`` pair; overhead_pct = telemetry seconds / drain
      seconds.  The wrapper cost lands in the numerator, so the measured
      number is an overestimate — conservative in the right direction.
    * "disabled": nothing installed, the hot path pays one attribute
      load + ``is None`` branch per seam.  The cell measures that guard
      on the live scheduler object with a ``timeit``-style loop and
      bills :data:`_OBS_SEAMS_PER_STEP` of them per executed decode
      step against the drain's wall clock.

    tok_s carries the median drain throughput per mode for context (it
    wobbles with the box; the gate rides overhead_pct).  Gate: disabled
    ≤ 1%, enabled ≤ 5%, enforced by benchmarks/check_bench_drift.py."""
    from repro import obs
    cfg, params, corpus = common.trained_model()
    sals = common.sals_settings(cfg, "25")
    proj = common.projectors_for(cfg, params, corpus, sals)
    eng = ServeEngine(params, proj, cfg,
                      ServeConfig(max_seq_len=256, max_batch=4, sals=sals))

    def workload():
        rng = np.random.default_rng(23)
        return [Request(corpus.batch(96_000 + i, 1,
                                     int(rng.integers(16, 40)))["tokens"][0],
                        max_new_tokens=int(rng.integers(48, 65)),
                        tenant_id=f"tenant{i % 2}")
                for i in range(16)]

    def drain(wrap=None):
        """One full continuous-mode drain; returns (tok_s, wall_s, steps).
        ``wrap(sched)`` runs after construction so a trial can instrument
        the scheduler before the hot loop starts."""
        sched = RequestScheduler(eng, mode="continuous")
        if wrap is not None:
            wrap(sched)
        reqs = workload()
        for r in reqs:
            sched.submit(r)
        t0 = time.perf_counter()
        done = sched.run()
        dt = time.perf_counter() - t0
        return sum(r.result.steps for r in done) / dt, dt, sched.steps

    for _ in range(6):                       # warm HLOs + engine caches
        drain()
    with obs.enabled():
        drain()                              # warm telemetry one-timers

    # -- disabled: measured guard cost × seam visits -----------------------
    probe = RequestScheduler(eng, mode="continuous")
    n_loop = 200_000
    t0 = time.perf_counter()
    for _ in range(n_loop):                  # the actual seam pattern
        if probe.tracer is not None:         # pragma: no cover
            raise AssertionError
    per_check_s = (time.perf_counter() - t0) / n_loop
    off = [drain() for _ in range(5)]
    off_tok = float(np.median([t for t, _, _ in off]))
    dis_pcts = [_OBS_SEAMS_PER_STEP * steps * per_check_s / wall * 100
                for _, wall, steps in off]
    dis_pct = float(np.median(dis_pcts))

    # -- enabled: in-drain attribution timing ------------------------------
    spent = {"t": 0.0}
    pc = time.perf_counter

    def timed(fn):
        def w(*a, **k):
            t0 = pc()
            try:
                return fn(*a, **k)
            finally:
                spent["t"] += pc() - t0
        return w

    def wrap(sched):
        tr = sched.tracer
        tr.begin = timed(tr.begin)
        tr.end = timed(tr.end)
        tr.end_track = timed(tr.end_track)
        tr.instant = timed(tr.instant)
        sched._publish_gauges = timed(sched._publish_gauges)

    en_pcts, on_toks = [], []
    for _ in range(5):
        with obs.enabled():
            spent["t"] = 0.0
            tok, wall, _ = drain(wrap=wrap)
            en_pcts.append(spent["t"] / wall * 100)
            on_toks.append(tok)
    en_pct = float(np.median(en_pcts))
    on_tok = float(np.median(on_toks))
    return [("obs-overhead-cpu", "disabled", round(off_tok, 1),
             round(dis_pct, 3), 1.0),
            ("obs-overhead-cpu", "enabled", round(on_tok, 1),
             round(en_pct, 2), 5.0)]


def run() -> list:
    rows = measured_rows() + projected_rows()
    common.emit(rows, ["table", "batch", "seq", "full_tok_s", "sals_tok_s",
                       "speedup"])
    print("# paper Table 7 reference: 1.4x @ 4k, 4.5x @ 32k vs GPT-fast")
    sched = scheduler_rows()
    common.emit(sched, ["table", "requests", "budget", "static_tok_s",
                        "continuous_tok_s", "speedup"])
    interleave = prefill_interleave_rows()
    common.emit(interleave, ["table", "mode", "config", "long_ttft_ms",
                             "max_intertoken_ms", "p99_intertoken_ms",
                             "median_intertoken_ms"])
    sharing = prefix_sharing_rows()
    common.emit(sharing, ["table", "mode", "requests", "last_ttft_ms",
                          "pages_high_water", "prefix_hits", "chunk_hlos",
                          "tok_s"])
    degradation = fault_degradation_rows()
    common.emit(degradation, ["table", "fault_rate", "done", "good_tok_s",
                              "p99_intertoken_ms", "retries", "step_faults",
                              "failures"])
    slo = slo_rows()
    common.emit(slo, ["table", "policy", "class", "ttft_ms",
                      "p99_gap_ms", "median_gap_ms", "good_tok_s", "parks",
                      "preemptions", "evictions"])
    spec = speculative_rows()
    common.emit(spec, ["table", "workload", "q_len", "acceptance",
                       "tok_per_round", "seq_tok_s", "spec_tok_s",
                       "speedup", "exact"])
    obs_rows = obs_overhead_rows()
    common.emit(obs_rows, ["table", "mode", "tok_s", "overhead_pct",
                           "budget_pct"])
    # read-modify-write: the modeled sections of BENCH_attention.json are
    # owned by benchmarks/attention_latency.py — only add the measured SLO
    # and speculative cells (drift-checked as required measured sections)
    import json
    from benchmarks.attention_latency import BENCH_JSON
    payload = json.loads(BENCH_JSON.read_text()) if BENCH_JSON.exists() \
        else {"bench": "attention"}
    payload["slo_report"] = [
        {"policy": p, "class": c, "ttft_ms": t, "p99_gap_ms": g,
         "median_gap_ms": m, "good_tok_s": tp, "parks": pk,
         "preemptions": pe, "evictions": ev}
        for _, p, c, t, g, m, tp, pk, pe, ev in slo]
    payload["speculative_throughput"] = [
        {"workload": w, "q_len": ql, "acceptance": a, "tok_per_round": tr,
         "seq_tok_s": sq, "spec_tok_s": sp, "speedup": x, "exact": ex}
        for _, w, ql, a, tr, sq, sp, x, ex in spec]
    payload["obs_overhead"] = [
        {"mode": m, "tok_s": t, "overhead_pct": o, "budget_pct": b}
        for _, m, t, o, b in obs_rows]
    BENCH_JSON.write_text(json.dumps(payload, indent=2) + "\n")
    print(f"# wrote slo_report + speculative_throughput + obs_overhead -> "
          f"{BENCH_JSON}")
    return rows + sched + interleave + sharing + degradation + slo + spec \
        + obs_rows


if __name__ == "__main__":
    run()
