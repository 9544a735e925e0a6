"""One run of one cell: build the system under test, warm it, offer the
cell's traffic for the window, then judge and measure what it served.

The system under test is ``launch/serve.build``'s scheduler and engine,
driven through ``RequestScheduler.run`` in continuous mode; the benchmark
submits requests, stamps every committed token through ``Request.on_token``
on the host clock, and cancels what is still running when it is done.

Everything that depends on the architecture comes from the two modules the
configuration file names: ``"architecture"`` (sizes, weights, projector,
the program's tree and configs, counts; see ``bench/archs/``) and
``"reference"`` (``hidden`` and ``head_stats``; see ``bench/reference/``).
"""
from __future__ import annotations

import collections
import dataclasses
import gc
import json
import os
import sys
import time
from typing import List, Optional

import numpy as np

from bench import model, trace
from bench.model import load_config, load_json, load_module, seed_words
from bench.traffic import generate, load_traffic

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
DRAIN_S = 20.0               # open loop: time after the window to finish
TRACE_AT = 0.3               # traced run: metrics read the trace from this
TRACE_S = 10.0               # share of the window on, for at most this long


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def now() -> float:
    return time.perf_counter()


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(bench: dict, name: str) -> dict:
    for c in bench["workloads"]:
        if c["name"] == name:
            return c
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json")


@dataclasses.dataclass
class Rec:
    """One request as the client sees it."""
    due: float                    # perf_counter time it was due
    prompt: np.ndarray
    max_new: int
    req: object = None
    stamps: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)

    def on_token(self, tok: int, idx: int) -> None:
        if idx == 0 and self.tokens:        # a restart re-emits from 0
            self.stamps.clear()
            self.tokens.clear()
        self.stamps.append(now())
        self.tokens.append(int(tok))

    @property
    def live(self) -> int:
        """Context length the next decode step of this row attends."""
        return len(self.prompt) + len(self.tokens)


class Context:
    """What the metric readers see."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def in_window(self, t: float) -> bool:
        return self.t0 <= t <= self.t1

    def window_tokens(self) -> int:
        return sum(self.in_window(t) for r in self.recs for t in r.stamps)

    def gaps_ms(self) -> list:
        out = []
        for r in self.recs:
            for a, b in zip(r.stamps, r.stamps[1:]):
                if self.in_window(a) and self.in_window(b):
                    out.append((b - a) * 1e3)
        return out

    def due_in_window(self) -> list:
        return [r for r in self.recs if self.t0 <= r.due < self.t1]

    def traced_steps(self) -> list:
        if self.extract is None:
            return []
        return [s for s in self.steps if self.tr0 <= s[0] <= self.tr1]


def queue_trend(ctx: Context) -> str:
    """Requests due and still without a first token, averaged over each
    third of the window: a count that climbs from third to third is a
    growing queue (the knee sweep's criterion, PERF.md)."""
    waits = [(r.due, r.stamps[0] if r.stamps else ctx.run_end)
             for r in ctx.recs]
    third = (ctx.t1 - ctx.t0) / 3
    out = []
    for i in range(3):
        a = ctx.t0 + i * third
        b = a + third
        out.append(sum(max(0.0, min(e, b) - max(s, a)) for s, e in waits)
                   / third)
    return "requests waiting, mean per third " + ", ".join(
        f"{x:.2f}" for x in out)


def _read_metric(name: str, ctx: Context):
    return load_module(model.path("metrics", f"{name}.py")).read(ctx)


def metrics_for(bench: dict, cell: dict, traced: bool) -> list:
    """The cell's metrics of this run: end-to-end untraced, per-layer
    traced.  A metric without ``workloads`` belongs to every cell."""
    group = bench["per_layer"] if traced else bench["end_to_end"]
    return [m for m in group
            if cell["name"] in m.get("workloads", [cell["name"]])]


# ---------------------------------------------------------------------------
# Driving the scheduler
# ---------------------------------------------------------------------------

class Client:
    """The load client: submits the cell's requests to the scheduler on
    their schedule, stamps what comes back, and keeps the window."""

    def __init__(self, sched, traffic: dict, plan, seconds: float,
                 capture: Optional[trace.Capture]):
        self.sched = sched
        self.eng = sched.engine
        self.traffic = traffic
        self.plan = plan
        self.seconds = seconds
        self.capture = capture
        self.recs: List[Rec] = []
        self.steps = []                     # (t, [live lengths]) in window
        self.t0 = self.t1 = None
        self.tr0 = self.tr1 = None
        self.lateness = 0.0
        self.backlog = 0                    # submitted, no token at close
        self.compiles = {}
        eng = self.eng
        self.jits = [eng._decode, eng._prefill_chunk, eng._admit,
                     eng._init_slots, eng._init_prefill]

    # -- shared ----------------------------------------------------------
    def _programs(self) -> int:
        """Programs compiled so far by the engine's jitted steps."""
        return sum(getattr(j, "_cache_size", lambda: 0)() for j in self.jits)

    def _submit(self, rec: Rec, t: float) -> None:
        from repro.serve import Request
        rec.req = Request(rec.prompt, max_new_tokens=rec.max_new,
                          on_token=rec.on_token)
        self.lateness = max(self.lateness, t - rec.due)
        self.recs.append(rec)
        self.sched.submit(rec.req)

    def _tick(self, t: float) -> None:
        """Window bookkeeping that every step and idle wake-up does."""
        cap = self.capture
        if self.t0 is None or cap is None:
            return
        if not cap.started and t >= self.t0 + TRACE_AT * self.seconds:
            cap.begin()
            self.tr0 = now()
        elif cap.started and not cap.ended and t >= self._trace_mark():
            cap.end()
            self.tr1 = now()
            if self.traffic["kind"] == "resident":
                cap.stop()          # nothing after this reads the host clock

    def _trace_mark(self) -> Optional[float]:
        """When the traced part of the window next has to begin or end."""
        cap = self.capture
        if cap is None or self.t0 is None or cap.ended:
            return None
        if not cap.started:
            return self.t0 + TRACE_AT * self.seconds
        return min(self.tr0 + min(TRACE_S, 0.5 * self.seconds),
                   self.t0 + self.seconds)

    def _open(self, t: float) -> None:
        self.t0 = t
        self.compiles["at_start"] = self._programs()

    def _close(self, t: float) -> None:
        if self.t1 is None:
            self.t1 = t
            self.compiles["at_end"] = self._programs()
            self.backlog = sum(not r.tokens for r in self.recs)
            if self.capture is not None:
                if self.capture.started and not self.capture.ended:
                    self.tr1 = now()
                self.capture.stop()

    def _record_step(self, t: float) -> None:
        if self.t0 is not None and self.t1 is None:
            self.steps.append((t, [r.live - 1 for r in self.recs
                                   if r.tokens and not r.req.finished]))

    # -- resident sessions --------------------------------------------------
    def run_resident(self) -> None:
        t = now()
        for p in self.plan:
            self._submit(Rec(t, p.prompt, p.max_new_tokens), t)

        def on_step(_sched, _step):
            t = now()
            if self.t0 is None:
                if all(r.tokens for r in self.recs):
                    if self.capture is not None:
                        self.capture.arm()
                    self._open(now())
                return
            self._record_step(t)
            self._tick(t)
            if t >= self.t0 + self.seconds:
                self._close(t)
                for r in self.recs:
                    r.req.cancel()

        self.sched.run(on_step=on_step)
        self.run_end = now()
        if self.t1 is None:
            raise RuntimeError(
                "the sessions ran out of room before the window closed: "
                f"{[len(r.tokens) for r in self.recs]} tokens served")

    # -- open loop ------------------------------------------------------------
    def warm(self, vocab: int) -> None:
        """One short request through every program the window uses."""
        from repro.serve import Request
        rng = np.random.default_rng(0)
        req = Request(rng.integers(1, vocab, 64, dtype=np.int32),
                      max_new_tokens=4)
        self.sched.submit(req)
        self.sched.run()
        if not req.done:
            raise RuntimeError(f"warm-up request ended {req.state}")

    def run_open_loop(self, lead_in: float) -> None:
        if self.capture is not None:
            self.capture.arm()
        start = now() + lead_in
        end = start + self.seconds
        queue = collections.deque(sorted(
            (Rec(start + p.due_s, p.prompt, p.max_new_tokens)
             for p in self.plan if p.due_s < self.seconds),
            key=lambda r: r.due))

        def submit_due(t):
            while queue and queue[0].due <= t:
                self._submit(queue.popleft(), t)

        def housekeeping(t):
            if self.t0 is None and t >= start:
                self._open(start)
            self._tick(t)
            if self.t1 is None and t >= end:
                self._close(end)
            if self.t1 is not None and t >= end + DRAIN_S:
                for r in self.recs:
                    if not r.req.finished:
                        r.req.cancel()

        def on_step(_sched, _step):
            t = now()
            self._record_step(t)
            submit_due(t)
            housekeeping(t)

        while True:
            t = now()
            submit_due(t)
            housekeeping(t)
            if any(not r.req.finished for r in self.recs):
                self.sched.run(on_step=on_step)
                continue
            if not queue:
                break
            wake = queue[0].due
            for mark in (start, end, self._trace_mark()):
                if mark is not None and t < mark < wake:
                    wake = mark
            self._idle(wake - now())
        t = now()
        self._close(end)
        self.run_end = t

    def _idle(self, dt: float) -> None:
        if dt <= 0:
            return
        if self.capture is not None and self.capture.armed:
            import jax
            with jax.profiler.TraceAnnotation("bench.idle_wait"):
                time.sleep(dt)
        else:
            time.sleep(dt)


def annotate(drv: Client) -> None:
    """Traced run: put the engine's step calls and the client's callbacks on
    the profiler's clock."""
    import jax
    eng = drv.eng
    decode, chunk = eng._decode, eng.prefill_chunk_step

    def decode_call(*a):
        with jax.profiler.TraceAnnotation("bench.decode_call"):
            return decode(*a)

    def chunk_call(task):
        with jax.profiler.TraceAnnotation("bench.prefill_chunk_call"):
            return chunk(task)

    eng._decode = decode_call
    eng.prefill_chunk_step = chunk_call
    submit = drv._submit

    def submit_call(rec, t):
        stamp = rec.on_token

        def on_token(*a):
            with jax.profiler.TraceAnnotation("bench.on_token"):
                stamp(*a)

        rec.on_token = on_token
        with jax.profiler.TraceAnnotation("bench.submit"):
            submit(rec, t)

    drv._submit = submit_call


# ---------------------------------------------------------------------------
# Correctness
# ---------------------------------------------------------------------------

def pick_sample(recs: List[Rec], traffic: dict, seed: int) -> List[Rec]:
    """The longest finished request, and others drawn from the seed until
    ``compare_tokens`` served tokens and ``compare_requests`` requests are
    in."""
    want = int(traffic["compare_tokens"])
    n_min = int(traffic.get("compare_requests", 1))
    done = [r for r in recs if r.tokens and (
        traffic["kind"] == "resident" or r.req.done)]
    if not done:
        return []
    done.sort(key=lambda r: -(len(r.prompt) + len(r.tokens)))
    sample, rest = [done[0]], done[1:]
    rng = np.random.default_rng(seed_words(seed, 3))
    for i in rng.permutation(len(rest)):
        if sum(len(r.tokens) for r in sample) >= want \
                and len(sample) >= n_min:
            break
        sample.append(rest[i])
    return sample


def compare(ref, w, u, arch, traffic: dict, sample: List[Rec],
            control: bool) -> dict:
    """Widest gap by which a served token's reference logit lies below the
    reference's best, over every served token of the sample; with
    ``control``, the same for the tokens the fp8 reference puts first."""
    nc = int(traffic["n_critical"])
    bucket = int(traffic["reference_bucket"])
    out = {"max_logit_gap": 0.0, "tokens": 0, "requests": len(sample)}
    if control:
        out["control_gap"] = 0.0
    for r in sample:
        p, n = len(r.prompt), len(r.tokens)
        toks = np.concatenate([r.prompt, np.asarray(r.tokens, np.int32)])
        t_len = min(int(traffic["max_seq"]), -(-len(toks) // bucket) * bucket)
        rows = np.full((-(-n // 256) * 256,), p - 1 + n - 1, np.int32)
        rows[:n] = p - 1 + np.arange(n)
        served = np.asarray(r.tokens, np.int32)
        hid = ref.hidden(w, u, arch, nc, toks, p, rows, t_len)
        st = ref.head_stats(w, arch, hid, served)
        out["max_logit_gap"] = max(out["max_logit_gap"],
                                   float(np.max(st["max"] - st["at"])))
        out["tokens"] += n
        if control:
            hc = ref.hidden(w, u, arch, nc, toks, p, rows, t_len, fp8=True)
            pick = ref.head_stats(w, arch, hc, served, fp8=True)["argmax"]
            sc = ref.head_stats(w, arch, hid, pick)
            out["control_gap"] = max(out["control_gap"],
                                     float(np.max(sc["max"] - sc["at"])))
    return out


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------

def run(cell_name: str, seed: int, seconds: float, traced: bool, t_start:
        float, *, control: bool = False, require_chip: bool = True,
        fault=None, bench: Optional[dict] = None) -> dict:
    """One run.  ``require_chip=False``, ``fault`` (called with the built
    scheduler, to break the timed path underneath) and ``bench`` (in place
    of BENCHMARK.json) are for the benchmark's own tests."""
    bench = bench or load_benchmark()
    cell = find_cell(bench, cell_name)
    c = load_config(cell["config"])
    t = load_traffic(cell["traffic"])
    am, ref = load_module(c["architecture"]), load_module(c["reference"])
    arch = am.Arch(c)

    import jax
    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu"
                         or len(devs) < int(cell["chips"])):
        raise NoChip(f"needs {cell['chips']} TPU chip(s); JAX found "
                     f"{len(devs)} x {devs[0].platform} "
                     f"({devs[0].device_kind})")
    dev = devs[0]
    from repro.launch.serve import build, use_compile_cache
    if dev.platform == "tpu":
        # every program, however small, in the checkout's own cache: only a
        # cell's first run in a checkout compiles
        os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        if use_compile_cache() != CACHE_DIR:
            raise RuntimeError("the program chose another compile cache")

    t_w = now()
    w = am.make_weights(arch, seed)
    u = am.make_projector(arch, w)
    jax.block_until_ready((w, u))
    log(f"[setup] weights and projector {now() - t_w:.2f}s: "
        f"{arch.summary()}")
    mcfg, scfg = am.program_configs(arch, c, t)
    t_b = now()
    sched, _, _ = build(mcfg, scfg, params=am.program_params(arch, w),
                        projectors=u, log=log)
    log(f"[setup] build {now() - t_b:.2f}s")
    if fault is not None:
        fault(sched)

    plan = generate(t, arch.vocab, seed, seconds)
    capture = trace.Capture(TRACE_DIR) if traced else None
    drv = Client(sched, t, plan, seconds, capture)
    if traced:
        annotate(drv)
    if t["kind"] == "resident":
        drv.run_resident()
    else:
        t_warm = now()
        drv.warm(arch.vocab)
        log(f"[setup] warm-up request {now() - t_warm:.2f}s")
        drv.run_open_loop(float(t["lead_in_s"]))
    setup_s = drv.t0 - t_start
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    n_decode = getattr(drv.jits[0], "_cache_size", lambda: None)()
    log(f"[window] {drv.t1 - drv.t0:.3f}s, {len(drv.steps)} decode steps, "
        f"programs compiled at start {drv.compiles.get('at_start')} and end "
        f"{drv.compiles.get('at_end')} ({n_decode} decode), generator late "
        f"by at most {drv.lateness * 1e3:.1f}ms, {drv.backlog} requests "
        f"without a first token at the close, peak HBM {peak}")

    extract = capture.extract() if traced else None
    recs = drv.recs
    states = collections.Counter(r.req.state.value for r in recs)
    log(f"[window] request states {dict(states)}")
    failed = sum(r.req.state.value in ("failed", "timed_out") for r in recs)
    ctx = Context(recs=recs, t0=drv.t0, t1=drv.t1, run_end=drv.run_end,
                  seconds=drv.t1 - drv.t0, setup_s=setup_s, steps=drv.steps,
                  extract=extract, tr0=drv.tr0, tr1=drv.tr1, arch=arch,
                  traffic=t, counts=am.counts, trace=trace,
                  peaks=load_json("peaks.json"), device_kind=dev.device_kind)
    attempted = len(ctx.due_in_window()) if t["kind"] == "open_loop" \
        else len(recs)
    log(f"[window] samples: {ctx.window_tokens()} tokens, "
        f"{len(ctx.gaps_ms())} gaps between tokens, {attempted} requests")
    if t["kind"] == "open_loop" and attempted:
        log(f"[window] {queue_trend(ctx)}")
    metrics = {}
    for m in metrics_for(bench, cell, traced):
        v = _read_metric(m["name"], ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    # correctness: the program's state goes first, then the reference
    sample = pick_sample(recs, t, seed)
    drv_tr = (drv.tr0, drv.tr1)
    del sched, drv
    gc.collect()
    t_ref = now()
    cmp = compare(ref, w, u, arch, t, sample, control)
    limit_path = model.path("limits", f"{cell_name}.json")
    limit = load_json(limit_path)["max_logit_gap"] \
        if os.path.exists(limit_path) else None
    log(f"[correct] reference over {cmp['requests']} requests, "
        f"{cmp['tokens']} served tokens, {now() - t_ref:.2f}s")
    # the control's gap, where it runs, stands in the program's place
    judged = cmp["control_gap"] if control else cmp["max_logit_gap"]
    correct = bool(sample) and limit is not None and judged <= limit
    checks = {"max_logit_gap": {"value": cmp["max_logit_gap"],
                                "limit": limit}}
    if control:
        checks["control_gap"] = {"value": cmp["control_gap"], "limit": limit}
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics,
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(devs), "memory_peak_bytes": peak}}
    if traced and extract is not None:
        result["device"]["busy_s"] = trace.busy_ns(extract) * 1e-9
        result["device"]["window_s"] = extract["window_ns"] * 1e-9
        result["breakdown"] = trace.breakdown(extract)
    result["checks"] = checks
    result["_recording"] = None if extract is None else {
        "config": cell["config"], "traffic": cell["traffic"],
        "device_kind": dev.device_kind, "extract": extract,
        "tr0": drv_tr[0], "tr1": drv_tr[1], "steps": ctx.traced_steps()}
    return result
