"""Serving launcher: SALS-compressed batched generation.

    PYTHONPATH=src python -m repro.launch.serve --arch yi-9b --reduced \
        --requests 8 --max-new-tokens 32 [--sals 0.25|0.125|off]
    PYTHONPATH=src python -m repro.launch.serve --arch paper-mistral-7b \
        --full --layers 8 --max-seq 4096      # published widths, 8 layers

Trains nothing: weights are random unless ``--ckpt`` points at a training
checkpoint.  Calibrates the SALS projector on the synthetic corpus (paper
§5.1), builds the engine, runs a batch of requests through the scheduler
and reports tokens/s.  :func:`build` is the one construction path
(``chip_smoke.py`` serves through it too).
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import checkpoint as ckpt_store
from repro.config import ModelConfig, SALSConfig, ServeConfig, TrainConfig
from repro.configs import ASSIGNED_ARCHS, PAPER_ARCHS, get_config
from repro.core import calibration as cal
from repro.data import CalibrationSampler, SyntheticCorpus
from repro.models import transformer as tf
from repro.serve import Request, RequestScheduler, ServeEngine
from repro.train import trainer


def calibrate(params, cfg, sals, corpus, n_sequences=16, seq_len=128):
    """Fit per-layer projectors from pre-RoPE keys (paper §4.2)."""
    sampler = CalibrationSampler(corpus, n_sequences=n_sequences,
                                 seq_len=seq_len, batch_size=4)

    # params are an argument, not a closure: a jitted closure would bake
    # every weight into the program as a constant
    key_fn = jax.jit(lambda p, tokens: collect_pre_rope_keys(
        p, cfg, {"tokens": tokens}))
    keys = cal.collect_keys(lambda tokens: key_fn(params, tokens),
                            sampler.batches(),
                            max_tokens=n_sequences * seq_len)
    return cal.fit_layer_projectors(keys, sals.rank(cfg.kv_dim))


def collect_pre_rope_keys(params, cfg, batch):
    """(L, B, S, kvd) pre-RoPE keys — runs the full prefill stack."""
    from repro.models import attention as attn
    from repro.models.layers import rmsnorm_apply
    x, prefix_len = tf.embed_inputs(params, cfg, batch)
    s = x.shape[1]
    positions = jnp.arange(s)[None, :]

    def body(x, bp):
        h = rmsnorm_apply(bp["attn_norm"], x, cfg.norm_eps)
        y, k_pre, v = attn.attend_prefill(bp["attn"], h, cfg, positions,
                                          prefix_len)
        x, _, _ = tf._block_fwd(bp, x, cfg, positions, prefix_len, False)
        b, s_, hkv, dh = k_pre.shape
        return x, k_pre.reshape(b, s_, hkv * dh)

    x, ks = jax.lax.scan(body, x, params["blocks"])
    return ks


REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is what JAX already uses and
    nothing else is set here; otherwise the cache is the fixed
    ``.jax_cache/`` at the repository root (git-ignored).  The path is part
    of the cache key, so it never depends on a temp name, pid or time."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(REPO_ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def model_config(arch: str, *, reduced: bool = False,
                 layers: int = 0) -> ModelConfig:
    """``arch``'s config.  ``reduced`` shrinks every width (CPU smoke
    runs); ``layers`` keeps the widths and cuts only the depth."""
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    if layers:
        if not 0 < layers <= cfg.n_layers:
            raise ValueError(f"--layers {layers} outside [1, {cfg.n_layers}]")
        cfg = dataclasses.replace(cfg, n_layers=layers)
    return cfg


def build(cfg: ModelConfig, scfg: ServeConfig, *, seed: int = 0,
          ckpt: str = "", n_groups: int = 1, params=None, projectors=None,
          log=print):
    """Params, SALS projectors, engine and scheduler for one ServeConfig.

    Params are random from ``seed`` (or restored from ``ckpt``) in
    ``cfg.dtype``; the projectors are calibrated on the synthetic corpus
    when SALS is on.  Pass ``params``/``projectors`` from an earlier build
    to serve another ServeConfig over the same model.  Returns
    (RequestScheduler, params, projectors)."""
    dtype = jnp.dtype(cfg.dtype)
    if params is None:
        key = jax.random.PRNGKey(seed)
        if ckpt:
            state = trainer.init_state(key, cfg, TrainConfig(), jnp.float32)
            state, step = ckpt_store.restore(ckpt, state)
            params = jax.tree.map(lambda a: a.astype(dtype), state["params"])
            log(f"[serve] loaded checkpoint step {step}")
        else:
            params = tf.init_params(key, cfg, dtype)
    sals = scfg.sals if scfg.sals.enabled and cfg.has_attention else None
    if projectors is None and sals is not None:
        t0 = time.time()
        projectors = calibrate(params, cfg, sals,
                               SyntheticCorpus(cfg.vocab_size, seed=seed))
        log(f"[serve] calibrated projectors in {time.time()-t0:.1f}s "
            f"(rank {sals.rank(cfg.kv_dim)}/{cfg.kv_dim})")
    engine = ServeEngine(params, projectors, cfg, scfg,
                         n_groups=n_groups)  # validates divisibility
    return RequestScheduler(engine), params, projectors


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True,
                    choices=ASSIGNED_ARCHS + PAPER_ARCHS)
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--layers", type=int, default=0,
                    help="serve only the first N layers (0 = all); with "
                         "--full the widths stay the published ones")
    ap.add_argument("--sals", default="0.25",
                    choices=("0.25", "0.125", "off"))
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--max-new-tokens", type=int, default=32)
    ap.add_argument("--max-seq", type=int, default=512)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--groups", type=int, default=1,
                    help="SALS decode selection layout: 1 = paper-faithful "
                         "global top-k, >1 = per-group top-(N_c/G) + LSE "
                         "merge (the sequence-sharded serving layout; rides "
                         "as LatentKVCache metadata)")
    ap.add_argument("--scheduler", default="continuous",
                    choices=("continuous", "static"),
                    help="continuous = slot-arena batching (requests join a "
                         "running batch between decode steps; per-slot "
                         "lengths, ragged positions); static = GPT-fast-"
                         "style fixed batches (also the automatic fallback "
                         "for recurrent-state families)")
    ap.add_argument("--prefill-chunk", type=int, default=32,
                    help="chunked-prefill step width: admission prefill "
                         "loops ONE compiled (1, chunk) HLO with a traced "
                         "offset — max-seq must be a multiple of it")
    ap.add_argument("--prefill-budget", type=int, default=256,
                    help="prefill tokens the continuous scheduler spends "
                         "between decode steps (bounds resident inter-token "
                         "latency while long prompts are admitted)")
    ap.add_argument("--page-size", type=int, default=0,
                    help="paged latent cache (ISSUE 5): tokens per physical "
                         "page; 0 = dense slot arena.  Must divide "
                         "--max-seq and be a multiple of --prefill-chunk; "
                         "admission reserves pages, same-prefix prompts "
                         "share them copy-on-write")
    ap.add_argument("--n-pages", type=int, default=0,
                    help="page-pool size (0 = auto: max-batch·max-seq/"
                         "page-size, the dense-equivalent capacity; smaller "
                         "pools admit on pages-available and evict-to-"
                         "requeue on exhaustion)")
    ap.add_argument("--hbm-pages", type=int, default=0,
                    help="two-tier page pool (ISSUE 7): device payload "
                         "slots for the hot tier; 0 = single-tier (every "
                         "page HBM-resident).  Needs --page-size; must be "
                         ">= max-batch + 1 (each resident pins its write "
                         "page hot) and <= the pool size.  Score columns "
                         "stay device-resident for EVERY page; overflow "
                         "payloads spill to host mirrors")
    ap.add_argument("--no-tier-prefetch", dest="tier_prefetch",
                    action="store_false", default=True,
                    help="disable selection-driven prefetch (two-tier "
                         "mode): cold pages are then fetched on demand "
                         "only, inside the fetch-and-rerun decode step")
    ap.add_argument("--no-prefix-cache", dest="prefix_cache",
                    action="store_false", default=True,
                    help="disable COW prefix sharing (paged mode): every "
                         "request prefills and stores its full prompt")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--max-queue", type=int, default=0,
                    help="bounded admission queue (ISSUE 6): submit past "
                         "this depth applies --queue-policy; 0 = unbounded")
    ap.add_argument("--queue-policy", default="reject",
                    choices=("reject", "shed-oldest"),
                    help="full-queue backpressure: reject raises QueueFull "
                         "at the client; shed-oldest cancels the stalest "
                         "pending request to admit the new one")
    ap.add_argument("--request-timeout-steps", type=int, default=0,
                    help="per-request deadline in scheduler steps (0 = "
                         "none); expiry tears the request down as "
                         "TIMED_OUT through the standard teardown path")
    ap.add_argument("--request-timeout-ms", type=float, default=0.0,
                    help="per-request WALL-CLOCK deadline in milliseconds "
                         "(ISSUE 9; 0 = none); may be combined with "
                         "--request-timeout-steps — whichever deadline "
                         "fires first tears the request down as TIMED_OUT "
                         "through the same path")
    ap.add_argument("--spec-window", type=int, default=0,
                    help="speculative decoding (ISSUE 9): verify-window "
                         "width Q in [2, 8] (0/1 = off).  Each decode "
                         "step drafts Q-1 tokens per row by n-gram prompt "
                         "lookup and verifies them through ONE windowed "
                         "HLO — one latent selection amortized over the "
                         "window; greedy-only, untiered cache, attention "
                         "families")
    ap.add_argument("--max-request-retries", type=int, default=2,
                    help="transient per-request faults retry this many "
                         "times with exponential backoff in steps before "
                         "the request fails")
    ap.add_argument("--priority-classes", type=int, default=1,
                    help="SLO scheduling (ISSUE 8): number of priority "
                         "classes; requests carry priority in [0, N) and "
                         "admission always serves the highest eligible "
                         "class first.  >1 with --preempt-policy park "
                         "needs --page-size (parked victims keep pages)")
    ap.add_argument("--preempt-policy", default="park",
                    choices=("park", "evict", "none"),
                    help="what a strictly higher waiting class does to the "
                         "lowest resident when no slot is free: park = "
                         "host-snapshot the victim's rows and HOLD its "
                         "pages (resume is token-exact, no re-prefill); "
                         "evict = requeue and re-prefill later; none = "
                         "priority orders admission only")
    ap.add_argument("--tenant-quantum", type=int, default=256,
                    help="deficit-round-robin quantum (tokens) for "
                         "admission across tenant_ids within one priority "
                         "class — one burst-happy tenant cannot monopolize "
                         "slots")
    ap.add_argument("--tenant-rate", type=float, default=0.0,
                    help="per-tenant admission rate limit: tokens of "
                         "credit accrued per scheduler iteration (0 = "
                         "unlimited); admission debits prompt + decode "
                         "budget, pacing bursts instead of rejecting them")
    ap.add_argument("--tenant-max-inflight", type=int, default=0,
                    help="per-tenant cap on requests holding serving "
                         "resources (resident + parked + admitting); "
                         "0 = uncapped")
    ap.add_argument("--gauge-history", type=int, default=0,
                    help="ring-buffer cap on the observability ledgers "
                         "(admissions / prefill chunks / pool gauges); "
                         "0 = unbounded (pre-ISSUE-8 behavior, grows "
                         "forever on a long-lived scheduler)")
    ap.add_argument("--stream", action="store_true",
                    help="attach an on_token callback to every request "
                         "and report per-request TTFT + p99 inter-token "
                         "gap as a streaming client would observe them")
    ap.add_argument("--audit-every", type=int, default=0,
                    help="run the cross-structure pager invariant audit "
                         "every N scheduler steps (0 = off); host-side "
                         "O(pages + residents) per run")
    ap.add_argument("--metrics-out", default="",
                    help="write the unified metrics registry at drain: a "
                         "path ending in .json gets the JSON snapshot "
                         "schema, anything else Prometheus text exposition")
    ap.add_argument("--trace-out", default="",
                    help="write the span tracer as Chrome-trace-event JSON "
                         "(load in Perfetto / chrome://tracing): one track "
                         "per request's lifecycle, and the scheduler track "
                         "of loop iterations and their parts (upkeep, "
                         "admit, prefill_chunk, decode_dispatch, sample, "
                         "device_wait, emit, on_step), on the host clock; "
                         "the same scheduler spans also reach any "
                         "jax.profiler trace as serve.<name>")
    ap.add_argument("--obs-snapshot-every", type=int, default=0,
                    help="re-export --metrics-out every N scheduler steps "
                         "while serving (0 = only at drain); implies "
                         "telemetry on")
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    use_compile_cache()
    cfg = model_config(args.arch, reduced=args.reduced, layers=args.layers)
    if not cfg.is_decoder:
        raise SystemExit(f"{args.arch} is encoder-only — no serving path")

    corpus = SyntheticCorpus(cfg.vocab_size, seed=args.seed)
    sals = None
    if args.sals != "off" and cfg.has_attention:
        sals = SALSConfig(
            rank_ratio=float(args.sals),
            v_bits=8 if args.sals == "0.25" else 4,
            n_critical=64, n_sink=4, n_recent=16,
            v_group=min(32, cfg.kv_dim),
            skip_layers_front=min(2, cfg.n_layers - 1), skip_layers_back=1)

    if args.page_size and (sals is None or not cfg.has_attention):
        raise SystemExit("--page-size needs SALS latent segments "
                         "(--sals 0.25|0.125 on an attention family)")
    # ServeConfig.__post_init__ validates the paging geometry at PARSE time
    # (max_seq % page_size, page_size % prefill_chunk, pool ≥ one max-seq
    # sequence) so misconfigurations fail here with a clear message instead
    # of as shape errors inside jit
    scfg = ServeConfig(max_seq_len=args.max_seq, max_batch=args.max_batch,
                       max_new_tokens=args.max_new_tokens,
                       temperature=args.temperature,
                       scheduler=args.scheduler,
                       prefill_chunk=args.prefill_chunk,
                       prefill_token_budget=args.prefill_budget,
                       page_size=args.page_size, n_pages=args.n_pages,
                       hbm_pages=args.hbm_pages,
                       tier_prefetch=args.tier_prefetch,
                       prefix_cache=args.prefix_cache,
                       max_queue=args.max_queue,
                       queue_policy=args.queue_policy,
                       request_timeout_steps=args.request_timeout_steps,
                       request_timeout_ms=args.request_timeout_ms,
                       spec_window=args.spec_window,
                       max_request_retries=args.max_request_retries,
                       audit_every=args.audit_every,
                       priority_classes=args.priority_classes,
                       preempt_policy=args.preempt_policy,
                       tenant_quantum=args.tenant_quantum,
                       tenant_rate=args.tenant_rate,
                       tenant_max_inflight=args.tenant_max_inflight,
                       gauge_history=args.gauge_history,
                       sals=sals or SALSConfig(enabled=False))
    # telemetry must be installed BEFORE the scheduler is built — it
    # adopts the active registry/tracer in __init__
    obs_handles = None
    if args.metrics_out or args.trace_out or args.obs_snapshot_every:
        from repro import obs
        obs_handles = obs.enable(gauge_history=args.gauge_history)
    sched, _, _ = build(cfg, scfg, seed=args.seed, ckpt=args.ckpt,
                        n_groups=args.groups)

    def write_metrics(path):
        from repro.obs import metrics as obs_metrics
        reg = obs_handles["registry"]
        with open(path, "w") as f:
            f.write(obs_metrics.snapshot_to_json(reg)
                    if path.endswith(".json") else reg.to_prometheus())

    timeline = None
    if args.stream:
        from repro.obs.trace import RequestTimeline
        timeline = RequestTimeline(
            clock=time.time,
            registry=obs_handles["registry"] if obs_handles else None)

    rng = np.random.default_rng(args.seed)
    for i in range(args.requests):
        plen = max(4, args.prompt_len + int(rng.integers(-8, 8)))
        prompt = corpus.batch(50_000 + i, 1, plen)["tokens"][0]
        # round-robin the priority classes and two demo tenants so the
        # SLO machinery is actually exercised when the flags enable it
        req = Request(prompt, max_new_tokens=args.max_new_tokens,
                      priority=i % args.priority_classes,
                      tenant_id=f"tenant{i % 2}")
        if timeline is not None:
            timeline.submitted(req.req_id)
            timeline.attach(req)
        sched.submit(req)

    on_step = None
    if args.obs_snapshot_every and args.metrics_out:
        def on_step(_sched, step, _every=args.obs_snapshot_every):
            if step % _every == 0:
                write_metrics(args.metrics_out)

    t0 = time.time()
    done = sched.run(on_step=on_step)
    dt = time.time() - t0
    ok = [r for r in done if r.done]
    total_new = sum(r.result.steps for r in ok)
    print(f"[serve] {len(ok)}/{len(done)} requests ok, {total_new} tokens "
          f"in {dt:.2f}s -> {total_new / dt:.1f} tok/s "
          f"(sals={args.sals}, arch={args.arch}, scheduler={sched.mode})")
    bad = [r for r in done if not r.done]
    if bad:
        print(f"[serve] terminal non-success: "
              + ", ".join(f"req {r.req_id}={r.state.value}" for r in bad))
    if sched.paged:
        hw = max((g["pages_in_use"] for g in sched.pool_gauges), default=0)
        print(f"[serve] paged pool: {sched.pool.n_pages - 1} pages × "
              f"{args.page_size} tokens, high-water {hw} pages, "
              f"prefix_hits={sched.prefix_hits} "
              f"cow_copies={sched.cow_copies} "
              f"stalls={sched.admission_stalls} "
              f"evictions={sched.evictions}")
        if sched.tiered:
            hh = max((g["host_pages"] for g in sched.pool_gauges), default=0)
            print(f"[serve] two-tier: {args.hbm_pages} hot slots, "
                  f"host high-water {hh} pages, "
                  f"spills={sched.pool.spills} "
                  f"fetch_hits={sched.fetch_hits} "
                  f"prefetch_hits={sched.prefetch_hits} "
                  f"cold_misses={sched.cold_misses}")
    if args.spec_window > 1 and sched.spec_rounds:
        acc = sched.spec_accepted / max(1, sched.spec_proposed)
        print(f"[serve] speculative: window {args.spec_window}, "
              f"{sched.spec_rounds} verify rounds, "
              f"{sched.spec_committed} tokens committed "
              f"({sched.spec_committed / sched.spec_rounds:.2f}/round), "
              f"draft acceptance {acc:.1%}")
    if args.priority_classes > 1:
        print(f"[serve] slo: {args.priority_classes} classes "
              f"(policy={args.preempt_policy}), parks={sched.parks} "
              f"resumes={sched.resumes} preemptions={sched.preemptions}")
    if args.tenant_rate or args.tenant_max_inflight or \
            len(sched.tenant_gauges) > 1:
        for tenant, g in sorted(sched.tenant_gauges.items()):
            print(f"[serve] tenant {tenant}: {g['admitted']}/"
                  f"{g['submitted']} admitted "
                  f"({g['admitted_tokens']} tokens), deferrals "
                  f"rate={g['rate_deferrals']} cap={g['cap_deferrals']}, "
                  f"max wait {g['max_wait_steps']} steps")
    if timeline is not None:
        s = timeline.summary()
        if s["ttft_p50_ms"] is not None:
            print(f"[serve] streaming: p50 ttft {s['ttft_p50_ms']:.1f}ms, "
                  f"p99 inter-token {s['inter_token_p99_ms'] or 0:.1f}ms "
                  f"(client-observed, includes queueing)")
    if obs_handles is not None:
        if args.metrics_out:
            write_metrics(args.metrics_out)
            print(f"[serve] metrics -> {args.metrics_out}")
        if args.trace_out:
            tracer = obs_handles["tracer"]
            tracer.dump(args.trace_out)
            print(f"[serve] trace -> {args.trace_out} "
                  f"({tracer.ended} spans, "
                  f"{'balanced' if tracer.balanced() else 'UNBALANCED'})")
    for r in ok[:3]:
        print(f"  req {r.req_id}: prompt[{r.result.prompt_len}] -> "
              f"{r.result.tokens[:10]}...")


if __name__ == "__main__":
    main()
