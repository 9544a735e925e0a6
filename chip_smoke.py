#!/usr/bin/env python3
"""One-chip smoke run of the served SALS decode path.

    python chip_smoke.py

Builds ``paper-mistral-7b`` at its published widths (d_model 4096, 32/8
GQA heads, head_dim 128, ff 14336, vocab 32000) cut to its first 8
layers, with random bf16 weights from a seed and the paper's SALS-25%
settings (rank 0.25, score 0.5, N_c 432, n_sink 16, n_recent 64, int8 V),
through ``repro.launch.serve.build`` — the launcher's own construction
path.  With 8 layers the 2 front + 1 back full-precision skip layers are
3/8 of the model instead of the published 3/32.

Phases, all on the chip, all checked:

  kernels  the Pallas score/select and reconstruct-attend kernels against
           their f32 jnp oracles at Mistral widths (B=8, S=4096), dense and
           paged, SALS-25% and SALS-12.5%;
  dense    8 seeded requests (1k-3k-token prompts, 32 new tokens each)
           through RequestScheduler in continuous mode, max_batch 4,
           max_seq 4096; every request DONE, one compiled decode program,
           Pallas custom calls present in it;
  paged    the same over the paged cache with the prefix cache on; two
           prompts share a 1k-token prefix, which must hit;
  logits   one decode step of the served program against the same step
           with the jnp oracle kernels, on one cache.

Needs a TPU: anywhere else it exits non-zero before printing a result.
The last line of standard output is one JSON object naming the device.
Times printed on the way are set-up and wall time of this run, not
benchmark metrics.
"""
from __future__ import annotations

import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "paper-mistral-7b"
LAYERS = 8
SEED = 0
MAX_SEQ = 4096
MAX_BATCH = 4
NEW_TOKENS = 32
N_REQUESTS = 8
PAGE_SIZE = 128
SHARED_PREFIX = 1024
# logits gap bound, relative to the largest |logit|: both programs run
# the kernel math in f32 at full precision, so they differ by rounding
# order only; the residual stream is bf16 between layers, where such a
# difference can flip one rounding (2^-8 relative) of a hidden element,
# and 8 layers of that stay within a few bf16 ulps of the logit scale.
LOGITS_TOL = 0.02
# kernel output gap bound, relative to max |y| (y = o / l): f32 end to
# end; the cos/sin and exp of the chip and of XLA may differ in the last
# bits at rotary angles up to 4096 rad, which moves logits by ~1e-3.
KERNEL_TOL = 1e-2


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok, what) -> None:
    """A failed check fails its phase (and the run), with ``what``."""
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def require_tpu():
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found "
                         f"{dev.platform!r} ({dev.device_kind})")
    return dev


def make_prompts(cfg, n: int, seed: int, shared_prefix: int = 0):
    """``n`` prompts of 1k-3k tokens; with ``shared_prefix``, prompt 1
    repeats prompt 0's first ``shared_prefix`` tokens."""
    import numpy as np
    from repro.data import SyntheticCorpus
    corpus = SyntheticCorpus(cfg.vocab_size, seed=seed)
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        plen = int(rng.integers(1024, 3 * 1024))
        out.append(corpus.batch(50_000 + i, 1, plen)["tokens"][0])
    if shared_prefix:
        out[1] = np.concatenate([out[0][:shared_prefix],
                                 out[1][shared_prefix:]])
    return out


def decode_custom_calls(engine) -> dict:
    """Pallas kernels in the compiled decode program: counts per name."""
    import jax.numpy as jnp
    b = engine.scfg.max_batch
    tok = jnp.zeros((b,), jnp.int32)
    hlo = engine._decode.lower(tok, engine.init_slot_cache(), tok) \
        .compile().as_text()
    calls = [ln for ln in hlo.splitlines() if "tpu_custom_call" in ln]
    names = ("sals_latent_topk", "sals_recon_attend", "flash_attention")
    return {"tpu_custom_call": len(calls),
            **{n: sum(n in ln for ln in calls) for n in names}}


def serve_phase(name, cfg, scfg, prompts, built=None):
    """Serve ``prompts`` through a fresh scheduler built by
    ``launch/serve.build``; returns (scheduler, params, projectors)."""
    import numpy as np
    from repro.launch.serve import build
    from repro.serve import Request
    from repro.serve.lifecycle import RequestState

    t0 = time.time()
    params, projectors = built or (None, None)
    sched, params, projectors = build(cfg, scfg, seed=SEED, params=params,
                                      projectors=projectors, log=log)
    # warm-up request: compiles the chunk, admission and decode programs,
    # so the timed window below serves from compiled code
    sched.submit(Request(np.asarray(prompts[-1][:256]), max_new_tokens=2))
    warm = sched.run()
    check([r.state for r in warm] == [RequestState.DONE], warm)
    setup_s = time.time() - t0

    reqs = [Request(p, max_new_tokens=NEW_TOKENS) for p in prompts]
    for r in reqs:
        sched.submit(r)
    t0 = time.time()
    done = sched.run()
    serve_s = time.time() - t0
    n_done = sum(r.state == RequestState.DONE for r in reqs)
    tokens = sum(r.result.steps for r in reqs if r.result is not None)
    log(f"[{name}] {n_done}/{len(reqs)} requests DONE, {tokens} tokens; "
        f"set-up (build + calibration + compile) {setup_s:.1f}s, "
        f"serve wall time {serve_s:.2f}s")
    check(len(done) == len(reqs) and n_done == len(reqs),
          [(r.req_id, r.state.value) for r in reqs])
    check(all(len(r.result.tokens) == NEW_TOKENS for r in reqs),
          "every request generated its full budget")
    n_decode = sched.engine._decode._cache_size()
    calls = decode_custom_calls(sched.engine)
    log(f"[{name}] compiled decode programs: {n_decode}; custom calls in "
        f"it: {calls}")
    check(n_decode == 1, f"{n_decode} compiled decode programs")
    check(calls["sals_latent_topk"] >= 1 and calls["sals_recon_attend"] >= 1,
          calls)
    return sched, params, projectors


def kernel_phase(seed: int, b: int = 8, s: int = MAX_SEQ, n_kv: int = 8,
                 dh: int = 128, h: int = 32, nc: int = 432,
                 ranks=((256, 8), (128, 4))) -> None:
    """Pallas vs f32 jnp oracle, by default at Mistral widths, B=8,
    S=4096, for SALS-25% (r 256, int8 V) and SALS-12.5% (r 128, int4 V)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import quantization as qz
    from repro.kernels import ops

    kvd = n_kv * dh
    pos = jnp.asarray(np.linspace(s - 1, s // 2, b).astype(np.int32))
    for rank, v_bits in ranks:
        r_star = rank // 2
        ks = jax.random.split(jax.random.PRNGKey(seed + rank), 5)
        lat = jax.random.normal(ks[0], (b, s, rank)).astype(jnp.bfloat16)
        u = (jax.random.normal(ks[1], (kvd, rank)) * rank ** -0.5) \
            .astype(jnp.bfloat16)
        vq = qz.quantize(jax.random.normal(ks[2], (b, s, kvd)), v_bits, 64)
        q = (3.0 * jax.random.normal(ks[3], (b, h, dh))).astype(jnp.bfloat16)
        q_lat = jax.random.normal(ks[4], (b, r_star))
        # paged twin: the same rows in a permuted page pool
        mp = s // PAGE_SIZE
        perm = np.random.default_rng(seed).permutation(b * mp) + 1
        table = jnp.asarray(perm.reshape(b, mp).astype(np.int32))

        def pool(a):
            rows = a.reshape(b * mp, PAGE_SIZE, *a.shape[2:])
            p = jnp.zeros((b * mp + 1, *rows.shape[1:]), a.dtype)
            return p.at[table.reshape(-1)].set(rows)

        layouts = {"dense": (lat, vq["q"], vq["scale"], vq["zero"], {}),
                   "paged": (pool(lat), pool(vq["q"]), pool(vq["scale"]),
                             pool(vq["zero"]),
                             dict(page_table=table, page_size=PAGE_SIZE))}
        for layout, (k_lat, v_q, v_s, v_z, kw) in layouts.items():
            sel = {}
            for be in ("pallas", "xla"):
                idx, valid = ops.latent_topk(
                    q_lat, k_lat, None, pos, n_critical=nc, n_sink=16,
                    n_recent=64, backend=be, **kw)
                sel[be] = (np.asarray(idx), np.asarray(valid))
            agree = np.mean([
                len(set(sel["pallas"][0][i][sel["pallas"][1][i]])
                    & set(sel["xla"][0][i][sel["xla"][1][i]]))
                / max(1, sel["xla"][1][i].sum()) for i in range(b)])
            idx = jnp.sort(jnp.asarray(sel["xla"][0]), axis=1)
            valid = jnp.ones_like(idx, bool)
            outs = {}
            for be in ("pallas", "xla"):
                m, l, o = ops.sparse_recon_attention(
                    q, k_lat, None, v_q, v_s, v_z, u, idx, valid, pos,
                    n_kv=n_kv, v_bits=v_bits, v_group=64, theta=1e6,
                    backend=be, **kw)
                outs[be] = np.asarray(o) / np.asarray(l)[..., None]
            gap = np.max(np.abs(outs["pallas"] - outs["xla"])) \
                / np.max(np.abs(outs["xla"]))
            log(f"[kernels] r={rank} r*={r_star} int{v_bits} V {layout}: "
                f"selection agreement {agree:.4f}, reconstruct-attend gap "
                f"{gap:.2e} (tolerance {KERNEL_TOL:g})")
            check(np.isfinite(outs["pallas"]).all(), "finite kernel output")
            check(agree >= 0.99, f"selection agreement {agree}")
            check(gap <= KERNEL_TOL, f"kernel gap {gap}")


def logits_phase(sched, params, projectors, cfg, prompts) -> None:
    """One decode step of the served program vs the jnp-oracle step."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops
    from repro.models import transformer as tf

    eng = sched.engine
    arena = eng.init_slot_cache()
    toks, lens = [], []
    for i, p in enumerate(prompts[:eng.scfg.max_batch]):
        lg, one = eng.prefill_one(p)
        arena = eng.admit(arena, one, i)
        toks.append(int(jnp.argmax(lg[0])))
        lens.append(len(p))
    tok = jnp.asarray(toks, jnp.int32)
    pos = jnp.asarray(lens, jnp.int32)
    served, _ = eng._decode(tok, jax.tree.map(jnp.copy, arena), pos)

    def oracle_step(w, t, c, p):
        return tf.decode_step(*w, c, t, p, cfg, eng.sals)[0]

    weights = (params, projectors)
    ops.set_default_backend("xla")           # read while tracing
    try:
        oracle = jax.jit(oracle_step).lower(weights, tok, arena, pos) \
            .compile()
    finally:
        ops.set_default_backend(None)
    ref = oracle(weights, tok, arena, pos)
    served, ref = np.asarray(served, np.float32), np.asarray(ref, np.float32)
    gap = np.max(np.abs(served - ref)) / np.max(np.abs(ref))
    same_top1 = int(np.sum(served.argmax(-1) == ref.argmax(-1)))
    log(f"[logits] served Pallas step vs jnp-oracle step: max |gap| "
        f"{gap:.2e} of max |logit| (tolerance {LOGITS_TOL:g}), top-1 "
        f"agrees on {same_top1}/{len(toks)} rows, finite "
        f"{bool(np.isfinite(served).all())}")
    check(np.isfinite(served).all(), "finite logits")
    check(gap <= LOGITS_TOL, f"logits gap {gap}")


def main() -> int:
    t_start = time.time()
    dev = require_tpu()
    import dataclasses
    import jax
    from repro.config import SALS_25, ServeConfig
    from repro.configs import get_config
    from repro.kernels import ops
    from repro.launch.serve import model_config, use_compile_cache

    cache_dir = use_compile_cache()
    cfg = model_config(ARCH, layers=LAYERS)
    n_full = SALS_25.skip_layers_front + SALS_25.skip_layers_back
    log(f"[setup] {cfg.name} at published widths: d_model {cfg.d_model}, "
        f"heads {cfg.n_heads}/{cfg.n_kv_heads}, head_dim {cfg.head_dim}, "
        f"ff {cfg.d_ff}, vocab {cfg.vocab_size}; {cfg.n_layers} of "
        f"{get_config(ARCH).n_layers} layers ({n_full} full-precision, "
        f"{cfg.n_layers - n_full} SALS); "
        f"{cfg.param_count() / 1e9:.2f}B params in {cfg.dtype}")
    log(f"[setup] device {dev.platform} {dev.device_kind} x"
        f"{len(jax.devices())}; kernel backend {ops.default_backend()}; "
        f"compile cache {cache_dir}")
    check(ops.default_backend() == "pallas", "Pallas backend on the TPU")

    scfg = ServeConfig(max_seq_len=MAX_SEQ, max_batch=MAX_BATCH,
                       max_new_tokens=NEW_TOKENS, prefill_chunk=128,
                       prefill_token_budget=512, sals=SALS_25, seed=SEED)
    failed = []

    def phase(name, fn, *args):
        try:
            return fn(*args)
        except Exception as exc:             # recorded; the run fails below
            failed.append(name)
            log(f"[{name}] FAILED: {exc!r}")
            traceback.print_exc()
            return None

    phase("kernels", kernel_phase, SEED)
    dense_prompts = make_prompts(cfg, N_REQUESTS, SEED)
    out = phase("dense", serve_phase, "dense", cfg, scfg, dense_prompts)
    built = None
    if out is not None:
        sched, params, projectors = out
        built = (params, projectors)
        phase("logits", logits_phase, sched, params, projectors, cfg,
              dense_prompts)
        del sched
    paged = dataclasses.replace(scfg, page_size=PAGE_SIZE, prefix_cache=True)
    paged_prompts = make_prompts(cfg, N_REQUESTS, SEED + 1,
                                 shared_prefix=SHARED_PREFIX)

    def paged_phase():
        sched, _, _ = serve_phase("paged", cfg, paged, paged_prompts, built)
        log(f"[paged] prefix_hits={sched.prefix_hits} "
            f"cow_copies={sched.cow_copies}")
        check(sched.prefix_hits >= 1, f"prefix_hits {sched.prefix_hits}")

    phase("paged", paged_phase)
    stats = dev.memory_stats() or {}
    log(f"[memory] peak_bytes_in_use {stats.get('peak_bytes_in_use')} of "
        f"bytes_limit {stats.get('bytes_limit')}")
    log(f"[total] wall time {time.time() - t_start:.1f}s")
    if failed:
        log(f"chip_smoke: phases failed: {', '.join(failed)}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
