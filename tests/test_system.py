"""End-to-end system tests: train → calibrate → SALS serve; checkpoint /
restart; straggler monitor; scheduler; serving quality of the compressed
model vs the uncompressed one on a TRAINED model (the paper's accuracy
claim, proxied on a model this repo trains itself)."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import checkpoint as ckpt
from repro.config import SALSConfig, ServeConfig, TrainConfig
from repro.configs import get_config
from repro.core import calibration as cal
from repro.core import metrics
from repro.data import SyntheticCorpus, make_batches
from repro.ft import StragglerMonitor, Supervisor
from repro.launch.serve import (build, calibrate, collect_pre_rope_keys,
                                model_config)
from repro.models import transformer as tf
from repro.serve import Request, RequestScheduler, ServeEngine
from repro.train import trainer

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def trained():
    """A small dense model trained enough to have structured attention."""
    cfg = get_config("qwen2-1.5b").reduced(n_layers=3, vocab_size=512)
    tcfg = TrainConfig(steps=40, batch_size=8, seq_len=64, lr=5e-3,
                       warmup_steps=5, log_every=100)
    corpus = SyntheticCorpus(cfg.vocab_size, seed=0)
    state = trainer.init_state(KEY, cfg, tcfg, jnp.float32)
    step = jax.jit(trainer.make_train_step(cfg, tcfg))
    first = last = None
    for i, batch in zip(range(tcfg.steps),
                        make_batches(corpus, 8, 64)):
        state, m = step(state, jax.tree.map(jnp.asarray, batch))
        if first is None:
            first = float(m["loss"])
        last = float(m["loss"])
    assert last < first - 0.5, (first, last)   # actually learned something
    return cfg, state["params"], corpus


def test_training_learns(trained):
    pass   # assertions inside the fixture


def test_calibrated_projector_beats_random(trained):
    """Calibration on real keys captures more energy than random basis."""
    cfg, params, corpus = trained
    sals = SALSConfig(rank_ratio=0.25, v_group=32)
    proj = calibrate(params, cfg, sals, corpus, n_sequences=8, seq_len=64)
    r = sals.rank(cfg.kv_dim)
    keys = np.asarray(collect_pre_rope_keys(
        params, cfg, {"tokens": jnp.asarray(corpus.batch(99, 4, 64)["tokens"])}))
    rnd = cal.random_layer_projectors(KEY, cfg, sals, cfg.n_layers)

    def recon_err(u_all):
        err = 0.0
        for l in range(cfg.n_layers):
            k = keys[l].reshape(-1, cfg.kv_dim)
            u = np.asarray(u_all[l], np.float64)
            rec = (k @ u) @ u.T
            err += np.linalg.norm(rec - k) / np.linalg.norm(k)
        return err / cfg.n_layers

    assert recon_err(proj["u"]) < recon_err(rnd["u"]) * 0.9


def test_overlap_score_on_trained_model(trained):
    """Paper Fig.2 claim (proxy): latent top-k captures most of the
    attention mass on a trained model with a calibrated projector."""
    cfg, params, corpus = trained
    sals = SALSConfig(rank_ratio=0.5, score_ratio=0.5, n_critical=24,
                      n_sink=2, n_recent=8, v_group=32)
    proj = calibrate(params, cfg, sals, corpus, n_sequences=8, seq_len=64)
    toks = jnp.asarray(corpus.batch(123, 2, 64)["tokens"])
    keys = collect_pre_rope_keys(params, cfg, {"tokens": toks})
    # query at the last position of layer 1 (a non-skip layer)
    x, _ = tf.embed_inputs(params, cfg, {"tokens": toks})
    from repro.models.attention import qkv_proj
    from repro.models.layers import rmsnorm_apply
    bp = jax.tree.map(lambda a: a[1], params["blocks"])
    h = rmsnorm_apply(bp["attn_norm"], x, cfg.norm_eps)
    q, _, _ = qkv_proj(bp["attn"], h, cfg)
    k_pre = keys[1].reshape(2, 64, cfg.n_kv_heads, cfg.head_dim)
    os_ = np.asarray(metrics.overlap_score(
        q[:, -1], jnp.asarray(k_pre), proj["u"][1], cfg, sals, pos=63))
    assert np.all(os_ > 0.5), os_    # >50% of mass with 34/64 tokens kept


def test_sals_serve_quality_vs_full(trained):
    """Compressed engine agrees with the full engine on most next tokens."""
    cfg, params, corpus = trained
    sals = SALSConfig(rank_ratio=0.5, score_ratio=0.5, n_critical=32,
                      n_sink=2, n_recent=8, v_bits=8, v_group=32,
                      skip_layers_front=1, skip_layers_back=1)
    proj = calibrate(params, cfg, sals, corpus, n_sequences=8, seq_len=64)
    scfg_full = ServeConfig(max_seq_len=128, max_new_tokens=16,
                            sals=SALSConfig(enabled=False))
    scfg_sals = ServeConfig(max_seq_len=128, max_new_tokens=16, sals=sals)
    full = ServeEngine(params, None, cfg, scfg_full)
    comp = ServeEngine(params, proj, cfg, scfg_sals)
    prompts = [corpus.batch(7_000 + i, 1, 48)["tokens"][0] for i in range(4)]
    out_f = full.generate(prompts, max_new_tokens=16)
    out_c = comp.generate(prompts, max_new_tokens=16)
    agree = np.mean([np.mean(a.tokens == b.tokens)
                     for a, b in zip(out_f, out_c)])
    assert agree > 0.7, agree


def test_scheduler_batches_and_completes(trained):
    cfg, params, corpus = trained
    scfg = ServeConfig(max_seq_len=128, max_new_tokens=8, max_batch=3,
                       sals=SALSConfig(enabled=False))
    eng = ServeEngine(params, None, cfg, scfg)
    sched = RequestScheduler(eng)
    ids = [sched.submit(Request(corpus.batch(8_000 + i, 1, 16 + 4 * i)
                                ["tokens"][0], max_new_tokens=4 + i % 3))
           for i in range(7)]
    done = sched.run()
    assert len(done) == 7
    for r in done:
        assert r.done and len(r.result.tokens) == r.max_new_tokens


# ---------------------------------------------------------------------------
# launcher construction path (launch/serve.py, shared with chip_smoke.py)
# ---------------------------------------------------------------------------

def test_model_config_cuts_depth_keeps_published_widths():
    full = get_config("paper-mistral-7b")
    cut = model_config("paper-mistral-7b", layers=8)
    assert cut.n_layers == 8
    assert (cut.d_model, cut.n_heads, cut.n_kv_heads, cut.head_dim,
            cut.d_ff, cut.vocab_size, cut.dtype) == \
        (full.d_model, full.n_heads, full.n_kv_heads, full.head_dim,
         full.d_ff, full.vocab_size, full.dtype) == \
        (4096, 32, 8, 128, 14336, 32000, "bfloat16")
    assert model_config("paper-mistral-7b", reduced=True).d_model == 128
    for bad in (-1, full.n_layers + 1):
        with pytest.raises(ValueError, match="--layers"):
            model_config("paper-mistral-7b", layers=bad)


def test_build_serves_with_params_in_config_dtype():
    """``build`` makes params in ``cfg.dtype`` (bf16), calibrates the SALS
    projectors, and its scheduler serves; a second build reuses both."""
    cfg = model_config("paper-mistral-7b", reduced=True, layers=3)
    sals = SALSConfig(rank_ratio=0.5, n_critical=16, n_sink=2, n_recent=8,
                      v_group=32, skip_layers_front=1, skip_layers_back=1)
    scfg = ServeConfig(max_seq_len=64, max_new_tokens=4, max_batch=2,
                       sals=sals)
    sched, params, proj = build(cfg, scfg, log=lambda _: None)
    assert {a.dtype for a in jax.tree.leaves(params)} == {jnp.dtype(cfg.dtype)}
    assert proj is not None
    prompt = SyntheticCorpus(cfg.vocab_size, seed=1).batch(0, 1, 40)["tokens"]
    sched.submit(Request(prompt[0], max_new_tokens=4))
    (r,) = sched.run()
    assert r.done and len(r.result.tokens) == 4
    again, p2, proj2 = build(cfg, scfg, params=params, projectors=proj)
    assert p2 is params and proj2 is proj and again is not sched


def test_use_compile_cache_follows_env_else_repo_dir(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins untouched; without it the cache is
    the fixed, git-ignored .jax_cache/ at the repo root."""
    from repro.launch import serve as launch_serve
    was = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    try:
        assert launch_serve.use_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = launch_serve.use_compile_cache()
        assert path == os.path.join(launch_serve.REPO_ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    with open(os.path.join(launch_serve.REPO_ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


# ---------------------------------------------------------------------------
# checkpoint / restart / elastic
# ---------------------------------------------------------------------------

def test_checkpoint_atomic_save_restore(tmp_path, trained):
    cfg, params, _ = trained
    tcfg = TrainConfig()
    state = {"params": params, "opt": trainer.adamw_init(params)}
    d = str(tmp_path / "ck")
    ckpt.save(d, 10, state, keep=2)
    ckpt.save(d, 20, state, keep=2)
    ckpt.save(d, 30, state, keep=2)
    assert ckpt.list_checkpoints(d) == [20, 30]      # keep-N pruning
    restored, step = ckpt.restore(d, state)
    assert step == 30
    for a, b in zip(jax.tree.leaves(state["params"]),
                    jax.tree.leaves(restored["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_ignores_partial_tmp(tmp_path, trained):
    cfg, params, _ = trained
    state = {"params": params}
    d = str(tmp_path / "ck")
    ckpt.save(d, 5, state)
    os.makedirs(os.path.join(d, "step_000000009.tmp"))  # simulated crash
    assert ckpt.latest_step(d) == 5
    _, step = ckpt.restore(d, state)
    assert step == 5


def test_checkpoint_latest_survives_torn_pointer(tmp_path, trained):
    """ISSUE 6 satellite: the LATEST pointer is advisory.  A torn write
    (garbage content) or truncation must fall back to the manifest-verified
    directory scan, not crash or return None."""
    cfg, params, _ = trained
    state = {"params": params}
    d = str(tmp_path / "ck")
    ckpt.save(d, 5, state)
    ckpt.save(d, 7, state)
    with open(os.path.join(d, "LATEST"), "w") as f:
        f.write("step_garbage\x00\x00")          # torn/corrupt pointer
    assert ckpt.latest_step(d) == 7
    with open(os.path.join(d, "LATEST"), "w") as f:
        f.write("")                              # truncated to empty
    assert ckpt.latest_step(d) == 7
    _, step = ckpt.restore(d, state)
    assert step == 7


def test_checkpoint_latest_survives_dangling_pointer(tmp_path, trained):
    """A pointer naming a pruned (or never-completed) step dir must not be
    trusted: scan wins.  Also: pointer at a dir whose manifest is missing
    counts as incomplete."""
    import shutil
    cfg, params, _ = trained
    state = {"params": params}
    d = str(tmp_path / "ck")
    ckpt.save(d, 3, state)
    ckpt.save(d, 9, state)
    shutil.rmtree(os.path.join(d, "step_000000009"))   # pruned behind LATEST
    assert ckpt.latest_step(d) == 3
    _, step = ckpt.restore(d, state)
    assert step == 3
    # dir exists but manifest never landed -> still not trusted
    os.makedirs(os.path.join(d, "step_000000011"))
    with open(os.path.join(d, "LATEST"), "w") as f:
        f.write("step_000000011")
    assert ckpt.latest_step(d) == 3
    # no checkpoints at all: None, not an exception
    empty = str(tmp_path / "empty")
    os.makedirs(empty)
    with open(os.path.join(empty, "LATEST"), "w") as f:
        f.write("step_000000001")
    assert ckpt.latest_step(empty) is None


def test_supervisor_passes_resume_step_through(tmp_path):
    """ISSUE 6 satellite: ``work(resume_step)`` receives the RESTORED step
    from the ``resume`` callable on retries (None on the first attempt) —
    the old contract passed a ``-1`` flag and made work re-derive it."""
    seen = []
    attempts = {"n": 0}

    def work(resume_step):
        seen.append(resume_step)
        attempts["n"] += 1
        if attempts["n"] < 3:
            raise RuntimeError(f"boom {attempts['n']}")
        return "done"

    sup = Supervisor(max_restarts=3, log=lambda *_: None)
    out = sup.run(work, resume=lambda: 40 + attempts["n"] * 2)
    assert out == "done"
    assert seen == [None, 42, 44]       # fresh start, then restored steps
    assert sup.restarts == 2


def test_supervisor_backoff_exponential_with_cap(monkeypatch):
    """Retry i sleeps min(backoff · 2^(i-1), cap) — and exhaustion raises
    RestartsExhausted chained to the last worker fault."""
    import time as _time
    from repro.ft import RestartsExhausted
    sleeps = []
    monkeypatch.setattr(_time, "sleep", sleeps.append)

    def work(_):
        raise RuntimeError("always down")

    sup = Supervisor(max_restarts=4, backoff_s=1.0, backoff_cap_s=5.0,
                     log=lambda *_: None)
    with pytest.raises(RestartsExhausted) as ei:
        sup.run(work)
    assert isinstance(ei.value.__cause__, RuntimeError)
    assert sleeps == [1.0, 2.0, 4.0, 5.0]   # doubling, then capped
    assert sup.restarts == 5                # 4 retries + the fatal attempt


def test_supervisor_restarts_and_resumes(tmp_path):
    """Crash mid-training; supervisor resumes from the checkpoint and the
    final state matches an uninterrupted run (deterministic data)."""
    cfg = get_config("qwen2-1.5b").reduced(n_layers=2, vocab_size=256)
    tcfg = TrainConfig(steps=10, batch_size=4, seq_len=32, lr=1e-3,
                       checkpoint_every=2, log_every=100)
    corpus = SyntheticCorpus(cfg.vocab_size, seed=3)
    d = str(tmp_path / "ck")
    crashed = {"done": False}

    def train_once(start_step):
        state = trainer.init_state(KEY, cfg, tcfg, jnp.float32)
        if start_step:
            state, start_step = ckpt.restore(d, state)
        step = jax.jit(trainer.make_train_step(cfg, tcfg))
        for i in range(start_step, tcfg.steps):
            if i == 5 and not crashed["done"]:
                crashed["done"] = True
                raise RuntimeError("simulated node failure")
            batch = jax.tree.map(jnp.asarray, corpus.batch(i, 4, 32))
            state, _ = step(state, batch)
            if (i + 1) % tcfg.checkpoint_every == 0:
                ckpt.save(d, i + 1, state, keep=2)
        return state

    def work(flag):
        start = ckpt.latest_step(d) or 0
        return train_once(start)

    sup = Supervisor(max_restarts=2)
    state_r = sup.run(work)
    assert sup.restarts == 1 and crashed["done"]

    # uninterrupted reference
    state_ref = trainer.init_state(KEY, cfg, tcfg, jnp.float32)
    step = jax.jit(trainer.make_train_step(cfg, tcfg))
    for i in range(tcfg.steps):
        batch = jax.tree.map(jnp.asarray, corpus.batch(i, 4, 32))
        state_ref, _ = step(state_ref, batch)
    for a, b in zip(jax.tree.leaves(state_r["params"]),
                    jax.tree.leaves(state_ref["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_straggler_monitor_flags_tail():
    mon = StragglerMonitor(window=20, threshold=1.5, patience=3)
    for i in range(20):
        mon.record(i, 0.10 + 0.001 * (i % 3))
    assert not mon.flags
    flagged = mon.record(20, 0.30)
    assert flagged
    mon.record(21, 0.31)
    mon.record(22, 0.32)
    assert mon.should_mitigate()
    mon.record(23, 0.10)
    assert not mon.should_mitigate()     # recovered


def test_elastic_restore_changes_mesh(tmp_path, trained):
    """Mesh-agnostic restore: save unsharded, restore onto a 1-device
    'mesh' sharding (device_put against NamedSharding)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    cfg, params, _ = trained
    d = str(tmp_path / "ck")
    ckpt.save(d, 1, {"params": params})
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    shardings = jax.tree.map(
        lambda _: NamedSharding(mesh, P()), {"params": params})
    restored, _ = ckpt.restore(d, {"params": params}, shardings=shardings)
    leaf = jax.tree.leaves(restored["params"])[0]
    assert leaf.sharding == NamedSharding(mesh, P())
