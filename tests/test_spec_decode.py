"""Speculative decoding through the fused SALS path (ISSUE 9).

Three layers of pinning:

  (a) KERNEL — the windowed recon-attention kernels at q_len = 1 are
      bit-identical to the single-token kernels (dense, paged, grouped,
      ragged), and each window query t equals a q_len = 1 call at base
      position q_pos + t (the per-draft-position mask advance is exactly a
      shifted single-token mask);
  (b) ENGINE — greedy ``generate_speculative`` is token-exact vs
      sequential ``generate`` for ANY draft sequence (the verify commits
      only argmax-matching prefixes), across real n-gram drafts and
      adversarial monkeypatched drafters spanning all-accept to all-reject
      schedules.  Exactness is guaranteed in the saturated-selection
      regime (n_critical covers the selectable range — the fixtures stay
      inside it);
  (c) SCHEDULER — the continuous scheduler with ``spec_window > 1``
      produces the same tokens as ``spec_window = 0`` on dense AND paged
      layouts, streams accepted tokens in commit order with contiguous
      indices, and never fires ``on_token`` for rejected draft positions.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:                       # optional dev extra (pip install .[dev]) — guarded
    from hypothesis import given, settings, strategies as st
except ImportError:        # property tests skip; everything else still runs
    from conftest import given, settings, st  # noqa: F401

from repro.config import SALSConfig, ServeConfig
from repro.configs import get_config
from repro.core import calibration as cal
from repro.core import quantization as qz
from repro.kernels import ops
from repro.models import transformer as tf
from repro.serve import Request, RequestScheduler, ServeEngine
from repro.serve.draft import NgramDrafter

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# kernel level: windowed == single-token
# ---------------------------------------------------------------------------

def _win_inputs(b, s, r, r_star, nc, n_kv, dh, h, ql, *, k_int8, seed=0,
                vg=16):
    kvd = n_kv * dh
    ks = jax.random.split(jax.random.fold_in(KEY, seed), 7)
    q = jax.random.normal(ks[0], (b, ql, h, dh), jnp.float32)
    lat = jax.random.normal(ks[1], (b, s, r))
    if k_int8:
        k_lat, k_scale = qz.quantize_latent_int8(lat)
    else:
        k_lat, k_scale = lat.astype(jnp.bfloat16), None
    v = jax.random.normal(ks[2], (b, s, kvd)) * 2.0
    vq = qz.quantize(v, 8, vg)
    u = jax.random.normal(ks[3], (kvd, r), jnp.float32)
    q_lat = jax.random.normal(ks[4], (b, r_star))
    return q, k_lat, k_scale, vq, u, q_lat


@pytest.mark.parametrize("backend", ["pallas", "xla"])
@pytest.mark.parametrize("k_int8", [False, True])
@pytest.mark.parametrize("pos_rows", [[159], [120, 37, 9]])
def test_window_qlen1_bit_identical_to_single_token(backend, k_int8,
                                                    pos_rows):
    """q_len = 1 through the WINDOWED kernels == the single-token kernels,
    bit for bit, dense layout, scalar and ragged positions."""
    b = len(pos_rows)
    n_kv, dh, h = 2, 32, 4
    s, r, r_star, nc, vg = 160, 16, 8, 24, 16
    q, k_lat, k_scale, vq, u, q_lat = _win_inputs(
        b, s, r, r_star, nc, n_kv, dh, h, 1, k_int8=k_int8)
    pos = jnp.asarray(pos_rows, jnp.int32) if b > 1 \
        else jnp.int32(pos_rows[0])
    idx, valid = ops.latent_topk(q_lat, k_lat, k_scale, pos, n_critical=nc,
                                 n_sink=2, n_recent=8, backend=backend)
    m1, l1, o1 = ops.sparse_recon_attention(
        q[:, 0], k_lat, k_scale, vq["q"], vq["scale"], vq["zero"], u, idx,
        valid, pos, n_kv=n_kv, v_bits=8, v_group=vg, backend=backend)
    mw, lw, ow = ops.sparse_recon_attention_window(
        q, k_lat, k_scale, vq["q"], vq["scale"], vq["zero"], u, idx, valid,
        pos, n_kv=n_kv, n_recent=0, v_bits=8, v_group=vg, backend=backend)
    assert np.array_equal(np.asarray(mw[:, 0]), np.asarray(m1))
    assert np.array_equal(np.asarray(lw[:, 0]), np.asarray(l1))
    assert np.array_equal(np.asarray(ow[:, 0]), np.asarray(o1))


@pytest.mark.parametrize("backend", ["pallas", "xla"])
def test_window_qlen1_bit_identical_grouped(backend):
    """Grouped (slab-folded, pos_base) layout: q_len = 1 windowed ==
    single-token, bit for bit."""
    b, g = 2, 2
    n_kv, dh, h = 2, 32, 4
    s, r, r_star, nc, vg = 160, 16, 8, 24, 16
    s_loc, k_loc = s // g, -(-24 // g)
    q, k_lat, k_scale, vq, u, q_lat = _win_inputs(
        b, s, r, r_star, nc, n_kv, dh, h, 1, k_int8=True, seed=5)

    def fold(a):
        return None if a is None else a.reshape(b * g, s_loc, *a.shape[2:])

    base = jnp.tile(jnp.arange(g, dtype=jnp.int32) * s_loc, b)
    pos = jnp.int32(s - 1)
    idx, valid = ops.latent_topk(
        jnp.repeat(q_lat, g, axis=0), fold(k_lat), fold(k_scale), pos,
        n_critical=k_loc, n_sink=2, n_recent=8, pos_base=base,
        backend=backend)
    args1 = (jnp.repeat(q[:, 0], g, axis=0), fold(k_lat), fold(k_scale),
             fold(vq["q"]), fold(vq["scale"]), fold(vq["zero"]), u, idx,
             valid, pos)
    m1, l1, o1 = ops.sparse_recon_attention(
        *args1, n_kv=n_kv, v_bits=8, v_group=vg, pos_base=base,
        backend=backend)
    argsw = (jnp.repeat(q, g, axis=0),) + args1[1:]
    mw, lw, ow = ops.sparse_recon_attention_window(
        *argsw, n_kv=n_kv, n_recent=0, v_bits=8, v_group=vg, pos_base=base,
        backend=backend)
    assert np.array_equal(np.asarray(mw[:, 0]), np.asarray(m1))
    assert np.array_equal(np.asarray(lw[:, 0]), np.asarray(l1))
    assert np.array_equal(np.asarray(ow[:, 0]), np.asarray(o1))


@pytest.mark.parametrize("backend", ["pallas", "xla"])
def test_window_qlen1_bit_identical_paged(backend):
    """Paged layout (page-table DMA walk): q_len = 1 windowed ==
    single-token, bit for bit, on a permuted page pool."""
    b, s, ps = 2, 96, 16
    n_kv, dh, h = 2, 32, 4
    r, r_star, nc, vg = 16, 8, 12, 16
    q, k_lat, k_scale, vq, u, q_lat = _win_inputs(
        b, s, r, r_star, nc, n_kv, dh, h, 1, k_int8=True, seed=7)
    mp = s // ps
    n_pages = mp * b + 3
    rng = np.random.default_rng(7)
    pt = rng.permutation(n_pages - 1)[: b * mp].reshape(b, mp) + 1
    pt = jnp.asarray(pt.astype(np.int32))

    def pool_of(dense):
        pool = np.zeros((n_pages, ps, *dense.shape[2:]),
                        np.asarray(dense).dtype)
        dnp = np.asarray(dense).reshape(b, mp, ps, *dense.shape[2:])
        for bb in range(b):
            for j in range(mp):
                pool[int(pt[bb, j])] = dnp[bb, j]
        return jnp.asarray(pool)

    pools = [pool_of(a) for a in (k_lat, k_scale, vq["q"], vq["scale"],
                                  vq["zero"])]
    pos = jnp.asarray([95, 40], jnp.int32)
    kw = dict(page_table=pt, page_size=ps, backend=backend)
    idx, valid = ops.latent_topk(q_lat, pools[0], pools[1], pos,
                                 n_critical=nc, n_sink=2, n_recent=8, **kw)
    m1, l1, o1 = ops.sparse_recon_attention(
        q[:, 0], *pools, u, idx, valid, pos, n_kv=n_kv, v_bits=8,
        v_group=vg, **kw)
    mw, lw, ow = ops.sparse_recon_attention_window(
        q, *pools, u, idx, valid, pos, n_kv=n_kv, n_recent=0, v_bits=8,
        v_group=vg, **kw)
    assert np.array_equal(np.asarray(mw[:, 0]), np.asarray(m1))
    assert np.array_equal(np.asarray(lw[:, 0]), np.asarray(l1))
    assert np.array_equal(np.asarray(ow[:, 0]), np.asarray(o1))


@pytest.mark.parametrize("backend", ["pallas", "xla"])
@pytest.mark.parametrize("ql", [2, 4, 8])
def test_window_mask_advance_equals_shifted_single(backend, ql):
    """Per-draft-position mask advance: with the SAME selection, window
    query t must equal a q_len = 1 windowed call at base q_pos + t with the
    same n_recent — the window is Q shifted single-token attends sharing
    one reconstruction.

    The Pallas kernel runs the same per-row body at every q_len, so it
    must match bit for bit.  The jnp oracle compiles the Q-query window
    and the one-query call as two XLA programs with different dot shapes
    (a batched gemm against the gemv of its q_len = 1 delegate); their
    f32 accumulation orders differ by a few ulps over the N_c-term sums,
    so the oracle is held to 1e-5 instead."""
    b, s = 2, 160
    n_kv, dh, h = 2, 32, 4
    r, r_star, nc, vg, n_rec = 16, 8, 24, 16, 8
    q, k_lat, k_scale, vq, u, q_lat = _win_inputs(
        b, s, r, r_star, nc, n_kv, dh, h, ql, k_int8=True, seed=11)

    def same(x, y, t):
        if backend == "pallas":
            assert np.array_equal(np.asarray(x), np.asarray(y)), t
        else:
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=1e-5, atol=1e-5, err_msg=str(t))
    pos = jnp.asarray([140, 60], jnp.int32)
    idx, valid = ops.latent_topk(q_lat, k_lat, k_scale, pos + ql - 1,
                                 n_critical=nc, n_sink=2, n_recent=n_rec,
                                 backend=backend)
    mw, lw, ow = ops.sparse_recon_attention_window(
        q, k_lat, k_scale, vq["q"], vq["scale"], vq["zero"], u, idx, valid,
        pos, n_kv=n_kv, n_recent=n_rec, v_bits=8, v_group=vg,
        backend=backend)
    for t in range(ql):
        m1, l1, o1 = ops.sparse_recon_attention_window(
            q[:, t:t + 1], k_lat, k_scale, vq["q"], vq["scale"], vq["zero"],
            u, idx, valid, pos + t, n_kv=n_kv, n_recent=n_rec, v_bits=8,
            v_group=vg, backend=backend)
        same(mw[:, t], m1[:, 0], t)
        same(lw[:, t], l1[:, 0], t)
        same(ow[:, t], o1[:, 0], t)


@pytest.mark.parametrize("ql", [2, 4])
def test_window_pallas_matches_oracle(ql):
    """Windowed Pallas vs the jnp window oracle on merged outputs."""
    b, s = 2, 160
    n_kv, dh, h = 2, 32, 4
    r, r_star, nc, vg = 16, 8, 24, 16
    q, k_lat, k_scale, vq, u, q_lat = _win_inputs(
        b, s, r, r_star, nc, n_kv, dh, h, ql, k_int8=True, seed=13)
    pos = jnp.asarray([150, 80], jnp.int32)
    out = {}
    for backend in ("pallas", "xla"):
        idx, valid = ops.latent_topk(q_lat, k_lat, k_scale, pos + ql - 1,
                                     n_critical=nc, n_sink=2, n_recent=8,
                                     backend=backend)
        m, l, o = ops.sparse_recon_attention_window(
            q, k_lat, k_scale, vq["q"], vq["scale"], vq["zero"], u, idx,
            valid, pos, n_kv=n_kv, n_recent=8, v_bits=8, v_group=vg,
            backend=backend)
        out[backend] = (np.asarray(o) /
                        np.maximum(np.asarray(l), 1e-30)[..., None])
        assert not np.any(np.isnan(out[backend]))
    np.testing.assert_allclose(out["pallas"], out["xla"], rtol=1e-3,
                               atol=1e-3)


@given(st.integers(0, 2 ** 31 - 1), st.integers(1, 8), st.booleans())
@settings(max_examples=15, deadline=None)
def test_window_backends_agree_property(seed, ql, k_int8):
    """Property: windowed pallas and oracle agree on merged outputs for
    arbitrary q_len, dtype, and window base positions."""
    b, s = 2, 160
    n_kv, dh, h = 2, 32, 4
    r, r_star, nc, vg = 16, 8, 24, 16
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(ks[0], (b, ql, h, dh), jnp.float32)
    lat = jax.random.normal(ks[1], (b, s, r))
    if k_int8:
        k_lat, k_scale = qz.quantize_latent_int8(lat)
    else:
        k_lat, k_scale = lat.astype(jnp.bfloat16), None
    vq = qz.quantize(jax.random.normal(ks[2], (b, s, n_kv * dh)), 8, vg)
    u = jax.random.normal(ks[3], (n_kv * dh, r), jnp.float32)
    q_lat = jax.random.normal(ks[4], (b, r_star))
    pos = jax.random.randint(ks[5], (b,), 20, s - ql).astype(jnp.int32)
    merged = {}
    for backend in ("pallas", "xla"):
        idx, valid = ops.latent_topk(q_lat, k_lat, k_scale, pos + ql - 1,
                                     n_critical=nc, n_sink=2, n_recent=8,
                                     backend=backend)
        m, l, o = ops.sparse_recon_attention_window(
            q, k_lat, k_scale, vq["q"], vq["scale"], vq["zero"], u, idx,
            valid, pos, n_kv=n_kv, n_recent=8, v_bits=8, v_group=vg,
            backend=backend)
        merged[backend] = (np.asarray(o) /
                           np.maximum(np.asarray(l), 1e-30)[..., None])
    np.testing.assert_allclose(merged["pallas"], merged["xla"], rtol=1e-3,
                               atol=1e-3)


# ---------------------------------------------------------------------------
# drafter
# ---------------------------------------------------------------------------

def test_ngram_drafter_longest_match_latest_occurrence():
    # trailing 3-gram [7, 8, 9] occurred earlier twice; the LATEST earlier
    # occurrence (followed by 5, 6) wins over the first (followed by 1, 2)
    d = NgramDrafter([7, 8, 9, 1, 2, 7, 8, 9, 5, 6, 7, 8, 9])
    assert d.propose(2) == [1, 2] or d.propose(2) == [5, 6]
    assert d.propose(2) == [5, 6]


def test_ngram_drafter_falls_through_orders_and_pads():
    # no 3/2-gram repeat; the 1-gram [4] occurred at index 1, followed by 9
    d = NgramDrafter([3, 4, 9, 4])
    assert d.propose(3) == [9, 4, 4]     # copy runs off history, pads last
    # nothing repeats at any order: repeat the last token
    assert NgramDrafter([1, 2, 3]).propose(2) == [3, 3]
    assert NgramDrafter([]).propose(2) == [0, 0]
    assert NgramDrafter([5]).propose(0) == []


def test_ngram_drafter_extend_shifts_match():
    d = NgramDrafter([1, 2, 3, 1, 2])
    assert d.propose(1) == [3]
    d.extend([3, 9])
    assert d.propose(1) == [9] or d.propose(1) == [1]
    # trailing [3, 9] is unique; 1-gram [9]... no earlier 9 -> falls to
    # the 2-gram/1-gram scan over the updated history
    assert d.history == [1, 2, 3, 1, 2, 3, 9]


def test_ngram_drafter_rejects_bad_order():
    with pytest.raises(ValueError):
        NgramDrafter([1], max_order=0)


# ---------------------------------------------------------------------------
# engine + scheduler fixtures (saturated-selection regime: n_critical
# covers every selectable position the episodes reach, so the window's one
# selection is exact and greedy spec == greedy sequential bit for bit)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def model():
    cfg = get_config("qwen2-1.5b").reduced(n_layers=3, vocab_size=128)
    params = tf.init_params(KEY, cfg, jnp.float32)
    sals = SALSConfig(rank_ratio=0.5, score_ratio=0.5, n_critical=64,
                      n_sink=2, n_recent=8, v_bits=8, v_group=32,
                      skip_layers_front=1, skip_layers_back=1)
    proj = cal.random_layer_projectors(KEY, cfg, sals, cfg.n_layers)
    return cfg, params, sals, proj


def _prompts(vocab=128):
    rng = np.random.default_rng(3)
    base = rng.integers(1, vocab, size=8)
    return [np.tile(base, 3).astype(np.int32)[: 18 + 4 * i]
            for i in range(2)] + \
        [rng.integers(1, vocab, size=21).astype(np.int32)]


def _engine(model, spec, **kw):
    cfg, params, sals, proj = model
    scfg = ServeConfig(max_seq_len=128, max_batch=3, temperature=0.0,
                       sals=sals, spec_window=spec, **kw)
    return ServeEngine(params, proj, cfg, scfg)


# ---------------------------------------------------------------------------
# engine level: token-exactness for any drafts
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("q", [2, 4, 8])
def test_generate_speculative_token_exact(model, q):
    prompts = _prompts()
    want = [r.tokens for r in
            _engine(model, 0).generate(prompts, max_new_tokens=17)]
    eng = _engine(model, q)
    got = eng.generate_speculative(prompts, max_new_tokens=17)
    for w, g in zip(want, got):
        np.testing.assert_array_equal(w, g.tokens)
    stats = eng.spec_stats
    # every token after each row's prefill token commits via a verify round
    assert stats["committed"] == sum(len(r.tokens) for r in got) - len(got)
    assert stats["rounds"] >= -(-16 // q)   # >= ceil((mnt - prefill) / q)
    assert 0 <= stats["accepted_drafts"] <= stats["proposed"]


@pytest.mark.parametrize("drafter", ["garbage", "constant", "repeat-last"])
def test_generate_speculative_exact_for_any_drafts(model, drafter,
                                                   monkeypatch):
    """Adversarial drafters spanning all-reject to mixed accept/reject
    schedules: the verify-accept loop must stay token-exact regardless of
    WHAT is proposed (correctness never depends on draft quality)."""
    rng = np.random.default_rng(9)

    def propose(self, n_draft):
        if drafter == "garbage":
            return [int(t) for t in rng.integers(1, 128, size=n_draft)]
        if drafter == "constant":
            return [5] * n_draft
        return [self.history[-1]] * n_draft

    monkeypatch.setattr(NgramDrafter, "propose", propose)
    prompts = _prompts()
    want = [r.tokens for r in
            _engine(model, 0).generate(prompts, max_new_tokens=13)]
    eng = _engine(model, 4)
    got = eng.generate_speculative(prompts, max_new_tokens=13)
    for w, g in zip(want, got):
        np.testing.assert_array_equal(w, g.tokens)
    # even all-rejected rounds make sequential progress (1 token/round)
    assert eng.spec_stats["committed"] >= eng.spec_stats["rounds"]


def test_generate_speculative_needs_window(model):
    with pytest.raises(ValueError):
        _engine(model, 0).generate_speculative(_prompts(),
                                               max_new_tokens=4)


# ---------------------------------------------------------------------------
# scheduler level: exactness + streaming through continuous batching
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("paged", [False, True])
def test_scheduler_speculative_token_exact_and_streams(model, paged):
    """spec_window = 4 through the continuous scheduler == spec_window = 0,
    token for token, on dense and paged layouts; accepted tokens stream in
    commit order with contiguous indices and rejected draft positions
    never fire on_token."""
    kw = dict(prefill_chunk=8, prefill_token_budget=32)
    if paged:
        kw.update(page_size=16, n_pages=40)

    def run(spec):
        eng = _engine(model, spec, **kw)
        sched = RequestScheduler(eng)
        streams, reqs = {}, []
        for p in _prompts():
            req = Request(p, max_new_tokens=17)
            streams[req.req_id] = []
            req.on_token = lambda tok, idx, r=req.req_id: \
                streams[r].append((idx, tok))
            reqs.append(req)
            sched.submit(req)
        sched.run()
        return reqs, streams, sched

    r0, _, _ = run(0)
    r4, s4, sc = run(4)
    for a, b in zip(r0, r4):
        assert a.done and b.done
        np.testing.assert_array_equal(a.result.tokens, b.result.tokens)
    for req in r4:
        idxs = [i for i, _ in s4[req.req_id]]
        assert idxs == list(range(len(idxs)))       # contiguous, in order
        toks = [t for _, t in s4[req.req_id]]
        assert toks == list(req.result.tokens)      # stream == result
    assert sc.spec_rounds > 0
    assert sc.spec_committed >= sc.spec_rounds      # progress every round
    assert sc.spec_accepted <= sc.spec_proposed
    # the drafter accepts on the repetitive prompts — the window actually
    # amortizes (strictly more tokens than verify rounds)
    assert sc.spec_committed > sc.spec_rounds


def test_static_mode_uses_speculative_path(model):
    eng = _engine(model, 4)
    sched = RequestScheduler(eng, mode="static")
    reqs = [Request(p, max_new_tokens=9) for p in _prompts()]
    for r in reqs:
        sched.submit(r)
    sched.run()
    want = [r.tokens for r in
            _engine(model, 0).generate(_prompts(), max_new_tokens=9)]
    for r, w in zip(reqs, want):
        assert r.done
        np.testing.assert_array_equal(r.result.tokens, w)
    assert eng.spec_stats["rounds"] > 0


# ---------------------------------------------------------------------------
# config validation
# ---------------------------------------------------------------------------

def test_spec_window_validation(model):
    cfg, params, sals, proj = model
    with pytest.raises(ValueError):
        ServeConfig(spec_window=9, sals=sals)       # kernel q_len cap
    with pytest.raises(ValueError):
        ServeConfig(spec_window=-1, sals=sals)
    with pytest.raises(ValueError):                  # > sals.n_recent
        import dataclasses
        ServeConfig(spec_window=4,
                    sals=dataclasses.replace(sals, n_recent=2))
    with pytest.raises(ValueError):                  # tiered cache
        ServeConfig(spec_window=4, sals=sals, page_size=16, n_pages=8,
                    hbm_pages=4)
    with pytest.raises(ValueError):                  # greedy-only
        ServeConfig(spec_window=4, sals=sals, temperature=0.7)
    # off (0 / 1) carries no constraints
    ServeConfig(spec_window=0, sals=sals, temperature=0.7)
    ServeConfig(spec_window=1, sals=sals, temperature=0.7)
