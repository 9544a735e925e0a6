"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quantization as qz
from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.latent_score import latent_score_pallas, latent_topk_pallas
from repro.kernels import sparse_recon_attention as sra
from repro.kernels.sparse_recon_attention import sparse_recon_attention_pallas

KEY = jax.random.PRNGKey(0)


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# backend dispatch and interpret mode follow the platform
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("platform,backend,interpret", [
    ("tpu", "pallas", False), ("cpu", "xla", True), ("gpu", "xla", None)])
def test_backend_and_interpret_follow_platform(monkeypatch, platform,
                                               backend, interpret):
    """A TPU runs the Pallas kernels compiled; only the CPU interprets
    them; any other platform refuses to interpret (it would time the
    interpreter).  An explicit backend overrides the platform until reset."""
    from repro.kernels import interpret_mode
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    assert ops.default_backend() == backend
    if interpret is None:
        with pytest.raises(RuntimeError, match="neither"):
            interpret_mode()
    else:
        assert interpret_mode() is interpret
    ops.set_default_backend("naive")
    try:
        assert ops.default_backend() == "naive"
    finally:
        ops.set_default_backend(None)
    assert ops.default_backend() == backend


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,sq,sk,h,dh", [
    (1, 128, 128, 2, 64),
    (2, 256, 256, 4, 64),
    (1, 128, 384, 2, 128),     # decode-style sq < sk
    (2, 192, 192, 3, 32),      # non-128-multiple seq -> padding path
])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(b, sq, sk, h, dh, causal, dtype):
    if not causal and sq != sk:
        pytest.skip("bidirectional requires square block")
    if not causal and sq % 128:
        pytest.skip("kv padding requires causal masking")
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, sq, h, dh), dtype)
    k = jax.random.normal(ks[1], (b, sk, h, dh), dtype)
    v = jax.random.normal(ks[2], (b, sk, h, dh), dtype)
    out = flash_attention_pallas(q, k, v, causal=causal,
                                 block_q=128, block_k=128)
    expected = ref.attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expected, np.float32), **tol(dtype))


def test_flash_attention_softcap():
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 256, 2, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 256, 2, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 256, 2, 64), jnp.float32)
    out = flash_attention_pallas(q, k, v, causal=True, softcap=30.0,
                                 block_q=128, block_k=128)
    expected = ref.attention_ref(q, k, v, causal=True, softcap=30.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_prefix_lm():
    """Prefix-LM mask (paligemma): prefix columns bidirectional."""
    ks = jax.random.split(KEY, 3)
    b, s, h, dh, pfx = 1, 256, 2, 64, 64
    q = jax.random.normal(ks[0], (b, s, h, dh), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, h, dh), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, h, dh), jnp.float32)
    out = flash_attention_pallas(q, k, v, causal=False, prefix_len=pfx,
                                 block_q=128, block_k=128)
    kv = jnp.arange(s)
    mask = ((kv[None, :] < pfx) |
            (jnp.arange(s)[:, None] >= kv[None, :]))[None, None]
    expected = ref.attention_ref(q, k, v, causal=False, mask=mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=2e-5, atol=2e-5)
    # ops dispatch agrees across backends
    for backend in ("naive", "xla", "pallas"):
        got = ops.flash_attention(q, k, v, causal=True, prefix_len=pfx,
                                  backend=backend)
        np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                                   rtol=2e-5, atol=2e-5)


def test_flash_xla_long_matches_naive():
    """Chunked XLA path beyond the naive-threshold sequence length."""
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 4096, 2, 32), jnp.float32)
    k = jax.random.normal(ks[1], (1, 4096, 2, 32), jnp.float32)
    v = jax.random.normal(ks[2], (1, 4096, 2, 32), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=True, backend="xla")
    expected = ref.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=3e-5, atol=3e-5)


# ---------------------------------------------------------------------------
# latent score + fused top-k selection
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,s,r,r_star", [
    (1, 256, 64, 32), (3, 1000, 128, 64), (2, 512, 96, 96),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_latent_score_matches_ref(b, s, r, r_star, dtype):
    q_lat = jax.random.normal(KEY, (b, r_star), dtype)
    k_lat = jax.random.normal(jax.random.fold_in(KEY, 1), (b, s, r), dtype)
    got = latent_score_pallas(q_lat, k_lat, block_s=128)
    want = ref.latent_score_ref(q_lat, k_lat)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **tol(dtype))


def test_latent_score_int8_scale():
    b, s, r, r_star = 2, 300, 64, 32
    lat = jax.random.normal(KEY, (b, s, r))
    k_q, k_scale = qz.quantize_latent_int8(lat)
    q_lat = jax.random.normal(jax.random.fold_in(KEY, 7), (b, r_star))
    got = latent_score_pallas(q_lat, k_q, k_scale, block_s=128)
    want = ref.latent_score_ref(q_lat, k_q, k_scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("s,block_s,n_critical", [
    (256, 64, 32),       # multi-block
    (1000, 256, 48),     # ragged tail block
    (100, 256, 64),      # single padded block
    (300, 64, 200),      # n_critical > block -> candidate padding
])
@pytest.mark.parametrize("int8", [False, True])
def test_latent_topk_matches_ref_exactly(s, block_s, n_critical, int8):
    """Per-block partial top-k + merge must equal full-seq lax.top_k
    bit-for-bit (indices AND valid), including tie-break order."""
    b, r, r_star = 2, 32, 16
    pos = jnp.int32(s - 1)
    lat = jax.random.normal(jax.random.fold_in(KEY, 2), (b, s, r))
    if int8:
        k_lat, k_scale = qz.quantize_latent_int8(lat)
    else:
        k_lat, k_scale = lat.astype(jnp.bfloat16), None
    q_lat = jax.random.normal(jax.random.fold_in(KEY, 3), (b, r_star))
    i_p, v_p = latent_topk_pallas(q_lat, k_lat, k_scale, pos,
                                  n_critical=n_critical, n_sink=4,
                                  n_recent=16, block_s=block_s)
    i_r, v_r = ref.latent_topk_ref(q_lat, k_lat, k_scale, pos,
                                   n_critical=n_critical, n_sink=4,
                                   n_recent=16)
    assert np.array_equal(np.asarray(i_p), np.asarray(i_r))
    assert np.array_equal(np.asarray(v_p), np.asarray(v_r))


def test_latent_topk_short_sequence_invalid_slots():
    """pos early in the sequence -> fewer selectable than N_c -> the extra
    slots must come back invalid, never NaN."""
    b, s, r = 1, 128, 16
    k_lat = jax.random.normal(KEY, (b, s, r), jnp.float32)
    q_lat = jax.random.normal(jax.random.fold_in(KEY, 4), (b, r))
    idx, valid = latent_topk_pallas(q_lat, k_lat, None, jnp.int32(20),
                                    n_critical=32, n_sink=4, n_recent=8,
                                    block_s=64)
    n_selectable = (20 - 8) - 4 + 1          # [n_sink, pos - n_recent]
    assert int(valid.sum()) == n_selectable
    sel = np.asarray(idx)[np.asarray(valid)]
    assert sel.min() >= 4 and sel.max() <= 12


# ---------------------------------------------------------------------------
# fused gather→dequant→reconstruct→RoPE→attention
# ---------------------------------------------------------------------------

def _fused_inputs(b, h, n_kv, dh, s, r, nc, *, k_int8, v_bits, v_group,
                  valid_frac=0.85, seed=0):
    kvd = n_kv * dh
    ks = jax.random.split(jax.random.fold_in(KEY, seed), 7)
    q = jax.random.normal(ks[0], (b, h, dh), jnp.float32)
    lat = jax.random.normal(ks[1], (b, s, r))
    if k_int8:
        k_lat, k_scale = qz.quantize_latent_int8(lat)
    else:
        k_lat, k_scale = lat.astype(jnp.bfloat16), None
    v = jax.random.normal(ks[2], (b, s, kvd)) * 2.0
    vq = qz.quantize(v, v_bits, v_group)
    u = jax.random.normal(ks[3], (kvd, r), jnp.float32)
    idx = jax.random.randint(ks[4], (b, nc), 0, s)
    valid = jax.random.bernoulli(ks[5], valid_frac, (b, nc))
    qp = jnp.full((b,), s + 7, jnp.int32)
    return (q, k_lat, k_scale, vq["q"], vq["scale"], vq["zero"], u, idx,
            valid, qp)


def _assert_fused_close(args, kw, rtol=1e-3, atol=1e-3):
    m1, l1, o1 = sparse_recon_attention_pallas(*args, **kw)
    m2, l2, o2 = ref.sparse_recon_attention_fused_ref(*args, **kw)
    np.testing.assert_allclose(np.asarray(m1), np.asarray(m2), rtol=rtol,
                               atol=atol)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), rtol=rtol,
                               atol=atol)
    y1 = np.asarray(o1) / np.maximum(np.asarray(l1), 1e-30)[..., None]
    y2 = np.asarray(o2) / np.maximum(np.asarray(l2), 1e-30)[..., None]
    np.testing.assert_allclose(y1, y2, rtol=rtol, atol=atol)


@pytest.mark.parametrize("h,n_kv,dh", [
    (4, 2, 64),      # GQA group 2
    (8, 2, 64),      # GQA group 4
    (8, 1, 128),     # MQA, gemma-style head_dim
    (6, 6, 32),      # MHA
])
@pytest.mark.parametrize("k_int8", [False, True])
def test_fused_sra_matches_oracle_gqa_dtypes(h, n_kv, dh, k_int8):
    args = _fused_inputs(2, h, n_kv, dh, 200, 32, 48, k_int8=k_int8,
                         v_bits=8, v_group=32)
    _assert_fused_close(args, dict(n_kv=n_kv, v_bits=8, v_group=32))


@pytest.mark.parametrize("v_bits", [8, 4])
@pytest.mark.parametrize("use_rope", [True, False])
@pytest.mark.parametrize("softcap", [0.0, 30.0])
def test_fused_sra_rope_softcap_vbits(v_bits, use_rope, softcap):
    args = _fused_inputs(1, 4, 2, 64, 160, 32, 40, k_int8=False,
                         v_bits=v_bits, v_group=32, seed=3)
    _assert_fused_close(args, dict(n_kv=2, v_bits=v_bits, v_group=32,
                                   use_rope=use_rope, softcap=softcap))


def test_fused_sra_ragged_validity():
    """Mostly-invalid selection (short sequences): padding slots must not
    contribute, and fully-invalid rows must give l=0, o=0, no NaN."""
    args = _fused_inputs(2, 4, 2, 32, 96, 16, 24, k_int8=False, v_bits=8,
                         v_group=16, valid_frac=0.3, seed=5)
    _assert_fused_close(args, dict(n_kv=2, v_bits=8, v_group=16))
    # all-invalid row
    args = list(args)
    args[8] = jnp.zeros_like(args[8])        # valid
    m, l, o = sparse_recon_attention_pallas(*args, n_kv=2, v_bits=8,
                                            v_group=16)
    assert np.all(np.asarray(l) == 0.0)
    assert np.all(np.asarray(o) == 0.0)
    assert not np.any(np.isnan(np.asarray(m)))


def test_fused_sra_positions_are_indices():
    """RoPE must be applied at each selected token's ORIGINAL position,
    i.e. its cache row index: permuting idx permutes (m, per-token p)
    consistently -> merged output is permutation-invariant."""
    args = _fused_inputs(1, 4, 2, 64, 128, 32, 32, k_int8=False, v_bits=8,
                         v_group=32, valid_frac=1.0, seed=9)
    kw = dict(n_kv=2, v_bits=8, v_group=32)
    m1, l1, o1 = sparse_recon_attention_pallas(*args, **kw)
    perm = jax.random.permutation(KEY, args[7].shape[1])
    args2 = list(args)
    args2[7] = args[7][:, perm]
    args2[8] = args[8][:, perm]
    m2, l2, o2 = sparse_recon_attention_pallas(*args2, **kw)
    np.testing.assert_allclose(np.asarray(m1), np.asarray(m2), rtol=1e-5,
                               atol=1e-5)
    y1 = np.asarray(o1) / np.asarray(l1)[..., None]
    y2 = np.asarray(o2) / np.asarray(l2)[..., None]
    np.testing.assert_allclose(y1, y2, rtol=1e-4, atol=1e-4)


# (entry point, N_c, V bits, int8 latents): N_c under one block of T
# tokens (one padded block) and N_c over one block, not a multiple of T
# (pad entries in the last block)
BLOCKED_CASES = [
    ("dense", 42, 8, False),
    ("dense", 200, 4, True),
    ("paged", 200, 8, True),
    ("paged", 42, 4, False),
    ("window", 136, 8, True),
    ("window_paged", 136, 4, False),
]


@pytest.mark.parametrize("entry,nc,v_bits,k_int8", BLOCKED_CASES)
def test_blocked_recon_matches_oracle(entry, nc, v_bits, k_int8):
    """The kernel takes the selection T tokens a grid step: every entry
    point matches its oracle with the last block padded, a row whose
    entries are all invalid, selected tokens sharing an 8-row tile, and
    tokens in the last tile of the cache."""
    b, s, ps, n_kv, dh, r, vg = 3, 256, 16, 2, 64, 32, 32
    h, kvd = 2 * n_kv, n_kv * dh
    window, paged = entry.startswith("window"), entry.endswith("paged")
    ks = jax.random.split(jax.random.fold_in(KEY, nc + v_bits), 6)
    ql = 3 if window else 1
    q = jax.random.normal(ks[0], (b, ql, h, dh), jnp.float32)
    lat = jax.random.normal(ks[1], (b, s, r))
    if k_int8:
        k_lat, k_scale = qz.quantize_latent_int8(lat)
    else:
        k_lat, k_scale = lat.astype(jnp.bfloat16), None
    vq = qz.quantize(jax.random.normal(ks[2], (b, s, kvd)) * 2.0, v_bits,
                     vg)
    u = jax.random.normal(ks[3], (kvd, r), jnp.float32)
    block, nb = sra._block_size(
        nc, [(8, x.shape[-1], x.dtype) for x in (k_lat, vq["q"])]
        + [(kvd // vg, 128, vq["scale"].dtype)] * 2)
    assert nb * block > nc and (nb > 1) == (nc > 128)

    rng = np.random.default_rng(nc)
    pinned = [16, 17, 23, 40, 41, s - 8, s - 2, s - 1]
    rest = np.setdiff1d(np.arange(s), pinned)
    idx = np.stack([np.sort(np.concatenate(
        [pinned, rng.choice(rest, nc - len(pinned), replace=False)]))
        for _ in range(b)]).astype(np.int32)
    valid = rng.random((b, nc)) < 0.85
    valid |= np.isin(idx, pinned)
    valid[2] = False                          # a row with nothing valid
    q_pos = jnp.asarray([s + 7, s - 3, s + 7], jnp.int32)
    args = [q if window else q[:, 0], k_lat, k_scale, vq["q"], vq["scale"],
            vq["zero"], u, jnp.asarray(idx), jnp.asarray(valid), q_pos]
    kw = dict(n_kv=n_kv, v_bits=v_bits, v_group=vg, theta=1e4)
    if window:
        kw["n_recent"] = 8
    if paged:                 # the rows scattered over a permuted page pool
        mp = s // ps
        pt = rng.permutation(b * mp + 2)[:b * mp].reshape(b, mp) + 1

        def pool(x):
            if x is None:
                return None
            x = np.asarray(x).reshape(b * mp, ps, *x.shape[2:])
            out = np.zeros((b * mp + 3, *x.shape[1:]), x.dtype)
            out[pt.reshape(-1)] = x
            return jnp.asarray(out)
        args[1:6] = [pool(x) for x in args[1:6]]
        kw.update(page_table=jnp.asarray(pt, jnp.int32), page_size=ps)
    kernel, oracle = {
        "dense": (sparse_recon_attention_pallas,
                  ref.sparse_recon_attention_fused_ref),
        "paged": (sra.sparse_recon_attention_paged_pallas,
                  ref.sparse_recon_attention_paged_ref),
        "window": (sra.sparse_recon_attention_window_pallas,
                   ref.sparse_recon_attention_fused_window_ref),
        "window_paged": (sra.sparse_recon_attention_window_paged_pallas,
                         ref.sparse_recon_attention_window_paged_ref),
    }[entry]
    m1, l1, o1 = (np.asarray(x) for x in kernel(*args, **kw))
    m2, l2, o2 = (np.asarray(x) for x in oracle(*args, **kw))
    np.testing.assert_allclose(m1, m2, rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(l1, l2, rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(o1 / np.maximum(l1, 1e-30)[..., None],
                               o2 / np.maximum(l2, 1e-30)[..., None],
                               rtol=1e-3, atol=1e-3)
    assert np.all(l1[2] == 0.0) and np.all(o1[2] == 0.0)
    assert not np.isnan(m1).any()


# ---------------------------------------------------------------------------
# grouped (sequence-sharded) layout: same fused kernels over group slabs
# ---------------------------------------------------------------------------

def _grouped_fold(arrs, g):
    """(B, S, ...) -> (B*G, S/G, ...) group slabs (metadata-only reshape)."""
    out = []
    for a in arrs:
        if a is None:
            out.append(None)
            continue
        b, s = a.shape[:2]
        out.append(a.reshape(b * g, s // g, *a.shape[2:]))
    return out


@pytest.mark.parametrize("h,n_kv,dh", [
    (4, 2, 64),      # GQA group 2
    (8, 2, 32),      # GQA group 4
    (4, 1, 64),      # MQA
])
@pytest.mark.parametrize("k_int8", [False, True])
@pytest.mark.parametrize("g,s,pos_v", [
    (2, 160, 159),   # full cache
    (4, 256, 100),   # later groups partially / fully in the future
    (2, 96, 30),     # ragged early-decode position
    (4, 128, 7),     # almost nothing selectable
])
def test_grouped_fused_matches_grouped_oracle_exactly(h, n_kv, dh, k_int8,
                                                      g, s, pos_v):
    """Per-slab fused top-k (pallas) must equal the per-slab jnp oracle
    BIT-FOR-BIT (indices AND valid, incl. top-k ties and fully-masked
    slabs), and the slab partials must agree on the merged output."""
    b, r, r_star, nc, vg = 2, 16, 8, 24, 16
    kvd = n_kv * dh
    ks = jax.random.split(jax.random.fold_in(KEY, 17 * g + s), 5)
    q = jax.random.normal(ks[0], (b, h, dh), jnp.float32)
    lat = jax.random.normal(ks[1], (b, s, r))
    if k_int8:
        k_lat, k_scale = qz.quantize_latent_int8(lat)
    else:
        k_lat, k_scale = lat.astype(jnp.bfloat16), None
    v = jax.random.normal(ks[2], (b, s, kvd))
    vq = qz.quantize(v, 8, vg)
    u = jax.random.normal(ks[3], (kvd, r), jnp.float32)
    q_lat = jax.random.normal(ks[4], (b, r_star))
    pos = jnp.int32(pos_v)
    s_loc = s // g
    k_loc = -(-nc // g)
    kg, ksg, vqg, vsg, vzg = _grouped_fold(
        [k_lat, k_scale, vq["q"], vq["scale"], vq["zero"]], g)
    base = jnp.tile(jnp.arange(g, dtype=jnp.int32) * s_loc, b)
    qg = jnp.repeat(q, g, axis=0)
    qlg = jnp.repeat(q_lat, g, axis=0)

    out = {}
    for backend in ("pallas", "xla"):
        idx, valid = ops.latent_topk(qlg, kg, ksg, pos, n_critical=k_loc,
                                     n_sink=2, n_recent=8, pos_base=base,
                                     backend=backend)
        m, l, o = ops.sparse_recon_attention(
            qg, kg, ksg, vqg, vsg, vzg, u, idx, valid, pos, n_kv=n_kv,
            v_bits=8, v_group=vg, pos_base=base, backend=backend)
        out[backend] = (np.asarray(idx), np.asarray(valid), np.asarray(m),
                        np.asarray(l), np.asarray(o))
    # selection agrees bit-for-bit (incl. ties + fully-masked slabs) ...
    assert np.array_equal(out["pallas"][0], out["xla"][0])
    assert np.array_equal(out["pallas"][1], out["xla"][1])
    # ... merged slab partials to 1e-3 (f32 accumulate)
    for i in (2, 3):
        np.testing.assert_allclose(out["pallas"][i], out["xla"][i],
                                   rtol=1e-3, atol=1e-3)
    y_p = out["pallas"][4] / np.maximum(out["pallas"][3], 1e-30)[..., None]
    y_x = out["xla"][4] / np.maximum(out["xla"][3], 1e-30)[..., None]
    np.testing.assert_allclose(y_p, y_x, rtol=1e-3, atol=1e-3)
    # a slab entirely in the future must come back all-invalid, not NaN
    if pos_v < s - s_loc:
        last_slab_valid = out["pallas"][1].reshape(b, g, k_loc)[:, -1]
        assert not last_slab_valid.any()
    assert not np.any(np.isnan(out["pallas"][2]))


# ---------------------------------------------------------------------------
# no dense-copy guarantee (the §4.5 traffic model, enforced on the jaxpr)
# ---------------------------------------------------------------------------

def _walk_eqns(jaxpr, out):
    for eqn in jaxpr.eqns:
        out.append(eqn)
        for v in eqn.params.values():
            if hasattr(v, "jaxpr"):          # ClosedJaxpr
                _walk_eqns(v.jaxpr, out)
            elif hasattr(v, "eqns"):         # Jaxpr
                _walk_eqns(v, out)
            elif isinstance(v, (list, tuple)):
                for x in v:
                    if hasattr(x, "jaxpr"):
                        _walk_eqns(x.jaxpr, out)
                    elif hasattr(x, "eqns"):
                        _walk_eqns(x, out)
    return out


def test_fused_path_materializes_no_cache_scale_buffers():
    """The decode hot path must not create any intermediate on the order of
    the old dense copies: the (B,S,r*) score-slice/pad, the (B,S,r) dequant
    pass, or the gathered (B,N_c,kvd) value buffer.  Every eqn output in the
    traced pipeline must stay below the smallest of those."""
    b, s, r, r_star, n_kv, dh, h, nc, vg = 2, 512, 32, 16, 2, 64, 4, 64, 32
    kvd = n_kv * dh
    args = _fused_inputs(b, h, n_kv, dh, s, r, nc, k_int8=True, v_bits=8,
                         v_group=vg, seed=11)
    q, k_lat, k_scale, v_q, v_scale, v_zero, u = args[:7]
    q_lat = jax.random.normal(KEY, (b, r_star))
    pos = jnp.int32(s - 1)

    def fused_pipeline(q, q_lat, k_lat, k_scale, v_q, v_scale, v_zero, u):
        idx, valid = ops.latent_topk(q_lat, k_lat, k_scale, pos,
                                     n_critical=nc, n_sink=4, n_recent=16,
                                     backend="pallas")
        return ops.sparse_recon_attention(
            q, k_lat, k_scale, v_q, v_scale, v_zero, u, idx, valid, pos,
            n_kv=n_kv, v_bits=8, v_group=vg, backend="pallas")

    jaxpr = jax.make_jaxpr(fused_pipeline)(q, q_lat, k_lat, k_scale, v_q,
                                           v_scale, v_zero, u)
    limit = min(b * s * r_star,              # old score slice/pad copy
                b * s * r,                   # old dense dequant pass
                b * nc * kvd)                # old gathered value buffer
    offenders = []
    for eqn in _walk_eqns(jaxpr.jaxpr, []):
        for ov in eqn.outvars:
            size = int(np.prod(ov.aval.shape)) if ov.aval.shape else 1
            if size >= limit:
                offenders.append((eqn.primitive.name, ov.aval.shape))
    assert not offenders, offenders


@pytest.mark.parametrize("seed,pos_rows", [
    (0, [159, 30, 7]),       # full / mid / almost-nothing-selectable
    (1, [15, 100]),          # below + above the sink+recent floor
    (2, [64, 64, 64, 64]),   # degenerate: uniform vector == scalar path
])
@pytest.mark.parametrize("k_int8", [False, True])
def test_ragged_rows_bit_identical_to_single_decodes(seed, pos_rows, k_int8):
    """Deterministic (hypothesis-free) ragged bit-parity: batched decode
    with heterogeneous per-row positions == B independent single-sequence
    decodes, bit-for-bit, through both fused kernels AND the jnp oracle."""
    b = len(pos_rows)
    n_kv, dh, group = 2, 32, 2
    h = n_kv * group
    s, r, r_star, nc, vg = 160, 16, 8, 24, 16
    kvd = n_kv * dh
    ks = jax.random.split(jax.random.fold_in(KEY, 31 + seed), 5)
    q = jax.random.normal(ks[0], (b, h, dh), jnp.float32)
    lat = jax.random.normal(ks[1], (b, s, r))
    if k_int8:
        k_lat, k_scale = qz.quantize_latent_int8(lat)
    else:
        k_lat, k_scale = lat.astype(jnp.bfloat16), None
    v = jax.random.normal(ks[2], (b, s, kvd))
    vq = qz.quantize(v, 8, vg)
    u = jax.random.normal(ks[3], (kvd, r), jnp.float32)
    q_lat = jax.random.normal(ks[4], (b, r_star))
    pos = jnp.asarray(pos_rows, jnp.int32)

    for backend in ("pallas", "xla"):
        idx, valid = ops.latent_topk(q_lat, k_lat, k_scale, pos,
                                     n_critical=nc, n_sink=2, n_recent=8,
                                     backend=backend)
        m, l, o = ops.sparse_recon_attention(
            q, k_lat, k_scale, vq["q"], vq["scale"], vq["zero"], u, idx,
            valid, pos, n_kv=n_kv, v_bits=8, v_group=vg, backend=backend)
        for i in range(b):
            sl = slice(i, i + 1)
            ks_i = None if k_scale is None else k_scale[sl]
            i1, v1 = ops.latent_topk(q_lat[sl], k_lat[sl], ks_i,
                                     jnp.int32(pos_rows[i]), n_critical=nc,
                                     n_sink=2, n_recent=8, backend=backend)
            m1, l1, o1 = ops.sparse_recon_attention(
                q[sl], k_lat[sl], ks_i, vq["q"][sl], vq["scale"][sl],
                vq["zero"][sl], u, i1, v1, jnp.int32(pos_rows[i]),
                n_kv=n_kv, v_bits=8, v_group=vg, backend=backend)
            assert np.array_equal(np.asarray(idx[i]), np.asarray(i1[0])), \
                (backend, i)
            assert np.array_equal(np.asarray(valid[i]), np.asarray(v1[0]))
            assert np.array_equal(np.asarray(m[i]), np.asarray(m1[0]))
            assert np.array_equal(np.asarray(l[i]), np.asarray(l1[0]))
            assert np.array_equal(np.asarray(o[i]), np.asarray(o1[0]))


def test_grouped_fused_path_materializes_no_dense_buffers():
    """ISSUE 2: the GROUPED (n_groups > 1) hot path must uphold the same
    invariant — no dense (B,S,r) dequant pass, no slice/pad copy, no XLA
    gather of latents.  Traces the production helper
    (core.sparse_attention._grouped_partials, fold-into-batch layout) and
    walks every eqn.  Size-preserving ``reshape`` eqns are exempt: the
    group fold is a metadata-only view of the raw cache (XLA bitcast), not
    a copy — every other primitive at cache scale is an offender."""
    from repro.config import SALSConfig
    from repro.configs import get_config
    from repro.core.latent_cache import LatentKVCache
    from repro.core.sparse_attention import DecodePlan, _grouped_partials

    cfg = get_config("yi-9b").reduced()          # H=4, Hkv=2, dh=32
    b, s, g, nc, vg = 2, 512, 4, 64, 32
    kvd = cfg.kv_dim
    sals = SALSConfig(rank_ratio=0.5, score_ratio=0.5, n_critical=nc,
                      n_sink=4, n_recent=16, v_bits=8, v_group=vg,
                      k_latent_dtype="int8")
    r = sals.rank(kvd)
    r_star = sals.score_rank(kvd)
    ks = jax.random.split(KEY, 4)
    lat = jax.random.normal(ks[0], (b, s, r))
    k_lat, k_scale = qz.quantize_latent_int8(lat)
    v = jax.random.normal(ks[1], (b, s, kvd))
    vq = qz.quantize(v, 8, vg)
    cache = LatentKVCache(
        k_lat=k_lat, k_scale=k_scale, v_q=vq["q"], v_scale=vq["scale"],
        v_zero=vq["zero"],
        sink_k=jnp.zeros((b, sals.n_sink, cfg.n_kv_heads, cfg.head_dim)),
        sink_v=jnp.zeros((b, sals.n_sink, cfg.n_kv_heads, cfg.head_dim)),
        recent_k=jnp.zeros((b, sals.n_recent, cfg.n_kv_heads, cfg.head_dim)),
        recent_v=jnp.zeros((b, sals.n_recent, cfg.n_kv_heads, cfg.head_dim)),
        n_groups=g)
    q0 = jax.random.normal(ks[2], (b, cfg.n_heads, cfg.head_dim))
    q_bar = jax.random.normal(ks[3], (b, kvd))
    u = jax.random.normal(KEY, (kvd, r), jnp.bfloat16)
    pos = jnp.int32(s - 1)
    plan = DecodePlan(n_groups=g, backend="pallas")

    jaxpr = jax.make_jaxpr(
        lambda q0, q_bar, u, cache: _grouped_partials(
            q0, q_bar, u, cache, pos, cfg, sals, plan))(q0, q_bar, u, cache)
    limit = min(b * s * r_star,              # old score slice/pad copy
                b * s * r,                   # old dense dequant pass
                b * nc * kvd)                # old gathered value buffer
    offenders = []
    for eqn in _walk_eqns(jaxpr.jaxpr, []):
        in_sizes = {int(np.prod(iv.aval.shape)) if iv.aval.shape else 1
                    for iv in eqn.invars if hasattr(iv, "aval")}
        for ov in eqn.outvars:
            size = int(np.prod(ov.aval.shape)) if ov.aval.shape else 1
            if size < limit:
                continue
            if eqn.primitive.name == "reshape" and size in in_sizes:
                continue                     # metadata-only group fold
            offenders.append((eqn.primitive.name, ov.aval.shape))
    assert not offenders, offenders


def test_ragged_fused_path_materializes_no_cache_scale_buffers():
    """ISSUE 3: vector (B,) decode positions must not silently reintroduce
    the dense gather/dequant buffers — the ragged hot path upholds the same
    jaxpr no-dense-copy invariant as the scalar one."""
    b, s, r, r_star, n_kv, dh, h, nc, vg = 3, 512, 32, 16, 2, 64, 4, 64, 32
    kvd = n_kv * dh
    args = _fused_inputs(b, h, n_kv, dh, s, r, nc, k_int8=True, v_bits=8,
                         v_group=vg, seed=13)
    q, k_lat, k_scale, v_q, v_scale, v_zero, u = args[:7]
    q_lat = jax.random.normal(KEY, (b, r_star))
    pos = jnp.array([511, 200, 37], jnp.int32)          # ragged positions

    def fused_pipeline(q, q_lat, k_lat, k_scale, v_q, v_scale, v_zero, u,
                       pos):
        idx, valid = ops.latent_topk(q_lat, k_lat, k_scale, pos,
                                     n_critical=nc, n_sink=4, n_recent=16,
                                     backend="pallas")
        return ops.sparse_recon_attention(
            q, k_lat, k_scale, v_q, v_scale, v_zero, u, idx, valid, pos,
            n_kv=n_kv, v_bits=8, v_group=vg, backend="pallas")

    jaxpr = jax.make_jaxpr(fused_pipeline)(q, q_lat, k_lat, k_scale, v_q,
                                           v_scale, v_zero, u, pos)
    limit = min(b * s * r_star,              # old score slice/pad copy
                b * s * r,                   # old dense dequant pass
                b * nc * kvd)                # old gathered value buffer
    offenders = []
    for eqn in _walk_eqns(jaxpr.jaxpr, []):
        for ov in eqn.outvars:
            size = int(np.prod(ov.aval.shape)) if ov.aval.shape else 1
            if size >= limit:
                offenders.append((eqn.primitive.name, ov.aval.shape))
    assert not offenders, offenders


def test_window_fused_path_materializes_no_cache_scale_buffers():
    """ISSUE 9: the WINDOWED (speculative verify, q_len > 1) hot path must
    uphold the same jaxpr no-dense-copy invariant — one selection and one
    in-kernel gather/dequant serve all q_len window queries without any
    cache-scale intermediate."""
    b, ql, s, r, r_star, n_kv, dh, h, nc, vg = 3, 4, 512, 32, 16, 2, 64, \
        4, 64, 32
    kvd = n_kv * dh
    args = _fused_inputs(b, h, n_kv, dh, s, r, nc, k_int8=True, v_bits=8,
                         v_group=vg, seed=17)
    _, k_lat, k_scale, v_q, v_scale, v_zero, u = args[:7]
    q = jax.random.normal(KEY, (b, ql, h, dh), jnp.float32)
    q_lat = jax.random.normal(KEY, (b, r_star))
    pos = jnp.array([500, 200, 37], jnp.int32)          # window bases

    def window_pipeline(q, q_lat, k_lat, k_scale, v_q, v_scale, v_zero, u,
                        pos):
        idx, valid = ops.latent_topk(q_lat, k_lat, k_scale, pos + ql - 1,
                                     n_critical=nc, n_sink=4, n_recent=16,
                                     backend="pallas")
        return ops.sparse_recon_attention_window(
            q, k_lat, k_scale, v_q, v_scale, v_zero, u, idx, valid, pos,
            n_kv=n_kv, n_recent=16, v_bits=8, v_group=vg, backend="pallas")

    jaxpr = jax.make_jaxpr(window_pipeline)(q, q_lat, k_lat, k_scale, v_q,
                                            v_scale, v_zero, u, pos)
    limit = min(b * s * r_star,              # old score slice/pad copy
                b * s * r,                   # old dense dequant pass
                b * nc * kvd)                # old gathered value buffer
    offenders = []
    for eqn in _walk_eqns(jaxpr.jaxpr, []):
        for ov in eqn.outvars:
            size = int(np.prod(ov.aval.shape)) if ov.aval.shape else 1
            if size >= limit:
                offenders.append((eqn.primitive.name, ov.aval.shape))
    assert not offenders, offenders
