"""Compile-only checks: the decode-path Pallas kernels at Mistral widths
compile for a TPU v5e chip.

Nothing runs on a chip here: the TPU compiler is installed, and it
compiles for a v5e that is described, not attached.  Interpret mode
accepts block shapes and in-kernel ops that Mosaic refuses (a (1, r)
block of a (B, S, r) array, a reshape across lanes and sublanes, a
select on bool vectors), so these tests are what keeps the served kernels
compilable between chip runs.  The topology is described inside a
module fixture, never at import, so every test worker collects the same
tests and only the worker that runs this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import flash_attention as fa
from repro.kernels import latent_score as ls
from repro.kernels import sparse_recon_attention as sra

# Mistral-7B widths (paper §5.1): kv heads 8 × head_dim 128, 32 query
# heads; the paper's 4k decode setting with N_c 432, sink 16, recent 64
B, S, H, N_KV, DH, N_C = 8, 4096, 32, 8, 128, 432
KVD = N_KV * DH
PAGE = 128
N_PAGES = B * S // PAGE + 1
# (r, r*, V bits, int8 latents): SALS-25% is r 256, r* 128, int8 V;
# SALS-12.5% is r 128, r* 64, int4 V; "sals25_k8" adds int8 latent keys,
# whose per-token scales take their own blocks (score) and SMEM (recon)
SETTINGS = {"sals25": (256, 128, 8, False), "sals125": (128, 64, 4, False),
            "sals25_k8": (256, 128, 8, True)}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def for_tpu(monkeypatch):
    """Compile the kernels for the chip (not interpret mode), with the
    persistent cache off: a chip compile cannot be read back here."""
    from jax.experimental.compilation_cache import compilation_cache
    for mod in (ls, sra, fa):
        monkeypatch.setattr(mod, "interpret_mode", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *args):
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo
    return hlo


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _latents(sharding, rows, r, k8):
    """Latent cache and its per-token scales (None unless int8)."""
    if not k8:
        return _spec(sharding, (*rows, r), jnp.bfloat16), None
    return (_spec(sharding, (*rows, r), jnp.int8),
            _spec(sharding, rows, jnp.bfloat16))


@pytest.mark.parametrize("setting", sorted(SETTINGS))
@pytest.mark.parametrize("paged", [False, True])
def test_latent_topk_compiles_for_v5e(one_chip, for_tpu, setting, paged):
    r, r_star, _, k8 = SETTINGS[setting]
    q = _spec(one_chip, (B, r_star), jnp.float32)
    pos = _spec(one_chip, (B,), jnp.int32)
    kw = dict(n_critical=N_C, n_sink=16, n_recent=64)
    if paged:
        pool, scale = _latents(one_chip, (N_PAGES, PAGE), r, k8)
        table = _spec(one_chip, (B, S // PAGE), jnp.int32)
        hlo = _compile(lambda q, k, ks, p, t: ls.latent_topk_paged_pallas(
            q, k, ks, p, page_table=t, page_size=PAGE, **kw),
            q, pool, scale, pos, table)
        assert "sals_latent_topk_paged" in hlo
    else:
        k_lat, scale = _latents(one_chip, (B, S), r, k8)
        hlo = _compile(lambda q, k, ks, p: ls.latent_topk_pallas(
            q, k, ks, p, **kw), q, k_lat, scale, pos)
        assert "sals_latent_topk" in hlo


def _compile_recon(one_chip, setting, variant, b, s, nc, page=PAGE):
    """Compile one reconstruct-attend entry point at (B, S, N_c), paged
    variants with pages of ``page`` rows, and return its HLO text."""
    r, _, v_bits, k8 = SETTINGS[setting]
    code_w, code_t = (KVD, jnp.int8) if v_bits == 8 else \
        (KVD // 2, jnp.uint8)
    paged = variant.endswith("paged")
    rows = (b * s // page + 1, page) if paged else (b, s)
    q_shape = (b, 4, H, DH) if variant.startswith("window") else (b, H, DH)
    k_lat, scale = _latents(one_chip, rows, r, k8)
    args = [_spec(one_chip, q_shape, jnp.bfloat16), k_lat, scale,
            _spec(one_chip, (*rows, code_w), code_t),
            _spec(one_chip, (*rows, KVD // 64), jnp.bfloat16),
            _spec(one_chip, (*rows, KVD // 64), jnp.bfloat16),
            _spec(one_chip, (KVD, r), jnp.bfloat16),
            _spec(one_chip, (b, nc), jnp.int32),
            _spec(one_chip, (b, nc), jnp.bool_),
            _spec(one_chip, (b,), jnp.int32)]
    kw = dict(n_kv=N_KV, v_bits=v_bits, v_group=64, theta=1e6)
    fn = {"dense": sra.sparse_recon_attention_pallas,
          "paged": sra.sparse_recon_attention_paged_pallas,
          "window": sra.sparse_recon_attention_window_pallas,
          "window_paged": sra.sparse_recon_attention_window_paged_pallas,
          }[variant]
    if variant.startswith("window"):
        kw["n_recent"] = 64
    if paged:
        args.append(_spec(one_chip, (b, s // page), jnp.int32))

        def call(q, k, ks, vq, vs, vz, u, i, v, p, t):
            return fn(q, k, ks, vq, vs, vz, u, i, v, p, page_table=t,
                      page_size=page, **kw)
    else:
        def call(q, k, ks, vq, vs, vz, u, i, v, p):
            return fn(q, k, ks, vq, vs, vz, u, i, v, p, **kw)
    return _compile(call, *args)


@pytest.mark.parametrize("setting", sorted(SETTINGS))
@pytest.mark.parametrize("variant", ["dense", "paged", "window",
                                     "window_paged"])
def test_sparse_recon_attention_compiles_for_v5e(one_chip, for_tpu,
                                                 setting, variant):
    hlo = _compile_recon(one_chip, setting, variant, B, S, N_C)
    assert "sals_recon_attend" in hlo


def test_sparse_recon_attention_compiles_for_v5e_at_32k(one_chip, for_tpu):
    """The long-context decode shapes: 4 rows of a 32k cache, N_c 864
    (the paper's 32k budget), SALS-25% with int8 V, dense cache."""
    hlo = _compile_recon(one_chip, "sals25", "dense", 4, 32768, 864)
    assert "sals_recon_attend" in hlo


@pytest.mark.parametrize("variant", ["paged", "window_paged"])
@pytest.mark.parametrize("page", [16, 64])
def test_sparse_recon_attention_compiles_for_v5e_small_pages(
        one_chip, for_tpu, variant, page):
    """Pages under 128 rows: the value scales' sequence axis is narrower
    than a copy may slice, so their selected columns arrive as blocks."""
    hlo = _compile_recon(one_chip, "sals25", variant, B, S, N_C, page=page)
    assert "sals_recon_attend_paged" in hlo


def test_flash_attention_compiles_for_v5e(one_chip, for_tpu):
    x = _spec(one_chip, (1, S, H, DH), jnp.bfloat16)
    hlo = _compile(lambda q, k, v: fa.flash_attention_pallas(q, k, v),
                   x, x, x)
    assert "flash_attention" in hlo


@pytest.mark.parametrize("step", ["decode", "prefill_chunk"])
def test_step_scopes_survive_the_v5e_compiler(one_chip, for_tpu, step):
    """The chip's compiler keeps the model's named scopes on the ops a
    profile attributes by them: the per-segment weight slices and the
    layer loop's cache carry (a small dense SALS model, XLA attention)."""
    import re

    from repro.config import ModelConfig, SALSConfig, ServeConfig
    from repro.models import transformer as tf
    from repro.serve import ServeEngine

    cfg = ModelConfig(name="tiny", family="dense", n_layers=4, d_model=256,
                      n_heads=4, n_kv_heads=2, head_dim=64, d_ff=512,
                      vocab_size=512, dtype="bfloat16")
    sals = SALSConfig(rank_ratio=0.5, score_ratio=0.5, n_critical=16,
                      n_sink=2, n_recent=8, v_bits=8, v_group=32,
                      skip_layers_front=1, skip_layers_back=1)
    scfg = ServeConfig(max_seq_len=256, max_batch=2, sals=sals,
                       prefill_chunk=32)

    def on_chip(tree):
        return jax.tree.map(
            lambda a: _spec(one_chip, a.shape, a.dtype), tree)

    params = on_chip(jax.eval_shape(
        lambda: tf.init_params(jax.random.PRNGKey(0), cfg)))
    kvd = cfg.n_kv_heads * cfg.head_dim
    proj = {"u": _spec(one_chip, (cfg.n_layers, kvd, sals.rank(kvd)),
                       jnp.bfloat16)}
    eng = ServeEngine(params, proj, cfg, scfg)
    i32 = jnp.int32
    if step == "decode":
        lowered = eng._decode.lower(
            _spec(one_chip, (2,), i32),
            on_chip(jax.eval_shape(eng._init_slots_impl)),
            _spec(one_chip, (2,), i32))
    else:
        cache, scratch = on_chip(jax.eval_shape(eng._init_prefill_impl))
        lowered = eng._prefill_chunk.lower(
            _spec(one_chip, (1, 32), i32), cache, scratch,
            _spec(one_chip, (), i32), _spec(one_chip, (1,), i32))
    hlo = lowered.compile().as_text()
    named = re.findall(r'%([\w.-]+) = .*?op_name="([^"]+)"', hlo)
    carry = {op.split(".")[0] for op, name in named
             if "/cache_carry/" in name}
    assert any("dynamic-update-slice" in op for op in carry), carry
    assert any("dynamic-slice" in op for op in carry), carry
    assert any("/weights/slice" in name for _, name in named)
