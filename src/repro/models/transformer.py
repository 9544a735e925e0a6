"""Model assembly: init / train-forward / prefill / decode for every family.

Layer stacking.  Block parameters are stacked over the layer axis L (leaf
shape (L, ...)), so the forward pass is a single ``lax.scan`` over layers
(one HLO block regardless of depth) and checkpoints are layout-stable.

Segments.  The SALS layer mask (paper §5.1: layers 0, 1 and the last bypass
sparsification) is always front/back-contiguous, so decode splits the stack
into up to three scanned segments — ``full | sals | full`` — each with its
own cache structure.  Step functions slice the stacked params per segment
(static slices on the leading axis; compiled for a TPU, each is a copy).

Named scopes.  ``decode_step`` and ``prefill_chunk`` name their parts, so
a device profile can attribute every op: ``weights`` (the per-segment
slices), ``seg<i>.<mode>`` (a segment's layer loop), ``cache_carry``
(inside it, each layer's cache rows read out of and written back into the
stack) and ``head`` (final norm and unembedding).

Entry points
------------
  init_params(key, cfg)                      -> params
  forward(params, cfg, batch, ...)           -> (logits, aux)     [train]
  init_cache(cfg, sals, batch, max_seq)      -> cache
  prefill(params, proj, cfg, sals, batch, max_seq[, lengths]) -> (last_logits, cache)
  init_prefill_scratch(cfg, sals, batch, max_seq) -> scratch
  prefill_chunk(params, proj, cfg, sals, cache, scratch, batch, off, lengths)
                                             -> (logits, cache, scratch)
  decode_step(params, proj, cache, tokens, pos, cfg, sals) -> (logits, cache)

``pos`` is a traced scalar or a (B,) per-row positions vector, and
``lengths`` right-pad-masks a ragged prompt batch — the continuous-batching
layout (see serve/engine.py).  ``prefill`` processes the whole prompt in one
monolithic forward (the chunked path's parity oracle, and the recurrent
families' only prefill); ``prefill_chunk`` builds the same cache one
fixed-width chunk at a time against the cache-so-far, with ``off`` a traced
scalar so every chunk of every prompt re-executes ONE compiled HLO.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.config import ModelConfig, SALSConfig
from repro.core import latent_cache as lc
from repro.core.sparse_attention import sals_decode_attend, sals_window_attend
from repro.distributed.sharding import constrain
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (embed_apply, embedding_init, embedding_specs,
                                 mlp_apply, mlp_init, mlp_specs, rmsnorm_apply,
                                 rmsnorm_init, rmsnorm_specs, unembed_apply)


# ---------------------------------------------------------------------------
# Segment plan
# ---------------------------------------------------------------------------

def segment_plan(cfg: ModelConfig, sals: Optional[SALSConfig]
                 ) -> List[Tuple[int, int, str]]:
    """[(start, stop, mode)] with mode in {"full", "sals"}."""
    l = cfg.n_layers
    if (sals is None or not sals.enabled or not cfg.has_attention
            or not cfg.is_decoder):
        return [(0, l, "full")]
    f = min(sals.skip_layers_front, l)
    b = min(sals.skip_layers_back, l - f)
    segs = []
    if f:
        segs.append((0, f, "full"))
    if l - f - b > 0:
        segs.append((f, l - b, "sals"))
    if b:
        segs.append((l - b, l, "full"))
    return segs


def _slice_tree(tree, i0: int, i1: int):
    return jax.tree.map(lambda a: a[i0:i1], tree)


def _scan_layers(step, x, xs, state):
    """``lax.scan`` of ``step(x, xs_l, state_l) -> (x, state_l, y_l)`` over
    a segment's layers; returns (x, state, ys).

    ``state`` is the segment's layer-stacked cache (L, ...).  Each layer's
    rows are read out and written back under the ``cache_carry`` scope, so
    a profile tells moving the cache in and out of the loop from the
    layer's own work.  The reads and writes are the ones ``lax.scan`` makes
    for its ``xs`` and ``ys`` (a dynamic slice of the stacked input, a
    dynamic update of a fresh stacked output), spelled out to carry that
    name: the program is the same."""
    with jax.named_scope("cache_carry"):
        out = jax.tree.map(jnp.zeros_like, state)

    def body(carry, xs_l):
        x, i, out = carry
        with jax.named_scope("cache_carry"):
            st = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False),
                state)
        x, st, y = step(x, xs_l, st)
        with jax.named_scope("cache_carry"):
            out = jax.tree.map(
                lambda o, s: jax.lax.dynamic_update_index_in_dim(o, s, i, 0),
                out, st)
        return (x, i + 1, out), y

    (x, _, out), ys = jax.lax.scan(body, (x, jnp.int32(0), out), xs)
    return x, out, ys


# ---------------------------------------------------------------------------
# Block params
# ---------------------------------------------------------------------------

def _block_init(key, cfg: ModelConfig, dtype) -> dict:
    ks = jax.random.split(key, 4)
    if cfg.family == "ssm":
        return {
            "norm1": rmsnorm_init(cfg, cfg.d_model, dtype),
            "norm2": rmsnorm_init(cfg, cfg.d_model, dtype),
            "rwkv": ssm_mod.rwkv_init(ks[0], cfg, dtype),
        }
    p = {
        "attn_norm": rmsnorm_init(cfg, cfg.d_model, dtype),
        "attn": attn.attention_init(ks[0], cfg, dtype),
        "mlp_norm": rmsnorm_init(cfg, cfg.d_model, dtype),
    }
    if cfg.family == "moe":
        p["moe"] = moe_mod.moe_init(ks[1], cfg, dtype)
    else:
        p["mlp"] = mlp_init(ks[1], cfg, dtype)
    if cfg.family == "hybrid":
        p["mamba"] = ssm_mod.mamba_init(ks[2], cfg, dtype)
    return p


def block_specs(cfg: ModelConfig) -> dict:
    """PartitionSpecs for one (stacked) block — leading layer axis unsharded."""
    def stack(spec_tree):
        return jax.tree.map(lambda s: P(None, *s), spec_tree,
                            is_leaf=lambda x: isinstance(x, P))
    if cfg.family == "ssm":
        return stack({
            "norm1": rmsnorm_specs(), "norm2": rmsnorm_specs(),
            "rwkv": ssm_mod.rwkv_specs(cfg),
        })
    sp = {
        "attn_norm": rmsnorm_specs(),
        "attn": attn.attention_specs(cfg),
        "mlp_norm": rmsnorm_specs(),
    }
    if cfg.family == "moe":
        sp["moe"] = moe_mod.moe_specs(cfg)
    else:
        sp["mlp"] = mlp_specs()
    if cfg.family == "hybrid":
        sp["mamba"] = ssm_mod.mamba_specs(cfg)
    return stack(sp)


def init_params(key, cfg: ModelConfig, dtype=None) -> dict:
    dtype = dtype or jnp.dtype(cfg.dtype)
    k_emb, k_blocks, k_norm = jax.random.split(key, 3)
    layer_keys = jax.random.split(k_blocks, cfg.n_layers)
    blocks = jax.vmap(lambda k: _block_init(k, cfg, dtype))(layer_keys)
    return {
        "embed": embedding_init(k_emb, cfg, dtype),
        "blocks": blocks,
        "final_norm": rmsnorm_init(cfg, cfg.d_model, dtype),
    }


def param_specs(cfg: ModelConfig) -> dict:
    return {
        "embed": embedding_specs(cfg),
        "blocks": block_specs(cfg),
        "final_norm": rmsnorm_specs(),
    }


# ---------------------------------------------------------------------------
# Block forward (full sequence — train / prefill / encode)
# ---------------------------------------------------------------------------

def _block_fwd(bp: dict, x: jnp.ndarray, cfg: ModelConfig,
               positions: jnp.ndarray, prefix_len: int,
               collect_kv: bool):
    """One block over a full sequence.

    Returns (x, aux_loss, extras) where extras = (k_pre, v[, ssm_state]) when
    ``collect_kv`` (prefill) else None.
    """
    aux = jnp.zeros((), jnp.float32)
    extras = None
    if cfg.family == "ssm":
        h = rmsnorm_apply(bp["norm1"], x, cfg.norm_eps)
        tm, wkv, tm_x = ssm_mod.rwkv_time_mix(bp["rwkv"], h, cfg, None)
        x = x + tm
        h2 = rmsnorm_apply(bp["norm2"], x, cfg.norm_eps)
        cm, cm_x = ssm_mod.rwkv_channel_mix(bp["rwkv"], h2, None)
        x = x + cm
        if collect_kv:
            extras = {"wkv": wkv, "tm_x": tm_x, "cm_x": cm_x}
        return x, aux, extras

    h = rmsnorm_apply(bp["attn_norm"], x, cfg.norm_eps)
    if collect_kv:
        a, k_pre, v = attn.attend_prefill(bp["attn"], h, cfg, positions,
                                          prefix_len)
        extras = {"k_pre": k_pre, "v": v}
    else:
        a = attn.attend_train(bp["attn"], h, cfg, positions, prefix_len)
    if cfg.family == "hybrid":
        if collect_kv:
            s_out, s_state = ssm_mod.mamba_apply(bp["mamba"], h, cfg,
                                                 return_state=True)
            extras["ssm"] = s_state
        else:
            s_out = ssm_mod.mamba_apply(bp["mamba"], h, cfg)
        a = (a + s_out) * 0.5
    x = x + a
    x = constrain(x, ("batch", "residual_seq", "embed"))
    h2 = rmsnorm_apply(bp["mlp_norm"], x, cfg.norm_eps)
    if cfg.family == "moe":
        m, aux = moe_mod.moe_apply(bp["moe"], h2, cfg)
    else:
        m = mlp_apply(bp["mlp"], h2, cfg.mlp_act)
    x = x + m
    x = constrain(x, ("batch", "residual_seq", "embed"))
    return x, aux, extras


# ---------------------------------------------------------------------------
# Inputs -> embeddings
# ---------------------------------------------------------------------------

def embed_inputs(params: dict, cfg: ModelConfig, batch: Dict[str, jnp.ndarray]
                 ) -> Tuple[jnp.ndarray, int]:
    """Returns (x (B,S,d), prefix_len) from the family's input dict.

    dense/moe/hybrid/ssm: {"tokens"}; encoder (audio): {"frames"} —
    precomputed frame embeddings (frontend stub); vlm: {"patches","tokens"}
    — precomputed patch embeddings prefix + token ids.
    """
    if cfg.family == "encoder":
        # cast to the params' compute dtype (tests train in f32)
        dtype = params["final_norm"]["scale"].dtype
        x = batch["frames"].astype(dtype)
        return constrain(x, ("batch", "seq", "embed")), 0
    tok_emb = embed_apply(params["embed"], batch["tokens"], cfg)
    if cfg.family == "vlm" and "patches" in batch:
        patches = batch["patches"].astype(tok_emb.dtype)
        x = jnp.concatenate([patches, tok_emb], axis=1)
        return constrain(x, ("batch", "seq", "embed")), patches.shape[1]
    return tok_emb, 0


# ---------------------------------------------------------------------------
# Train / encode forward
# ---------------------------------------------------------------------------

def hidden(params: dict, cfg: ModelConfig, batch: Dict[str, jnp.ndarray], *,
           remat: str = "none") -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full-sequence forward up to the final norm.

    Returns (hidden (B,S,d), aux_loss)."""
    x, prefix_len = embed_inputs(params, cfg, batch)
    s = x.shape[1]
    positions = jnp.arange(s)[None, :]

    def body(carry, bp):
        x, aux = carry
        x, a, _ = _block_fwd(bp, x, cfg, positions, prefix_len, False)
        return (x, aux + a), None

    if remat in ("block", "save_dots"):
        # "block": save only block boundaries (x carried between layers);
        # "save_dots": also keep matmul outputs (less recompute, more HBM)
        policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable \
            if remat == "save_dots" else None
        body = jax.checkpoint(body, policy=policy)

    (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                               params["blocks"])
    x = rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
    return x, aux


def forward(params: dict, cfg: ModelConfig, batch: Dict[str, jnp.ndarray], *,
            remat: str = "none") -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full-sequence forward. Returns (logits (B,S,V) f32, aux_loss)."""
    x, aux = hidden(params, cfg, batch, remat=remat)
    logits = unembed_apply(params["embed"], x, cfg)
    return logits, aux


def forward_loss(params: dict, cfg: ModelConfig, batch: Dict[str, jnp.ndarray],
                 *, remat: str = "none", ce_chunk: int = 512
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Forward + CHUNKED cross-entropy (the production train loss).

    The (B,S,V) logits tensor is never materialized: the unembed matmul and
    logsumexp run per seq-chunk inside a rematerialized scan, so peak memory
    holds one (B, chunk, V) tile (e.g. llama4-scout: 202k vocab × 1M tokens
    would otherwise be ~800 GB/step in f32).  Returns (mean_nll, aux)."""
    x, aux = hidden(params, cfg, batch, remat=remat)
    labels = batch["labels"]
    if x.shape[1] != labels.shape[1]:        # vlm: loss over the text suffix
        x = x[:, -labels.shape[1]:]
    b, s, d = x.shape
    c = min(ce_chunk, s)
    if s % c:
        c = s  # fall back to unchunked for odd small shapes
    nc = s // c

    @jax.checkpoint
    def chunk_nll(x_c, y_c):
        logits = unembed_apply(params["embed"], x_c, cfg)      # (B, c, V) f32
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, y_c[..., None], axis=-1)[..., 0]
        return jnp.sum(logz - gold)

    def body(acc, xy):
        x_c, y_c = xy
        return acc + chunk_nll(x_c, y_c), None

    xs = jnp.moveaxis(x.reshape(b, nc, c, d), 1, 0)
    ys = jnp.moveaxis(labels.reshape(b, nc, c), 1, 0)
    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (xs, ys))
    return total / (b * s), aux


# ---------------------------------------------------------------------------
# Cache init
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, sals: Optional[SALSConfig], batch: int,
               max_seq: int, dtype=None, n_groups: int = 1,
               page_size: int = 0, n_pages: int = 0,
               hbm_pages: int = 0) -> dict:
    """``n_groups`` is the SALS decode selection layout (see LatentKVCache):
    it rides as static metadata on the latent segments.  ``page_size`` > 0
    backs the SALS segments with ``n_pages`` physical pages instead of the
    dense ``(B, max_seq, ·)`` slot arena (ISSUE 5; full-precision segments
    keep their dense per-slot cache — the paged pool holds the compressed
    latent fields, which dominate steady-state HBM).  ``hbm_pages`` > 0
    makes the pool TWO-TIER (ISSUE 7): payload pools shrink to that many
    device slots (+1 trash) while the r* score pool and the page table keep
    the full ``n_pages`` logical capacity."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    if not cfg.is_decoder:
        raise ValueError("encoder family has no decode cache")
    if page_size and cfg.family in ("ssm", "hybrid"):
        raise ValueError(f"{cfg.family} state is recurrent — the paged "
                         "latent cache needs an attention family")
    segs = segment_plan(cfg, sals)
    cache: Dict[str, Any] = {}
    for si, (i0, i1, mode) in enumerate(segs):
        ls = i1 - i0
        if cfg.family == "ssm":
            st = ssm_mod.rwkv_state_init(cfg, batch)
            seg = jax.tree.map(lambda a: jnp.zeros((ls, *a.shape), a.dtype), st)
        elif mode == "full":
            kv = attn.init_full_cache(cfg, batch, max_seq, dtype)
            seg = {k: jnp.zeros((ls, *v.shape), v.dtype)
                   for k, v in kv.items()}
        elif page_size:
            seg = lc.LatentKVCache.init_paged(
                cfg, sals, ls, batch, max_seq, n_pages, page_size, dtype,
                n_groups=n_groups, hbm_pages=hbm_pages)
        else:
            seg = lc.LatentKVCache.init(cfg, sals, ls, batch, max_seq, dtype,
                                        n_groups=n_groups)
        if cfg.family == "hybrid":
            st = ssm_mod.mamba_state_init(cfg, batch)
            ssm = jax.tree.map(
                lambda a: jnp.zeros((ls, *a.shape), a.dtype), st)
            if mode == "sals":
                seg = seg.replace(ssm=ssm)
            else:
                seg["ssm"] = ssm
        cache[f"seg{si}"] = seg
    return cache


# ---------------------------------------------------------------------------
# Prefill
# ---------------------------------------------------------------------------

def prefill(params: dict, projectors: Optional[dict], cfg: ModelConfig,
            sals: Optional[SALSConfig], batch: Dict[str, jnp.ndarray],
            max_seq: int, n_groups: int = 1,
            lengths: Optional[jnp.ndarray] = None
            ) -> Tuple[jnp.ndarray, dict]:
    """Process the prompt, build the decode cache.

    ``n_groups`` stamps the SALS segments' decode selection layout.
    ``lengths`` (B,) int32: per-row true prompt lengths for RIGHT-padded
    ragged batches — the SALS segments store per-slot lengths (sink/recent
    windows filled from each row's real positions) and the returned logits
    are taken at each row's own last real token.  None = all rows span the
    full padded width.  Returns (last-position logits (B, V) f32, cache).
    """
    dtype = jnp.dtype(cfg.dtype)
    x, prefix_len = embed_inputs(params, cfg, batch)
    b, s, _ = x.shape
    positions = jnp.arange(s)[None, :]
    segs = segment_plan(cfg, sals)
    cache: Dict[str, Any] = {}
    len_v = None if lengths is None else jnp.asarray(lengths, jnp.int32)
    # cache positions include any vision prefix (vlm): a row's true span in
    # the cache is prefix_len + its token length
    cache_len = None if len_v is None else prefix_len + len_v

    for si, (i0, i1, mode) in enumerate(segs):
        bp_seg = _slice_tree(params["blocks"], i0, i1)
        if mode == "sals":
            u_seg = projectors["u"][i0:i1]

            def body_s(x, bp_u):
                bp, u_l = bp_u
                x, _, ex = _block_fwd(bp, x, cfg, positions, prefix_len, True)
                layer = lc.LatentKVCache.prefill_layer(
                    cfg, sals, u_l, ex["k_pre"], ex["v"], max_seq, dtype,
                    n_groups=n_groups, lengths=cache_len)
                if cfg.family == "hybrid":
                    layer = layer.replace(ssm=ex["ssm"])
                return x, layer

            x, seg = jax.lax.scan(body_s, x, (bp_seg, u_seg))
        else:
            def body_f(x, bp):
                x, _, ex = _block_fwd(bp, x, cfg, positions, prefix_len, True)
                if cfg.family == "ssm":
                    return x, ex
                k_r = attn.apply_rope(ex["k_pre"], positions, cfg.rope_theta) \
                    if cfg.use_rope else ex["k_pre"]
                layer = {"k": _pad_seq(k_r.astype(dtype), max_seq),
                         "v": _pad_seq(ex["v"].astype(dtype), max_seq)}
                if cfg.family == "hybrid":
                    layer["ssm"] = ex["ssm"]
                return x, layer

            x, seg = jax.lax.scan(body_f, x, bp_seg)
        cache[f"seg{si}"] = seg

    x = rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
    if len_v is None:
        last = x[:, -1:, :]
    else:        # ragged: each row's last REAL token (+ any vision prefix)
        last_idx = prefix_len + len_v - 1
        last = jnp.take_along_axis(x, last_idx[:, None, None], axis=1)
    logits = unembed_apply(params["embed"], last, cfg)[:, 0]
    return logits, cache


def init_prefill_scratch(cfg: ModelConfig, sals: Optional[SALSConfig],
                         batch: int, max_seq: int, dtype=None) -> dict:
    """Full-precision prompt-K/V scratch for the SALS segments of a CHUNKED
    prefill.

    SALS layers store only compressed latents plus the small sink/recent
    windows, but chunk queries must attend EXACTLY to every previous prompt
    token — so chunked prefill carries a transient post-RoPE K/V buffer per
    SALS layer, written chunk by chunk and discarded once the prompt is
    done (the full-precision segments use their own decode cache as the
    scratch).  Returns {"seg{i}": {"k": (ls,B,S,Hkv,dh), "v": ...}} for the
    SALS segments only ({} when SALS is off).
    """
    dtype = dtype or jnp.dtype(cfg.dtype)
    scratch: Dict[str, Any] = {}
    for si, (i0, i1, mode) in enumerate(segment_plan(cfg, sals)):
        if mode != "sals":
            continue
        ls = i1 - i0
        shape = (ls, batch, max_seq, cfg.n_kv_heads, cfg.head_dim)
        scratch[f"seg{si}"] = {"k": jnp.zeros(shape, dtype),
                               "v": jnp.zeros(shape, dtype)}
    return scratch


def prefill_chunk(params: dict, projectors: Optional[dict], cfg: ModelConfig,
                  sals: Optional[SALSConfig], cache: dict, scratch: dict,
                  batch: Dict[str, jnp.ndarray], off,
                  lengths: jnp.ndarray) -> Tuple[jnp.ndarray, dict, dict]:
    """One fixed-width chunked-prefill step: prompt tokens [off, off+C)
    against the cache-so-far.

    ``batch``: {"tokens": (B, C)} — one chunk of the right-padded prompt;
    ``off`` is a TRACED scalar (the same compiled HLO serves every chunk of
    every prompt length); ``lengths`` (B,) are the TRUE prompt lengths.
    ``cache`` is the decode cache being built (from :func:`init_cache`) and
    ``scratch`` the SALS prompt-K/V buffer (:func:`init_prefill_scratch`).

    Each layer LSE-merges a cache partial (positions < off) with the
    intra-chunk causal partial (attention.attend_prefill_chunk), appends the
    chunk's K/V — full layers into their decode cache, SALS layers into the
    scratch plus incremental latent/ring/sink writes at per-slot offsets
    (LatentKVCache.append_chunk) — and advances per-slot lengths to
    min(lengths, off+C).

    Recurrent-state families (ssm, hybrid) scan their state over the whole
    sequence and are not chunkable — they keep the monolithic :func:`prefill`.
    Returns (logits (B, V) at each row's last real token AS COVERED SO FAR
    (clip(lengths-1-off, 0, C-1)) — the chunk containing position
    lengths-1 returns the real last-token logits — cache, scratch).
    """
    if cfg.family in ("ssm", "hybrid"):
        raise ValueError(f"{cfg.family} prefill is recurrent — chunked "
                         "prefill supports attention-only families")
    if not cfg.is_decoder:
        raise ValueError("encoder family has no decode cache to prefill")
    x = embed_apply(params["embed"], batch["tokens"], cfg)
    b, c, _ = x.shape
    len_v = jnp.asarray(lengths, jnp.int32)
    segs = segment_plan(cfg, sals)
    new_cache: Dict[str, Any] = {}
    new_scratch: Dict[str, Any] = {}

    for si, (i0, i1, mode) in enumerate(segs):
        with jax.named_scope("weights"):
            bp_seg = _slice_tree(params["blocks"], i0, i1)
            u_seg = projectors["u"][i0:i1] if mode == "sals" else None
        seg_cache = cache[f"seg{si}"]
        if mode == "sals":
            sc = scratch[f"seg{si}"]

            def body_s(x, bp_u, cl_sc):
                (bp, u_l), (cl, sk, sv) = bp_u, cl_sc
                h = rmsnorm_apply(bp["attn_norm"], x, cfg.norm_eps)
                a, k_pre, v, sk, sv = attn.attend_prefill_chunk(
                    bp["attn"], h, cfg, off, sk, sv)
                cl = cl.append_chunk(cfg, sals, u_l, off, k_pre, v, len_v)
                x, cl = _finish_block(bp, x, h, a, cl, None, cfg)
                return x, (cl, sk, sv), None

            with jax.named_scope(f"seg{si}.{mode}"):
                x, (seg, sk, sv), _ = _scan_layers(
                    body_s, x, (bp_seg, u_seg), (seg_cache, sc["k"], sc["v"]))
            new_scratch[f"seg{si}"] = {"k": sk, "v": sv}
        else:
            def body_f(x, bp, cl):
                h = rmsnorm_apply(bp["attn_norm"], x, cfg.norm_eps)
                a, _, _, kc, vc = attn.attend_prefill_chunk(
                    bp["attn"], h, cfg, off, cl["k"], cl["v"])
                x, cl = _finish_block(bp, x, h, a, {"k": kc, "v": vc},
                                      None, cfg)
                return x, cl, None

            with jax.named_scope(f"seg{si}.{mode}"):
                x, seg, _ = _scan_layers(body_f, x, bp_seg, seg_cache)
        new_cache[f"seg{si}"] = seg

    with jax.named_scope("head"):
        x = rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
        last_idx = jnp.clip(len_v - 1 - off, 0, c - 1)
        last = jnp.take_along_axis(x, last_idx[:, None, None], axis=1)
        logits = unembed_apply(params["embed"], last, cfg)[:, 0]
    return logits, new_cache, new_scratch


def _pad_seq(a: jnp.ndarray, max_seq: int) -> jnp.ndarray:
    """Pad axis 1 (seq) of (B, S, ...) up to max_seq."""
    s = a.shape[1]
    if s == max_seq:
        return a
    pad = [(0, 0), (0, max_seq - s)] + [(0, 0)] * (a.ndim - 2)
    return jnp.pad(a, pad)


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------

def decode_step(params: dict, projectors: Optional[dict], cache: dict,
                tokens: jnp.ndarray, pos, cfg: ModelConfig,
                sals: Optional[SALSConfig],
                collect_selection: bool = False):
    """One decode step. tokens: (B,) int32; pos: traced scalar, or a (B,)
    per-row positions vector — the ragged continuous-batching layout where
    every sequence advances at its own position (all attention paths mask,
    RoPE, and write per row; recurrent ssm/hybrid state is position-free).

    The SALS selection layout (global vs grouped) is read from the latent
    segments' ``n_groups`` metadata — set at init_cache/prefill time.
    Returns (logits (B, V) f32, updated cache); with ``collect_selection``
    (paged SALS caches only) additionally returns {seg_name: (ls, B,
    max_pages) bool} touched-page masks — which LOGICAL pages each layer's
    selection reconstructed from, the tiered scheduler's fetch oracle.
    """
    if not cfg.is_decoder:
        raise ValueError("encoder family has no decode step")
    x = embed_apply(params["embed"], tokens[:, None], cfg)     # (B,1,d)
    segs = segment_plan(cfg, sals)
    new_cache: Dict[str, Any] = {}
    touched: Dict[str, Any] = {}

    for si, (i0, i1, mode) in enumerate(segs):
        with jax.named_scope("weights"):
            bp_seg = _slice_tree(params["blocks"], i0, i1)
            u_seg = projectors["u"][i0:i1] if mode == "sals" else None
        seg_cache = cache[f"seg{si}"]
        if cfg.family == "ssm":
            def body(x, bp, st):
                h = rmsnorm_apply(bp["norm1"], x, cfg.norm_eps)
                tm, wkv, tm_x = ssm_mod.rwkv_time_mix(bp["rwkv"], h, cfg, st)
                x = x + tm
                h2 = rmsnorm_apply(bp["norm2"], x, cfg.norm_eps)
                cm, cm_x = ssm_mod.rwkv_channel_mix(bp["rwkv"], h2, st)
                x = x + cm
                return x, {"wkv": wkv, "tm_x": tm_x, "cm_x": cm_x}, None
            xs = bp_seg
        elif mode == "sals":
            def body(x, bp_u, cl):
                bp, u_l = bp_u
                h = rmsnorm_apply(bp["attn_norm"], x, cfg.norm_eps)
                ssm_cl = cl.ssm if cfg.family == "hybrid" else None
                t = None
                if collect_selection:
                    a, cl, t = sals_decode_attend(bp["attn"], u_l, cl, h,
                                                  pos, cfg, sals,
                                                  collect=True)
                else:
                    a, cl = sals_decode_attend(bp["attn"], u_l, cl, h, pos,
                                               cfg, sals)
                x, cl = _finish_block(bp, x, h, a, cl, ssm_cl, cfg)
                return x, cl, t
            xs = (bp_seg, u_seg)
        else:
            def body(x, bp, cl):
                cl = dict(cl)
                h = rmsnorm_apply(bp["attn_norm"], x, cfg.norm_eps)
                ssm_cl = cl.pop("ssm") if cfg.family == "hybrid" else None
                a, k_c, v_c = attn.attend_decode_full(bp["attn"], h, cfg,
                                                      cl["k"], cl["v"], pos)
                cl = {"k": k_c, "v": v_c}
                x, cl = _finish_block(bp, x, h, a, cl, ssm_cl, cfg)
                return x, cl, None
            xs = bp_seg
        with jax.named_scope(f"seg{si}.{mode}"):
            x, new_seg, seg_touch = _scan_layers(body, x, xs, seg_cache)
        if collect_selection and mode == "sals":
            touched[f"seg{si}"] = seg_touch        # (ls, B, max_pages) bool
        new_cache[f"seg{si}"] = new_seg

    with jax.named_scope("head"):
        x = rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
        logits = unembed_apply(params["embed"], x, cfg)[:, 0]
    if collect_selection:
        return logits, new_cache, touched
    return logits, new_cache


def decode_window(params: dict, projectors: Optional[dict], cache: dict,
                  tokens: jnp.ndarray, pos, cfg: ModelConfig,
                  sals: Optional[SALSConfig]):
    """Speculative VERIFY WINDOW: Q tokens through one forward (ISSUE 9).

    tokens: (B, Q) int32 — the pending token plus Q−1 draft tokens at
    positions pos..pos+Q−1 (``pos`` scalar or (B,) per-row window base).
    READ-ONLY w.r.t. ``cache``: nothing is appended (rejected drafts must
    never reach the destructive cache writes) — the caller verifies the
    drafts against the returned logits and commits the accepted prefix
    with :func:`commit_window`.  Each SALS layer runs ONE latent
    selection for the whole window (core.sparse_attention.
    sals_window_attend); full-precision layers attend a transient
    scattered view.  At Q = 1 every layer's math is bit-identical to
    :func:`decode_step` minus the cache write.

    Returns (logits (B, Q, V) f32, aux) — ``aux["seg{i}"]`` holds the
    per-layer window K/V ((ls, B, Q, Hkv, dh) pre-RoPE for SALS segments,
    post-RoPE for full segments) that :func:`commit_window` consumes.
    """
    if not cfg.is_decoder:
        raise ValueError("encoder family has no decode step")
    if cfg.family in ("ssm", "hybrid"):
        raise ValueError(f"{cfg.family} decode carries recurrent state — a "
                         "rejected draft would need a state rollback; "
                         "speculative windows support attention families")
    x = embed_apply(params["embed"], tokens, cfg)              # (B,Q,d)
    segs = segment_plan(cfg, sals)
    aux: Dict[str, Any] = {}

    for si, (i0, i1, mode) in enumerate(segs):
        bp_seg = _slice_tree(params["blocks"], i0, i1)
        seg_cache = cache[f"seg{si}"]
        if mode == "sals":
            u_seg = projectors["u"][i0:i1]

            def body_sw(x, bp_u_cl):
                bp, u_l, cl = bp_u_cl
                h = rmsnorm_apply(bp["attn_norm"], x, cfg.norm_eps)
                a, k_pre, v = sals_window_attend(bp["attn"], u_l, cl, h,
                                                 pos, cfg, sals)
                x, _ = _finish_block(bp, x, h, a, None, None, cfg)
                return x, {"k": k_pre, "v": v}

            x, seg_aux = jax.lax.scan(body_sw, x, (bp_seg, u_seg, seg_cache))
        else:
            def body_fw(x, bp_cl):
                bp, cl = bp_cl
                h = rmsnorm_apply(bp["attn_norm"], x, cfg.norm_eps)
                a, k_r, v = attn.attend_decode_full_window(
                    bp["attn"], h, cfg, cl["k"], cl["v"], pos)
                x, _ = _finish_block(bp, x, h, a, None, None, cfg)
                return x, {"k": k_r, "v": v}

            x, seg_aux = jax.lax.scan(body_fw, x, (bp_seg, seg_cache))
        aux[f"seg{si}"] = seg_aux

    x = rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
    logits = unembed_apply(params["embed"], x, cfg)            # (B,Q,V)
    return logits, aux


def commit_window(projectors: Optional[dict], cache: dict, aux: dict, pos,
                  n_accept, cfg: ModelConfig, sals: Optional[SALSConfig]
                  ) -> dict:
    """Commit a verify window's ACCEPTED prefix into the decode cache.

    ``aux`` is :func:`decode_window`'s window-K/V pytree; ``n_accept``
    (B,) counts accepted tokens per row (window slot t commits at
    position pos + t iff t < n_accept[b]).  The write path is the same
    masked per-slot append the sequential decode uses — latent projection
    + V quantization + ring/sink inserts for SALS layers (so committed
    cache bytes are bit-identical to sequential decode of the accepted
    tokens), plain row scatters for full-precision layers.
    """
    segs = segment_plan(cfg, sals)
    new_cache: Dict[str, Any] = {}
    for si, (i0, i1, mode) in enumerate(segs):
        seg_cache = cache[f"seg{si}"]
        seg_aux = aux[f"seg{si}"]
        if mode == "sals":
            u_seg = projectors["u"][i0:i1]

            def body_cs(carry, u_cl_ax):
                u_l, cl, ax = u_cl_ax
                k_pre, v = ax["k"], ax["v"]            # (B, Q, Hkv, dh)
                b, ql = k_pre.shape[:2]
                k_flat = k_pre.reshape(b, ql, cfg.kv_dim)
                v_flat = v.reshape(b, ql, cfg.kv_dim)
                k_lat = jnp.einsum("bqk,kr->bqr", k_flat.astype(jnp.float32),
                                   u_l.astype(jnp.float32))
                cl = cl.write_window(sals, pos, k_lat, v_flat, k_pre, v,
                                     n_accept)
                return carry, cl

            _, new_seg = jax.lax.scan(body_cs, 0, (u_seg, seg_cache, seg_aux))
        else:
            def body_cf(carry, cl_ax):
                cl, ax = cl_ax
                k_c, v_c = attn.commit_full_window(cl["k"], cl["v"], ax["k"],
                                                   ax["v"], pos, n_accept)
                return carry, {"k": k_c, "v": v_c}

            _, new_seg = jax.lax.scan(body_cf, 0, (seg_cache, seg_aux))
        new_cache[f"seg{si}"] = new_seg
    return new_cache


def _finish_block(bp, x, h, a, cl, ssm_cl, cfg: ModelConfig):
    """Shared tail of a decode block: hybrid SSM merge + MLP/MoE residual."""
    if cfg.family == "hybrid":
        s_out, new_ssm = ssm_mod.mamba_decode(bp["mamba"], h, cfg, ssm_cl)
        a = (a + s_out) * 0.5
        if isinstance(cl, lc.LatentKVCache):
            cl = cl.replace(ssm=new_ssm)
        else:
            cl = dict(cl)
            cl["ssm"] = new_ssm
    x = x + a
    h2 = rmsnorm_apply(bp["mlp_norm"], x, cfg.norm_eps)
    if cfg.family == "moe":
        m, _ = moe_mod.moe_apply(bp["moe"], h2, cfg)
    else:
        m = mlp_apply(bp["mlp"], h2, cfg.mlp_act)
    return x + m, cl


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------

def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray,
                  mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Token-mean CE. logits (B,S,V) f32; labels (B,S) int32; mask optional."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is not None:
        nll = nll * mask
        return jnp.sum(nll) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
