"""SALS decode attention: selective reconstruction + exact sparse attention
(paper §4.4, Algorithm 1), over a typed :class:`LatentKVCache`.

One decode step per SALS layer:

  1. project the new token's pre-RoPE key to the latent space and append;
     quantize + append its value; insert (k_pre, v) into the recent ring;
  2. score cached latents with the truncated latent query (§4.3);
  3. top-N_c select (global = paper-faithful; grouped = per-slab local);
  4. gather + reconstruct ONLY the selected latents (K̃_C·U_rᵀ), apply RoPE
     at their original positions, dequantize their values;
  5. exact attention over [sink ∪ selected ∪ recent], LSE-merged
     flash-style.

Stages 2-4 are ONE fused code path for both layouts, dispatched through a
small :class:`DecodePlan` (backend + layout) instead of a global/grouped
``if`` fork: scoring + selection stream the quantized latents once
(ops.latent_topk), then the top-k indices are the ONLY artifact handed to
the attention kernel, which gathers / dequantizes / reconstructs in-kernel
via scalar-prefetch indexing — no dense score buffer, no gathered or
dequantized (B, N_c, ·) intermediate ever reaches HBM.

Grouped layout (``cache.n_groups > 1``, kv_seq-sharded): the group axis
matches the cache's sequence sharding, each group slab runs the SAME fused
kernels with a per-row ``pos_base`` offset (slab-local indices, global
positions), and the per-group flash partials LSE-merge with the sink/recent
window — under a sequence-sharded cache that merge lowers to one small
all-reduce of (B,G,H)(+dh) instead of an all-gather of scores or selected
K/V.  Inside a sharding context whose kv_seq axes multiply to n_groups the
slabs run shard-LOCALLY via ``shard_map``; otherwise the group axis is
folded into the kernel batch axis (unit tests, single device).  The old
dense-score + XLA-gather branch survives only as jnp oracles in
kernels/ref.py.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.config import ModelConfig, SALSConfig
from repro.core import selection as sel
from repro.core.latent_cache import LatentKVCache
from repro.distributed.sharding import constrain, current_ctx, mesh_axes_for
from repro.kernels import ops
from repro.models.attention import out_proj, qkv_proj
from repro.models.layers import apply_rope

NEG = sel.NEG


# ---------------------------------------------------------------------------
# Decode plan
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DecodePlan:
    """How one decode step executes: kernel backend + selection layout.

    ``n_groups``   1 = global top-N_c; >1 = per-slab top-(N_c/G) + LSE merge.
    ``backend``    kernel dispatch override (None = ops default backend).
    ``shard_axes`` mesh axes backing the group axis — non-empty means the
                   grouped kernels run shard-locally under shard_map;
                   empty means the group axis folds into the kernel batch.
    """

    n_groups: int = 1
    backend: Optional[str] = None
    shard_axes: Tuple[str, ...] = ()


def plan_decode(cache: LatentKVCache, backend: Optional[str] = None
                ) -> DecodePlan:
    """Derive the decode plan from the cache's layout metadata + the
    ambient sharding context."""
    g = cache.n_groups
    if g <= 1:
        return DecodePlan(1, backend)
    if cache.paged:
        # paged pools are not kv_seq-sharded: grouped slabs always fold
        # into the kernel batch axis (the page TABLE reshapes per slab)
        return DecodePlan(g, backend)
    axes, total = mesh_axes_for(cache.shard_axis)
    if total == g:
        return DecodePlan(g, backend, axes)
    return DecodePlan(g, backend)


# ---------------------------------------------------------------------------
# Region partials (sink/recent window — dense jnp, small, always attended)
# ---------------------------------------------------------------------------

def _region_logits(q_r: jnp.ndarray, k_pre: jnp.ndarray,
                   positions: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """RoPE + GQA QK^T for one region of pre-RoPE keys.

    q_r: (B, H, dh) already-RoPE'd f32 query.
    k_pre: (B, N, Hkv, dh); positions broadcastable to (B, N).
    Returns logits (B, H, N) in f32 (scaled, softcapped).

    GQA is contracted with an explicit (Hkv, group) split of the query —
    no repeat_kv materialization.
    """
    if cfg.use_rope:
        k = apply_rope(k_pre, jnp.broadcast_to(positions, k_pre.shape[:-2]),
                       cfg.rope_theta)
    else:
        k = k_pre
    b = q_r.shape[0]
    q_g = q_r.reshape(b, cfg.n_kv_heads, cfg.group_size, cfg.head_dim) \
        .astype(jnp.float32)
    logits = jnp.einsum("bkrd,bnkd->bkrn", q_g, k.astype(jnp.float32))
    logits = logits.reshape(b, cfg.n_heads, k.shape[1])
    logits = logits * (cfg.head_dim ** -0.5)
    if cfg.attn_logit_softcap:
        logits = cfg.attn_logit_softcap * jnp.tanh(logits / cfg.attn_logit_softcap)
    return logits


def _region_logits_window(q_r: jnp.ndarray, k_pre: jnp.ndarray,
                          positions: jnp.ndarray, cfg: ModelConfig
                          ) -> jnp.ndarray:
    """Verify-window twin of :func:`_region_logits`.

    q_r: (B, Q, H, dh) already-RoPE'd f32 queries (query t at position
    base+t); k_pre: (B, Q, N, Hkv, dh) PER-QUERY region keys (each query
    sees the buffer state its sequential step would read); positions
    broadcastable to (B, Q, N).  Returns logits (B, Q, H, N) — the same
    elementwise RoPE + dot as the single-token path, so per (b, t) slice
    the logits are bit-identical to sequential step base+t.
    """
    if cfg.use_rope:
        k = apply_rope(k_pre, jnp.broadcast_to(positions, k_pre.shape[:-2]),
                       cfg.rope_theta)
    else:
        k = k_pre
    b, ql = q_r.shape[:2]
    q_g = q_r.reshape(b, ql, cfg.n_kv_heads, cfg.group_size, cfg.head_dim) \
        .astype(jnp.float32)
    logits = jnp.einsum("bqkrd,bqnkd->bqkrn", q_g, k.astype(jnp.float32))
    logits = logits.reshape(b, ql, cfg.n_heads, k.shape[2])
    logits = logits * (cfg.head_dim ** -0.5)
    if cfg.attn_logit_softcap:
        logits = cfg.attn_logit_softcap * jnp.tanh(logits / cfg.attn_logit_softcap)
    return logits


def _partial_attend(logits: jnp.ndarray, v: jnp.ndarray, cfg: ModelConfig
                    ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Flash-style partial softmax stats over the last axis.

    logits: (..., H, N) f32; v: (..., N, Hkv, dh) — UNEXPANDED kv heads;
    the GQA value contraction splits H into (Hkv, group) instead of
    materializing repeat_kv'd values (×group memory).
    Returns (m (...,H), l (...,H), o (...,H,dh)) with o = Σ exp(x-m)·v.
    """
    m = jnp.max(logits, axis=-1)
    p = jnp.exp(logits - m[..., None])
    p = jnp.where(logits <= NEG / 2, 0.0, p)   # fully-masked rows -> 0
    l = jnp.sum(p, axis=-1)
    lead = logits.shape[:-2]
    n = logits.shape[-1]
    p_g = p.reshape(*lead, cfg.n_kv_heads, cfg.group_size, n)
    o = jnp.einsum("...krn,...nkd->...krd", p_g, v.astype(jnp.float32))
    return m, l, o.reshape(*lead, cfg.n_heads, cfg.head_dim)


# ---------------------------------------------------------------------------
# Selected-token partials (stages 2-4, fused kernels) per layout
# ---------------------------------------------------------------------------

def _touched_pages(idx, valid, page_size: int, max_pages: int):
    """Selected logical indices -> (B, max_pages) bool touched-page mask.

    idx/valid: (B, K) GLOBAL logical token indices (grouped callers fold
    ``pos_base`` in first).  Invalid slots scatter out of range and drop.
    """
    b = idx.shape[0]
    page = jnp.where(valid, idx // page_size, max_pages)
    return jnp.zeros((b, max_pages), bool).at[
        jnp.arange(b)[:, None], page].set(True, mode="drop")


def _global_partials(q0, q_bar, u, cache: LatentKVCache, pos,
                     cfg: ModelConfig, sals: SALSConfig, plan: DecodePlan,
                     collect: bool = False):
    """Paper-faithful global top-N_c.  Returns (m, l, o, touched) with a
    G=1 axis on the partials; touched is None unless ``collect``."""
    r_star = sals.score_rank(cfg.kv_dim)
    k_lat, k_scale = cache.latent_views()
    pt, ps = cache.page_table, cache.page_size
    if cache.tiered:
        # two-table routing: scoring reads the always-hot r* score pool at
        # PHYSICAL pages; reconstruction reads the payload pools at HOT
        # SLOTS (the scheduler fetches every selected page hot before the
        # step that gets consumed — see RequestScheduler)
        score_k, score_scale = cache.k_score, cache.k_scale_score
        recon_table = cache.hot_table
    else:
        score_k, score_scale = k_lat, k_scale
        recon_table = pt
    if not cache.paged:
        k_lat = constrain(k_lat, ("batch", "kv_seq", None))
        score_k = k_lat
        if k_scale is not None:
            k_scale = constrain(k_scale, ("batch", "kv_seq"))
            score_scale = k_scale
    idx, valid = sel.topk_latent(q_bar, u, score_k, score_scale, pos, sals,
                                 r_star, page_table=pt, page_size=ps,
                                 backend=plan.backend)
    # ascending-position order: page-bucketed DMA for the paged kernel,
    # same accumulation order for BOTH layouts (paged == dense bit-exact)
    idx, valid = sel.sort_selected(idx, valid)
    m, l, o = ops.sparse_recon_attention(
        q0, k_lat, k_scale, cache.v_q, cache.v_scale, cache.v_zero, u, idx,
        valid, pos, n_kv=cfg.n_kv_heads, v_bits=sals.v_bits,
        v_group=sals.v_group, theta=cfg.rope_theta,
        softcap=cfg.attn_logit_softcap, use_rope=cfg.use_rope,
        page_table=recon_table, page_size=ps, backend=plan.backend)
    touched = None
    if collect:
        if not cache.paged:
            raise ValueError("selection collection requires the paged cache")
        touched = _touched_pages(idx, valid, ps, pt.shape[1])
    return m[:, None], l[:, None], o[:, None], touched


def _slab_partials(q0, q_lat, k_lat, k_scale, v_q, v_scale, v_zero, u, pos,
                   base, cfg: ModelConfig, sals: SALSConfig, k_loc: int,
                   backend, page_table=None, page_size=0, score_k=None,
                   score_scale=None, recon_table=None, collect: bool = False):
    """Fused top-k + recon-attend over sequence slabs (rows = slabs).

    All per-token arrays are (N, S_loc, ...) — or page pools with a
    per-slab ``page_table`` — ``pos`` is a scalar or (N,) per-row decode
    positions; ``base`` (N,) holds each row's global position offset.
    Tiered pools route scoring through ``score_k``/``score_scale`` (full
    physical pool, ``page_table`` ids) and reconstruction through
    ``recon_table`` (hot slots); both default to the untiered operands.
    Returns flash partials (N, H[, dh]), plus (idx, valid) if ``collect``.
    """
    sk = k_lat if score_k is None else score_k
    ss = k_scale if score_k is None else score_scale
    rt = page_table if recon_table is None else recon_table
    idx, valid = ops.latent_topk(
        q_lat, sk, ss, pos, n_critical=k_loc, n_sink=sals.n_sink,
        n_recent=sals.n_recent, pos_base=base, page_table=page_table,
        page_size=page_size, backend=backend)
    idx, valid = sel.sort_selected(idx, valid)
    m, l, o = ops.sparse_recon_attention(
        q0, k_lat, k_scale, v_q, v_scale, v_zero, u, idx, valid, pos,
        n_kv=cfg.n_kv_heads, v_bits=sals.v_bits, v_group=sals.v_group,
        theta=cfg.rope_theta, softcap=cfg.attn_logit_softcap,
        use_rope=cfg.use_rope, pos_base=base, page_table=rt,
        page_size=page_size, backend=backend)
    if collect:
        return m, l, o, idx, valid
    return m, l, o


def _grouped_partials(q0, q_bar, u, cache: LatentKVCache, pos,
                      cfg: ModelConfig, sals: SALSConfig, plan: DecodePlan,
                      collect: bool = False):
    """Per-group top-(N_c/G) through the SAME fused kernels.

    Group g covers slab [g·S/G, (g+1)·S/G); kernels see slab-local indices
    and a per-row ``pos_base`` offset.  Returns (m, l, o, touched) with a
    G axis on the partials; touched is None unless ``collect``.
    """
    g = plan.n_groups
    r_star = sals.score_rank(cfg.kv_dim)
    k_lat, k_scale = cache.latent_views()
    k_loc = -(-sals.n_critical // g)
    q_lat = sel.latent_query(q_bar, u, r_star)                  # (B, r*)
    h = q0.shape[1]
    if collect and not cache.paged:
        raise ValueError("selection collection requires the paged cache")

    if cache.paged:
        # paged grouped fold: the POOLS are physical (no slab structure) —
        # only the page TABLE splits per slab.  Row (b, g) of the folded
        # batch sees table row pt[b, g·mp/G:(g+1)·mp/G]: slab-local logical
        # indices, global positions via pos_base, same kernels.
        pt = cache.page_table                                   # (B, mp)
        b, mp = pt.shape
        ps = cache.page_size
        s_loc = (mp // g) * ps
        ptg = pt.reshape(b * g, mp // g)
        htg = None
        if cache.tiered:
            htg = cache.hot_table.reshape(b * g, mp // g)
        base = jnp.tile(jnp.arange(g, dtype=jnp.int32) * s_loc, b)
        qg = jnp.repeat(q0, g, axis=0)
        qlg = jnp.repeat(q_lat, g, axis=0)
        pos_g = jnp.repeat(jnp.broadcast_to(
            jnp.asarray(pos, jnp.int32).reshape(-1), (b,)), g)
        out = _slab_partials(qg, qlg, k_lat, k_scale, cache.v_q,
                             cache.v_scale, cache.v_zero, u, pos_g, base,
                             cfg, sals, k_loc, plan.backend,
                             page_table=ptg, page_size=ps,
                             score_k=cache.k_score if cache.tiered else None,
                             score_scale=cache.k_scale_score,
                             recon_table=htg, collect=collect)
        touched = None
        if collect:
            m, l, o, idx, valid = out
            # fold pos_base back in: slab-local -> global logical indices,
            # then union the per-slab masks row-wise into (B, mp)
            gidx = (base[:, None] + idx).reshape(b, -1)
            touched = _touched_pages(gidx, valid.reshape(b, -1), ps, mp)
        else:
            m, l, o = out
        return (m.reshape(b, g, h), l.reshape(b, g, h),
                o.reshape(b, g, h, cfg.head_dim), touched)

    b, s, r = k_lat.shape
    s_loc = s // g

    if plan.shard_axes:
        # shard-LOCAL slabs: each kv_seq shard scores + gathers its own slab
        # (shard_map), so no latent, score, or selected-K/V collective —
        # only the (B,G,H)(+dh) partial merge leaves the shard (§Perf A3).
        m, l, o = _grouped_shardmap(q0, q_lat, k_lat, k_scale, cache.v_q,
                                    cache.v_scale, cache.v_zero, u, pos, cfg,
                                    sals, plan, s_loc, k_loc)
        return m, l, o, None

    # no matching mesh: fold the group axis into the kernel batch axis
    # (metadata-only reshapes of the raw cache — no copy, no dequant)
    kg = k_lat.reshape(b * g, s_loc, r)
    ksg = None if k_scale is None else k_scale.reshape(b * g, s_loc)
    vqg = cache.v_q.reshape(b * g, s_loc, -1)
    vsg = cache.v_scale.reshape(b * g, s_loc, -1)
    vzg = cache.v_zero.reshape(b * g, s_loc, -1)
    base = jnp.tile(jnp.arange(g, dtype=jnp.int32) * s_loc, b)  # row = b·G+g
    qg = jnp.repeat(q0, g, axis=0)                              # (B·G, H, dh)
    qlg = jnp.repeat(q_lat, g, axis=0)
    pos_g = jnp.repeat(jnp.broadcast_to(
        jnp.asarray(pos, jnp.int32).reshape(-1), (b,)), g)      # (B·G,)
    m, l, o = _slab_partials(qg, qlg, kg, ksg, vqg, vsg, vzg, u, pos_g, base,
                             cfg, sals, k_loc, plan.backend)
    return (m.reshape(b, g, h), l.reshape(b, g, h),
            o.reshape(b, g, h, cfg.head_dim), None)


def _grouped_shardmap(q0, q_lat, k_lat, k_scale, v_q, v_scale, v_zero, u,
                      pos, cfg: ModelConfig, sals: SALSConfig,
                      plan: DecodePlan, s_loc: int, k_loc: int):
    ctx = current_ctx()
    axes = plan.shard_axes
    sizes = dict(zip(ctx.mesh.axis_names, ctx.mesh.devices.shape))
    ba = ctx.rules.get("batch")
    # per-row positions ride with the batch sharding (ragged decode)
    pos_arr = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1),
                               (q0.shape[0],))

    def local_fn(q0, q_lat, k_lat, k_scale, v_q, v_scale, v_zero, u, pos):
        gi = jnp.int32(0)
        for a in axes:
            gi = gi * sizes[a] + jax.lax.axis_index(a)
        base = jnp.full((q0.shape[0],), gi * s_loc, jnp.int32)
        m, l, o = _slab_partials(q0, q_lat, k_lat, k_scale, v_q, v_scale,
                                 v_zero, u, pos, base, cfg, sals, k_loc,
                                 plan.backend)
        return m[:, None], l[:, None], o[:, None]   # local G axis of 1

    seq = axes if len(axes) > 1 else axes[0]
    tok_specs = [P(ba, seq, None), P(ba, seq, None), P(ba, seq, None),
                 P(ba, seq, None)]                  # k_lat, v_q, v_scale, v_zero
    scale_spec = P(ba, seq)
    in_specs = (P(ba, None, None), P(ba, None), tok_specs[0],
                scale_spec if k_scale is not None else P(),
                tok_specs[1], tok_specs[2], tok_specs[3],
                P(None, None), P(ba))
    out_specs = (P(ba, seq), P(ba, seq), P(ba, seq, None))
    k_scale_arg = k_scale if k_scale is not None \
        else jnp.zeros((), jnp.int32)               # unused placeholder

    def wrapper(q0, q_lat, k_lat, k_scale_a, v_q, v_scale, v_zero, u, pos):
        ks = k_scale_a if k_scale is not None else None
        return local_fn(q0, q_lat, k_lat, ks, v_q, v_scale, v_zero, u, pos)

    return jax.shard_map(wrapper, mesh=ctx.mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)(
        q0, q_lat, k_lat, k_scale_arg, v_q, v_scale, v_zero, u, pos_arr)


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------

def sals_decode_attend(params: dict, u: jnp.ndarray, cache: LatentKVCache,
                       x: jnp.ndarray, pos, cfg: ModelConfig,
                       sals: SALSConfig, plan: Optional[DecodePlan] = None,
                       collect: bool = False):
    """One-token SALS attention for one layer.

    x: (B, 1, d); pos: traced scalar position of this token, or a (B,)
    per-row positions vector (ragged continuous batching — every stage
    masks, RoPEs, and writes per row; a batch of heterogeneous positions is
    bit-identical to the same rows decoded alone).  The selection layout
    comes from ``cache.n_groups`` (via :func:`plan_decode`) unless an
    explicit ``plan`` is given.  Returns (y (B,1,d), updated cache), plus
    a (B, max_pages) bool touched-page mask when ``collect`` (paged caches
    only — the tiered fetch-and-rerun loop reads it to decide which cold
    pages the NEXT run of this same step will reconstruct from).
    """
    if plan is None:
        plan = plan_decode(cache)
    b = x.shape[0]
    kvd = cfg.kv_dim
    w = sals.n_recent
    pos_v = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))

    q, k_new, v_new = qkv_proj(params, x, cfg)        # (B,1,H,dh)/(B,1,Hkv,dh)
    k_flat = k_new.reshape(b, kvd)
    v_flat = v_new.reshape(b, kvd)

    # ---- stage 1: append to caches ---------------------------------------
    k_lat_new = (k_flat.astype(jnp.float32) @ u.astype(jnp.float32))
    cache = cache.write(sals, pos_v, k_lat_new, v_flat, k_new[:, 0],
                        v_new[:, 0])

    # ---- stage 2 input: head-group-summed query ---------------------------
    q_bar = sel.group_query(q[:, 0], cfg)             # (B, kvd)

    # RoPE'd query for the exact attention (per-row position)
    q_r = (apply_rope(q, pos_v[:, None], cfg.rope_theta)
           if cfg.use_rope else q)[:, 0]

    # ---- sink + recent region (always attended, full precision) ----------
    ns = sals.n_sink
    sink_pos = jnp.broadcast_to(jnp.arange(ns)[None, :], (b, ns))
    rec_pos = sel.ring_positions(pos_v, w)            # (B, w)
    sr_k = jnp.concatenate([cache.sink_k, cache.recent_k], axis=1)
    sr_v = jnp.concatenate([cache.sink_v, cache.recent_v], axis=1)
    sr_positions = jnp.concatenate([sink_pos, rec_pos], axis=1)  # (B, ns+w)
    sr_valid = (sr_positions >= 0) & (sr_positions <= pos_v[:, None])
    sr_logits = _region_logits(q_r, sr_k, sr_positions, cfg)
    sr_logits = jnp.where(sr_valid[:, None, :], sr_logits, NEG)
    m_sr, l_sr, o_sr = _partial_attend(sr_logits, sr_v, cfg)

    # ---- stages 2-4: fused selected-token partials, (B, G, H[, dh]) -------
    attend = _global_partials if plan.n_groups <= 1 else _grouped_partials
    m_c, l_c, o_c, touched = attend(q[:, 0], q_bar, u, cache, pos_v, cfg,
                                    sals, plan, collect)

    # ---- stage 5: flash-style LSE merge across groups + window ------------
    m_all = jnp.maximum(jnp.max(m_c, axis=1), m_sr)   # (B,H)
    wc = jnp.exp(m_c - m_all[:, None, :])             # (B,G,H)
    wsr = jnp.exp(m_sr - m_all)
    denom = jnp.sum(wc * l_c, axis=1) + wsr * l_sr
    numer = jnp.sum(wc[..., None] * o_c, axis=1) + wsr[..., None] * o_sr
    o = numer / jnp.maximum(denom, 1e-30)[..., None]

    y = out_proj(params, o[:, None].astype(x.dtype), cfg)
    if collect:
        return y, cache, touched
    return y, cache


# ---------------------------------------------------------------------------
# Speculative verify window (ISSUE 9): one selection, Q queries
# ---------------------------------------------------------------------------

def _global_window_partials(q, q_bar, u, cache: LatentKVCache, pos, ql: int,
                            cfg: ModelConfig, sals: SALSConfig,
                            plan: DecodePlan):
    """Windowed twin of :func:`_global_partials`: ONE global top-N_c
    (masked at the window's LAST position, so it covers every query's
    selectable range) feeds the windowed recon kernel, which reconstructs
    each selected token once and gates query t to positions
    <= pos+t-n_recent in-kernel.  Returns (m, l, o) with a G=1 axis:
    (B, 1, Q, H[, dh])."""
    if cache.tiered:
        raise NotImplementedError(
            "speculative windows need untiered caches: the hot-set "
            "prefetch contract is per committed step")
    r_star = sals.score_rank(cfg.kv_dim)
    k_lat, k_scale = cache.latent_views()
    pt, ps = cache.page_table, cache.page_size
    if not cache.paged:
        k_lat = constrain(k_lat, ("batch", "kv_seq", None))
        if k_scale is not None:
            k_scale = constrain(k_scale, ("batch", "kv_seq"))
    idx, valid = sel.topk_latent(q_bar, u, k_lat, k_scale, pos + (ql - 1),
                                 sals, r_star, page_table=pt, page_size=ps,
                                 backend=plan.backend)
    idx, valid = sel.sort_selected(idx, valid)
    m, l, o = ops.sparse_recon_attention_window(
        q, k_lat, k_scale, cache.v_q, cache.v_scale, cache.v_zero, u, idx,
        valid, pos, n_kv=cfg.n_kv_heads, n_recent=sals.n_recent,
        v_bits=sals.v_bits, v_group=sals.v_group, theta=cfg.rope_theta,
        softcap=cfg.attn_logit_softcap, use_rope=cfg.use_rope,
        page_table=pt, page_size=ps, backend=plan.backend)
    return m[:, None], l[:, None], o[:, None]


def _slab_window_partials(q, q_lat, k_lat, k_scale, v_q, v_scale, v_zero, u,
                          pos, base, ql: int, cfg: ModelConfig,
                          sals: SALSConfig, k_loc: int, backend,
                          page_table=None, page_size=0):
    """Windowed twin of :func:`_slab_partials` (rows = slabs; ``pos`` is
    the per-row WINDOW BASE, selection masks at pos + ql - 1)."""
    idx, valid = ops.latent_topk(
        q_lat, k_lat, k_scale, pos + (ql - 1), n_critical=k_loc,
        n_sink=sals.n_sink, n_recent=sals.n_recent, pos_base=base,
        page_table=page_table, page_size=page_size, backend=backend)
    idx, valid = sel.sort_selected(idx, valid)
    return ops.sparse_recon_attention_window(
        q, k_lat, k_scale, v_q, v_scale, v_zero, u, idx, valid, pos,
        n_kv=cfg.n_kv_heads, n_recent=sals.n_recent, v_bits=sals.v_bits,
        v_group=sals.v_group, theta=cfg.rope_theta,
        softcap=cfg.attn_logit_softcap, use_rope=cfg.use_rope,
        pos_base=base, page_table=page_table, page_size=page_size,
        backend=backend)


def _grouped_window_partials(q, q_bar, u, cache: LatentKVCache, pos, ql: int,
                             cfg: ModelConfig, sals: SALSConfig,
                             plan: DecodePlan):
    """Windowed per-group partials, group axis FOLDED into the kernel
    batch (the shard-local shard_map slab path is a tree-attention
    follow-up — :func:`sals_window_attend` strips ``shard_axes``).
    Returns (m, l, o) shaped (B, G, Q, H[, dh])."""
    if cache.tiered:
        raise NotImplementedError(
            "speculative windows need untiered caches: the hot-set "
            "prefetch contract is per committed step")
    g = plan.n_groups
    r_star = sals.score_rank(cfg.kv_dim)
    k_lat, k_scale = cache.latent_views()
    k_loc = -(-sals.n_critical // g)
    q_lat = sel.latent_query(q_bar, u, r_star)                  # (B, r*)
    b, h = q.shape[0], q.shape[2]

    if cache.paged:
        pt = cache.page_table                                   # (B, mp)
        mp = pt.shape[1]
        ps = cache.page_size
        s_loc = (mp // g) * ps
        ptg = pt.reshape(b * g, mp // g)
        base = jnp.tile(jnp.arange(g, dtype=jnp.int32) * s_loc, b)
        qg = jnp.repeat(q, g, axis=0)                           # (B·G,Q,H,dh)
        qlg = jnp.repeat(q_lat, g, axis=0)
        pos_g = jnp.repeat(pos, g)
        m, l, o = _slab_window_partials(qg, qlg, k_lat, k_scale, cache.v_q,
                                        cache.v_scale, cache.v_zero, u,
                                        pos_g, base, ql, cfg, sals, k_loc,
                                        plan.backend, page_table=ptg,
                                        page_size=ps)
        return (m.reshape(b, g, ql, h), l.reshape(b, g, ql, h),
                o.reshape(b, g, ql, h, cfg.head_dim))

    s = k_lat.shape[1]
    r = k_lat.shape[2]
    s_loc = s // g
    kg = k_lat.reshape(b * g, s_loc, r)
    ksg = None if k_scale is None else k_scale.reshape(b * g, s_loc)
    vqg = cache.v_q.reshape(b * g, s_loc, -1)
    vsg = cache.v_scale.reshape(b * g, s_loc, -1)
    vzg = cache.v_zero.reshape(b * g, s_loc, -1)
    base = jnp.tile(jnp.arange(g, dtype=jnp.int32) * s_loc, b)
    qg = jnp.repeat(q, g, axis=0)
    qlg = jnp.repeat(q_lat, g, axis=0)
    pos_g = jnp.repeat(pos, g)
    m, l, o = _slab_window_partials(qg, qlg, kg, ksg, vqg, vsg, vzg, u,
                                    pos_g, base, ql, cfg, sals, k_loc,
                                    plan.backend)
    return (m.reshape(b, g, ql, h), l.reshape(b, g, ql, h),
            o.reshape(b, g, ql, h, cfg.head_dim))


def sals_window_attend(params: dict, u: jnp.ndarray, cache: LatentKVCache,
                       x: jnp.ndarray, pos, cfg: ModelConfig,
                       sals: SALSConfig, plan: Optional[DecodePlan] = None):
    """Multi-token VERIFY-WINDOW SALS attention for one layer (ISSUE 9).

    x: (B, Q, d) — the pending token plus Q−1 drafts at positions
    pos..pos+Q−1 (``pos`` scalar or (B,) per-row WINDOW BASE; requires
    1 <= pos per row and Q <= n_recent so selection never reads
    uncommitted slots).  READ-ONLY w.r.t. the cache: nothing is appended
    — a rejected draft must never reach the destructive ring/sink/latent
    writes — the caller commits the accepted prefix afterwards through
    :meth:`LatentKVCache.write_window` with the returned window K/V.

    ONE latent selection (the FIRST window token's RoPE-free grouped
    query, masked at the window's LAST position) serves all Q queries;
    the windowed recon kernel reconstructs each selected token once and
    applies the per-draft-position mask advance (query t only attends
    selected positions <= pos+t−n_recent).  The sink/recent window is
    SIMULATED per query: the sequential writes of window tokens 0..t into
    the ring (slot (pos+s) % W) and sink (while pos+s < n_sink) are
    replayed into per-query buffer views, so query t reads byte-for-byte
    the buffers its sequential step would read — greedy verify is then
    token-exact with sequential decode whenever N_c covers each query's
    selectable range (every selectable token selected; the in-kernel gate
    reduces query t's set to exactly sequential step t's, and the gated
    leftovers are exact online-softmax no-ops).

    Returns (y (B, Q, d), k_pre (B, Q, Hkv, dh), v (B, Q, Hkv, dh)).
    """
    if plan is None:
        plan = plan_decode(cache)
    # fold the group axis into the kernel batch: shard-local windowed
    # slabs ride with the tree-attention follow-up (ROADMAP)
    plan = dataclasses.replace(plan, shard_axes=())
    b, ql, _ = x.shape
    w = sals.n_recent
    if ql > w:
        raise ValueError(f"verify window {ql} > n_recent {w}: the widest "
                         "selection mask would cover uncommitted positions")
    pos_v = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    t_idx = jnp.arange(ql, dtype=jnp.int32)
    qpos = pos_v[:, None] + t_idx[None, :]                       # (B, Q)

    q, k_new, v_new = qkv_proj(params, x, cfg)    # (B,Q,H,dh)/(B,Q,Hkv,dh)

    # RoPE-free scoring query: the window ANCHOR (always committed)
    q_bar = sel.window_query(q, cfg)              # (B, kvd)
    q_r = apply_rope(q, qpos, cfg.rope_theta) if cfg.use_rope else q

    # ---- per-query sink + recent ring (simulated sequential writes) ------
    # Q <= W, so each ring slot j receives AT MOST one in-window token:
    # s_j = (j - pos) mod W, live for query t iff s_j <= t.  Sink position
    # p in [pos, pos+t] holds window token p - pos.  Everything else reads
    # the committed buffers; validity is the sequential (0 <= p <= pos+t).
    ns = sals.n_sink
    k_win = k_new.astype(cache.recent_k.dtype)
    v_win = v_new.astype(cache.recent_v.dtype)

    j = jnp.arange(w, dtype=jnp.int32)[None, :]                  # (1, w)
    s_j = (j - pos_v[:, None]) % w                               # (B, w)
    ring_hit = s_j[:, None, :] <= t_idx[None, :, None]           # (B, Q, w)
    sj_c = jnp.clip(s_j, 0, ql - 1)[..., None, None]             # (B, w, 1, 1)
    ring_wk = jnp.take_along_axis(k_win, sj_c, axis=1)           # (B, w, kv, dh)
    ring_wv = jnp.take_along_axis(v_win, sj_c, axis=1)
    hit = ring_hit[..., None, None]
    ring_k = jnp.where(hit, ring_wk[:, None], cache.recent_k[:, None])
    ring_v = jnp.where(hit, ring_wv[:, None], cache.recent_v[:, None])
    rec_pos = sel.ring_positions(qpos, w)                        # (B, Q, w)

    sp = jnp.arange(ns, dtype=jnp.int32)[None, :]                # (1, ns)
    s_sink = sp - pos_v[:, None]                                 # (B, ns)
    sink_hit = (s_sink[:, None, :] >= 0) \
        & (s_sink[:, None, :] <= t_idx[None, :, None])           # (B, Q, ns)
    ss_c = jnp.clip(s_sink, 0, ql - 1)[..., None, None]
    sink_wk = jnp.take_along_axis(k_win, ss_c, axis=1)
    sink_wv = jnp.take_along_axis(v_win, ss_c, axis=1)
    shit = sink_hit[..., None, None]
    sink_k = jnp.where(shit, sink_wk[:, None], cache.sink_k[:, None])
    sink_v = jnp.where(shit, sink_wv[:, None], cache.sink_v[:, None])
    sink_pos = jnp.broadcast_to(sp[None], (b, ql, ns))

    sr_k = jnp.concatenate([sink_k, ring_k], axis=2)    # (B, Q, ns+w, kv, dh)
    sr_v = jnp.concatenate([sink_v, ring_v], axis=2)
    sr_positions = jnp.concatenate([sink_pos, rec_pos], axis=2)
    sr_valid = (sr_positions >= 0) & (sr_positions <= qpos[..., None])

    sr_logits = _region_logits_window(q_r, sr_k, sr_positions, cfg)
    sr_logits = jnp.where(sr_valid[:, :, None, :], sr_logits, NEG)
    m_sr, l_sr, o_sr = _partial_attend(sr_logits, sr_v, cfg)

    # ---- selected-token partials, (B, G, Q, H[, dh]) ----------------------
    attend = _global_window_partials if plan.n_groups <= 1 \
        else _grouped_window_partials
    m_c, l_c, o_c = attend(q, q_bar, u, cache, pos_v, ql, cfg, sals, plan)

    # ---- LSE merge across groups + window region --------------------------
    m_all = jnp.maximum(jnp.max(m_c, axis=1), m_sr)   # (B,Q,H)
    wc = jnp.exp(m_c - m_all[:, None])                # (B,G,Q,H)
    wsr = jnp.exp(m_sr - m_all)
    denom = jnp.sum(wc * l_c, axis=1) + wsr * l_sr
    numer = jnp.sum(wc[..., None] * o_c, axis=1) + wsr[..., None] * o_sr
    o = numer / jnp.maximum(denom, 1e-30)[..., None]

    y = out_proj(params, o.astype(x.dtype), cfg)
    return y, k_new, v_new
