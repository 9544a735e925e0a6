"""Pure-jnp oracles for every Pallas kernel (allclose targets in tests).

These are small, obviously-correct implementations: naive materialized
attention, naive latent scoring, naive gather→reconstruct→RoPE→attend.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -2.0 ** 30
# The f32 contractions of the SALS oracles run at full f32 precision on
# every platform (a TPU's default would round operands to bf16), so an
# on-chip comparison with the Pallas kernels measures the kernels.
_F32 = jax.lax.Precision.HIGHEST


def _rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (..., seq, heads, dh); positions broadcastable to (..., seq)."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions[..., :, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :half], x32[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                  causal: bool = True, softcap: float = 0.0,
                  mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Naive attention. q: (B,Sq,H,dh), k/v: (B,Sk,H,dh) -> (B,Sq,H,dh)."""
    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if softcap:
        logits = softcap * jnp.tanh(logits / softcap)
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        cm = jnp.arange(sq)[:, None] + (sk - sq) >= jnp.arange(sk)[None, :]
        logits = jnp.where(cm[None, None], logits, NEG_INF)
    if mask is not None:
        logits = jnp.where(mask, logits, NEG_INF)
    p = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)


def latent_score_ref(q_lat: jnp.ndarray, k_lat: jnp.ndarray,
                     k_scale: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """q_lat: (B, r*), k_lat: (B, S, r>=r*) -> (B, S) f32 scores.

    ``k_scale`` (B, S): per-token dequant scale for int8 latents."""
    r_star = q_lat.shape[-1]
    scores = jnp.einsum("br,bsr->bs", q_lat.astype(jnp.float32),
                        k_lat[..., :r_star].astype(jnp.float32),
                        precision=_F32)
    if k_scale is not None:
        scores = scores * k_scale.astype(jnp.float32)
    return scores


def latent_topk_ref(q_lat: jnp.ndarray, k_lat: jnp.ndarray,
                    k_scale: Optional[jnp.ndarray], pos, *, n_critical: int,
                    n_sink: int, n_recent: int,
                    pos_base: Optional[jnp.ndarray] = None
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused §4.3 scoring + selection oracle over the raw latent cache.

    Scores every cached latent, masks the sink / recent / future ranges,
    takes the global top-N_c.  ``pos`` is a scalar or (B,) per-row decode
    positions (ragged batches); ``pos_base`` (B,) offsets row b's global
    positions (grouped layout; returned indices stay row-local).  Returns
    (idx (B, N_c) int32, valid (B, N_c) bool); ``valid`` is False for slots
    that fell on masked entries.
    """
    scores = latent_score_ref(q_lat, k_lat, k_scale)
    b, s = scores.shape
    base = jnp.zeros((b,), jnp.int32) if pos_base is None \
        else jnp.broadcast_to(jnp.asarray(pos_base, jnp.int32), (b,))
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    positions = jnp.arange(s)[None, :] + base[:, None]          # (B, S)
    mask = (positions >= n_sink) & (positions <= pos_b[:, None] - n_recent)
    masked = jnp.where(mask, scores, NEG_INF)
    vals, idx = jax.lax.top_k(masked, n_critical)
    return idx.astype(jnp.int32), vals > NEG_INF / 2


def paged_logical_view(pool: jnp.ndarray, page_table: jnp.ndarray,
                       page_size: int) -> jnp.ndarray:
    """ORACLE-ONLY: materialize the logical (B, S, ...) view of a paged
    field.  pool: (n_pages, page_size, ...); page_table: (B, max_pages)
    int32.  S = max_pages · page_size.  The Pallas paged kernels never
    build this array — it exists so the paged layout can reuse every dense
    oracle (and so the "xla" CPU backend has a correct fallback)."""
    b, mp = page_table.shape
    pages = jnp.take(pool, page_table.reshape(-1), axis=0)     # (B·mp, ps, ·)
    return pages.reshape(b, mp * page_size, *pool.shape[2:])


def latent_topk_paged_ref(q_lat: jnp.ndarray, k_lat: jnp.ndarray,
                          k_scale, pos, *, page_table: jnp.ndarray,
                          page_size: int, n_critical: int, n_sink: int,
                          n_recent: int, pos_base=None
                          ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Paged selection oracle: gather the logical view, run the dense
    oracle.  Same tie-breaks, so it is the bit-exactness target for
    ``latent_topk_paged_pallas``."""
    k_log = paged_logical_view(k_lat, page_table, page_size)
    ks_log = None if k_scale is None else \
        paged_logical_view(k_scale, page_table, page_size)
    return latent_topk_ref(q_lat, k_log, ks_log, pos, n_critical=n_critical,
                           n_sink=n_sink, n_recent=n_recent,
                           pos_base=pos_base)


def sparse_recon_attention_paged_ref(
        q, k_lat, k_scale, v_q, v_scale, v_zero, u, idx, valid, q_pos, *,
        page_table: jnp.ndarray, page_size: int, n_kv: int, v_bits: int = 8,
        v_group: int = 64, theta: float = 10_000.0, softcap: float = 0.0,
        use_rope: bool = True, pos_base=None
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Paged fused-attention oracle: gather logical views, delegate.  The
    cache operands are page pools; ``idx`` stays logical."""
    view = lambda a: None if a is None else \
        paged_logical_view(a, page_table, page_size)
    return sparse_recon_attention_fused_ref(
        q, view(k_lat), view(k_scale), view(v_q), view(v_scale),
        view(v_zero), u, idx, valid, q_pos, n_kv=n_kv, v_bits=v_bits,
        v_group=v_group, theta=theta, softcap=softcap, use_rope=use_rope,
        pos_base=pos_base)


def sparse_recon_attention_window_paged_ref(
        q, k_lat, k_scale, v_q, v_scale, v_zero, u, idx, valid, q_pos, *,
        page_table: jnp.ndarray, page_size: int, n_kv: int, n_recent: int = 0,
        v_bits: int = 8, v_group: int = 64, theta: float = 10_000.0,
        softcap: float = 0.0, use_rope: bool = True, pos_base=None
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Paged WINDOWED fused-attention oracle: gather logical views,
    delegate.  The cache operands are page pools; ``idx`` stays logical."""
    view = lambda a: None if a is None else \
        paged_logical_view(a, page_table, page_size)
    return sparse_recon_attention_fused_window_ref(
        q, view(k_lat), view(k_scale), view(v_q), view(v_scale),
        view(v_zero), u, idx, valid, q_pos, n_kv=n_kv, n_recent=n_recent,
        v_bits=v_bits, v_group=v_group, theta=theta, softcap=softcap,
        use_rope=use_rope, pos_base=pos_base)


def dequantize_values_ref(code: jnp.ndarray, scale: jnp.ndarray,
                          zero: jnp.ndarray, v_bits: int, v_group: int
                          ) -> jnp.ndarray:
    """KIVI-style group dequant oracle (mirrors core.quantization.dequantize,
    duplicated here so the kernel layer stays import-free of core).

    code: (..., code_w) int8/uint8; scale/zero: (..., G).  Returns f32."""
    if v_bits == 4:
        lo = (code & 0x0F).astype(jnp.float32)
        hi = ((code >> 4) & 0x0F).astype(jnp.float32)
        vals = jnp.stack([lo, hi], axis=-1).reshape(
            *code.shape[:-1], code.shape[-1] * 2)
    else:
        vals = code.astype(jnp.float32) + 128.0
    vg = vals.reshape(*vals.shape[:-1], -1, v_group)
    out = vg * scale[..., None].astype(jnp.float32) \
        + zero[..., None].astype(jnp.float32)
    return out.reshape(vals.shape)


def gather_dequant_ref(k_lat: jnp.ndarray, k_scale: Optional[jnp.ndarray],
                       v_q: jnp.ndarray, v_scale: jnp.ndarray,
                       v_zero: jnp.ndarray, idx: jnp.ndarray, *, v_bits: int,
                       v_group: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """XLA gather + dequant oracle for the fused kernel's in-kernel gather.

    idx: (B, N_c) cache rows.  Returns (lat (B, N_c, r) f32,
    v (B, N_c, kvd) f32) — the dense intermediates the Pallas path never
    materializes.
    """
    lat = jnp.take_along_axis(k_lat, idx[..., None], axis=-2) \
        .astype(jnp.float32)
    if k_scale is not None:
        sc = jnp.take_along_axis(k_scale.astype(jnp.float32), idx, axis=-1)
        lat = lat * sc[..., None]
    v = dequantize_values_ref(
        jnp.take_along_axis(v_q, idx[..., None], axis=-2),
        jnp.take_along_axis(v_scale, idx[..., None], axis=-2),
        jnp.take_along_axis(v_zero, idx[..., None], axis=-2),
        v_bits, v_group)
    return lat, v


def sparse_recon_attention_fused_ref(
        q: jnp.ndarray, k_lat: jnp.ndarray, k_scale: Optional[jnp.ndarray],
        v_q: jnp.ndarray, v_scale: jnp.ndarray, v_zero: jnp.ndarray,
        u: jnp.ndarray, idx: jnp.ndarray, valid: jnp.ndarray, q_pos, *,
        n_kv: int, v_bits: int = 8, v_group: int = 64,
        theta: float = 10_000.0, softcap: float = 0.0, use_rope: bool = True,
        pos_base: Optional[jnp.ndarray] = None
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Index-taking oracle: gather-then-attend in plain jnp.

    Same contract as the fused Pallas kernel — the selected rows' positions
    are ``pos_base[b] + idx[b, n]`` (pos_base None -> the indices
    themselves).  This is what the "xla" backend dispatches (CPU +
    multi-pod dry-run), and the allclose target for interpret tests.
    """
    lat, v = gather_dequant_ref(k_lat, k_scale, v_q, v_scale, v_zero, idx,
                                v_bits=v_bits, v_group=v_group)
    sel_pos = idx if pos_base is None else \
        idx + jnp.broadcast_to(jnp.asarray(pos_base, jnp.int32),
                               (idx.shape[0],))[:, None]
    return sparse_recon_attention_ref(q, lat, v, u, sel_pos, valid, q_pos,
                                      n_kv=n_kv, theta=theta, softcap=softcap,
                                      use_rope=use_rope)


def sparse_recon_attention_ref(q: jnp.ndarray, lat_sel: jnp.ndarray,
                               v_sel: jnp.ndarray, u: jnp.ndarray,
                               sel_pos: jnp.ndarray, valid: jnp.ndarray,
                               q_pos: jnp.ndarray, *, n_kv: int,
                               theta: float = 10_000.0,
                               softcap: float = 0.0,
                               use_rope: bool = True
                               ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused reconstruct→RoPE→partial-attention oracle (decode, one token).

    q: (B, H, dh) pre-RoPE query; lat_sel: (B, N, r) selected latents;
    v_sel: (B, N, kvd) dequantized selected values; u: (kvd, r);
    sel_pos/valid: (B, N); q_pos: scalar or (B,).
    Returns flash-style partials (m (B,H), l (B,H), o (B,H,dh)).
    """
    b, h, dh = q.shape
    n = lat_sel.shape[1]
    kvd = u.shape[0]
    group = h // (kvd // dh)
    k_flat = jnp.matmul(lat_sel.astype(jnp.float32), u.T.astype(jnp.float32),
                        precision=_F32)  # (B,N,kvd)
    k_pre = k_flat.reshape(b, n, n_kv, dh)
    if use_rope:
        k_r = _rope(k_pre, jnp.broadcast_to(sel_pos, (b, n)), theta)
        q_r = _rope(q[:, None].astype(jnp.float32), jnp.broadcast_to(
            jnp.asarray(q_pos).reshape(-1, 1), (b, 1)), theta)[:, 0]
    else:
        k_r, q_r = k_pre, q
    kk = jnp.repeat(k_r, group, axis=2)                             # (B,N,H,dh)
    logits = jnp.einsum("bhd,bnhd->bhn", q_r.astype(jnp.float32),
                        kk.astype(jnp.float32),
                        precision=_F32) * dh ** -0.5
    if softcap:
        logits = softcap * jnp.tanh(logits / softcap)
    logits = jnp.where(valid[:, None, :], logits, NEG_INF)
    m = jnp.max(logits, axis=-1)
    p = jnp.where(logits <= NEG_INF / 2, 0.0, jnp.exp(logits - m[..., None]))
    l = jnp.sum(p, axis=-1)
    vv = jnp.repeat(v_sel.reshape(b, n, n_kv, dh), group, axis=2)
    o = jnp.einsum("bhn,bnhd->bhd", p, vv.astype(jnp.float32),
                   precision=_F32)
    return m, l, o


def sparse_recon_attention_fused_window_ref(
        q: jnp.ndarray, k_lat: jnp.ndarray, k_scale: Optional[jnp.ndarray],
        v_q: jnp.ndarray, v_scale: jnp.ndarray, v_zero: jnp.ndarray,
        u: jnp.ndarray, idx: jnp.ndarray, valid: jnp.ndarray, q_pos, *,
        n_kv: int, n_recent: int = 0, v_bits: int = 8, v_group: int = 64,
        theta: float = 10_000.0, softcap: float = 0.0, use_rope: bool = True,
        pos_base: Optional[jnp.ndarray] = None
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Index-taking WINDOWED oracle: gather-then-attend in plain jnp.

    Same contract as :func:`sparse_recon_attention_fused_ref` except q
    carries a ``q_len`` axis — see :func:`sparse_recon_attention_window_ref`.
    """
    lat, v = gather_dequant_ref(k_lat, k_scale, v_q, v_scale, v_zero, idx,
                                v_bits=v_bits, v_group=v_group)
    sel_pos = idx if pos_base is None else \
        idx + jnp.broadcast_to(jnp.asarray(pos_base, jnp.int32),
                               (idx.shape[0],))[:, None]
    return sparse_recon_attention_window_ref(
        q, lat, v, u, sel_pos, valid, q_pos, n_kv=n_kv, n_recent=n_recent,
        theta=theta, softcap=softcap, use_rope=use_rope)


def sparse_recon_attention_window_ref(q: jnp.ndarray, lat_sel: jnp.ndarray,
                                      v_sel: jnp.ndarray, u: jnp.ndarray,
                                      sel_pos: jnp.ndarray,
                                      valid: jnp.ndarray, q_pos, *,
                                      n_kv: int, n_recent: int = 0,
                                      theta: float = 10_000.0,
                                      softcap: float = 0.0,
                                      use_rope: bool = True
                                      ) -> Tuple[jnp.ndarray, jnp.ndarray,
                                                 jnp.ndarray]:
    """WINDOWED reconstruct→RoPE→partial-attention oracle (speculative
    decode: one selection amortized over a multi-token verify window).

    q: (B, Q, H, dh) pre-RoPE queries; query t sits at position
    ``q_pos + t`` (q_pos scalar or (B,) window base).  The selected set
    (lat_sel/v_sel/sel_pos/valid) is SHARED by the whole window — it is
    reconstructed once.  ``n_recent`` > 0 applies the per-draft-position
    mask advance: query t attends only selected tokens with
    ``sel_pos <= q_pos + t - n_recent`` — exactly the positions a
    sequential decode step at q_pos + t could have selected; younger
    positions are covered by the ring / in-window region partials the
    caller merges in.  Returns partials (m (B,Q,H), l (B,Q,H),
    o (B,Q,H,dh)); with Q = 1 this is bit-identical to
    :func:`sparse_recon_attention_ref`.
    """
    b, ql, h, dh = q.shape
    n = lat_sel.shape[1]
    kvd = u.shape[0]
    group = h // (kvd // dh)
    base = jnp.broadcast_to(jnp.asarray(q_pos, jnp.int32).reshape(-1), (b,))
    if ql == 1:
        # delegate to the single-token oracle: the degenerate q axis makes
        # XLA pick a different dot lowering (gemv vs gemm accumulation
        # order), which would break the documented bit-identity
        ok1 = valid
        if n_recent:
            ok1 = ok1 & (sel_pos <= base[:, None] - n_recent)
        m, l, o = sparse_recon_attention_ref(
            q[:, 0], lat_sel, v_sel, u, sel_pos, ok1, q_pos, n_kv=n_kv,
            theta=theta, softcap=softcap, use_rope=use_rope)
        return m[:, None], l[:, None], o[:, None]
    qpos = base[:, None] + jnp.arange(ql, dtype=jnp.int32)[None, :]  # (B,Q)
    k_flat = jnp.matmul(lat_sel.astype(jnp.float32), u.T.astype(jnp.float32),
                        precision=_F32)  # (B,N,kvd)
    k_pre = k_flat.reshape(b, n, n_kv, dh)
    if use_rope:
        k_r = _rope(k_pre, jnp.broadcast_to(sel_pos, (b, n)), theta)
        q_r = _rope(q.astype(jnp.float32), qpos, theta)
    else:
        k_r, q_r = k_pre, q
    kk = jnp.repeat(k_r, group, axis=2)                         # (B,N,H,dh)
    logits = jnp.einsum("bqhd,bnhd->bqhn", q_r.astype(jnp.float32),
                        kk.astype(jnp.float32),
                        precision=_F32) * dh ** -0.5
    if softcap:
        logits = softcap * jnp.tanh(logits / softcap)
    ok = jnp.broadcast_to(valid[:, None, None, :], logits.shape)
    if n_recent:
        gate = sel_pos[:, None, :] <= qpos[..., None] - n_recent  # (B,Q,N)
        ok = ok & gate[:, :, None, :]
    logits = jnp.where(ok, logits, NEG_INF)
    m = jnp.max(logits, axis=-1)
    p = jnp.where(logits <= NEG_INF / 2, 0.0, jnp.exp(logits - m[..., None]))
    l = jnp.sum(p, axis=-1)
    vv = jnp.repeat(v_sel.reshape(b, n, n_kv, dh), group, axis=2)
    o = jnp.einsum("bqhn,bnhd->bqhd", p, vv.astype(jnp.float32),
                   precision=_F32)
    return m, l, o
