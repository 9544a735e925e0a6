"""Public kernel entry points with backend dispatch.

The default backend follows the platform: ``pallas`` on TPU, ``xla`` on
every other platform.  ``xla`` and ``naive`` exist as test oracles:

backend="pallas" — pl.pallas_call TPU kernels (interpret=True on CPU so the
                   same code validates here and compiles on real TPUs).
backend="xla"    — pure-jnp implementations (chunked/online-softmax flash);
                   what the multi-pod dry-run lowers, and the CPU default.
backend="naive"  — materialized reference (small shapes / tests).

Models call these; nothing below imports from repro.models.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref

NEG_INF = _ref.NEG_INF

_DEFAULT_BACKEND: Optional[str] = None     # None: follow the platform
# Prefill attention up to this many query and key positions runs the
# materialized reference on every backend: one fused XLA softmax whose
# (Sq, Sk) logits are small at these lengths.  The flash kernels serve
# longer monolithic prefills, where those logits would not fit.
MATERIALIZED_MAX_SEQ = 2048
_Q_BLOCK = 512
_KV_BLOCK = 512


def set_default_backend(name: Optional[str]) -> None:
    """Override the process-wide backend (tests comparing against an
    oracle); ``None`` restores the platform default."""
    global _DEFAULT_BACKEND
    assert name in (None, "xla", "pallas", "naive")
    _DEFAULT_BACKEND = name


def default_backend() -> str:
    if _DEFAULT_BACKEND is not None:
        return _DEFAULT_BACKEND
    return "pallas" if jax.default_backend() == "tpu" else "xla"


# ---------------------------------------------------------------------------
# Flash attention
# ---------------------------------------------------------------------------

def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, softcap: float = 0.0,
                    prefix_len: int = 0,
                    mask: Optional[jnp.ndarray] = None,
                    backend: Optional[str] = None) -> jnp.ndarray:
    """q: (B,Sq,H,dh); k/v: (B,Sk,H,dh) (GQA pre-expanded) -> (B,Sq,H,dh).

    ``prefix_len`` > 0 gives prefix-LM masking (first ``prefix_len`` kv
    positions bidirectional, the rest causal — paligemma) without ever
    materializing an (Sq, Sk) mask.  ``mask`` (broadcastable to
    (B,H,Sq,Sk)) forces the naive path — tests only.  Shapes within
    ``MATERIALIZED_MAX_SEQ`` take the materialized path on every backend
    (see there); served prompts go through the chunked prefill instead.
    """
    backend = backend or default_backend()
    sq, sk = q.shape[1], k.shape[1]
    if mask is not None or backend == "naive" or (
            sq <= MATERIALIZED_MAX_SEQ and sk <= MATERIALIZED_MAX_SEQ):
        if mask is None and prefix_len:
            kvp = jnp.arange(sk)
            mask = ((kvp[None, :] < prefix_len) |
                    (jnp.arange(sq)[:, None] + (sk - sq) >= kvp[None, :])
                    )[None, None]
            causal = False
        return _ref.attention_ref(q, k, v, causal=causal, softcap=softcap,
                                  mask=mask)
    if backend == "pallas":
        from repro.kernels import flash_attention as fa
        return fa.flash_attention_pallas(q, k, v, causal=causal,
                                         softcap=softcap,
                                         prefix_len=prefix_len)
    return _flash_xla(q, k, v, causal, softcap, prefix_len)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_xla(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
               causal: bool, softcap: float, prefix_len: int = 0
               ) -> jnp.ndarray:
    """Online-softmax chunked attention in plain jnp, with a
    FlashAttention-2-style CUSTOM BACKWARD.

    Forward: outer scan over q blocks, inner over kv blocks — the live set
    is one (B,H,Qb,Kb) logits tile, so a 32k×32k prefill never
    materializes S².  Only (O, LSE) are saved for the backward.

    Backward: recomputes each P tile from (q, k, LSE) — WITHOUT the custom
    vjp, jax's scan differentiation stashes every f32 probability tile
    ((nq·nk)·B·qb·kb floats ≈ 8.6 GiB/device/layer at yi-9b×train_4k) and
    pays its HBM round-trip (§Perf iteration C1).
    """
    return _flash_fwd_impl(q, k, v, causal, softcap, prefix_len)[0]


def _mask_logits(logits, q_pos, k_pos, causal, prefix_len):
    if causal or prefix_len:
        cm = q_pos[:, None] >= k_pos[None, :]
        if prefix_len:      # prefix-LM: prefix kv columns bidirectional
            cm = cm | (k_pos[None, :] < prefix_len)
        logits = jnp.where(cm[None, None], logits, NEG_INF)
    return logits


def _flash_fwd_impl(q, k, v, causal, softcap, prefix_len):
    b, sq, h, dh = q.shape
    sk = k.shape[1]
    qb = _largest_divisor_block(sq, _Q_BLOCK)
    kb = _largest_divisor_block(sk, _KV_BLOCK)
    nq, nk = sq // qb, sk // kb
    scale = dh ** -0.5
    q_off = sk - sq  # decode-style alignment when sq < sk

    qs = jnp.moveaxis(q.reshape(b, nq, qb, h, dh), 1, 0)      # (nQ,B,qb,H,dh)
    ks = jnp.moveaxis(k.reshape(b, nk, kb, h, dh), 1, 0)
    vs = jnp.moveaxis(v.reshape(b, nk, kb, h, dh), 1, 0)

    def q_step(_, qi_blk):
        qi, q_blk = qi_blk                                     # (), (B,qb,H,dh)
        q_pos = q_off + qi * qb + jnp.arange(qb)

        def kv_step(carry, kj_blk):
            m, l, acc = carry
            kj, k_blk, v_blk = kj_blk
            logits = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k_blk,
                                preferred_element_type=jnp.float32) * scale
            if softcap:
                logits = softcap * jnp.tanh(logits / softcap)
            logits = _mask_logits(logits, q_pos, kj * kb + jnp.arange(kb),
                                  causal, prefix_len)
            m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
            p = jnp.exp(logits - m_new[..., None])
            p = jnp.where(logits <= NEG_INF / 2, 0.0, p)
            alpha = jnp.exp(m - m_new)
            l = l * alpha + jnp.sum(p, axis=-1)
            # PV dot with bf16 P (FA2 practice): halves the tile bytes the
            # MXU streams; accumulation stays f32 (§Perf iteration C3)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p.astype(v_blk.dtype), v_blk,
                preferred_element_type=jnp.float32)
            return (m_new, l, acc), None

        init = (jnp.full((b, h, qb), NEG_INF, jnp.float32),
                jnp.zeros((b, h, qb), jnp.float32),
                jnp.zeros((b, h, qb, dh), jnp.float32))
        (m, l, acc), _ = jax.lax.scan(
            kv_step, init, (jnp.arange(nk), ks, vs))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        lse = m + jnp.log(jnp.maximum(l, 1e-30))               # (B,H,qb)
        return None, (jnp.moveaxis(out, 1, 2).astype(q.dtype), lse)

    _, (blocks, lses) = jax.lax.scan(q_step, None, (jnp.arange(nq), qs))
    out = jnp.moveaxis(blocks, 0, 1).reshape(b, sq, h, dh)
    lse = jnp.moveaxis(lses, 0, 2).reshape(b, h, sq)           # (B,H,Sq)
    return out, lse


def _flash_fwd_rule(q, k, v, causal, softcap, prefix_len):
    out, lse = _flash_fwd_impl(q, k, v, causal, softcap, prefix_len)
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(causal, softcap, prefix_len, res, dout):
    q, k, v, out, lse = res
    b, sq, h, dh = q.shape
    sk = k.shape[1]
    qb = _largest_divisor_block(sq, _Q_BLOCK)
    kb = _largest_divisor_block(sk, _KV_BLOCK)
    nq, nk = sq // qb, sk // kb
    scale = dh ** -0.5
    q_off = sk - sq

    # D_i = rowsum(dO ∘ O)  (B,H,Sq)
    d_rows = jnp.einsum("bqhd,bqhd->bhq", dout.astype(jnp.float32),
                        out.astype(jnp.float32))
    qs = jnp.moveaxis(q.reshape(b, nq, qb, h, dh), 1, 0)
    dos = jnp.moveaxis(dout.reshape(b, nq, qb, h, dh), 1, 0)
    lses = jnp.moveaxis(lse.reshape(b, h, nq, qb), 2, 0)       # (nQ,B,H,qb)
    ds_rows = jnp.moveaxis(d_rows.reshape(b, h, nq, qb), 2, 0)
    ks = jnp.moveaxis(k.reshape(b, nk, kb, h, dh), 1, 0)
    vs = jnp.moveaxis(v.reshape(b, nk, kb, h, dh), 1, 0)

    def kv_step(dq_acc, kj_blk):
        kj, k_blk, v_blk = kj_blk                              # (B,kb,H,dh)
        k_pos = kj * kb + jnp.arange(kb)

        def q_step(carry, qi_blk):
            dq_acc, dk_j, dv_j = carry
            qi, q_blk, do_blk, lse_blk, d_blk = qi_blk
            q_pos = q_off + qi * qb + jnp.arange(qb)
            s_raw = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k_blk,
                               preferred_element_type=jnp.float32) * scale
            if softcap:
                t = jnp.tanh(s_raw / softcap)
                logits = softcap * t
            else:
                logits = s_raw
            logits = _mask_logits(logits, q_pos, k_pos, causal, prefix_len)
            p = jnp.exp(logits - lse_blk[..., None])           # (B,H,qb,kb)
            p = jnp.where(logits <= NEG_INF / 2, 0.0, p)
            p16 = p.astype(do_blk.dtype)
            dv_j = dv_j + jnp.einsum("bhqk,bqhd->bkhd", p16, do_blk,
                                     preferred_element_type=jnp.float32)
            dp = jnp.einsum("bqhd,bkhd->bhqk", do_blk, v_blk,
                            preferred_element_type=jnp.float32)
            dlogits = p * (dp - d_blk[..., None])
            if softcap:
                dlogits = dlogits * (1.0 - t * t)
            dlogits = dlogits * scale
            dl16 = dlogits.astype(k_blk.dtype)
            dq_upd = jnp.einsum("bhqk,bkhd->bqhd", dl16, k_blk,
                                preferred_element_type=jnp.float32)
            dq_acc = jax.lax.dynamic_update_slice_in_dim(
                dq_acc, jax.lax.dynamic_slice_in_dim(dq_acc, qi * qb, qb, 1)
                + dq_upd, qi * qb, axis=1)
            dk_j = dk_j + jnp.einsum("bhqk,bqhd->bkhd", dl16, q_blk,
                                     preferred_element_type=jnp.float32)
            return (dq_acc, dk_j, dv_j), None

        init = (dq_acc,
                jnp.zeros((b, kb, h, dh), jnp.float32),
                jnp.zeros((b, kb, h, dh), jnp.float32))
        (dq_acc, dk_j, dv_j), _ = jax.lax.scan(
            q_step, init, (jnp.arange(nq), qs, dos, lses, ds_rows))
        return dq_acc, (dk_j, dv_j)

    dq0 = jnp.zeros((b, sq, h, dh), jnp.float32)
    dq, (dks, dvs) = jax.lax.scan(kv_step, dq0, (jnp.arange(nk), ks, vs))
    dk = jnp.moveaxis(dks, 0, 1).reshape(b, sk, h, dh)
    dv = jnp.moveaxis(dvs, 0, 1).reshape(b, sk, h, dh)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash_xla.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def _largest_divisor_block(s: int, target: int) -> int:
    """Largest block <= target that divides s (vlm seqs like 4352 need 256)."""
    bk = min(target, s)
    while s % bk:
        bk -= 1
    return bk


# ---------------------------------------------------------------------------
# Latent scoring (SALS stage 2)
# ---------------------------------------------------------------------------

def latent_score(q_lat: jnp.ndarray, k_lat: jnp.ndarray,
                 k_scale: Optional[jnp.ndarray] = None, *,
                 backend: Optional[str] = None) -> jnp.ndarray:
    """q_lat (B, r*), k_lat (B, S, r) raw latents (+ optional int8 per-token
    ``k_scale`` (B, S)) -> (B, S) f32.  The Pallas path streams the leading
    r* columns via BlockSpec — no dense slice/pad/dequant copy."""
    backend = backend or default_backend()
    if backend == "pallas":
        from repro.kernels import latent_score as ls
        return ls.latent_score_pallas(q_lat, k_lat, k_scale)
    return _ref.latent_score_ref(q_lat, k_lat, k_scale)


def latent_topk(q_lat: jnp.ndarray, k_lat: jnp.ndarray,
                k_scale: Optional[jnp.ndarray], pos, *, n_critical: int,
                n_sink: int, n_recent: int,
                pos_base: Optional[jnp.ndarray] = None,
                page_table: Optional[jnp.ndarray] = None, page_size: int = 0,
                backend: Optional[str] = None):
    """Fused scoring + top-N_c selection over the raw latent cache.

    Returns (idx (B, N_c) int32, valid (B, N_c) bool).  ``pos`` is a scalar
    or (B,) per-row decode positions (ragged batches).  ``pos_base`` (B,)
    offsets row b's global positions — the grouped layout scores each
    sequence slab with the same kernel (indices stay slab-local).  The
    Pallas path emits per-seq-block candidates so the final ``lax.top_k``
    runs over (B, nb·k) instead of (B, S); indices match the oracle exactly
    (including tie-breaks).

    PAGED layout: ``page_table`` (B, max_pages) + ``page_size`` make
    ``k_lat``/``k_scale`` physical page pools; the Pallas path walks pages
    through the table (scalar prefetch), the xla/naive path materializes
    the logical view (oracle-only dense copy).  Returned idx is LOGICAL
    and bit-identical to the dense layout."""
    backend = backend or default_backend()
    if page_table is not None:
        if backend == "pallas":
            from repro.kernels import latent_score as ls
            return ls.latent_topk_paged_pallas(
                q_lat, k_lat, k_scale, pos, page_table=page_table,
                page_size=page_size, n_critical=n_critical, n_sink=n_sink,
                n_recent=n_recent, pos_base=pos_base)
        return _ref.latent_topk_paged_ref(
            q_lat, k_lat, k_scale, pos, page_table=page_table,
            page_size=page_size, n_critical=n_critical, n_sink=n_sink,
            n_recent=n_recent, pos_base=pos_base)
    if backend == "pallas":
        from repro.kernels import latent_score as ls
        return ls.latent_topk_pallas(q_lat, k_lat, k_scale, pos,
                                     n_critical=n_critical, n_sink=n_sink,
                                     n_recent=n_recent, pos_base=pos_base)
    return _ref.latent_topk_ref(q_lat, k_lat, k_scale, pos,
                                n_critical=n_critical, n_sink=n_sink,
                                n_recent=n_recent, pos_base=pos_base)


# ---------------------------------------------------------------------------
# Fused gather→dequant→reconstruct→RoPE→sparse-attention (SALS stages 3-4)
# ---------------------------------------------------------------------------

def sparse_recon_attention(q, k_lat, k_scale, v_q, v_scale, v_zero, u,
                           idx, valid, q_pos, *, n_kv: int, v_bits: int = 8,
                           v_group: int = 64, theta: float = 10_000.0,
                           softcap: float = 0.0, use_rope: bool = True,
                           pos_base: Optional[jnp.ndarray] = None,
                           page_table: Optional[jnp.ndarray] = None,
                           page_size: int = 0,
                           backend: Optional[str] = None):
    """Selected-token decode attention over the RAW cache arrays.

    The top-k ``idx`` (B, N_c) is the only selection artifact passed in; the
    Pallas path gathers + dequantizes in-kernel via scalar-prefetch indexing
    (zero HBM intermediates), the "xla"/"naive" oracle gathers with
    ``take_along_axis``.  ``q_pos`` is a scalar or (B,) per-row decode
    positions (ragged batches).  ``pos_base`` (B,) offsets each row's RoPE
    positions (grouped layout: idx is slab-local, position is
    ``pos_base[b] + idx[b, n]``).  ``page_table``/``page_size``: paged
    layout — cache operands are page pools, ``idx`` stays logical, the
    Pallas path copies each selected token's 8-row tile (and, with pages
    of 128 rows or more, its scale chunk) from its page through the
    table.  See ref.sparse_recon_attention_fused_ref for the full
    contract."""
    backend = backend or default_backend()
    if page_table is not None:
        if backend == "pallas":
            from repro.kernels import sparse_recon_attention as sra
            return sra.sparse_recon_attention_paged_pallas(
                q, k_lat, k_scale, v_q, v_scale, v_zero, u, idx, valid,
                q_pos, page_table=page_table, page_size=page_size,
                n_kv=n_kv, v_bits=v_bits, v_group=v_group, theta=theta,
                softcap=softcap, use_rope=use_rope, pos_base=pos_base)
        return _ref.sparse_recon_attention_paged_ref(
            q, k_lat, k_scale, v_q, v_scale, v_zero, u, idx, valid, q_pos,
            page_table=page_table, page_size=page_size, n_kv=n_kv,
            v_bits=v_bits, v_group=v_group, theta=theta, softcap=softcap,
            use_rope=use_rope, pos_base=pos_base)
    if backend == "pallas":
        from repro.kernels import sparse_recon_attention as sra
        return sra.sparse_recon_attention_pallas(
            q, k_lat, k_scale, v_q, v_scale, v_zero, u, idx, valid, q_pos,
            n_kv=n_kv, v_bits=v_bits, v_group=v_group, theta=theta,
            softcap=softcap, use_rope=use_rope, pos_base=pos_base)
    return _ref.sparse_recon_attention_fused_ref(
        q, k_lat, k_scale, v_q, v_scale, v_zero, u, idx, valid, q_pos,
        n_kv=n_kv, v_bits=v_bits, v_group=v_group, theta=theta,
        softcap=softcap, use_rope=use_rope, pos_base=pos_base)


def sparse_recon_attention_window(q, k_lat, k_scale, v_q, v_scale, v_zero, u,
                                  idx, valid, q_pos, *, n_kv: int,
                                  n_recent: int = 0, v_bits: int = 8,
                                  v_group: int = 64, theta: float = 10_000.0,
                                  softcap: float = 0.0, use_rope: bool = True,
                                  pos_base: Optional[jnp.ndarray] = None,
                                  page_table: Optional[jnp.ndarray] = None,
                                  page_size: int = 0,
                                  backend: Optional[str] = None):
    """WINDOWED selected-token decode attention (speculative verify).

    Same contract as :func:`sparse_recon_attention` except ``q`` is
    (B, q_len, H, dh) and ``q_pos`` is the WINDOW BASE: query t is RoPE'd
    at ``q_pos + t`` and — with ``n_recent`` > 0 — only attends selected
    positions ``<= q_pos + t - n_recent`` (the per-draft-position mask
    advance; younger positions belong to the ring / in-window region the
    caller merges).  One selection serves the whole window: the selected
    tokens are gathered / dequantized / reconstructed ONCE.  Returns
    (m (B,Q,H), l (B,Q,H), o (B,Q,H,dh)); q_len = 1 is bit-identical to
    :func:`sparse_recon_attention`."""
    backend = backend or default_backend()
    if page_table is not None:
        if backend == "pallas":
            from repro.kernels import sparse_recon_attention as sra
            return sra.sparse_recon_attention_window_paged_pallas(
                q, k_lat, k_scale, v_q, v_scale, v_zero, u, idx, valid,
                q_pos, page_table=page_table, page_size=page_size,
                n_kv=n_kv, n_recent=n_recent, v_bits=v_bits, v_group=v_group,
                theta=theta, softcap=softcap, use_rope=use_rope,
                pos_base=pos_base)
        return _ref.sparse_recon_attention_window_paged_ref(
            q, k_lat, k_scale, v_q, v_scale, v_zero, u, idx, valid, q_pos,
            page_table=page_table, page_size=page_size, n_kv=n_kv,
            n_recent=n_recent, v_bits=v_bits, v_group=v_group, theta=theta,
            softcap=softcap, use_rope=use_rope, pos_base=pos_base)
    if backend == "pallas":
        from repro.kernels import sparse_recon_attention as sra
        return sra.sparse_recon_attention_window_pallas(
            q, k_lat, k_scale, v_q, v_scale, v_zero, u, idx, valid, q_pos,
            n_kv=n_kv, n_recent=n_recent, v_bits=v_bits, v_group=v_group,
            theta=theta, softcap=softcap, use_rope=use_rope,
            pos_base=pos_base)
    return _ref.sparse_recon_attention_fused_window_ref(
        q, k_lat, k_scale, v_q, v_scale, v_zero, u, idx, valid, q_pos,
        n_kv=n_kv, n_recent=n_recent, v_bits=v_bits, v_group=v_group,
        theta=theta, softcap=softcap, use_rope=use_rope, pos_base=pos_base)
