"""Pallas TPU kernel — zero-materialization selected-token decode attention
(SALS stages 3-4: gather → dequant → reconstruct → RoPE → online-softmax).

The top-k indices are the ONLY thing that travels from selection to this
kernel.  The (B, N_c) index array arrives as a scalar-prefetch operand
(``pltpu.PrefetchScalarGridSpec``); every cache operand's ``index_map``
dereferences it, so the pipeline DMAs the block holding each selected
token straight from the raw cache arrays in HBM into VMEM — the TPU
analogue of the paper's fused Triton gather:

    dense  (B, S, ·) arrays    -> the aligned 8-row tile holding the token
    paged  (n_pages, ps, ·)    -> the whole page holding the token

(a TPU block's second-minor dim must be a multiple of 8, so a single row
cannot be a block).  The selection is sorted ascending, so consecutive
grid steps that land in the same tile or page keep the block index and
Pallas skips the re-DMA.  The kernel picks the token's row from the block
with a masked sublane sum.

Per selected token, entirely in VMEM/vregs, in a flat (·, kvd) lane
layout (head h owns lanes [h·dh, (h+1)·dh); no reshape crosses lanes
into sublanes, which Mosaic refuses):

    1. dequantize the latent (int8 × per-token scale, read from SMEM),
    2. reconstruct  k = k̃ · U_rᵀ  (one (1,r)·(r,kvd) matvec on the MXU),
    3. RoPE at the token's *original* position with lane rolls,
    4. GQA logits: the RoPE'd query tile (q_len·group, kvd) times k, summed
       per head's lane span -> (q_len·group, n_kv),
    5. dequantize the value codes (int4 codes unpack to [even | odd]
       channel order; the output is put back in order outside) and
       online-softmax accumulate (m, l, acc) across the N_c grid steps.

No gathered, dequantized, or reconstructed buffer ever touches HBM.
Returns flash-style partials (m, l, o) for LSE-merging with the
sink/recent-window partials (and across shards under a sequence-sharded
cache).

One kernel body serves all four entry points: dense or paged cache, and
a query window of ``q_len`` positions (speculative verify; ``q_len = 1``
is a single decode step).  With a window, each selected token is
reconstructed ONCE and scored by every query (query t RoPE'd at
``q_pos + t``), and a static ``n_recent`` applies the per-draft-position
mask advance (query t only sees selected positions
``<= q_pos + t - n_recent``; younger positions belong to the ring /
in-window region the caller merges).  Since the single-token entry points
run the same body with q_len = 1, the window of one is bit-identical to
them by construction.

Validated on CPU via ``interpret=True`` against
``ref.sparse_recon_attention_fused_ref``; ``tests/test_tpu_compile.py``
compiles it for v5e at Mistral widths.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode
from repro.kernels.ref import NEG_INF, _rope

_TILE = 8          # rows of the dense gather block / of a row pick


def _pick_row(ref, row, dtype) -> jnp.ndarray:
    """Row ``row`` of the (1, T, w) block ``ref`` as a (1, w) ``dtype``
    value: an aligned 8-row slice, then a masked sublane sum (exact —
    one term is the row, the rest are zeros)."""
    t = ref.shape[1]
    if t > _TILE:
        start = pl.multiple_of((row // _TILE) * _TILE, _TILE)
        x = ref[0, pl.ds(start, _TILE), :]
        row = row - start
    else:
        x = ref[0]
    x = x.astype(dtype)
    hit = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0) == row
    return jnp.sum(jnp.where(hit, x, jnp.zeros_like(x)), axis=0,
                   keepdims=True)


def _spread(x: jnp.ndarray, key: jnp.ndarray, n: int) -> jnp.ndarray:
    """(rows, n) -> (rows, kvd): lane i takes column ``key[i]`` of x."""
    out = jnp.zeros((x.shape[0], key.shape[1]), jnp.float32)
    for c in range(n):
        out = jnp.where(key == c, x[:, c:c + 1], out)
    return out


def _head_sums(prod: jnp.ndarray, n_kv: int, dh: int) -> jnp.ndarray:
    """(rows, kvd) -> (rows, n_kv): sum over each head's lane span."""
    col = jax.lax.broadcasted_iota(jnp.int32, (prod.shape[0], n_kv), 1)
    out = jnp.zeros((prod.shape[0], n_kv), jnp.float32)
    for h in range(n_kv):
        s = jnp.sum(prod[:, h * dh:(h + 1) * dh], axis=1, keepdims=True)
        out = jnp.where(col == h, s, out)
    return out


def _rope_flat(k: jnp.ndarray, pos, freq: jnp.ndarray, dh: int
               ) -> jnp.ndarray:
    """Half-rotation RoPE of a (1, kvd) key at ``pos``; ``freq`` (1, kvd)
    holds each lane's rotary frequency."""
    half = dh // 2
    kvd = k.shape[1]
    ang = pos.astype(jnp.float32) * freq
    lane = jax.lax.broadcasted_iota(jnp.int32, k.shape, 1) % dh
    ahead = pltpu.roll(k, kvd - half, 1)          # k[i + half]
    behind = pltpu.roll(k, half, 1)               # k[i - half]
    rot = jnp.where(lane < half, -ahead, behind)
    return k * jnp.cos(ang) + rot * jnp.sin(ang)


def _value_lanes(kvd: int, v_bits: int) -> jnp.ndarray:
    """(1, kvd) channel held by each value lane: natural order for int8,
    [even channels | odd channels] for unpacked int4 codes."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, kvd), 1)
    if v_bits != 4:
        return lane
    half = kvd // 2
    return jnp.where(lane < half, 2 * lane, 2 * (lane - half) + 1)


def _dequant_row(vq_ref, vs_ref, vz_ref, row, chan, *, v_bits: int,
                 v_group: int) -> jnp.ndarray:
    """One token's values (1, kvd) f32 in ``chan`` lane order (matches
    quantization.dequantize)."""
    if v_bits == 4:
        code = _pick_row(vq_ref, row, jnp.int32)            # (1, kvd/2)
        vals = jnp.concatenate([(code & 0x0F).astype(jnp.float32),
                                ((code >> 4) & 0x0F).astype(jnp.float32)],
                               axis=1)
    else:
        vals = _pick_row(vq_ref, row, jnp.float32) + 128.0
    n_groups = vs_ref.shape[2]
    grp = chan // v_group
    scale = _spread(_pick_row(vs_ref, row, jnp.float32), grp, n_groups)
    zero = _spread(_pick_row(vz_ref, row, jnp.float32), grp, n_groups)
    return vals * scale + zero


def _recon_kernel(idx_ref, valid_ref, qpos_ref, base_ref, ksel_ref, *rest,
                  paged: bool, tile: int, n_kv: int, dh: int, group: int,
                  softcap: float, use_rope: bool, nc: int, v_bits: int,
                  v_group: int, n_recent: int):
    if paged:
        rest = rest[1:]                     # the page table feeds index maps
    (q_ref, freq_ref, lat_ref, vq_ref, vs_ref, vz_ref, u_ref,
     m_ref, l_ref, o_ref, m_s, l_s, acc_s) = rest
    b_, n_ = pl.program_id(0), pl.program_id(1)
    kvd = n_kv * dh

    @pl.when(n_ == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    tok = idx_ref[b_, n_]
    row = jax.lax.rem(tok, tile)

    # ---- 1-2. dequantize the latent, reconstruct k = lat · Uᵀ --------------
    lat = _pick_row(lat_ref, row, jnp.float32) * ksel_ref[b_, n_]   # (1, r)
    k = jax.lax.dot_general(
        lat, u_ref[...].astype(jnp.float32), (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)                         # (1, kvd)

    # ---- 3. RoPE at the original position (= base + the cache index) ------
    pos = tok + base_ref[b_]
    if use_rope:
        k = _rope_flat(k, pos, freq_ref[...], dh)

    # ---- 4. GQA logits of every (query, head) row vs this token ------------
    logits = _head_sums(q_ref[0] * k, n_kv, dh) * (dh ** -0.5)
    if softcap:
        logits = softcap * jnp.tanh(logits / softcap)
    ok = valid_ref[b_, n_] != 0
    if n_recent:
        t_of = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 0) // group
        ok = ok & (pos <= qpos_ref[b_] + t_of - n_recent)
    logits = jnp.where(ok, logits, NEG_INF)

    # ---- 5. dequant value + online-softmax accumulate ----------------------
    m_prev = m_s[...]
    m_new = jnp.maximum(m_prev, logits)
    p = jnp.where(logits <= NEG_INF / 2, 0.0, jnp.exp(logits - m_new))
    alpha = jnp.exp(m_prev - m_new)
    l_s[...] = l_s[...] * alpha + p
    chan = _value_lanes(kvd, v_bits)
    v = _dequant_row(vq_ref, vs_ref, vz_ref, row, chan, v_bits=v_bits,
                     v_group=v_group)
    head = chan // dh
    acc_s[...] = acc_s[...] * _spread(alpha, head, n_kv) \
        + _spread(p, head, n_kv) * v
    m_s[...] = m_new

    @pl.when(n_ == nc - 1)
    def _finish():
        m_ref[0] = m_s[...]
        l_ref[0] = l_s[...]
        o_ref[0] = acc_s[...]


def _recon_call(q, k_lat, k_scale, v_q, v_scale, v_zero, u, idx, valid,
                q_pos, *, page_table, page_size: int, n_kv: int,
                n_recent: int, v_bits: int, v_group: int, theta: float,
                softcap: float, use_rope: bool, pos_base):
    """Shared body of the four entry points.  q: (B, Q, H, dh) pre-RoPE
    queries; returns (m (B,Q,H), l (B,Q,H), o (B,Q,H,dh)) f32 partials."""
    b, ql, h, dh = q.shape
    kvd = n_kv * dh
    group = h // n_kv
    nc = idx.shape[1]
    r = k_lat.shape[-1]
    paged = page_table is not None

    qpos = jnp.broadcast_to(jnp.asarray(q_pos, jnp.int32).reshape(-1), (b,))
    base = jnp.zeros((b,), jnp.int32) if pos_base is None \
        else jnp.broadcast_to(jnp.asarray(pos_base, jnp.int32), (b,))
    idx_i = idx.astype(jnp.int32)

    # RoPE'd queries as a (Q·group, kvd) tile per row: row t·group + j,
    # lanes of kv head g hold query head g·group + j
    q32 = q.astype(jnp.float32)
    if use_rope:
        q32 = _rope(q32, qpos[:, None] + jnp.arange(ql, dtype=jnp.int32),
                    theta)
    q_t = q32.reshape(b, ql, n_kv, group, dh).transpose(0, 1, 3, 2, 4) \
        .reshape(b, ql * group, kvd)
    half = dh // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    freq = jnp.tile(freqs, 2 * n_kv).reshape(1, kvd)

    # per-token int8 latent scales of the selected rows -> SMEM scalars
    if paged:
        mp = page_table.shape[1]
        n_pages = k_lat.shape[0]
        pt = page_table.astype(jnp.int32)
        tile = page_size
        phys = jnp.clip(jnp.take_along_axis(
            pt, jnp.minimum(idx_i // page_size, mp - 1), axis=1),
            0, n_pages - 1)                                    # (B, N_c)
        if k_scale is None:
            ksel = jnp.ones((b, nc), jnp.float32)
        else:
            ksel = k_scale[phys, idx_i % page_size].astype(jnp.float32)

        def blk(b_, n_, i_, v_, p_, bb_, ks_, pt_):
            lp = jnp.minimum(i_[b_, n_] // page_size, mp - 1)  # invalid: clamp
            return jnp.clip(pt_[b_, lp], 0, n_pages - 1)
        prefetch = (idx_i, valid.astype(jnp.int32), qpos, base, ksel, pt)
    else:
        s = k_lat.shape[1]
        tile = math.gcd(s, _TILE)
        if k_scale is None:
            ksel = jnp.ones((b, nc), jnp.float32)
        else:
            ksel = jnp.take_along_axis(k_scale, jnp.clip(idx_i, 0, s - 1),
                                       axis=1).astype(jnp.float32)

        def blk(b_, n_, i_, v_, p_, bb_, ks_):
            return b_, jnp.minimum(i_[b_, n_] // tile, s // tile - 1)
        prefetch = (idx_i, valid.astype(jnp.int32), qpos, base, ksel)

    def cache_spec(width):
        if paged:
            return pl.BlockSpec((1, tile, width),
                                lambda *a: (blk(*a), 0, 0))
        return pl.BlockSpec((1, tile, width), lambda *a: (*blk(*a), 0))

    def whole(shape):
        return pl.BlockSpec(shape, lambda *a: (0,) * len(shape))

    def per_row(shape):
        return pl.BlockSpec((1, *shape), lambda b_, *a: (b_,) + (0,) * len(shape))

    qg = ql * group
    kernel = functools.partial(
        _recon_kernel, paged=paged, tile=tile, n_kv=n_kv, dh=dh, group=group,
        softcap=softcap, use_rope=use_rope, nc=nc, v_bits=v_bits,
        v_group=v_group, n_recent=n_recent)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(b, nc),
        in_specs=[per_row((qg, kvd)), whole((1, kvd)), cache_spec(r),
                  cache_spec(v_q.shape[-1]), cache_spec(v_scale.shape[-1]),
                  cache_spec(v_zero.shape[-1]), whole(u.shape)],
        out_specs=[per_row((qg, n_kv)), per_row((qg, n_kv)),
                   per_row((qg, kvd))],
        scratch_shapes=[pltpu.VMEM((qg, n_kv), jnp.float32),
                        pltpu.VMEM((qg, n_kv), jnp.float32),
                        pltpu.VMEM((qg, kvd), jnp.float32)],
    )
    m, l, o = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, qg, n_kv), jnp.float32),
                   jax.ShapeDtypeStruct((b, qg, n_kv), jnp.float32),
                   jax.ShapeDtypeStruct((b, qg, kvd), jnp.float32)],
        interpret=interpret_mode(),
        name="sals_recon_attend_paged" if paged else "sals_recon_attend",
    )(*prefetch, q_t, freq, k_lat, v_q, v_scale, v_zero, u)

    if v_bits == 4:                 # [even | odd] lanes -> channel order
        o = jnp.stack([o[..., :kvd // 2], o[..., kvd // 2:]], axis=-1) \
            .reshape(b, qg, kvd)

    def heads(x):                   # (B, Q·group, n_kv, ·) -> (B, Q, H, ·)
        x = x.reshape(b, ql, group, n_kv, -1).transpose(0, 1, 3, 2, 4)
        return x.reshape(b, ql, h, -1)

    return (heads(m[..., None])[..., 0], heads(l[..., None])[..., 0],
            heads(o.reshape(b, qg, n_kv, dh)))


_STATIC = ("n_kv", "v_bits", "v_group", "theta", "softcap", "use_rope")


@functools.partial(jax.jit, static_argnames=_STATIC)
def sparse_recon_attention_pallas(
        q: jnp.ndarray, k_lat: jnp.ndarray, k_scale: Optional[jnp.ndarray],
        v_q: jnp.ndarray, v_scale: jnp.ndarray, v_zero: jnp.ndarray,
        u: jnp.ndarray, idx: jnp.ndarray, valid: jnp.ndarray, q_pos, *,
        n_kv: int, v_bits: int = 8, v_group: int = 64,
        theta: float = 10_000.0, softcap: float = 0.0, use_rope: bool = True,
        pos_base: Optional[jnp.ndarray] = None
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused decode partial-attention, gathered in-kernel from the raw cache.

    q: (B, H, dh) pre-RoPE query; k_lat: (B, S, r); k_scale: (B, S) or None;
    v_q: (B, S, code_w); v_scale/v_zero: (B, S, G); u: (kvd, r);
    idx/valid: (B, N_c) selected cache rows; q_pos: scalar or (B,) per-row
    decode positions — each row's query is RoPE'd at its own position, so
    ragged (continuous-batching) batches decode bit-identically to the same
    rows decoded alone; pos_base: (B,) per-row global offset of cache row 0
    (grouped layout — RoPE is applied at ``pos_base[b] + idx[b, n]``), or
    None for 0.  Returns (m (B,H), l (B,H), o (B,H,dh)) flash partials, f32.
    """
    m, l, o = _recon_call(
        q[:, None], k_lat, k_scale, v_q, v_scale, v_zero, u, idx, valid,
        q_pos, page_table=None, page_size=0, n_kv=n_kv, n_recent=0,
        v_bits=v_bits, v_group=v_group, theta=theta, softcap=softcap,
        use_rope=use_rope, pos_base=pos_base)
    return m[:, 0], l[:, 0], o[:, 0]


@functools.partial(jax.jit, static_argnames=_STATIC + ("page_size",))
def sparse_recon_attention_paged_pallas(
        q: jnp.ndarray, k_lat: jnp.ndarray, k_scale: Optional[jnp.ndarray],
        v_q: jnp.ndarray, v_scale: jnp.ndarray, v_zero: jnp.ndarray,
        u: jnp.ndarray, idx: jnp.ndarray, valid: jnp.ndarray, q_pos, *,
        page_table: jnp.ndarray, page_size: int,
        n_kv: int, v_bits: int = 8, v_group: int = 64,
        theta: float = 10_000.0, softcap: float = 0.0, use_rope: bool = True,
        pos_base: Optional[jnp.ndarray] = None
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Paged twin of :func:`sparse_recon_attention_pallas`.

    Cache operands are physical page pools (``k_lat (n_pages, ps, r)``,
    ``v_q (n_pages, ps, code_w)``, ...); ``idx`` holds LOGICAL positions;
    ``page_table`` (B, max_pages) rides as a scalar-prefetch operand and
    every cache index_map resolves page ``idx // ps`` through it.  The DMA
    unit is the whole page — sorted indices make consecutive same-page
    steps reuse the resident block, so the selected-token HBM traffic is
    per page touched.  Bit-identical to the dense kernel given the same
    idx order (per-token math is unchanged).
    """
    m, l, o = _recon_call(
        q[:, None], k_lat, k_scale, v_q, v_scale, v_zero, u, idx, valid,
        q_pos, page_table=page_table, page_size=page_size, n_kv=n_kv,
        n_recent=0, v_bits=v_bits, v_group=v_group, theta=theta,
        softcap=softcap, use_rope=use_rope, pos_base=pos_base)
    return m[:, 0], l[:, 0], o[:, 0]


@functools.partial(jax.jit, static_argnames=_STATIC + ("n_recent",))
def sparse_recon_attention_window_pallas(
        q: jnp.ndarray, k_lat: jnp.ndarray, k_scale: Optional[jnp.ndarray],
        v_q: jnp.ndarray, v_scale: jnp.ndarray, v_zero: jnp.ndarray,
        u: jnp.ndarray, idx: jnp.ndarray, valid: jnp.ndarray, q_pos, *,
        n_kv: int, n_recent: int = 0, v_bits: int = 8, v_group: int = 64,
        theta: float = 10_000.0, softcap: float = 0.0, use_rope: bool = True,
        pos_base: Optional[jnp.ndarray] = None
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Windowed fused decode attention (speculative verify window).

    q: (B, q_len, H, dh) pre-RoPE queries; ``q_pos`` (scalar or (B,)) is
    the WINDOW BASE — query t is RoPE'd at ``q_pos + t``.  One selected
    set (idx/valid) serves the whole window: each token is reconstructed
    once and attended by all queries, with the per-draft-position mask
    advance applied in-kernel (``n_recent`` static; see module docstring).
    Returns (m (B,Q,H), l (B,Q,H), o (B,Q,H,dh)) f32 partials.
    """
    return _recon_call(
        q, k_lat, k_scale, v_q, v_scale, v_zero, u, idx, valid, q_pos,
        page_table=None, page_size=0, n_kv=n_kv, n_recent=n_recent,
        v_bits=v_bits, v_group=v_group, theta=theta, softcap=softcap,
        use_rope=use_rope, pos_base=pos_base)


@functools.partial(jax.jit,
                   static_argnames=_STATIC + ("n_recent", "page_size"))
def sparse_recon_attention_window_paged_pallas(
        q: jnp.ndarray, k_lat: jnp.ndarray, k_scale: Optional[jnp.ndarray],
        v_q: jnp.ndarray, v_scale: jnp.ndarray, v_zero: jnp.ndarray,
        u: jnp.ndarray, idx: jnp.ndarray, valid: jnp.ndarray, q_pos, *,
        page_table: jnp.ndarray, page_size: int, n_kv: int,
        n_recent: int = 0, v_bits: int = 8, v_group: int = 64,
        theta: float = 10_000.0, softcap: float = 0.0, use_rope: bool = True,
        pos_base: Optional[jnp.ndarray] = None
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Paged twin of :func:`sparse_recon_attention_window_pallas`: cache
    operands are physical page pools, ``idx`` stays logical, and sorted
    indices keep the whole-page DMA once-per-page-touched.  Bit-identical
    to the dense windowed kernel given the same idx order."""
    return _recon_call(
        q, k_lat, k_scale, v_q, v_scale, v_zero, u, idx, valid, q_pos,
        page_table=page_table, page_size=page_size, n_kv=n_kv,
        n_recent=n_recent, v_bits=v_bits, v_group=v_group, theta=theta,
        softcap=softcap, use_rope=use_rope, pos_base=pos_base)
