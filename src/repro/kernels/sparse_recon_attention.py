"""Pallas TPU kernel — zero-materialization selected-token decode attention
(SALS stages 3-4: gather → dequant → reconstruct → RoPE → online-softmax).

The top-k indices are the ONLY thing that travels from selection to this
kernel.  The selection is padded to ``n_blocks × T`` entries (pad entries
invalid) and the grid is ``(B, n_blocks)``: each grid step handles a
block of T selected tokens.  T follows the shapes the kernel sees — at
most 128 (one MXU pass), fewer where N_c is small or the double-buffered
gather would not fit its VMEM share — and is balanced over the blocks so
the pad stays under 8 entries a block.

Gather.  The cache arrays stay in HBM (``memory_space=ANY``) and the
kernel copies what each token needs with manual DMAs.  A TPU copy's
second-minor extent must be a multiple of 8, so a single row cannot be
copied: a token's latents and value codes come in the aligned tile of 8
rows holding its row,

    dense  (B, S, ·) arrays    -> rows [tok // 8 · 8, +8) of row b
    paged  (n_pages, ps, ·)    -> the same rows of the physical page,
                                  resolved through the page table

and its value group scales and zero points, G = kvd / v_group wide, in
the aligned chunk of 128 positions holding it, from the arrays viewed
sequence axis last, (B, G, S) or (n_pages, G, ps).  XLA keeps arrays
this narrow with the sequence axis in lanes, so the view costs no copy.
Mosaic copies no lane slice narrower than 128, so where 128 does not
divide the sequence axis (pages under 128 rows) the selected tokens'
scales are gathered outside instead and arrive as (G, T) blocks.
The copies land in a ``(2, T, ·, ·)`` VMEM buffer per array; those of
the next block are started before this block is computed (double
buffering across the whole grid, rows included), and one wait per array
and slot covers all T copies.  Each token's row is then picked from its
tile with a masked sublane sum, and its scales from their chunk with a
masked lane sum (exact: one term is the entry, the rest are zeros); the
int8 latent scale, where there is one, rides in SMEM.

Per block, entirely in VMEM/vregs:

    1. reconstruct Kᵀ = U_r · latᵀ, one (kvd, r)·(r, T) matmul at
       ``Precision.HIGHEST`` (U_r is read into VMEM once per call);
    2. RoPE each column at the token's *original* position
       (``pos_base + idx``), from one (dh/2, T) angle table;
    3. logits of every (query, kv head) row against the T tokens with one
       matmul: the kernel spreads the queries block-diagonal once a row,
       row ``h·Q·group + j`` holding query j of kv head h in head h's
       lanes and zeros elsewhere, so one (n_kv·Q·group, kvd)·(kvd, T)
       product gives every head's logits; softcap, then the valid and
       windowed ``n_recent`` masks per column;
    4. dequantize the T value rows (each group's scale and zero point
       reach its lanes through one matmul with a 0/1 matrix; int4 codes
       unpack to [even | odd] channel order) and online-softmax
       accumulate (m, l, acc) with one (rows, T)·(T, kvd) matmul; after
       the last block each row keeps its own head's channels, in channel
       order, through one matmul with a 0/1 (kvd, dh) matrix.

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

_TILE = 8               # rows of the tile copied for a token's latents/codes
_LANES = 128            # lanes of the chunk copied for a token's value scales
_BLOCK = 128            # selected tokens a grid step at most: one MXU pass
_GATHER_VMEM = 16 << 20  # VMEM the double-buffered gather may hold
_VMEM_LIMIT = 48 << 20  # the kernel's scoped VMEM (v5e has 128 MiB)
_NEVER = jnp.iinfo(jnp.int32).max   # visible position of an invalid entry
_HIGHEST = jax.lax.Precision.HIGHEST


def _block_size(nc: int, slabs) -> Tuple[int, int]:
    """(T, n_blocks) for N_c selected tokens: as few blocks of at most
    _BLOCK tokens as the gather's two slots per cache array fit in
    _GATHER_VMEM, T a multiple of 8 that balances them.  ``slabs`` holds
    the (rows, lanes, dtype) of one token's copy of each array; VMEM pads
    it to the dtype's packed sublanes and to 128 lanes."""
    per_token = 0
    for rows, lanes, dtype in slabs:
        size, pack = jnp.dtype(dtype).itemsize, 32 // jnp.dtype(dtype).itemsize
        per_token += (pl.cdiv(rows, pack) * pack * pl.cdiv(lanes, 128) * 128
                      * size)
    t_max = _BLOCK
    while t_max > _TILE and 2 * t_max * per_token > _GATHER_VMEM:
        t_max //= 2
    nb = max(1, pl.cdiv(nc, t_max))
    return _TILE * pl.cdiv(pl.cdiv(nc, nb), _TILE), nb


def _as_f32(x: jnp.ndarray) -> jnp.ndarray:
    """f32 copy of a tile, through int32 for integer codes."""
    if jnp.issubdtype(x.dtype, jnp.integer):
        x = x.astype(jnp.int32)
    return x.astype(jnp.float32)


def _pick(x: jnp.ndarray, at, axis: int) -> jnp.ndarray:
    """Index ``at`` of ``axis`` of the f32 tile x, kept as a length-1 axis:
    a masked sum (exact — one term is the entry, the rest are zeros)."""
    hit = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis) == at
    return jnp.sum(jnp.where(hit, x, 0.0), axis=axis, keepdims=True)


def _rope_cols(kt: jnp.ndarray, pos: jnp.ndarray, freq: jnp.ndarray,
               n_kv: int, dh: int) -> jnp.ndarray:
    """Half-rotation RoPE of Kᵀ (kvd, T), column t at position
    ``pos[0, t]``; ``freq`` (dh/2, 1) holds the rotary frequencies."""
    half = dh // 2
    ang = pos.astype(jnp.float32) * freq                     # (dh/2, T)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    parts = []
    for h in range(n_kv):
        k1, k2 = kt[h * dh:h * dh + half], kt[h * dh + half:(h + 1) * dh]
        parts += [k1 * cos - k2 * sin, k2 * cos + k1 * sin]
    return jnp.concatenate(parts, axis=0)


def _channel(lane: jnp.ndarray, kvd: int, v_bits: int) -> jnp.ndarray:
    """Channel held by each value lane: natural order for int8,
    [even channels | odd channels] for unpacked int4."""
    if v_bits != 4:
        return lane
    half = kvd // 2
    return jnp.where(lane < half, 2 * lane, 2 * (lane - half) + 1)


def _iota(shape, axis: int) -> jnp.ndarray:
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _dot(a, b, dims=((1,), (0,))) -> jnp.ndarray:
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=_HIGHEST,
                               preferred_element_type=jnp.float32)


def _dequant(codes, scale_t, zero_t, kvd: int, *, v_bits: int,
             v_group: int) -> jnp.ndarray:
    """(T, kvd) f32 values from picked codes (T, code_w) and the group
    scales and zero points as columns (G, T), in value-lane order (matches
    quantization.dequantize).  Each group's scale reaches its lanes through
    one matmul with a 0/1 (G, kvd) matrix: exact, one nonzero term."""
    if v_bits == 4:
        c = codes.astype(jnp.int32)
        vals = jnp.concatenate([(c & 0x0F).astype(jnp.float32),
                                ((c >> 4) & 0x0F).astype(jnp.float32)],
                               axis=1)
    else:
        vals = codes + 128.0
    shape = (scale_t.shape[0], kvd)
    spread = (_channel(_iota(shape, 1), kvd, v_bits) // v_group
              == _iota(shape, 0)).astype(jnp.float32)
    contract = ((0,), (0,))
    return vals * _dot(scale_t, spread, contract) + _dot(zero_t, spread,
                                                         contract)


def _recon_kernel(*refs, n_prefetch: int, paged: bool, has_ksel: bool,
                  scale_blocks: bool, tile: int, chunk: int, block: int,
                  n_kv: int, dh: int, softcap: float, use_rope: bool,
                  v_bits: int, v_group: int):
    pre, refs = refs[:n_prefetch], refs[n_prefetch:]
    tok_ref = pre[0]                        # row of the token: in its row
    page_ref = pre[1] if paged else None    # of the cache, or in its page
    ksel_ref = pre[-1] if has_ksel else None
    (q_ref, lim_ref, freq_ref, pos_ref, vis_ref, u_ref, *refs) = refs
    if scale_blocks:                        # the block's (G, T) scale columns
        vs_ref, vz_ref, *refs = refs
    # the arrays copied here (latents, codes[, scales, zero points]), the
    # outputs; scratch: a gather buffer per copied array, picked latent and
    # code tiles, the DMA semaphores, the block-diagonal queries, the
    # online-softmax state
    n_copy = 2 if scale_blocks else 4
    caches, (m_ref, l_ref, o_ref), bufs, picks = (
        refs[:n_copy], refs[n_copy:n_copy + 3],
        refs[n_copy + 3:2 * n_copy + 3], refs[2 * n_copy + 3:2 * n_copy + 5])
    sems, q_s, m_s, l_s, acc_s = refs[2 * n_copy + 5:]
    b_, n_ = pl.program_id(0), pl.program_id(1)
    nb = pl.num_programs(1)
    step = b_ * nb + n_
    slot = step % 2
    rows, kvd = acc_s.shape
    qg = rows // n_kv

    def gather(b, n, slot):
        """Start the copies of block (b, n)'s tiles into ``slot``."""
        def one(t, carry):
            row = tok_ref[b, n * block + t]
            lead = page_ref[b, n * block + t] if paged else b
            at = pl.ds(pl.multiple_of(row - row % tile, tile), tile)
            srcs = [caches[0].at[lead, at], caches[1].at[lead, at]]
            if not scale_blocks:
                lanes = pl.ds(pl.multiple_of(row - row % chunk, chunk), chunk)
                srcs += [caches[2].at[lead, :, lanes],
                         caches[3].at[lead, :, lanes]]
            for a, (src, buf) in enumerate(zip(srcs, bufs)):
                pltpu.make_async_copy(src, buf.at[slot, t], sems.at[a, slot]
                                      ).start()
            return carry
        jax.lax.fori_loop(0, block, one, 0)

    @pl.when(step == 0)
    def _first():
        gather(b_, n_, slot)

    @pl.when(step + 1 < pl.num_programs(0) * nb)
    def _prefetch():
        gather((step + 1) // nb, (step + 1) % nb, 1 - slot)

    # row h·qg + i of the queries and of the output belongs to kv head h
    @pl.when(n_ == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)
        # queries block-diagonal: row h·qg + i in kv head h's lanes
        rep = (_iota((dh, kvd), 1) % dh == _iota((dh, kvd), 0))
        own = _iota((rows, kvd), 1) // dh == _iota((rows, kvd), 0) // qg
        q_s[...] = jnp.where(own, _dot(q_ref[0], rep.astype(jnp.float32)),
                             0.0)

    for a, buf in enumerate(bufs):          # one wait covers the T copies
        pltpu.make_async_copy(buf.at[slot], buf.at[slot],
                              sems.at[a, slot]).wait()

    # ---- pick each token's rows, 8 tokens at a time ------------------------
    def pick(c, cols):
        t0 = pl.multiple_of(c * _TILE, _TILE)
        tiles = [jnp.zeros((_TILE, p.shape[1]), jnp.float32) for p in picks]
        for i in range(_TILE):
            j = n_ * block + t0 + i
            row = tok_ref[b_, j]
            for a, buf in enumerate(bufs[:2]):
                x = _pick(_as_f32(buf[slot, t0 + i]), row % tile, 0)
                if a == 0 and has_ksel:
                    x = x * ksel_ref[b_, j]
                tiles[a] = jnp.where(_iota(tiles[a].shape, 0) == i, x,
                                     tiles[a])
            if cols:                        # scales copied here
                at = _iota(cols[0].shape, 1) == t0 + i
                cols = tuple(
                    jnp.where(at, _pick(_as_f32(buf[slot, t0 + i]),
                                        row % chunk, 1), col)
                    for buf, col in zip(bufs[2:], cols))
        for p, x in zip(picks, tiles):
            p[pl.ds(t0, _TILE), :] = x
        return cols
    if scale_blocks:
        jax.lax.fori_loop(0, block // _TILE, pick, ())
        scale_t, zero_t = (r[0, 0].astype(jnp.float32)
                           for r in (vs_ref, vz_ref))
    else:
        groups = bufs[2].shape[2]
        scale_t, zero_t = jax.lax.fori_loop(
            0, block // _TILE, pick,
            (jnp.zeros((groups, block), jnp.float32),) * 2)  # (G, T) each

    # ---- 1-2. reconstruct Kᵀ = U · latᵀ, RoPE each column ------------------
    kt = _dot(u_ref[...].astype(jnp.float32), picks[0][...],
              ((1,), (1,)))                                  # (kvd, T)
    if use_rope:
        kt = _rope_cols(kt, pos_ref[0, 0], freq_ref[...], n_kv, dh)

    # ---- 3. every head's logits in one matmul, masked per column -----------
    s = _dot(q_s[...], kt) * (dh ** -0.5)
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    s = jnp.where(vis_ref[0, 0] <= lim_ref[0], s, NEG_INF)   # (rows, T)

    # ---- 4. dequant values + online-softmax accumulate ---------------------
    m_prev = m_s[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - m_new))
    alpha = jnp.exp(m_prev - m_new)
    l_s[...] = l_s[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    v = _dequant(picks[1][...], scale_t, zero_t, kvd, v_bits=v_bits,
                 v_group=v_group)
    acc_s[...] = acc_s[...] * alpha + _dot(p, v)
    m_s[...] = m_new

    @pl.when(n_ == nb - 1)
    def _finish():
        m_ref[0] = m_s[...]
        l_ref[0] = l_s[...]
        # each row keeps its own head's channels, in channel order
        ch = _channel(_iota((rows, kvd), 1), kvd, v_bits)
        own = ch // dh == _iota((rows, kvd), 0) // qg
        sel = _channel(_iota((kvd, dh), 0), kvd, v_bits) % dh \
            == _iota((kvd, dh), 1)
        o_ref[0] = _dot(jnp.where(own, acc_s[...], 0.0),
                        sel.astype(jnp.float32))


def _recon_call(q, k_lat, k_scale, v_q, v_scale, v_zero, u, idx, valid,
                q_pos, *, page_table, page_size: int, n_kv: int,
                n_recent: int, v_bits: int, v_group: int, theta: float,
                softcap: float, use_rope: bool, pos_base):
    """Shared body of the four entry points.  q: (B, Q, H, dh) pre-RoPE
    queries; returns (m (B,Q,H), l (B,Q,H), o (B,Q,H,dh)) f32 partials."""
    b, ql, h, dh = q.shape
    kvd = n_kv * dh
    group = h // n_kv
    qg = ql * group
    rows = n_kv * qg
    nc = idx.shape[1]
    paged = page_table is not None
    has_ksel = k_scale is not None

    qpos = jnp.broadcast_to(jnp.asarray(q_pos, jnp.int32).reshape(-1), (b,))
    base = jnp.zeros((b,), jnp.int32) if pos_base is None \
        else jnp.broadcast_to(jnp.asarray(pos_base, jnp.int32), (b,))

    # the value group scales, sequence axis last: (B or pages, G, S or ps),
    # the layout XLA keeps them in, so the view is free
    scales = [jnp.swapaxes(x, 1, 2) for x in (v_scale, v_zero)]
    seq = page_size if paged else k_lat.shape[1]
    tile, chunk = math.gcd(seq, _TILE), _LANES
    # Mosaic copies no lane slice narrower than 128 (nor a whole lane axis
    # that 128 does not divide): there the selected tokens' scales are
    # gathered here and arrive as (G, T) blocks
    scale_blocks = seq % chunk != 0
    caches = (k_lat, v_q) if scale_blocks else (k_lat, v_q, *scales)
    block, nb = _block_size(nc, [(tile, x.shape[-1], x.dtype)
                                 for x in caches[:2]]
                            + [(x.shape[1], chunk, x.dtype)
                               for x in caches[2:]])
    pad = ((0, 0), (0, nb * block - nc))
    idx_i = jnp.pad(idx.astype(jnp.int32), pad)
    valid_i = jnp.pad(valid, pad)

    # where each token's row lives: (page,) row; its int8 latent scale
    if paged:
        mp = page_table.shape[1]
        lp = jnp.clip(idx_i // page_size, 0, mp - 1)       # invalid: clamp
        page = jnp.clip(jnp.take_along_axis(
            page_table.astype(jnp.int32), lp, axis=1), 0, k_lat.shape[0] - 1)
        row = jnp.clip(idx_i - lp * page_size, 0, page_size - 1)
        prefetch = [row, page]

        def sel(x):                 # the selected tokens' rows of x
            return x[page, row]
    else:
        row = jnp.clip(idx_i, 0, k_lat.shape[1] - 1)
        prefetch = [row]

        def sel(x):
            return jnp.take_along_axis(
                x, row.reshape(*row.shape, *(1,) * (x.ndim - 2)), axis=1)
    if has_ksel:
        prefetch.append(sel(k_scale).astype(jnp.float32))
    # (B, n_blocks, G, T): each block's scale columns
    scale_in = [sel(x).reshape(b, nb, block, -1).swapaxes(2, 3)
                for x in (v_scale, v_zero)] if scale_blocks else []

    # RoPE'd queries, row h·qg + t·group + j: query head h·group + j at
    # position t (the kernel spreads each row into its kv head's lanes)
    q32 = q.astype(jnp.float32)
    if use_rope:
        q32 = _rope(q32, qpos[:, None] + jnp.arange(ql, dtype=jnp.int32),
                    theta)
    q_rows = q32.reshape(b, ql, n_kv, group, dh).transpose(0, 2, 1, 3, 4) \
        .reshape(b, rows, dh)

    # per column: RoPE position, and the position a row must reach to see
    # it (_NEVER if invalid); per row: the last position it sees
    pos = base[:, None] + idx_i
    vis = jnp.where(valid_i, pos, _NEVER)
    if n_recent:
        t_of = jnp.arange(qg, dtype=jnp.int32) // group
        lim = qpos[:, None] + t_of - n_recent                 # (B, qg)
    else:
        lim = jnp.full((b, qg), _NEVER - 1, jnp.int32)
    lim = jnp.broadcast_to(lim[:, None], (b, n_kv, qg)).reshape(b, rows, 1)
    half = dh // 2
    freq = (1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
            ).reshape(half, 1)

    def per_row(shape):
        return pl.BlockSpec((1, *shape),
                            lambda b_, n_, *_: (b_,) + (0,) * len(shape))

    def per_block(rows, width):
        return pl.BlockSpec((1, 1, rows, width),
                            lambda b_, n_, *_: (b_, n_, 0, 0))

    def whole(shape):
        return pl.BlockSpec(shape, lambda *_: (0,) * len(shape))

    any_ = pl.BlockSpec(memory_space=pl.ANY)
    kernel = functools.partial(
        _recon_kernel, n_prefetch=len(prefetch), paged=paged,
        has_ksel=has_ksel, scale_blocks=scale_blocks, tile=tile, chunk=chunk,
        block=block, n_kv=n_kv, dh=dh, softcap=softcap, use_rope=use_rope,
        v_bits=v_bits, v_group=v_group)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(b, nb),
        in_specs=[per_row((rows, dh)), per_row((rows, 1)),
                  whole((half, 1)), per_block(1, block), per_block(1, block),
                  whole(u.shape)]
        + [per_block(*x.shape[2:]) for x in scale_in] + [any_] * len(caches),
        out_specs=[per_row((rows, 1)), per_row((rows, 1)),
                   per_row((rows, dh))],
        scratch_shapes=[pltpu.VMEM((2, block, tile, a.shape[-1]), a.dtype)
                        for a in caches[:2]]
        + [pltpu.VMEM((2, block, a.shape[1], chunk), a.dtype)
           for a in caches[2:]]
        + [pltpu.VMEM((block, a.shape[-1]), jnp.float32) for a in caches[:2]]
        + [pltpu.SemaphoreType.DMA((len(caches), 2)),
           pltpu.VMEM((rows, kvd), jnp.float32),
           pltpu.VMEM((rows, 1), jnp.float32),
           pltpu.VMEM((rows, 1), jnp.float32),
           pltpu.VMEM((rows, kvd), jnp.float32)],
    )
    blocks = lambda x: x.reshape(b, nb, 1, block)           # noqa: E731
    m, l, o = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, rows, 1), jnp.float32),
                   jax.ShapeDtypeStruct((b, rows, 1), jnp.float32),
                   jax.ShapeDtypeStruct((b, rows, dh), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret_mode(),
        name="sals_recon_attend_paged" if paged else "sals_recon_attend",
    )(*prefetch, q_rows, lim, freq, blocks(pos), blocks(vis), u, *scale_in,
      *caches)

    def heads(x):               # (B, n_kv·Q·group, ·) -> (B, Q, H, ·)
        x = x.reshape(b, n_kv, ql, group, -1).transpose(0, 2, 1, 3, 4)
        return x.reshape(b, ql, h, -1)

    return heads(m)[..., 0], heads(l)[..., 0], heads(o)


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
    ``page_table`` (B, max_pages) maps each selected token's page
    ``idx // ps`` to its physical page, which rides as a scalar-prefetch
    operand; the token's copies are the tile of 8 rows and the chunk of
    scales holding it in that page, as in the dense kernel (pages under
    128 rows: the scales arrive as blocks).  Bit-identical
    to the dense kernel given the same idx order (per-token math is
    unchanged).
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
    operands are physical page pools, ``idx`` stays logical, and each
    token's copies read its page through the page table.  Bit-identical
    to the dense windowed kernel given the same idx order."""
    return _recon_call(
        q, k_lat, k_scale, v_q, v_scale, v_zero, u, idx, valid, q_pos,
        page_table=page_table, page_size=page_size, n_kv=n_kv,
        n_recent=n_recent, v_bits=v_bits, v_group=v_group, theta=theta,
        softcap=softcap, use_rope=use_rope, pos_base=pos_base)
