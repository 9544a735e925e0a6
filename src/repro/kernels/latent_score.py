"""Pallas TPU kernels — SALS critical-token scoring (paper §4.3), fused.

Two kernels over the RAW latent cache (no host-side slice/pad/dequant copy —
the §4.5 traffic model's ``s·r*`` term is paid exactly once, streaming):

``latent_score_pallas``
    scores = K̃[:, :r*] · q̃[:r*] as one blocked matvec per batch row.  The
    leading r* columns of the (B, S, r) cache are read directly via BlockSpec
    — no ``k_lat[..., :r_star]`` copy, no pad copy; the ragged seq tail is
    masked in-kernel.  int8 latents are handled by a per-token scale
    multiply on the *scores* (the scale is per-token, so it commutes out of
    the r* contraction).

``latent_topk_pallas``
    The same streaming scores, plus the §4.3 selection fused in: the decode
    positions arrive as a per-batch-row (B,) scalar-prefetch operand (a
    scalar broadcasts — ragged continuous-batching rows each carry their own
    position), the sink/recent selectability mask is computed from an
    in-kernel iota, and each seq block is sorted by a bitonic
    compare-exchange network (log²(bs) fully vectorized stages) and emits
    its leading candidates.  The host-side ``jax.lax.top_k`` then runs over
    (B, nb·kc) candidates instead of (B, S).
    Per-block top-min(N_c, bs) is *exact*: a token in the global top-N_c has
    at most N_c-1 tokens above it, so at most N_c-1 in its own block.
    Candidate emission order (value desc, index asc; blocks in seq order)
    makes the final merge tie-break identically to a full-sequence
    ``lax.top_k`` — indices match the oracle bit-for-bit.

    TPU layout.  A block's scores live as a (rows, lanes) tile, flat
    position ``row·lanes + lane`` (lanes = 128 at real sizes, so a 1024
    block is one (8, 128) vreg).  Compare-exchange partners at distance j
    are fetched with lane rolls (j < lanes) or sublane rolls (j >= lanes)
    — no reshape crosses the lane/sublane boundary, which Mosaic refuses.
    A block emits its first ``ceil(min(N_c, bs) / lanes)`` sorted rows:
    a few more candidates than the exact minimum, all ranked below it, so
    the merge result is unchanged.

    ``pos_base`` (optional, (B,) int32, second scalar-prefetch operand)
    offsets the in-kernel selectability mask: row b's token j sits at
    global position ``pos_base[b] + j``.  This is what lets the SAME kernel
    score one group slab of a sequence-sharded cache (the grouped decode
    layout folds the group axis into the batch axis, or runs per shard
    under shard_map) — emitted indices stay slab-LOCAL.

    PAGED layout: with ``page_table`` ((B, max_pages) int32, an additional
    scalar-prefetch operand) + ``page_size``, ``k_lat`` is the physical
    page POOL ``(n_pages, page_size, r)`` and the kernel walks row b's
    pages IN LOGICAL ORDER through a third grid axis (pages per
    superblock): each step's BlockSpec index_map dereferences the table,
    DMA-ing one whole page; its scores land in the rows of a VMEM scratch
    tile until the superblock (= the dense kernel's seq block) is complete,
    then the SAME per-block extraction runs over it.  Paged selection is
    bit-for-bit the dense selection.

Validated on CPU via ``interpret=True`` against ``ref.latent_score_ref`` /
``ref.latent_topk_ref``; ``tests/test_tpu_compile.py`` compiles them for
v5e at Mistral widths.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode
from repro.kernels.ref import NEG_INF

DEFAULT_BLOCK_S = 1024
_LANES = 128


def _score_width(r_star: int, r: int) -> int:
    """Lane width of the latent block the score kernels read: the leading
    r* columns when r* fills whole 128-lane tiles, else all r columns (a
    TPU block's last dim must be a multiple of 128 or the full array)."""
    return r_star if r_star % _LANES == 0 else r


def _pow2_ceil(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


def _tile_lanes(bs: int, page_size: int = 0) -> int:
    """Lane width of a block's score tile.  Paged blocks need each page to
    fill whole tile rows (the lowest power of two dividing the page)."""
    if page_size:
        return min(_LANES, page_size & -page_size)
    return min(_LANES, _pow2_ceil(bs))


def topk_candidate_shape(s: int, n_critical: int,
                         block_s: int = DEFAULT_BLOCK_S,
                         page_size: int = 0) -> Tuple[int, int]:
    """(n_blocks, candidates_per_block) emitted by ``latent_topk_pallas``
    (or the paged twin, given ``page_size``).

    Exported so the traffic-model ledger (benchmarks/memory_access.py)
    stays in lockstep with the kernel's actual candidate count."""
    bs = min(block_s, s)
    lanes = _tile_lanes(bs, page_size)
    kr = -(-min(n_critical, bs) // lanes)
    return -(-s // bs), kr * lanes


def _bitonic_sort_desc(v: jnp.ndarray, ids: jnp.ndarray
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sort a (rows, lanes) tile by (value desc, id asc) in flat order.

    rows·lanes must be a power of two.  Every stage is a vectorized
    compare-exchange with the partner at flat distance j, fetched by a
    roll along lanes (j < lanes) or sublanes (j >= lanes)."""
    rows, lanes = v.shape
    n = rows * lanes
    flat = (jax.lax.broadcasted_iota(jnp.int32, v.shape, 0) * lanes
            + jax.lax.broadcasted_iota(jnp.int32, v.shape, 1))
    k = 2
    while k <= n:
        j = k // 2
        while j >= 1:
            if j < lanes:
                axis, d, ext = 1, j, lanes
            else:
                axis, d, ext = 0, j // lanes, rows
            lower = (flat & j) == 0
            # lower element's partner sits at +j, upper element's at -j
            pv = jnp.where(lower, pltpu.roll(v, ext - d, axis),
                           pltpu.roll(v, d, axis))
            pi = jnp.where(lower, pltpu.roll(ids, ext - d, axis),
                           pltpu.roll(ids, d, axis))
            own_first = (v > pv) | ((v == pv) & (ids < pi))
            partner_first = (pv > v) | ((pv == v) & (pi < ids))
            lower_first = (lower & own_first) | (~lower & partner_first)
            # k-blocks alternate direction; the final merge is descending
            keep = lower_first == ((flat & k) == 0)
            v = jnp.where(keep, v, pv)
            ids = jnp.where(keep, ids, pi)
            j //= 2
        k *= 2
    return v, ids


def _extract_block(scores, i, pos, base, *, bs: int, s: int, kr: int,
                   n_sink: int, n_recent: int):
    """Mask one (rows, lanes) score tile of seq block ``i`` and return its
    first ``kr`` sorted rows as (values, block-local ids).  Flat
    positions >= ``bs`` are padding (-inf: below every masked token)."""
    rows, lanes = scores.shape
    flat = (jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0) * lanes
            + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1))
    posn = i * bs + flat                                    # row-local
    pglob = posn + base                                     # global position
    ok = (pglob >= n_sink) & (pglob <= pos - n_recent) & (posn < s)
    scores = jnp.where(flat < bs, jnp.where(ok, scores, NEG_INF), -jnp.inf)
    vals, ids = _bitonic_sort_desc(scores, flat)
    return vals[:kr], ids[:kr]


def _row_scores(q, k, scale, lanes: int):
    """(1, lanes) scores of up to ``lanes`` latent rows ``k`` (n, r*);
    rows past n are -inf padding."""
    sc = jax.lax.dot_general(q, k.astype(jnp.float32),
                             (((1,), (1,)), ((), ())),
                             precision=jax.lax.Precision.HIGHEST,
                             preferred_element_type=jnp.float32)
    if scale is not None:
        # per-token scale commutes out of the r* contraction
        sc = sc * scale.astype(jnp.float32)
    n = k.shape[0]
    if n < lanes:
        sc = jnp.concatenate(
            [sc, jnp.full((1, lanes - n), -jnp.inf, jnp.float32)], axis=1)
    return sc


def _tile_scores(q, k_ref, scale_ref, n: int, rows: int, lanes: int):
    """Scores of the first ``n`` rows of latent block ``k_ref`` laid out as
    a (rows, lanes) tile — one (1, lanes) matvec per tile row, so the
    flat order never crosses lanes into sublanes."""
    r_star = q.shape[1]
    tile = jnp.full((rows, lanes), -jnp.inf, jnp.float32)
    row_id = jax.lax.broadcasted_iota(jnp.int32, (rows, lanes), 0)
    for a in range(rows):
        lo, hi = a * lanes, min(n, (a + 1) * lanes)
        if hi <= lo:
            break
        scale = None if scale_ref is None else scale_ref[0, :, lo:hi]
        sc = _row_scores(q, k_ref[0, lo:hi, :r_star], scale, lanes)
        tile = jnp.where(row_id == a, sc, tile)
    return tile


# ---------------------------------------------------------------------------
# plain scoring (dense (B, S) output — metrics / overlap benchmarks)
# ---------------------------------------------------------------------------

def _score_kernel(q_ref, k_ref, *rest, bs: int, s: int):
    scale_ref = rest[0] if len(rest) == 2 else None
    o_ref = rest[-1]
    i = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)                        # (1, r*)
    k = k_ref[0, :, :q.shape[1]]                            # (bs, r*)
    scale = None if scale_ref is None else scale_ref[0]
    scores = _row_scores(q, k, scale, bs)
    col = jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
    o_ref[0] = jnp.where(i * bs + col < s, scores, 0.0)


@functools.partial(jax.jit, static_argnames=("block_s",))
def latent_score_pallas(q_lat: jnp.ndarray, k_lat: jnp.ndarray,
                        k_scale: Optional[jnp.ndarray] = None,
                        block_s: int = DEFAULT_BLOCK_S) -> jnp.ndarray:
    """q_lat: (B, r*); k_lat: (B, S, r>=r*) raw latents (bf16/f32/int8);
    k_scale: (B, S) per-token scale for int8 latents, else None.
    Returns (B, S) f32 scores.  No (B, S, r*) host copy is made."""
    b, r_star = q_lat.shape
    s = k_lat.shape[1]
    bs = min(block_s, s)
    nb = pl.cdiv(s, bs)
    w = _score_width(r_star, k_lat.shape[2])
    in_specs = [
        pl.BlockSpec((1, 1, r_star), lambda b_, i: (b_, 0, 0)),
        pl.BlockSpec((1, bs, w), lambda b_, i: (b_, i, 0)),
    ]
    args = [q_lat.reshape(b, 1, r_star), k_lat]
    if k_scale is not None:
        in_specs.append(pl.BlockSpec((1, 1, bs), lambda b_, i: (b_, 0, i)))
        args.append(k_scale.reshape(b, 1, s))
    out = pl.pallas_call(
        functools.partial(_score_kernel, bs=bs, s=s),
        grid=(b, nb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, bs), lambda b_, i: (b_, 0, i)),
        out_shape=jax.ShapeDtypeStruct((b, 1, nb * bs), jnp.float32),
        interpret=interpret_mode(),
        name="sals_latent_score",
    )(*args)
    return out[:, 0, :s]


# ---------------------------------------------------------------------------
# fused scoring -> per-block sorted candidates (the decode hot path)
# ---------------------------------------------------------------------------

def _topk_kernel(pos_ref, base_ref, q_ref, k_ref, *rest, bs: int, s: int,
                 kr: int, rows: int, lanes: int, n_sink: int, n_recent: int):
    scale_ref = rest[0] if len(rest) == 3 else None
    vals_ref, idx_ref = rest[-2:]
    b_, i = pl.program_id(0), pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)                        # (1, r*)
    scores = _tile_scores(q, k_ref, scale_ref, bs, rows, lanes)
    vals, ids = _extract_block(scores, i, pos_ref[b_], base_ref[b_], bs=bs,
                               s=s, kr=kr, n_sink=n_sink, n_recent=n_recent)
    vals_ref[0, 0] = vals
    idx_ref[0, 0] = i * bs + ids


def _topk_paged_kernel(pt_ref, pos_ref, base_ref, q_ref, k_ref, *rest,
                       ppb: int, rpp: int, bs: int, s: int, kr: int,
                       rows: int, lanes: int, n_sink: int, n_recent: int):
    """Grid (B, n_superblocks, pages_per_superblock).  Step (b, i, j)
    scores ONE page (logical page i·ppb+j, physical page pt[b, ·]) into
    tile rows [j·rpp, (j+1)·rpp) of the scratch; the last page of a
    superblock runs the SAME extraction the dense kernel runs over its
    block — flat tile order == logical order, so candidates (values,
    indices, tie-breaks) are bit-identical to the dense layout."""
    scale_ref = rest[0] if len(rest) == 4 else None
    vals_ref, idx_ref, sc_ref = rest[-3:]
    b_, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    q = q_ref[0].astype(jnp.float32)                        # (1, r*)
    page = _tile_scores(q, k_ref, scale_ref, rpp * lanes, rpp, lanes)
    sc_ref[pl.ds(pl.multiple_of(j * rpp, rpp), rpp), :] = page

    @pl.when(j == ppb - 1)
    def _extract():
        scores = sc_ref[...]
        if ppb * rpp < rows:                 # non-power-of-two superblock
            scores = jnp.concatenate(
                [scores, jnp.full((rows - ppb * rpp, lanes), -jnp.inf,
                                  jnp.float32)], axis=0)
        vals, ids = _extract_block(scores, i, pos_ref[b_], base_ref[b_],
                                   bs=bs, s=s, kr=kr, n_sink=n_sink,
                                   n_recent=n_recent)
        vals_ref[0, 0] = vals
        idx_ref[0, 0] = i * bs + ids


def _merge_candidates(cand_v, cand_i, n_critical: int):
    """Final top-N_c over the (B, nb, kr, lanes) per-block candidates."""
    b = cand_v.shape[0]
    cand_v = cand_v.reshape(b, -1)
    cand_i = cand_i.reshape(b, -1)
    if cand_v.shape[1] < n_critical:         # tiny caches: pad the candidates
        pad = n_critical - cand_v.shape[1]
        cand_v = jnp.concatenate(
            [cand_v, jnp.full((b, pad), NEG_INF, jnp.float32)], axis=1)
        cand_i = jnp.concatenate(
            [cand_i, jnp.zeros((b, pad), jnp.int32)], axis=1)
    vals, top = jax.lax.top_k(cand_v, n_critical)
    idx = jnp.take_along_axis(cand_i, top, axis=1)
    return idx, vals > NEG_INF / 2


def _per_row(x, b: int):
    return jnp.broadcast_to(jnp.asarray(x, jnp.int32).reshape(-1), (b,))


@functools.partial(jax.jit, static_argnames=("n_critical", "n_sink",
                                             "n_recent", "block_s",
                                             "page_size"))
def latent_topk_paged_pallas(q_lat: jnp.ndarray, k_lat: jnp.ndarray,
                             k_scale: Optional[jnp.ndarray], pos, *,
                             page_table: jnp.ndarray, page_size: int,
                             n_critical: int, n_sink: int, n_recent: int,
                             block_s: int = DEFAULT_BLOCK_S,
                             pos_base: Optional[jnp.ndarray] = None
                             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Paged twin of :func:`latent_topk_pallas`.

    q_lat: (B, r*); k_lat: (n_pages, page_size, r) physical page POOL
    (k_scale: (n_pages, page_size) or None); page_table: (B, max_pages)
    int32 — an additional scalar-prefetch operand whose rows map logical to
    physical pages (unmapped entries may hold anything: the per-row
    position mask keeps garbage pages unselectable).  The logical sequence
    extent is ``max_pages · page_size``.  Returns (idx, valid) with idx in
    LOGICAL positions — bit-identical to the dense kernel on the same
    logical contents.
    """
    b, r_star = q_lat.shape
    ps = page_size
    mp = page_table.shape[1]
    s = mp * ps
    bs = min(block_s, s)
    if bs % ps:
        raise ValueError(f"superblock {bs} must be a multiple of page_size "
                         f"{ps} (page_size must divide "
                         f"min(block_s={block_s}, max_seq={s}))")
    ppb = bs // ps
    lanes = _tile_lanes(bs, ps)
    rpp = ps // lanes
    rows = _pow2_ceil(bs) // lanes
    nb, kc = topk_candidate_shape(s, n_critical, block_s, ps)
    kr = kc // lanes
    n_pages = k_lat.shape[0]

    def page_of(b_, i, j, pt_):
        # clamp: the rectangular grid may run past the table on a ragged
        # last superblock; those positions are masked (posn >= s)
        lp = jnp.minimum(i * ppb + j, mp - 1)
        return jnp.clip(pt_[b_, lp], 0, n_pages - 1)

    w = _score_width(r_star, k_lat.shape[2])
    in_specs = [
        pl.BlockSpec((1, 1, r_star), lambda b_, i, j, pt_, p, bb: (b_, 0, 0)),
        pl.BlockSpec((1, ps, w),
                     lambda b_, i, j, pt_, p, bb: (page_of(b_, i, j, pt_),
                                                   0, 0)),
    ]
    args = [q_lat.reshape(b, 1, r_star), k_lat]
    if k_scale is not None:
        in_specs.append(pl.BlockSpec(
            (1, 1, ps), lambda b_, i, j, pt_, p, bb: (page_of(b_, i, j, pt_),
                                                      0, 0)))
        args.append(k_scale.reshape(n_pages, 1, ps))
    kernel = functools.partial(
        _topk_paged_kernel, ppb=ppb, rpp=rpp, bs=bs, s=s, kr=kr, rows=rows,
        lanes=lanes, n_sink=n_sink, n_recent=n_recent)
    out_spec = pl.BlockSpec((1, 1, kr, lanes),
                            lambda b_, i, j, pt_, p, bb: (b_, i, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, nb, ppb),
        in_specs=in_specs,
        out_specs=[out_spec, out_spec],
        scratch_shapes=[pltpu.VMEM((ppb * rpp, lanes), jnp.float32)],
    )
    cand_v, cand_i = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, nb, kr, lanes), jnp.float32),
            jax.ShapeDtypeStruct((b, nb, kr, lanes), jnp.int32),
        ],
        interpret=interpret_mode(),
        name="sals_latent_topk_paged",
    )(page_table.astype(jnp.int32), _per_row(pos, b),
      _per_row(0 if pos_base is None else pos_base, b), *args)
    return _merge_candidates(cand_v, cand_i, n_critical)


@functools.partial(jax.jit, static_argnames=("n_critical", "n_sink",
                                             "n_recent", "block_s"))
def latent_topk_pallas(q_lat: jnp.ndarray, k_lat: jnp.ndarray,
                       k_scale: Optional[jnp.ndarray], pos, *,
                       n_critical: int, n_sink: int, n_recent: int,
                       block_s: int = DEFAULT_BLOCK_S,
                       pos_base: Optional[jnp.ndarray] = None
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused §4.3 scoring + selection over the raw latent cache.

    q_lat: (B, r*); k_lat: (B, S, r); k_scale: (B, S) or None; pos: traced
    decode position — scalar, or (B,) per-row positions (ragged continuous
    batching: each batch row masks against its own position); pos_base:
    (B,) per-row global offset of column 0 (grouped layout), or None for 0.
    Returns (idx (B, N_c) int32 row-LOCAL, valid (B, N_c) bool) — identical
    (incl. tie-breaks) to masking + full-seq lax.top_k.
    """
    b, r_star = q_lat.shape
    s = k_lat.shape[1]
    bs = min(block_s, s)
    lanes = _tile_lanes(bs)
    rows = _pow2_ceil(bs) // lanes
    nb, kc = topk_candidate_shape(s, n_critical, block_s)
    kr = kc // lanes

    w = _score_width(r_star, k_lat.shape[2])
    in_specs = [
        pl.BlockSpec((1, 1, r_star), lambda b_, i, p, bb: (b_, 0, 0)),
        pl.BlockSpec((1, bs, w), lambda b_, i, p, bb: (b_, i, 0)),
    ]
    args = [q_lat.reshape(b, 1, r_star), k_lat]
    if k_scale is not None:
        in_specs.append(pl.BlockSpec((1, 1, bs),
                                     lambda b_, i, p, bb: (b_, 0, i)))
        args.append(k_scale.reshape(b, 1, s))
    kernel = functools.partial(_topk_kernel, bs=bs, s=s, kr=kr, rows=rows,
                               lanes=lanes, n_sink=n_sink, n_recent=n_recent)
    out_spec = pl.BlockSpec((1, 1, kr, lanes),
                            lambda b_, i, p, bb: (b_, i, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, nb),
        in_specs=in_specs,
        out_specs=[out_spec, out_spec],
    )
    cand_v, cand_i = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, nb, kr, lanes), jnp.float32),
            jax.ShapeDtypeStruct((b, nb, kr, lanes), jnp.int32),
        ],
        interpret=interpret_mode(),
        name="sals_latent_topk",
    )(_per_row(pos, b), _per_row(0 if pos_base is None else pos_base, b),
      *args)
    return _merge_candidates(cand_v, cand_i, n_critical)
