"""Typed latent KV cache (paper §4.2 + §5.1 mixed-precision scheme).

:class:`LatentKVCache` is a registered-pytree dataclass — the decode cache is
a first-class object rather than a bag of arrays.  Per SALS layer it stores,
for every position:
  * ``k_lat``   — pre-RoPE keys projected to the r-dim latent space
                  (bf16, or int8 + per-token ``k_scale`` under the
                  beyond-paper latent quant),
  * ``v_q``     — channel-group-quantized values (+ per-group scale/zero),
and two small full-precision regions that are *always* attended:
  * ``sink_k/v``   — the first ``n_sink`` tokens (pre-RoPE K),
  * ``recent_k/v`` — ring buffer of the last ``n_recent`` tokens (pre-RoPE K),
                     slot = position % n_recent.

Sink/recent tokens also exist in the latent arrays (written once, never
selected — the scoring mask excludes their ranges) so a token sliding out of
the recent ring becomes selectable without any copying.

The batch axis is a SLOT ARENA for continuous batching: ``lengths`` ([L,] B)
counts the tokens written per slot, writes take per-row (B,) positions
(ragged decode — every kernel masks per row), and
:meth:`prefill_into_slot` / :meth:`free_slot` replace one slot's row in
place so a finished sequence's slot is reusable by a joining request
without recompiling (same array shapes, same HLO).

Layout metadata rides with the arrays as static pytree aux data:

  ``n_groups``   — decode selection layout.  1 = paper-faithful global
                   top-k; >1 = per-group top-(N_c/G) + LSE merge, with the
                   group axis matching the ``shard_axis`` sharding.
  ``shard_axis`` — the logical axis name the sequence dimension is sharded
                   over (see distributed/sharding.py).
  ``page_size``  — 0 = dense slot arena (above); > 0 = PAGED layout
                   (ISSUE 5): the five per-token fields are physical page
                   POOLS shaped ``([L,] n_pages, page_size, ·)`` shared by
                   every sequence, and ``page_table`` ``([L,] B,
                   max_pages)`` int32 maps row b's logical page j to its
                   physical page (same page id in every layer's pool — one
                   host-side allocator, ``core/pager.py``).  Token t of row
                   b lives at pool row ``(page_table[b, t // ps], t % ps)``;
                   both Pallas kernels take the table as a scalar-prefetch
                   operand and dereference it in their index maps, so the
                   paged hot path still never materializes a dense
                   ``(B, S, ·)`` gather.  The sink/recent window and
                   ``lengths`` stay slot-resident (fixed per-RESIDENT
                   bytes, not per token — the capacity model counts them
                   as such).  Unmapped table entries are 0: kernels mask
                   by per-row position, so a garbage page read is never
                   selectable.

TWO-TIER paged layout (ISSUE 7, ``core/tiering.py``): when the payload
pools are smaller than the logical pool (``hbm_pages`` device slots for
``n_pages`` live pages), three extra arrays appear:

  ``k_score``        — ``([L,] n_pages, ps, r*)`` device pool holding the
                       leading ``r*`` latent columns of EVERY live page
                       (k_lat's dtype).  The score kernel reads THIS pool
                       through ``page_table`` — identical bytes to the
                       untiered ``k_lat[..., :r*]`` slice, so selection is
                       bit-equal and completely oblivious to tiering.
  ``k_scale_score``  — ``([L,] n_pages, ps)`` per-token int8 scale twin
                       (int8 latents only; the SAME scale as ``k_scale`` —
                       quantization happens once in the write path).
  ``hot_table``      — ``([L,] B, max_pages)`` int32 mapping row b's
                       logical page j to its HBM payload SLOT (0 = cold /
                       unmapped → the trash slot).  The reconstruct kernel
                       takes this table instead of ``page_table`` — same
                       kernel, different scalar-prefetch operand; the
                       scheduler guarantees every page it can select is
                       hot before the step commits (fetch-and-rerun).

All arrays carry a leading layer axis L when built by :meth:`init` so the
decode loop can ``lax.scan`` over layers (batch axis 1, sequence axis 2);
:meth:`layer_view` / the scan slice drop L for single-layer use.  ``ssm``
optionally carries the hybrid family's recurrent state alongside (it scans
with the same leading axis).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig, SALSConfig
from repro.core import quantization as qz
from repro.core.projection import to_latent

_PER_TOKEN_FIELDS = ("k_lat", "k_scale", "v_q", "v_scale", "v_zero")


@dataclasses.dataclass
class LatentKVCache:
    """One SALS cache (a layer stack, one layer, or a grouped view)."""

    k_lat: jnp.ndarray                    # ([L,] B, S, r) bf16 | int8
    v_q: jnp.ndarray                      # ([L,] B, S, code_w)
    v_scale: jnp.ndarray                  # ([L,] B, S, G)
    v_zero: jnp.ndarray                   # ([L,] B, S, G)
    sink_k: jnp.ndarray                   # ([L,] B, n_sink, Hkv, dh)
    sink_v: jnp.ndarray
    recent_k: jnp.ndarray                 # ([L,] B, n_recent, Hkv, dh)
    recent_v: jnp.ndarray
    k_scale: Optional[jnp.ndarray] = None  # ([L,] B, S) int8-latent scale
    ssm: Any = None                        # hybrid-family recurrent state
    lengths: Optional[jnp.ndarray] = None  # ([L,] B) int32 tokens per slot
    page_table: Optional[jnp.ndarray] = None  # ([L,] B, max_pages) int32
    k_score: Optional[jnp.ndarray] = None  # ([L,] n_pages, ps, r*) tiered
    k_scale_score: Optional[jnp.ndarray] = None  # ([L,] n_pages, ps)
    hot_table: Optional[jnp.ndarray] = None  # ([L,] B, max_pages) int32
    # --- static layout metadata (pytree aux data) --------------------------
    n_groups: int = 1
    shard_axis: str = "kv_seq"
    page_size: int = 0                     # 0 = dense; >0 = paged pools

    @property
    def paged(self) -> bool:
        return self.page_size > 0

    @property
    def tiered(self) -> bool:
        """Two-tier paged layout: payload pools are HBM slots addressed
        through ``hot_table``; scores live in the full-size ``k_score``."""
        return self.hot_table is not None

    # ------------------------------------------------------------------ init

    @classmethod
    def init(cls, cfg: ModelConfig, sals: SALSConfig, n_layers: int,
             batch: int, max_seq: int, dtype=jnp.bfloat16,
             n_groups: int = 1) -> "LatentKVCache":
        """Zero-initialized cache with a leading layer axis."""
        if n_groups > 1 and max_seq % n_groups:
            raise ValueError(f"max_seq {max_seq} must be divisible by "
                             f"n_groups {n_groups}")
        kvd = cfg.kv_dim
        r = sals.rank(kvd)
        w = sals.n_recent
        groups = kvd // sals.v_group
        code_w = qz.quant_channels(kvd, sals.v_bits)
        code_dtype = jnp.int8 if sals.v_bits == 8 else jnp.uint8
        win = (n_layers, batch, sals.n_sink, cfg.n_kv_heads, cfg.head_dim)
        ring = (n_layers, batch, w, cfg.n_kv_heads, cfg.head_dim)
        if sals.k_latent_dtype == "int8":
            k_lat = jnp.zeros((n_layers, batch, max_seq, r), jnp.int8)
            k_scale = jnp.zeros((n_layers, batch, max_seq), qz.SCALE_DTYPE)
        else:
            k_lat = jnp.zeros((n_layers, batch, max_seq, r), dtype)
            k_scale = None
        return cls(
            k_lat=k_lat, k_scale=k_scale,
            v_q=jnp.zeros((n_layers, batch, max_seq, code_w), code_dtype),
            v_scale=jnp.zeros((n_layers, batch, max_seq, groups),
                              qz.SCALE_DTYPE),
            v_zero=jnp.zeros((n_layers, batch, max_seq, groups),
                             qz.SCALE_DTYPE),
            sink_k=jnp.zeros(win, dtype), sink_v=jnp.zeros(win, dtype),
            recent_k=jnp.zeros(ring, dtype), recent_v=jnp.zeros(ring, dtype),
            lengths=jnp.zeros((n_layers, batch), jnp.int32),
            n_groups=n_groups,
        )

    @classmethod
    def init_paged(cls, cfg: ModelConfig, sals: SALSConfig, n_layers: int,
                   batch: int, max_seq: int, n_pages: int, page_size: int,
                   dtype=jnp.bfloat16, n_groups: int = 1,
                   hbm_pages: int = 0) -> "LatentKVCache":
        """Zero-initialized PAGED cache: per-token fields are page pools.

        ``n_pages`` physical pages of ``page_size`` tokens back every
        sequence; ``max_seq`` only sizes the per-row page TABLE
        (``max_seq // page_size`` entries).  The host-side allocator
        (``core/pager.PagePool``) owns which pages are live — this method
        just shapes the device arrays.

        ``hbm_pages`` > 0 builds the TWO-TIER layout (ISSUE 7): the
        payload pools shrink to ``hbm_pages + 1`` device slots (slot 0 =
        trash, mirroring physical page 0), a full-size ``k_score``
        (+ ``k_scale_score``) pool keeps every live page's leading ``r*``
        score columns HBM-resident, and ``hot_table`` maps logical pages
        to payload slots (0 = cold).
        """
        if max_seq % page_size:
            raise ValueError(f"max_seq {max_seq} must be a multiple of "
                             f"page_size {page_size}")
        if n_pages * page_size < max_seq:
            raise ValueError(f"pool {n_pages}×{page_size} cannot hold one "
                             f"max_seq {max_seq} sequence")
        if n_groups > 1 and (max_seq // page_size) % n_groups:
            raise ValueError(f"pages per sequence {max_seq // page_size} "
                             f"must be divisible by n_groups {n_groups} "
                             "(the grouped fold splits the page table)")
        if hbm_pages and hbm_pages + 1 > n_pages:
            raise ValueError(f"hbm_pages {hbm_pages} exceeds the pool "
                             f"({n_pages} incl. trash)")
        dense = cls.init(cfg, sals, n_layers, 1, page_size, dtype,
                         n_groups=1)          # template: 1 page of rows
        payload_pages = (hbm_pages + 1) if hbm_pages else n_pages
        out = {}
        for name in _PER_TOKEN_FIELDS:
            a = getattr(dense, name)
            if a is None:
                out[name] = None
                continue
            # (L, 1, ps, ·) template -> (L, n_pages, ps, ·) pool
            out[name] = jnp.zeros((n_layers, payload_pages, *a.shape[2:]),
                                  a.dtype)
        if hbm_pages:
            r_star = sals.score_rank(cfg.kv_dim)
            out["k_score"] = jnp.zeros(
                (n_layers, n_pages, page_size, r_star), dense.k_lat.dtype)
            if dense.k_scale is not None:
                out["k_scale_score"] = jnp.zeros(
                    (n_layers, n_pages, page_size), dense.k_scale.dtype)
            out["hot_table"] = jnp.zeros(
                (n_layers, batch, max_seq // page_size), jnp.int32)
        win = (n_layers, batch, sals.n_sink, cfg.n_kv_heads, cfg.head_dim)
        ring = (n_layers, batch, sals.n_recent, cfg.n_kv_heads, cfg.head_dim)
        return cls(
            **out,
            sink_k=jnp.zeros(win, dtype), sink_v=jnp.zeros(win, dtype),
            recent_k=jnp.zeros(ring, dtype), recent_v=jnp.zeros(ring, dtype),
            lengths=jnp.zeros((n_layers, batch), jnp.int32),
            page_table=jnp.zeros((n_layers, batch, max_seq // page_size),
                                 jnp.int32),
            n_groups=n_groups, page_size=page_size,
        )

    @classmethod
    def prefill_layer(cls, cfg: ModelConfig, sals: SALSConfig,
                      u: jnp.ndarray, k_pre: jnp.ndarray, v: jnp.ndarray,
                      max_seq: int, dtype=jnp.bfloat16,
                      n_groups: int = 1,
                      lengths: Optional[jnp.ndarray] = None
                      ) -> "LatentKVCache":
        """Build ONE layer's cache (no leading L axis) from prefill tensors.

        k_pre/v: (B, S, n_kv, dh) pre-RoPE keys / values, S <= max_seq.
        ``lengths`` (B,) int32: per-row true prompt lengths for RIGHT-padded
        ragged batches — the sink/recent windows are filled from each row's
        own real positions (pad-position latents land in the arrays but the
        per-row decode position keeps them forever unselectable).  None
        means every row is exactly ``s`` tokens.
        """
        if n_groups > 1 and max_seq % n_groups:
            raise ValueError(f"max_seq {max_seq} must be divisible by "
                             f"n_groups {n_groups}")
        b, s = k_pre.shape[:2]
        kvd = cfg.kv_dim
        k_flat = k_pre.reshape(b, s, kvd)
        v_flat = v.reshape(b, s, kvd)
        lat = to_latent(u.astype(jnp.float32), k_flat)           # (B,S,r)
        vq = qz.quantize(v_flat, sals.v_bits, sals.v_group)

        def pad(x):
            if s == max_seq:
                return x
            cfgp = [(0, 0), (0, max_seq - s)] + [(0, 0)] * (x.ndim - 2)
            return jnp.pad(x, cfgp)

        w = sals.n_recent
        ns = sals.n_sink
        if lengths is None:
            len_v = jnp.full((b,), s, jnp.int32)
            # ring layout: slot = position % w for the last min(s, w) positions
            n_tail = min(s, w)
            tail_pos = jnp.arange(s - n_tail, s)
            slots = tail_pos % w
            rk = jnp.zeros((b, w, cfg.n_kv_heads, cfg.head_dim), dtype)
            rv = jnp.zeros_like(rk)
            rk = rk.at[:, slots].set(k_pre[:, s - n_tail:].astype(dtype))
            rv = rv.at[:, slots].set(v[:, s - n_tail:].astype(dtype))

            sk = jnp.zeros((b, ns, cfg.n_kv_heads, cfg.head_dim), dtype)
            sv = jnp.zeros_like(sk)
            n_head = min(s, ns)
            sk = sk.at[:, :n_head].set(k_pre[:, :n_head].astype(dtype))
            sv = sv.at[:, :n_head].set(v[:, :n_head].astype(dtype))
        else:
            len_v = jnp.asarray(lengths, jnp.int32)
            # ragged ring: slot j of row b holds that row's own position
            # p = last - (last - j) % w (last = len-1); p < 0 -> empty slot
            last = (len_v - 1)[:, None]                          # (B, 1)
            p = last - (last - jnp.arange(w)[None, :]) % w       # (B, w)
            ring_ok = p >= 0
            pc = jnp.clip(p, 0, s - 1)[..., None, None]
            rk = jnp.where(ring_ok[..., None, None],
                           jnp.take_along_axis(k_pre, pc, axis=1), 0) \
                .astype(dtype)
            rv = jnp.where(ring_ok[..., None, None],
                           jnp.take_along_axis(v, pc, axis=1), 0) \
                .astype(dtype)
            # ragged sink: first min(len, n_sink) real positions per row
            n_head = min(s, ns)
            sink_ok = (jnp.arange(ns)[None, :] < len_v[:, None]) \
                & (jnp.arange(ns)[None, :] < n_head)
            sk = jnp.zeros((b, ns, cfg.n_kv_heads, cfg.head_dim), dtype)
            sv = jnp.zeros_like(sk)
            sk = sk.at[:, :n_head].set(k_pre[:, :n_head].astype(dtype))
            sv = sv.at[:, :n_head].set(v[:, :n_head].astype(dtype))
            sk = jnp.where(sink_ok[..., None, None], sk, 0)
            sv = jnp.where(sink_ok[..., None, None], sv, 0)

        if sals.k_latent_dtype == "int8":
            q, scale = qz.quantize_latent_int8(lat)
            k_lat = pad(q)
            k_scale = pad(scale.astype(qz.SCALE_DTYPE))
        else:
            k_lat, k_scale = pad(lat.astype(dtype)), None
        return cls(
            k_lat=k_lat, k_scale=k_scale,
            v_q=pad(vq["q"]), v_scale=pad(vq["scale"]),
            v_zero=pad(vq["zero"]),
            sink_k=sk, sink_v=sv, recent_k=rk, recent_v=rv,
            lengths=len_v,
            n_groups=n_groups,
        )

    # ----------------------------------------------------------------- views

    def replace(self, **kw) -> "LatentKVCache":
        return dataclasses.replace(self, **kw)

    def layer_view(self, l) -> "LatentKVCache":
        """Drop the leading layer axis: cache for layer ``l``."""
        return jax.tree.map(lambda a: a[l], self)

    def group_view(self, g: Optional[int] = None) -> "LatentKVCache":
        """Seq axis of the per-token arrays reshaped to (B, G, S/G, ...).

        ORACLE/TEST view — the fused decode path never materializes it; the
        grouped kernels index group slabs of the flat arrays directly.
        Only valid on a single-layer view (use :meth:`layer_view` first).
        """
        if self.paged:
            raise ValueError("group_view is a dense-layout oracle; the "
                             "paged grouped fold reshapes the page TABLE, "
                             "not the pools (see sparse_attention)")
        if self.k_lat.ndim != 3:
            raise ValueError("group_view needs a single-layer cache "
                             f"(B, S, r); got k_lat {self.k_lat.shape} — "
                             "take layer_view(l) first")
        g = g or self.n_groups
        out = {}
        for name in _PER_TOKEN_FIELDS:
            a = getattr(self, name)
            if a is None:
                continue
            b, s = a.shape[:2]
            out[name] = a.reshape(b, g, s // g, *a.shape[2:])
        return self.replace(**out)

    def latent_views(self) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
        """Raw quantized latent views for the fused decode kernels.

        Returns (k_lat (B, S, r) — bf16 or int8, exactly as stored — and
        k_scale (B, S) or None).  The hot path hands these straight to
        ops.latent_topk / ops.sparse_recon_attention, which index them
        in-kernel; no dequantized or gathered copy is materialized.
        """
        return self.k_lat, self.k_scale

    # ---------------------------------------------------------------- writes

    def write(self, sals: SALSConfig, pos, k_lat: jnp.ndarray,
              v_flat: jnp.ndarray, k_pre: jnp.ndarray, v: jnp.ndarray
              ) -> "LatentKVCache":
        """Append one token everywhere: latent K + quantized V at ``pos``,
        plus the full-precision recent ring / sink insert.

        k_lat: (B, r) pre-RoPE latent keys; v_flat: (B, kv_dim);
        k_pre/v: (B, n_kv, dh).  ``pos`` is a traced scalar or (B,) per-row
        positions (ragged continuous batching: each slot appends at its own
        position).
        """
        return self.write_latents(sals, pos, k_lat, v_flat) \
                   .write_ring(sals, pos, k_pre, v)

    def write_window(self, sals: SALSConfig, pos, k_lat: jnp.ndarray,
                     v_flat: jnp.ndarray, k_pre: jnp.ndarray, v: jnp.ndarray,
                     n_accept) -> "LatentKVCache":
        """Commit the ACCEPTED prefix of a speculative verify window.

        k_lat: (B, Q, r) pre-RoPE latent keys; v_flat: (B, Q, kv_dim);
        k_pre/v: (B, Q, n_kv, dh) — the window K/V returned by the
        read-only windowed attend.  ``pos`` (scalar or (B,)) is the WINDOW
        BASE: slot t lands at position pos + t iff ``t < n_accept[b]``
        ((B,) per-row accepted counts).  Rejected draft positions are
        NEVER written — their scatters redirect out of range and drop —
        so the cache bytes are bit-identical to sequentially appending
        exactly the accepted tokens.  One unrolled masked append per
        window slot (Q <= 8: Q small static writes, one compiled HLO).
        """
        cache = self
        b, q = k_lat.shape[:2]
        pos_v = _row_positions(pos, b)
        n_acc = jnp.broadcast_to(
            jnp.asarray(n_accept, jnp.int32).reshape(-1), (b,))
        for t in range(q):
            keep = t < n_acc
            cache = cache.write_latents(sals, pos_v + t, k_lat[:, t],
                                        v_flat[:, t], keep=keep) \
                         .write_ring(sals, pos_v + t, k_pre[:, t], v[:, t],
                                     keep=keep)
        return cache

    def write_latents(self, sals: SALSConfig, pos, k_lat: jnp.ndarray,
                      v_flat: jnp.ndarray,
                      keep: Optional[jnp.ndarray] = None) -> "LatentKVCache":
        """Write one token's latent K + quantized V at ``pos`` (scalar or
        (B,) per-row; no ring update — see :meth:`write_ring`).

        ``keep`` (B,) bool masks the write per row (speculative window
        commits): a masked-out row's scatter index moves out of range and
        the update DROPS (``mode="drop"``), leaving the row untouched.
        """
        pos_v = _row_positions(pos, k_lat.shape[0])
        upd_score = None
        if self.paged:
            # logical pos -> (physical page, in-page row); the page MUST
            # already be mapped (the scheduler reserves pages ahead of the
            # decode step — see RequestScheduler._ensure_pages)
            lp = (pos_v // self.page_size)[:, None]              # (B, 1)
            pid = jnp.take_along_axis(self.page_table, lp, axis=1)[:, 0]
            row = pos_v % self.page_size
            if keep is not None:
                row = jnp.where(keep, row, self.page_size)       # OOB -> drop
            if self.tiered:
                # payloads land in the HOT SLOT (the scheduler pins each
                # row's write page hot, so slot > 0 whenever pos is real);
                # scores land in the full-size pool at the physical page
                slot = jnp.take_along_axis(self.hot_table, lp, axis=1)[:, 0]
                upd = lambda arr, val: \
                    arr.at[slot, row].set(val.astype(arr.dtype), mode="drop")
                upd_score = lambda arr, val: \
                    arr.at[pid, row].set(val.astype(arr.dtype), mode="drop")
            else:
                upd = lambda arr, val: \
                    arr.at[pid, row].set(val.astype(arr.dtype), mode="drop")
        else:
            wpos = pos_v if keep is None \
                else jnp.where(keep, pos_v, self.k_lat.shape[1])
            upd = lambda arr, val: _upd_rows(arr, val, wpos)
        out = {}
        if sals.k_latent_dtype == "int8":
            # quantize ONCE; the score pool gets the leading r* columns of
            # the SAME int8 rows + the SAME per-token scale, so the tiered
            # score pass is bit-identical to the untiered [..., :r*] read
            q, scale = qz.quantize_latent_int8(k_lat)
            out["k_lat"] = upd(self.k_lat, q)
            out["k_scale"] = upd(self.k_scale, scale)
            if upd_score is not None:
                r_star = self.k_score.shape[-1]
                out["k_score"] = upd_score(self.k_score, q[..., :r_star])
                out["k_scale_score"] = upd_score(self.k_scale_score, scale)
        else:
            out["k_lat"] = upd(self.k_lat, k_lat)
            if upd_score is not None:
                r_star = self.k_score.shape[-1]
                out["k_score"] = upd_score(self.k_score,
                                           k_lat[..., :r_star])
        vq = qz.quantize(v_flat, sals.v_bits, sals.v_group)
        out["v_q"] = upd(self.v_q, vq["q"])
        # the group scales are G = kvd / v_group lanes wide and XLA keeps
        # them with the sequence axis in lanes; a masked write leaves them
        # so, where a row scatter moves the whole array to rows and back
        put = upd if self.paged else lambda arr, val: _put_rows(arr, val,
                                                                  wpos)
        out["v_scale"] = put(self.v_scale, vq["scale"])
        out["v_zero"] = put(self.v_zero, vq["zero"])
        if self.lengths is not None:
            adv = pos_v + 1 if keep is None else \
                jnp.where(keep, pos_v + 1, 0)
            out["lengths"] = jnp.maximum(self.lengths, adv)
        return self.replace(**out)

    def append_chunk(self, cfg: ModelConfig, sals: SALSConfig,
                     u: jnp.ndarray, off, k_pre: jnp.ndarray, v: jnp.ndarray,
                     lengths: jnp.ndarray) -> "LatentKVCache":
        """Append one CHUNK of prompt tokens at positions [off, off+C).

        The chunked-prefill write path (single-layer view): ``k_pre``/``v``
        are (B, C, n_kv, dh) pre-RoPE keys / values of the chunk, ``off`` is
        a traced scalar (chunks land at the same offset for every row — the
        ragged batch is right-padded), and ``lengths`` (B,) holds each
        row's TRUE prompt length.

        Latent-K + quantized-V writes cover every chunk position, pad
        positions included — byte parity with :meth:`prefill_layer`, and the
        per-slot lengths keep pads forever unselectable.  Ring/sink inserts
        are masked to each row's REAL positions: an unmasked pad write at
        position p >= lengths[b] could evict a real token from ring slot
        p % n_recent.  Per-slot ``lengths`` advance to min(lengths, off+C).
        """
        if self.paged:
            raise ValueError("append_chunk writes a DENSE single-request "
                             "prefill cache; paged admission scatters its "
                             "pages afterwards (ServeEngine._admit_paged)")
        b, c = k_pre.shape[:2]
        kvd = cfg.kv_dim
        len_v = jnp.asarray(lengths, jnp.int32)
        lat = to_latent(u.astype(jnp.float32), k_pre.reshape(b, c, kvd))
        vq = qz.quantize(v.reshape(b, c, kvd), sals.v_bits, sals.v_group)

        def put(arr, val):
            return jax.lax.dynamic_update_slice_in_dim(
                arr, val.astype(arr.dtype), off, axis=1)

        out = {}
        if sals.k_latent_dtype == "int8":
            q8, scale = qz.quantize_latent_int8(lat)
            out["k_lat"] = put(self.k_lat, q8)
            out["k_scale"] = put(self.k_scale, scale)
        else:
            out["k_lat"] = put(self.k_lat, lat)
        out["v_q"] = put(self.v_q, vq["q"])
        out["v_scale"] = put(self.v_scale, vq["scale"])
        out["v_zero"] = put(self.v_zero, vq["zero"])

        # ragged ring: slot j receives the LAST real chunk position p ≡ j
        # (mod w); p outside [off, min(len, off+C)) leaves the slot alone
        # (earlier chunks' tokens stay resident until genuinely evicted)
        w = sals.n_recent
        last = jnp.minimum(len_v, off + c)[:, None] - 1          # (B, 1)
        p = last - (last - jnp.arange(w)[None, :]) % w           # (B, w)
        ring_ok = (p >= off) & (len_v[:, None] > off)
        pc = jnp.clip(p - off, 0, c - 1)[..., None, None]
        rk = jnp.take_along_axis(k_pre, pc, axis=1)
        rv = jnp.take_along_axis(v, pc, axis=1)
        keep = ring_ok[..., None, None]
        out["recent_k"] = jnp.where(keep, rk.astype(self.recent_k.dtype),
                                    self.recent_k)
        out["recent_v"] = jnp.where(keep, rv.astype(self.recent_v.dtype),
                                    self.recent_v)

        # ragged sink: positions [off, off+C) ∩ [0, n_sink) ∩ [0, len)
        ns = sals.n_sink
        sidx = jnp.arange(ns)[None, :]                           # (1, ns)
        sink_ok = (sidx >= off) & (sidx < off + c) \
            & (sidx < len_v[:, None])
        spc = jnp.broadcast_to(jnp.clip(sidx - off, 0, c - 1),
                               (b, ns))[..., None, None]
        sk = jnp.take_along_axis(k_pre, spc, axis=1)
        sv = jnp.take_along_axis(v, spc, axis=1)
        keep_s = sink_ok[..., None, None]
        out["sink_k"] = jnp.where(keep_s, sk.astype(self.sink_k.dtype),
                                  self.sink_k)
        out["sink_v"] = jnp.where(keep_s, sv.astype(self.sink_v.dtype),
                                  self.sink_v)

        if self.lengths is not None:
            out["lengths"] = jnp.minimum(len_v, off + c)
        return self.replace(**out)

    def write_ring(self, sals: SALSConfig, pos, k_pre: jnp.ndarray,
                   v: jnp.ndarray,
                   keep: Optional[jnp.ndarray] = None) -> "LatentKVCache":
        """Insert one token into the full-precision recent ring (and the
        sink region while pos < n_sink).  k_pre/v: (B, n_kv, dh); ``pos``
        scalar or (B,) per-row positions.  ``keep`` (B,) bool masks the
        insert per row (see :meth:`write_latents`)."""
        w = sals.n_recent
        pos_v = _row_positions(pos, k_pre.shape[0])
        slot = jax.lax.rem(pos_v, w)
        if keep is not None:
            slot = jnp.where(keep, slot, w)                 # OOB -> drop
        out = {
            "recent_k": _upd_rows(self.recent_k, k_pre, slot),
            "recent_v": _upd_rows(self.recent_v, v, slot),
        }
        in_sink = pos_v < sals.n_sink                       # (B,)
        if keep is not None:
            in_sink = in_sink & keep
        sink_pos = jnp.where(in_sink, pos_v, 0)
        new_sk = _upd_rows(self.sink_k, k_pre, sink_pos)
        new_sv = _upd_rows(self.sink_v, v, sink_pos)
        keep = in_sink[:, None, None, None]
        out["sink_k"] = jnp.where(keep, new_sk, self.sink_k)
        out["sink_v"] = jnp.where(keep, new_sv, self.sink_v)
        return self.replace(**out)

    # ------------------------------------------------------------ slot arena

    def prefill_into_slot(self, slot, other: "LatentKVCache"
                          ) -> "LatentKVCache":
        """Replace batch row ``slot`` with ``other``'s (batch=1) arrays.

        ``other`` must have the same treedef (same layer stacking, same
        ``n_groups`` / optional-field pattern) with batch size 1 — e.g. a
        freshly prefilled single request joining a running slot arena.
        ``slot`` may be a traced scalar, so admission re-executes ONE
        compiled HLO regardless of which slot frees up.
        """
        if self.paged:
            raise ValueError("paged caches admit through the page-scatter "
                             "path (ServeEngine._admit_paged), not slot "
                             "row splices")
        ax = 1 if self.k_lat.ndim == 4 else 0

        def put(a, o):
            return jax.lax.dynamic_update_slice_in_dim(
                a, o.astype(a.dtype), slot, axis=ax)

        return jax.tree.map(put, self, other)

    def free_slot(self, slot) -> "LatentKVCache":
        """Release batch row ``slot`` — METADATA ONLY (ISSUE 5).

        Resets the slot's length (and, paged, its page-table row); the
        payload bytes are deliberately left in place — no O(max_seq)
        zeroing.  Safety: per-slot ``lengths``/positions gate every read
        (the top-k selectability mask and the window validity mask are
        per-row position tests), and the next admission overwrites the
        row's windows and either splices (dense) or page-scatters (paged)
        fresh per-token data, so a recycled slot or page can never leak the
        previous request's tokens into selection — pinned by
        tests/test_paged.py::test_recycled_pages_never_leak_into_topk.
        """
        ax = 1 if self.k_lat.ndim == 4 else 0   # [L,] stacked vs layer view

        def clr_meta(a):
            if a is None:
                return None
            row = jax.lax.dynamic_slice_in_dim(a, slot, 1, axis=ax)
            return jax.lax.dynamic_update_slice_in_dim(
                a, jnp.zeros_like(row), slot, axis=ax)

        out = {}
        if self.lengths is not None:
            out["lengths"] = clr_meta(self.lengths)
        if self.page_table is not None:
            out["page_table"] = clr_meta(self.page_table)
        if self.hot_table is not None:
            out["hot_table"] = clr_meta(self.hot_table)
        return self.replace(**out)

    # --------------------------------------------------------------- oracles

    def gather_reconstruct(self, u: jnp.ndarray, sals: SALSConfig,
                           idx: jnp.ndarray, cfg: ModelConfig,
                           dtype=jnp.bfloat16
                           ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """ORACLE-ONLY dense gather + reconstruct (tests / analysis).

        Gathers ``idx`` (..., Nc) token latents + quant values as explicit
        buffers and reconstructs K̃_C·U_rᵀ.  The serving hot path instead
        passes raw cache views (:meth:`latent_views`) to the fused Pallas
        kernel, which gathers via scalar-prefetch indexing and never
        materializes these arrays.

        Returns (k_pre (..., Nc, n_kv, dh), v (..., Nc, n_kv, dh)).
        """
        lat = jnp.take_along_axis(self.k_lat, idx[..., None], axis=-2)
        if sals.k_latent_dtype == "int8":
            scale = jnp.take_along_axis(self.k_scale, idx, axis=-1)
            lat = qz.dequantize_latent_int8(lat, scale, dtype)
        else:
            lat = lat.astype(dtype)
        k_flat = (lat.astype(jnp.float32)
                  @ u.astype(jnp.float32).T).astype(dtype)   # (..., Nc, kvd)
        vq = {
            "q": jnp.take_along_axis(self.v_q, idx[..., None], axis=-2),
            "scale": jnp.take_along_axis(self.v_scale, idx[..., None],
                                         axis=-2),
            "zero": jnp.take_along_axis(self.v_zero, idx[..., None],
                                        axis=-2),
        }
        v_flat = qz.dequantize(vq, sals.v_bits, sals.v_group, dtype)
        shape = (*idx.shape, cfg.n_kv_heads, cfg.head_dim)
        return k_flat.reshape(shape), v_flat.reshape(shape)

    # ------------------------------------------------------------ bookkeeping

    @property
    def bytes_per_token(self) -> float:
        """Stored bytes/token/layer, derived from the ACTUAL per-token field
        shapes and dtypes — the single source of truth for the compression
        bookkeeping (paper Table 1).  Works on concrete arrays and on
        ``jax.eval_shape`` stand-ins alike."""
        n_slots = math.prod(self.k_lat.shape[:-1])   # [L·]B·S token slots
        total = 0
        for name in _PER_TOKEN_FIELDS:
            a = getattr(self, name)
            if a is not None:
                total += math.prod(a.shape) * jnp.dtype(a.dtype).itemsize
        return total / n_slots


jax.tree_util.register_dataclass(
    LatentKVCache,
    data_fields=["k_lat", "v_q", "v_scale", "v_zero", "sink_k", "sink_v",
                 "recent_k", "recent_v", "k_scale", "ssm", "lengths",
                 "page_table", "k_score", "k_scale_score", "hot_table"],
    meta_fields=["n_groups", "shard_axis", "page_size"])


def cache_bytes_per_token(cfg: ModelConfig, sals: SALSConfig) -> float:
    """Stored bytes/token/layer for a (cfg, sals) setting.

    Derived from the abstract :class:`LatentKVCache` field shapes/dtypes
    (``jax.eval_shape`` — no allocation), so the bookkeeping can never
    drift from what the cache actually stores.
    """
    shapes = jax.eval_shape(functools.partial(
        LatentKVCache.init, cfg, sals, 1, 1, max(sals.n_recent, 8)))
    return shapes.bytes_per_token


def _row_positions(pos, batch: int) -> jnp.ndarray:
    """Normalize a scalar-or-(B,) decode position to (B,) int32."""
    return jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (batch,))


def _put_rows(arr, val, pos_v):
    """:func:`_upd_rows` as a masked write over the whole (B, S, ·) array:
    the same values, with no scatter; a position out of range writes
    nothing."""
    hit = jnp.arange(arr.shape[1])[None, :, None] == pos_v[:, None, None]
    return jnp.where(hit, val[:, None].astype(arr.dtype), arr)


def _upd_rows(arr, val, pos_v):
    """Write val[b] into arr[b, pos_v[b]] (per-row scatter along axis 1).

    ``mode="drop"`` so masked speculative commits can redirect rejected
    rows out of range; in-bounds writes are unaffected."""
    b = arr.shape[0]
    return arr.at[jnp.arange(b), pos_v].set(val.astype(arr.dtype),
                                            mode="drop")
