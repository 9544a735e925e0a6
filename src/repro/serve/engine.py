"""Serving engine: chunked prefill + SALS decode over a slot arena.

One engine per (model, SALS setting).  The decode step is jitted once with a
static max_seq cache and traced per-row positions, so generation is a fixed
HLO re-executed per token — the serving equivalent of the paper's GPT-fast
baseline, with SALS latent-cache attention replacing full KV attention on
the middle layers.

Batching is RAGGED: prompts are right-padded with ``scfg.pad_id`` and carry
their true lengths (per-slot ``lengths`` on the LatentKVCache, per-row
decode positions through every kernel), so pad tokens are never selectable
by the latent top-k nor attended by the window/full paths.  The batch axis
is a slot arena for continuous batching: :meth:`init_slot_cache`,
:meth:`start_prefill` / :meth:`prefill_chunk_step`, and :meth:`admit` let
the scheduler prefill a single joining request and splice it into an empty
slot of a RUNNING batch between decode steps — the decode HLO is compiled
once and reused across admissions (the slot index is a traced scalar).

Prefill is CHUNKED: a joining request's prompt is processed as a loop over
ONE jitted fixed-width chunk step (``scfg.prefill_chunk`` tokens; the chunk
offset is a traced scalar, so heterogeneous prompt lengths all re-execute
the same compiled HLO — no per-length or per-bucket recompiles, and peak
prefill activation memory is (1, chunk, d) instead of (1, S_prompt, d)).
The chunk state (:class:`PrefillTask`) is resumable between decode steps,
which is what lets the scheduler interleave long-prompt admission work with
resident decoding instead of head-of-line blocking the arena.

Exception: recurrent-state families (ssm, hybrid) build their state by
scanning the padded sequence, so right-padding would fold pad tokens into
the state and chunking would have to carry it.  For those,
:meth:`generate` falls back to the uniform-length monolithic layout
(left-fill with the first prompt token, exact positions) and the scheduler
uses static batching.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig, SALSConfig, ServeConfig
from repro.core.latent_cache import LatentKVCache
from repro.models import transformer as tf
from repro.serve.faults import maybe_fault


class _WeightsJit:
    """``jax.jit`` of a step whose first argument is the model weights,
    called without them: every call passes ``weights()``.  The weights are
    arguments, never captured by a closure — a jitted closure over a
    full-width model bakes gigabytes of constants into the program."""

    def __init__(self, fn, weights, donate_argnums=()):
        self._jit = jax.jit(fn, donate_argnums=tuple(
            i + 1 for i in donate_argnums))
        self._weights = weights

    def __call__(self, *args):
        return self._jit(self._weights(), *args)

    def lower(self, *args):
        return self._jit.lower(self._weights(), *args)

    def _cache_size(self) -> int:
        return self._jit._cache_size()


@dataclasses.dataclass
class GenerationResult:
    tokens: np.ndarray          # (new_tokens,) generated ids
    prompt_len: int
    steps: int
    # False: a partial stream flushed by a non-DONE teardown (cancel /
    # timeout / terminal failure mid-decode) — ``tokens`` holds everything
    # committed before the request died (ISSUE 8 streaming).
    complete: bool = True


@dataclasses.dataclass
class PrefillTask:
    """One request's chunked prefill in flight.

    Created by :meth:`ServeEngine.start_prefill`; each
    :meth:`ServeEngine.prefill_chunk_step` advances it by one fixed-width
    chunk.  ``cache`` is the single-slot decode cache being built and
    ``scratch`` the transient full-precision prompt-K/V buffer the SALS
    segments attend against across chunks (dropped when the task is
    admitted).  ``logits`` always holds the last chunk's per-row
    last-real-token logits — after the final chunk that IS the prompt's
    next-token distribution.
    """

    tokens: np.ndarray           # (1, n_chunks·C) right-padded prompt
    prompt_len: int
    cache: dict
    scratch: dict
    n_chunks: int
    next_chunk: int = 0
    logits: Optional[jnp.ndarray] = None
    start_chunk: int = 0         # >0: prefix-cache resume (shared pages
    #                              skipped — chunks [0, start_chunk) were
    #                              paid for once, by the prefix registrant)
    boundary_rings: Optional[dict] = None  # {n_pages -> SALS ring snapshot}

    @property
    def done(self) -> bool:
        return self.next_chunk >= self.n_chunks


class ServeEngine:
    """Holds params + projectors and runs batched generation."""

    def __init__(self, params, projectors, cfg: ModelConfig,
                 scfg: ServeConfig, n_groups: int = 1):
        if not cfg.is_decoder:
            raise ValueError("encoder models cannot be served autoregressively")
        self.params = params
        self.projectors = projectors
        self.cfg = cfg
        self.scfg = scfg
        self.sals: Optional[SALSConfig] = scfg.sals if (
            scfg.sals and scfg.sals.enabled and cfg.has_attention) else None
        # decode selection layout — stamped on the LatentKVCache segments at
        # prefill time; decode_step reads it back from the cache metadata
        if n_groups > 1 and scfg.max_seq_len % n_groups:
            raise ValueError(f"max_seq_len {scfg.max_seq_len} must be "
                             f"divisible by n_groups {n_groups}")
        self.n_groups = n_groups
        if self.ragged_ok:
            if scfg.prefill_chunk < 1:
                raise ValueError("prefill_chunk must be >= 1")
            if scfg.max_seq_len % scfg.prefill_chunk:
                # guarantees every chunk write [off, off+C) stays in-bounds
                # for every admissible prompt (dynamic_update_slice would
                # otherwise clamp the offset and silently shift the write)
                raise ValueError(
                    f"max_seq_len {scfg.max_seq_len} must be a multiple of "
                    f"prefill_chunk {scfg.prefill_chunk}")
        if scfg.page_size > 0 and self.sals is None:
            # refuse rather than silently fall back to the dense arena:
            # the caller configured a page pool (capacity bound, prefix
            # cache) that would otherwise be ignored without a message
            raise ValueError("page_size > 0 needs SALS latent segments "
                             "(sals enabled on an attention family) — the "
                             "page pool backs the compressed cache")
        if self.paged:
            if not self.ragged_ok:
                raise ValueError(f"{cfg.family} state is recurrent — the "
                                 "paged latent cache needs chunked prefill "
                                 "(attention families)")
            from repro.kernels.latent_score import DEFAULT_BLOCK_S
            bs = min(DEFAULT_BLOCK_S, scfg.max_seq_len)
            if bs % scfg.page_size:
                # the paged score kernel walks pages_per_superblock grid
                # steps per seq block — catch the geometry HERE, not as a
                # ValueError inside the first jitted decode step
                raise ValueError(
                    f"page_size {scfg.page_size} must divide the score "
                    f"kernel's seq block min(block_s={DEFAULT_BLOCK_S}, "
                    f"max_seq_len={scfg.max_seq_len}) = {bs}")
            mp = scfg.max_seq_len // scfg.page_size
            if n_groups > 1 and mp % n_groups:
                raise ValueError(
                    f"pages per sequence {mp} must be divisible by "
                    f"n_groups {n_groups} (the grouped fold splits the "
                    "page table per slab)")
            if scfg.pool_pages * scfg.page_size < scfg.max_seq_len:
                raise ValueError(
                    f"pool of {scfg.pool_pages} pages cannot hold one "
                    f"max_seq_len {scfg.max_seq_len} sequence")
        def weighted(fn, donate_argnums=()):
            return _WeightsJit(fn, lambda: (self.params, self.projectors),
                               donate_argnums)

        self._prefill = weighted(self._prefill_impl)
        self._prefill_chunk = weighted(self._prefill_chunk_impl,
                                       donate_argnums=(1, 2))
        self._init_prefill = jax.jit(self._init_prefill_impl)
        self._decode = weighted(self._decode_impl, donate_argnums=(1,))
        self._decode_sel = weighted(self._decode_sel_impl,
                                    donate_argnums=(1,))
        # speculative verify window (ISSUE 9): the window forward reads the
        # cache WITHOUT donating it (the same buffers are re-read by the
        # commit), then the commit donates cache + window K/V
        self._decode_window = weighted(self._decode_window_impl)
        self._commit_window = weighted(self._commit_window_impl,
                                       donate_argnums=(0,))
        if scfg.spec_window > 1 and not self.ragged_ok:
            raise ValueError(
                f"{cfg.family} decode carries recurrent state — a rejected "
                "draft would need a state rollback; speculative decoding "
                "needs an attention family (set spec_window=0)")
        self._admit = jax.jit(self._admit_impl, donate_argnums=(0,))
        self._admit_paged = jax.jit(self._admit_paged_impl,
                                    donate_argnums=(0,))
        self._admit_tiered = jax.jit(self._admit_tiered_impl,
                                     donate_argnums=(0,))
        self._copy_page = jax.jit(self._copy_page_impl, donate_argnums=(0,))
        self._copy_score_page = jax.jit(self._copy_score_page_impl,
                                        donate_argnums=(0,))
        self._load_page = jax.jit(self._load_page_impl, donate_argnums=(0,))
        self._detach_slot = jax.jit(self._detach_slot_impl)
        self._attach_slot = jax.jit(self._attach_slot_impl,
                                    donate_argnums=(0,))
        self._release_slot = jax.jit(self._release_slot_impl,
                                     donate_argnums=(0,))
        self._init_slots = jax.jit(self._init_slots_impl)

    @property
    def paged(self) -> bool:
        """Paged latent cache active (ISSUE 5): SALS segments backed by the
        refcounted page pool instead of the dense slot arena."""
        return self.sals is not None and self.scfg.page_size > 0

    @property
    def tiered(self) -> bool:
        """Two-tier page pool active (ISSUE 7): payload pools hold only
        ``scfg.hbm_pages`` hot device slots; the r* score pool keeps every
        live page HBM-resident, cold payloads live in host mirrors."""
        return self.paged and self.scfg.hbm_pages > 0

    @property
    def ragged_ok(self) -> bool:
        """Right-padded ragged batching (and chunked prefill) is exact for
        attention families; recurrent ssm/hybrid state would absorb pad
        tokens and spans chunk boundaries."""
        return self.cfg.family not in ("ssm", "hybrid")

    # -- jitted bodies -------------------------------------------------------

    def _prefill_impl(self, weights, batch, lengths=None):
        return tf.prefill(*weights, self.cfg, self.sals,
                          batch, self.scfg.max_seq_len,
                          n_groups=self.n_groups, lengths=lengths)

    def _prefill_chunk_impl(self, weights, tokens, cache, scratch, off,
                            lengths):
        return tf.prefill_chunk(*weights, self.cfg,
                                self.sals, cache, scratch,
                                {"tokens": tokens}, off, lengths)

    def _init_prefill_impl(self):
        cache = tf.init_cache(self.cfg, self.sals, 1, self.scfg.max_seq_len,
                              n_groups=self.n_groups)
        scratch = tf.init_prefill_scratch(self.cfg, self.sals, 1,
                                          self.scfg.max_seq_len)
        return cache, scratch

    def _decode_impl(self, weights, tokens, cache, pos):
        return tf.decode_step(*weights, cache, tokens,
                              pos, self.cfg, self.sals)

    def _decode_sel_impl(self, weights, tokens, cache, pos):
        """Decode step that also reports WHICH logical pages the SALS
        selection reconstructed from, unioned over layers/segments to one
        (B, max_pages) bool mask — the tiered fetch-and-rerun loop's
        residency probe (see RequestScheduler._tiered_decode)."""
        logits, cache, touched = tf.decode_step(
            *weights, cache, tokens, pos, self.cfg,
            self.sals, collect_selection=True)
        union = None
        for seg_touch in touched.values():         # (ls, B, max_pages)
            seg_any = jnp.any(seg_touch, axis=0)
            union = seg_any if union is None else union | seg_any
        if union is None:
            # no SALS segments (every layer full-precision): nothing is
            # ever reconstructed from the payload pools, so no page is
            # ever demanded — the tiered loop sees an all-cold-safe mask
            mp = self.scfg.max_seq_len // self.scfg.page_size
            union = jnp.zeros((tokens.shape[0], mp), bool)
        return logits, cache, union

    def _decode_window_impl(self, weights, tokens, cache, pos):
        return tf.decode_window(*weights, cache, tokens,
                                pos, self.cfg, self.sals)

    def _commit_window_impl(self, weights, cache, aux, pos, n_accept):
        return tf.commit_window(weights[1], cache, aux, pos, n_accept,
                                self.cfg, self.sals)

    def _admit_impl(self, cache, one, slot):
        # every cache leaf is layer-stacked (L, B, ...): splice batch row
        # ``slot`` (a TRACED scalar — one admission HLO for every slot).
        # Latent segments go through the typed slot-arena method; the
        # full-precision / recurrent segments are plain leaf splices.
        def splice(seg, one_seg):
            if isinstance(seg, LatentKVCache):
                return seg.prefill_into_slot(slot, one_seg)
            return jax.tree.map(
                lambda a, o: jax.lax.dynamic_update_slice_in_dim(
                    a, o.astype(a.dtype), slot, axis=1),
                seg, one_seg)

        return {k: splice(seg, one[k]) for k, seg in cache.items()}

    def _init_slots_impl(self):
        # +1: physical page 0 is the reserved TRASH page (unmapped table
        # entries and idle-slot parked writes land there — see core/pager)
        page_size = self.scfg.page_size if self.paged else 0
        return tf.init_cache(self.cfg, self.sals, self.scfg.max_batch,
                             self.scfg.max_seq_len, n_groups=self.n_groups,
                             page_size=page_size,
                             n_pages=self.scfg.pool_pages + 1,
                             hbm_pages=self.scfg.hbm_pages)

    # -- paged-cache device ops (host bookkeeping lives in core/pager.py) ----

    def _latent_segs(self, cache):
        return {k: seg for k, seg in cache.items()
                if isinstance(seg, LatentKVCache)}

    def _admit_paged_impl(self, cache, one, slot, pt_row, start_page, plen):
        """Splice a finished single-request prefill into the PAGED arena.

        ``one`` is the task's DENSE single-request cache; its SALS
        per-token rows for pages [start_page, ceil(plen/ps)) are scattered
        into the pool pages named by ``pt_row`` (shared prefix pages
        [0, start_page) are NOT written — their bytes already live in the
        pool, stored once).  Windows/lengths splice per slot as in the
        dense arena; the slot's page-table row is installed.  ``slot``,
        ``start_page`` and ``plen`` are traced — one compiled admission
        HLO for every slot / prompt length / share depth.
        """
        ps = self.scfg.page_size
        mp = self.scfg.max_seq_len // ps
        n_pages = self.scfg.pool_pages + 1     # device pool incl. trash page
        n_req_pages = (plen + ps - 1) // ps
        page_idx = jnp.arange(mp)
        # out-of-range target -> OOB -> mode="drop": pages outside
        # [start_page, n_req_pages) must not touch the pool (their pt_row
        # entries are unallocated or SHARED)
        tgt = jnp.where((page_idx >= start_page) & (page_idx < n_req_pages),
                        pt_row[:mp], n_pages)

        def splice(seg, one_seg):
            if isinstance(seg, LatentKVCache):
                out = {}
                for name in ("k_lat", "k_scale", "v_q", "v_scale", "v_zero"):
                    pool = getattr(seg, name)
                    dense = getattr(one_seg, name)
                    if pool is None:
                        continue
                    ls = dense.shape[0]
                    vals = dense.reshape(ls, mp, ps, *dense.shape[3:])
                    out[name] = pool.at[:, tgt].set(
                        vals.astype(pool.dtype), mode="drop")
                for name in ("sink_k", "sink_v", "recent_k", "recent_v"):
                    arr = getattr(seg, name)
                    out[name] = jax.lax.dynamic_update_slice_in_dim(
                        arr, getattr(one_seg, name).astype(arr.dtype), slot,
                        axis=1)
                out["lengths"] = jax.lax.dynamic_update_slice_in_dim(
                    seg.lengths, jnp.broadcast_to(
                        jnp.int32(plen), (seg.lengths.shape[0], 1)),
                    slot, axis=1)
                row = jnp.broadcast_to(pt_row[None, None, :mp],
                                       (seg.page_table.shape[0], 1, mp))
                out["page_table"] = jax.lax.dynamic_update_slice(
                    seg.page_table, row, (0, slot, 0))
                return seg.replace(**out)
            return jax.tree.map(
                lambda a, o: jax.lax.dynamic_update_slice_in_dim(
                    a, o.astype(a.dtype), slot, axis=1),
                seg, one_seg)

        return {k: splice(seg, one[k]) for k, seg in cache.items()}

    def _admit_tiered_impl(self, cache, one, slot, pt_row, hot_row,
                           start_page, plen):
        """Tiered admission: like :meth:`_admit_paged_impl` but the payload
        rows scatter into HOT SLOTS (``hot_row``; 0 = the page was admitted
        cold, its bytes go to the host mirror instead — dropped here) while
        the leading-r* score rows scatter into the full-size score pool at
        the PHYSICAL pages (``pt_row`` — always, hot or cold).  Installs
        BOTH table rows for the slot."""
        ps = self.scfg.page_size
        mp = self.scfg.max_seq_len // ps
        n_slots = self.scfg.hbm_pages + 1      # payload pool incl. trash slot
        n_pages = self.scfg.pool_pages + 1
        n_req_pages = (plen + ps - 1) // ps
        page_idx = jnp.arange(mp)
        in_range = (page_idx >= start_page) & (page_idx < n_req_pages)
        # cold pages (hot_row == 0) must NOT land in the trash slot either —
        # out-of-range target + mode="drop" skips them entirely
        tgt_pay = jnp.where(in_range & (hot_row[:mp] > 0), hot_row[:mp],
                            n_slots)
        tgt_score = jnp.where(in_range, pt_row[:mp], n_pages)

        def splice(seg, one_seg):
            if isinstance(seg, LatentKVCache):
                out = {}
                for name in ("k_lat", "k_scale", "v_q", "v_scale", "v_zero"):
                    pool = getattr(seg, name)
                    dense = getattr(one_seg, name)
                    if pool is None:
                        continue
                    ls = dense.shape[0]
                    vals = dense.reshape(ls, mp, ps, *dense.shape[3:])
                    out[name] = pool.at[:, tgt_pay].set(
                        vals.astype(pool.dtype), mode="drop")
                r_star = seg.k_score.shape[-1]
                ls = one_seg.k_lat.shape[0]
                sc = one_seg.k_lat[..., :r_star].reshape(
                    ls, mp, ps, r_star)
                out["k_score"] = seg.k_score.at[:, tgt_score].set(
                    sc.astype(seg.k_score.dtype), mode="drop")
                if seg.k_scale_score is not None:
                    scale = one_seg.k_scale.reshape(ls, mp, ps)
                    out["k_scale_score"] = seg.k_scale_score.at[
                        :, tgt_score].set(
                        scale.astype(seg.k_scale_score.dtype), mode="drop")
                for name in ("sink_k", "sink_v", "recent_k", "recent_v"):
                    arr = getattr(seg, name)
                    out[name] = jax.lax.dynamic_update_slice_in_dim(
                        arr, getattr(one_seg, name).astype(arr.dtype), slot,
                        axis=1)
                out["lengths"] = jax.lax.dynamic_update_slice_in_dim(
                    seg.lengths, jnp.broadcast_to(
                        jnp.int32(plen), (seg.lengths.shape[0], 1)),
                    slot, axis=1)
                for tname, trow in (("page_table", pt_row),
                                    ("hot_table", hot_row)):
                    arr = getattr(seg, tname)
                    row = jnp.broadcast_to(trow[None, None, :mp],
                                           (arr.shape[0], 1, mp))
                    out[tname] = jax.lax.dynamic_update_slice(
                        arr, row, (0, slot, 0))
                return seg.replace(**out)
            return jax.tree.map(
                lambda a, o: jax.lax.dynamic_update_slice_in_dim(
                    a, o.astype(a.dtype), slot, axis=1),
                seg, one_seg)

        return {k: splice(seg, one[k]) for k, seg in cache.items()}

    def _load_page_impl(self, cache, slot, payload):
        """Host→HBM fetch, device half: install one page's payload rows
        (``payload`` = {seg: {field: (ls, ps, ·)}} host mirror) into payload
        slot ``slot`` of every SALS segment.  Traced slot — one HLO."""
        def load(seg, pl):
            out = {}
            for name, val in pl.items():
                pool = getattr(seg, name)
                out[name] = jax.lax.dynamic_update_slice_in_dim(
                    pool, val[:, None].astype(pool.dtype), slot, axis=1)
            return seg.replace(**out)

        return {k: (load(seg, payload[k]) if k in payload else seg)
                for k, seg in cache.items()}

    def _copy_page_impl(self, cache, src, dst):
        """Copy-on-write worker: duplicate physical page ``src`` into
        ``dst`` across every SALS segment/layer (windows are per-slot, not
        paged).  Traced page ids — one compiled HLO."""
        def cow(seg):
            if not isinstance(seg, LatentKVCache):
                return seg
            out = {}
            for name in ("k_lat", "k_scale", "v_q", "v_scale", "v_zero"):
                pool = getattr(seg, name)
                if pool is None:
                    continue
                row = jax.lax.dynamic_slice_in_dim(pool, src, 1, axis=1)
                out[name] = jax.lax.dynamic_update_slice_in_dim(
                    pool, row, dst, axis=1)
            return seg.replace(**out)

        return {k: cow(seg) for k, seg in cache.items()}

    def _copy_score_page_impl(self, cache, src, dst):
        """Tiered copy-on-write, score half: duplicate PHYSICAL page src ->
        dst in the always-hot r* score pool (the payload half goes through
        :meth:`_copy_page_impl` on hot SLOTS, or a host-mirror copy when
        the source is cold)."""
        def cow(seg):
            if not isinstance(seg, LatentKVCache) or seg.k_score is None:
                return seg
            out = {}
            for name in ("k_score", "k_scale_score"):
                pool = getattr(seg, name)
                if pool is None:
                    continue
                row = jax.lax.dynamic_slice_in_dim(pool, src, 1, axis=1)
                out[name] = jax.lax.dynamic_update_slice_in_dim(
                    pool, row, dst, axis=1)
            return seg.replace(**out)

        return {k: cow(seg) for k, seg in cache.items()}

    _SPILL_FIELDS = ("k_lat", "k_scale", "v_q", "v_scale", "v_zero")

    def read_page_payload(self, cache, slot: int) -> dict:
        """HBM→host spill, device half: pull payload slot ``slot`` of every
        SALS segment back as a host mirror {seg: {field: np (ls, ps, ·)}}.
        Pure reads — the arena stays valid."""
        out = {}
        for k, seg in self._latent_segs(cache).items():
            fields = {}
            for name in self._SPILL_FIELDS:
                pool = getattr(seg, name)
                if pool is not None:
                    fields[name] = np.asarray(pool[:, slot])
            out[k] = fields
        return out

    def extract_page_payload_dense(self, one_cache, page: int) -> dict:
        """Host mirror of logical page ``page`` taken from a finished
        prefill task's DENSE single-request cache — the cold half of a
        tiered admission (pages past the hot tier never touch the device
        pools at all)."""
        ps = self.scfg.page_size
        out = {}
        for k, seg in self._latent_segs(one_cache).items():
            fields = {}
            for name in self._SPILL_FIELDS:
                arr = getattr(seg, name)
                if arr is not None:     # dense layout: (ls, 1, S, ·)
                    fields[name] = np.asarray(
                        arr[:, 0, page * ps:(page + 1) * ps])
            out[k] = fields
        return out

    # Per-slot state a PARK must carry across the slot release (ISSUE 8):
    # the attention windows + the slot length.  The paged per-token payload
    # stays in the pool (the parked request keeps its page refcounts); the
    # page-table row is host state (reinstalled via with_page_tables).
    _PARK_FIELDS = ("sink_k", "sink_v", "recent_k", "recent_v", "lengths")

    def _detach_slot_impl(self, cache, slot):
        """Park, device half: pure per-slot reads of every segment's slot
        row (latent segments: the window fields; full-precision segments:
        every leaf at the batch axis).  Traced slot — one HLO."""
        def take(seg):
            if isinstance(seg, LatentKVCache):
                return {name: jax.lax.dynamic_slice_in_dim(
                            getattr(seg, name), slot, 1, axis=1)
                        for name in self._PARK_FIELDS}
            return jax.tree.map(
                lambda a: jax.lax.dynamic_slice_in_dim(a, slot, 1, axis=1),
                seg)

        return {k: take(seg) for k, seg in cache.items()}

    def _attach_slot_impl(self, cache, snap, slot):
        """Resume, device half: splice a park snapshot back into batch row
        ``slot`` (the mirror of :meth:`_detach_slot_impl`; the paged
        payload never moved).  Traced slot — one HLO."""
        def put(seg, s):
            if isinstance(seg, LatentKVCache):
                out = {}
                for name in self._PARK_FIELDS:
                    arr = getattr(seg, name)
                    out[name] = jax.lax.dynamic_update_slice_in_dim(
                        arr, s[name].astype(arr.dtype), slot, axis=1)
                return seg.replace(**out)
            return jax.tree.map(
                lambda a, o: jax.lax.dynamic_update_slice_in_dim(
                    a, o.astype(a.dtype), slot, axis=1),
                seg, s)

        return {k: put(seg, snap[k]) for k, seg in cache.items()}

    def _release_slot_impl(self, cache, slot):
        """Metadata-only slot release: per-slot lengths (+ page-table row)
        reset; NO payload zeroing (ISSUE 5 — freeing is O(1), and per-row
        position masks keep recycled bytes unselectable)."""
        def rel(seg):
            if isinstance(seg, LatentKVCache):
                return seg.free_slot(slot)
            return seg
        return {k: rel(seg) for k, seg in cache.items()}

    def with_page_tables(self, cache, table: np.ndarray,
                         hot_table: Optional[np.ndarray] = None):
        """Install the host page table ((B, max_pages) int32) — and, when
        tiered, the hot-slot table — into every SALS segment (broadcast
        over its layer axis).  Pure leaf swap — no jit, no copy of the
        pools."""
        row = jnp.asarray(table, jnp.int32)
        hot = None if hot_table is None else jnp.asarray(hot_table, jnp.int32)

        def upd(seg):
            if isinstance(seg, LatentKVCache) and seg.paged:
                ls = seg.page_table.shape[0]
                out = {"page_table": jnp.broadcast_to(row[None],
                                                      (ls, *row.shape))}
                if hot is not None:
                    out["hot_table"] = jnp.broadcast_to(hot[None],
                                                        (ls, *hot.shape))
                return seg.replace(**out)
            return seg
        return {k: upd(seg) for k, seg in cache.items()}

    def sals_ring_state(self, cache) -> dict:
        """Deep-copied (recent_k, recent_v) of every SALS segment — the
        page-boundary snapshot a prefix-cache entry stores (the ring is the
        one non-append-only piece of prefill state).  Copies are explicit:
        the next chunk step DONATES the cache, which would invalidate bare
        references."""
        return {k: (jnp.copy(seg.recent_k), jnp.copy(seg.recent_v))
                for k, seg in self._latent_segs(cache).items()}

    def resume_seed(self, entry, n_shared_pages: int):
        """Build (cache, scratch) to resume a chunked prefill at page
        boundary ``n_shared_pages`` from a prefix-cache entry.

        Everything append-only (latent rows, sink, scratch K/V, full-layer
        K/V) is taken from the entry's final state — positions >= the
        resume offset are either masked (history reads test ``< off``) or
        overwritten by the suffix chunks.  The ring is restored from the
        entry's snapshot AT the boundary.  Deep copies throughout: the
        chunk loop donates its cache/scratch, and the entry must outlive
        this request.
        """
        cache = jax.tree.map(jnp.copy, entry.cache)
        scratch = jax.tree.map(jnp.copy, entry.scratch)
        rings = entry.boundary_rings[n_shared_pages]
        for name, (rk, rv) in rings.items():
            cache[name] = cache[name].replace(recent_k=jnp.copy(rk),
                                              recent_v=jnp.copy(rv))
        return cache, scratch

    # -- sampling ------------------------------------------------------------

    def _sample(self, logits: jnp.ndarray, key) -> jnp.ndarray:
        if self.scfg.temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(
            key, logits / self.scfg.temperature, axis=-1).astype(jnp.int32)

    def sample_checked(self, logits: jnp.ndarray, key
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Sample plus a per-row validity verdict: ``ok[i]`` is False when
        row i's logits contain NaN/inf or the sampled id falls outside the
        vocab.  The scheduler fails ONLY the flagged rows (NanLogitsError,
        transient) — the other residents' tokens are taken as-is, which is
        what confines a poisoned row to its own request.  Both stay on the
        device: the caller reads them back together, once."""
        tok = self._sample(logits, key)
        finite = jnp.all(jnp.isfinite(logits), axis=-1)
        in_vocab = (tok >= 0) & (tok < self.cfg.vocab_size)
        return tok, finite & in_vocab

    # -- continuous-batching primitives (used by RequestScheduler) -----------

    def init_slot_cache(self):
        """Zeroed slot-arena decode cache with ``max_batch`` slots."""
        return self._init_slots()

    def start_prefill(self, prompt: np.ndarray,
                      resume: Optional[Tuple] = None) -> PrefillTask:
        """Begin a chunked prefill for ONE request.

        The prompt is right-padded to a whole number of ``prefill_chunk``
        tokens; every :meth:`prefill_chunk_step` then re-executes the SAME
        compiled chunk HLO (fixed (1, chunk) shape, traced offset) — no
        per-length buckets, no recompiles across heterogeneous prompts.

        ``resume`` (paged mode, prefix-cache hit): ``(entry,
        n_shared_pages)`` — the task's cache/scratch are seeded from the
        entry (:meth:`resume_seed`) and the chunk loop starts at the page
        boundary: only the SUFFIX chunks run.  The page boundary is
        chunk-aligned by config validation, so the suffix chunks execute
        the exact same HLO sequence an unshared run would execute from
        that offset — greedy outputs are identical.
        """
        if not self.ragged_ok:
            raise ValueError(f"{self.cfg.family} prefill is recurrent — "
                             "chunked prefill needs an attention family "
                             "(the scheduler falls back to static batching)")
        plen = len(prompt)
        if plen > self.scfg.max_seq_len:
            raise ValueError(f"prompt {plen} exceeds max_seq "
                             f"{self.scfg.max_seq_len}")
        c = self.scfg.prefill_chunk
        n = max(1, -(-plen // c))
        toks = np.full((1, n * c), self.scfg.pad_id, np.int32)
        toks[0, :plen] = prompt
        if resume is not None:
            maybe_fault("prefix_resume")
            entry, n_shared = resume
            start = n_shared * self.scfg.page_size // c
            if not 0 < start < n:
                raise ValueError(f"resume boundary {n_shared} pages does "
                                 f"not leave a suffix chunk (prompt {plen})")
            cache, scratch = self.resume_seed(entry, n_shared)
            return PrefillTask(tokens=toks, prompt_len=plen, cache=cache,
                               scratch=scratch, n_chunks=n,
                               next_chunk=start, start_chunk=start)
        cache, scratch = self._init_prefill()
        return PrefillTask(tokens=toks, prompt_len=plen, cache=cache,
                           scratch=scratch, n_chunks=n)

    def prefill_chunk_step(self, task: PrefillTask) -> bool:
        """Advance ``task`` by one chunk; returns True when the prompt is
        fully processed (``task.logits`` then holds the next-token logits
        and ``task.cache`` the finished single-slot cache)."""
        c = self.scfg.prefill_chunk
        j = task.next_chunk
        # fault point BEFORE the jitted call: _prefill_chunk donates
        # cache/scratch, so an injection after it would leave the task
        # holding dead buffers — firing here keeps the task retryable
        maybe_fault("prefill_chunk")
        chunk = jnp.asarray(task.tokens[:, j * c:(j + 1) * c])
        task.logits, task.cache, task.scratch = self._prefill_chunk(
            chunk, task.cache, task.scratch, jnp.int32(j * c),
            jnp.asarray([task.prompt_len], jnp.int32))
        task.next_chunk += 1
        if self.paged and self.scfg.prefix_cache:
            # page-boundary ring snapshot: the resume state a prefix-cache
            # entry needs (everything else about prefill is append-only).
            # Bounded to the first prefix_share_pages boundaries — shared
            # prefixes are prompt HEADS (system prompts), and each
            # snapshot is a deep copy (the next chunk step donates the
            # cache), so the cap is what keeps per-task snapshot bytes
            # independent of prompt length.
            ps = self.scfg.page_size
            off_end = task.next_chunk * c
            if off_end % ps == 0 and off_end <= task.prompt_len \
                    and off_end // ps <= self.scfg.prefix_share_pages:
                if task.boundary_rings is None:
                    task.boundary_rings = {}
                task.boundary_rings[off_end // ps] = \
                    self.sals_ring_state(task.cache)
        return task.done

    def prefill_one(self, prompt: np.ndarray) -> Tuple[jnp.ndarray, dict]:
        """Prefill ONE request by draining its chunk loop.  Returns (logits
        (1, V) at the last real token, single-slot cache).  The scheduler
        instead drives :meth:`start_prefill` / :meth:`prefill_chunk_step`
        directly so chunks interleave with decode steps."""
        task = self.start_prefill(prompt)
        while not task.done:
            self.prefill_chunk_step(task)
        return task.logits, task.cache

    def admit(self, cache, one_cache, slot: int):
        """Splice a prefilled single-request cache into batch row ``slot``
        of a running slot arena (same compiled HLO for every slot)."""
        maybe_fault("admit")        # before the donate: arena stays alive
        return self._admit(cache, one_cache, jnp.int32(slot))

    def admit_paged(self, cache, one_cache, slot: int, page_ids, start_page:
                    int, prompt_len: int):
        """Paged admission: scatter the task's pages [start_page, ·) into
        the pool pages ``page_ids`` (host list, padded to a table row) and
        install the slot's metadata.  Shared prefix pages are never
        rewritten."""
        maybe_fault("admit")        # before the donate: arena stays alive
        mp = self.scfg.max_seq_len // self.scfg.page_size
        row = np.zeros((mp,), np.int32)
        row[:len(page_ids)] = page_ids
        return self._admit_paged(cache, one_cache, jnp.int32(slot),
                                 jnp.asarray(row), jnp.int32(start_page),
                                 jnp.int32(prompt_len))

    def admit_tiered(self, cache, one_cache, slot: int, page_ids, hot_slots,
                     start_page: int, prompt_len: int):
        """Tiered admission: payload pages with a hot slot (``hot_slots[j]``
        > 0) scatter into the device payload pool; every page's leading-r*
        rows scatter into the score pool; both table rows install.  Cold
        pages' payloads are the caller's job (extract_page_payload_dense →
        TieredPagePool.set_cold)."""
        maybe_fault("admit")        # before the donate: arena stays alive
        mp = self.scfg.max_seq_len // self.scfg.page_size
        row = np.zeros((mp,), np.int32)
        row[:len(page_ids)] = page_ids
        hrow = np.zeros((mp,), np.int32)
        hrow[:len(hot_slots)] = hot_slots
        return self._admit_tiered(cache, one_cache, jnp.int32(slot),
                                  jnp.asarray(row), jnp.asarray(hrow),
                                  jnp.int32(start_page),
                                  jnp.int32(prompt_len))

    def load_page(self, cache, slot: int, payload: dict):
        """Device half of a host→HBM fetch: install a host mirror into
        payload slot ``slot`` (the TieredPagePool fires the ``host_fetch``
        fault point BEFORE this donating call — see begin_fetch)."""
        return self._load_page(cache, jnp.int32(slot),
                               jax.tree.map(jnp.asarray, payload))

    def copy_page(self, cache, src: int, dst: int):
        """Device half of copy-on-write: duplicate pool page src -> dst.
        Tiered mode passes payload SLOT ids here and physical page ids to
        :meth:`copy_score_page`."""
        maybe_fault("cow_copy")     # before the donate: arena stays alive
        return self._copy_page(cache, jnp.int32(src), jnp.int32(dst))

    def copy_score_page(self, cache, src: int, dst: int):
        """Tiered COW, score half: duplicate score-pool page src -> dst.
        No separate fault point — it always rides with a cow_copy (hot
        source) or a host-mirror copy (cold source), which fire first."""
        return self._copy_score_page(cache, jnp.int32(src), jnp.int32(dst))

    def release_slot(self, cache, slot: int):
        """Metadata-only slot free (paged): lengths + page-table row."""
        return self._release_slot(cache, jnp.int32(slot))

    def detach_slot(self, cache, slot: int) -> dict:
        """Park: snapshot batch row ``slot``'s per-slot state to HOST
        memory (windows + lengths for latent segments, whole slot rows for
        full-precision segments).  Pure reads — the arena stays valid, and
        the host copy survives any later donating call.  Fires the ``park``
        fault point BEFORE touching anything: an injected park fault leaves
        the victim fully resident."""
        maybe_fault("park")         # before any read: victim stays resident
        snap = self._detach_slot(cache, jnp.int32(slot))
        return jax.tree.map(np.asarray, snap)

    def attach_slot(self, cache, slot: int, snap: dict):
        """Resume: splice a :meth:`detach_slot` snapshot back into batch
        row ``slot``.  Fires the ``resume`` fault point BEFORE the donating
        splice, so on an injected fault the snapshot and the arena are both
        still whole (the scheduler then releases the parked pages and
        retries the request from scratch)."""
        maybe_fault("resume")       # before the donate: snapshot stays whole
        return self._attach_slot(cache, jax.tree.map(jnp.asarray, snap),
                                 jnp.int32(slot))

    # -- public API ----------------------------------------------------------

    def generate(self, prompts: List[np.ndarray], max_new_tokens: Optional[int]
                 = None, eos_id: Optional[int] = None
                 ) -> List[GenerationResult]:
        """Generate for a batch of prompts (each a 1-D int array).

        Rows finishing early (``eos_id``) are truncated at their OWN eos:
        each row's result carries exactly the tokens up to and including its
        first eos (the batch keeps stepping for unfinished rows; a finished
        row's later samples are discarded, never reported).
        """
        mnt = max_new_tokens or self.scfg.max_new_tokens
        b = len(prompts)
        lens = [len(p) for p in prompts]
        max_len = max(lens)
        if max_len + mnt > self.scfg.max_seq_len:
            raise ValueError(
                f"prompt {max_len} + new {mnt} exceeds max_seq "
                f"{self.scfg.max_seq_len}")
        if self.ragged_ok:
            # right-pad with the real pad id; per-slot lengths mask the pads
            toks = np.full((b, max_len), self.scfg.pad_id, np.int32)
            for i, p in enumerate(prompts):
                toks[i, :lens[i]] = p
            pos0 = jnp.asarray(lens, jnp.int32)
            logits, cache = self._prefill({"tokens": jnp.asarray(toks)}, pos0)
        else:
            # recurrent state: uniform-length layout (left-fill with the
            # first real token — positions stay exact, state stays causal)
            toks = np.zeros((b, max_len), np.int32)
            for i, p in enumerate(prompts):
                toks[i, max_len - lens[i]:] = p
                toks[i, :max_len - lens[i]] = p[0]
            pos0 = jnp.full((b,), max_len, jnp.int32)
            logits, cache = self._prefill({"tokens": jnp.asarray(toks)})

        key = jax.random.PRNGKey(self.scfg.seed)
        out = np.zeros((b, mnt), np.int32)
        done = np.zeros((b,), bool)
        n_out = np.zeros((b,), np.int32)       # per-row emitted count
        next_tok = self._sample(logits, key)
        for t in range(mnt):
            out[:, t] = np.asarray(next_tok)
            n_out[~done] = t + 1               # finished rows stop counting
            if eos_id is not None:
                done |= out[:, t] == eos_id
                if done.all():
                    break
            if t == mnt - 1:
                break
            key, sub = jax.random.split(key)
            logits, cache = self._decode(next_tok, cache, pos0 + t)
            next_tok = self._sample(logits, sub)
        return [GenerationResult(out[i, :n_out[i]], lens[i], int(n_out[i]))
                for i in range(b)]

    def generate_speculative(self, prompts: List[np.ndarray],
                             max_new_tokens: Optional[int] = None,
                             eos_id: Optional[int] = None
                             ) -> List[GenerationResult]:
        """Greedy generation through the speculative verify window.

        Each round drafts ``spec_window − 1`` tokens per row (prompt-lookup,
        :class:`~repro.serve.draft.NgramDrafter`), runs ONE windowed decode
        HLO over [pending token + drafts], and commits the longest prefix
        whose greedy continuations match the drafts.  Window slot 0 is the
        already-emitted pending token, so every round makes progress —
        all-rejected drafts still commit one token, exactly a sequential
        step.  Verification is the model's own windowed forward (bit-exact
        vs sequential per query), so the emitted stream is TOKEN-EXACT with
        :meth:`generate` under greedy decoding.

        Per-row EOS / budget truncation mirrors :meth:`generate`: a row's
        commits stop at its own eos (later window slots are never
        committed), and ``self.spec_stats`` afterwards holds the round /
        draft / acceptance counters the throughput benchmark reads.
        """
        q = self.scfg.spec_window
        if q < 2:
            raise ValueError("generate_speculative needs spec_window >= 2 "
                             f"(got {q}); use generate() for sequential")
        if self.scfg.temperature > 0:
            raise ValueError("speculative verify is greedy: argmax "
                             "continuations are compared token-exactly "
                             "(temperature must be 0)")
        if self.tiered:
            raise ValueError("speculative decoding needs the untiered "
                             "cache (hot-set prefetch is per committed "
                             "step)")
        from repro.serve.draft import NgramDrafter
        mnt = max_new_tokens or self.scfg.max_new_tokens
        b = len(prompts)
        lens = [len(p) for p in prompts]
        max_len = max(lens)
        if max_len + mnt + q - 1 > self.scfg.max_seq_len:
            # the last verify window may READ (never commit) up to q-1
            # positions past the final token
            raise ValueError(
                f"prompt {max_len} + new {mnt} + window {q}-1 exceeds "
                f"max_seq {self.scfg.max_seq_len}")
        toks = np.full((b, max_len), self.scfg.pad_id, np.int32)
        for i, p in enumerate(prompts):
            toks[i, :lens[i]] = p
        pos0 = jnp.asarray(lens, jnp.int32)
        logits, cache = self._prefill({"tokens": jnp.asarray(toks)}, pos0)

        out = np.zeros((b, mnt), np.int32)
        done = np.zeros((b,), bool)
        n_out = np.zeros((b,), np.int32)
        pending = np.array(jnp.argmax(logits, -1), np.int32)     # (B,)
        out[:, 0] = pending
        n_out[:] = 1
        if eos_id is not None:
            done |= pending == eos_id
        done |= n_out >= mnt
        drafters = [NgramDrafter(list(map(int, prompts[i])) + [int(pending[i])])
                    for i in range(b)]
        pos = np.asarray(lens, np.int32)                         # window base
        self.spec_stats = {"rounds": 0, "proposed": 0, "accepted_drafts": 0,
                           "committed": 0}

        while not done.all():
            wt = np.zeros((b, q), np.int32)
            wt[:, 0] = pending
            for i in range(b):
                if not done[i]:
                    wt[i, 1:] = drafters[i].propose(q - 1)
            win_logits, aux = self._decode_window(
                jnp.asarray(wt), cache, jnp.asarray(pos))
            preds = np.asarray(jnp.argmax(win_logits, -1), np.int32)  # (B,Q)
            match = wt[:, 1:] == preds[:, :-1]                        # (B,Q-1)
            n_matched = np.cumprod(match, axis=1).sum(axis=1)
            n_emit = np.where(done, 0,
                              np.minimum(n_matched + 1, mnt - n_out))
            emitted_rows: List[List[int]] = []
            for i in range(b):
                row = [int(t) for t in preds[i, :n_emit[i]]]
                if eos_id is not None and eos_id in row:
                    row = row[:row.index(eos_id) + 1]   # stop at own eos
                emitted_rows.append(row)
            n_commit = np.asarray([len(r) for r in emitted_rows], np.int32)
            # commit exactly the emitted tokens' input slots: slot t's
            # input is correct for t < n_commit, and the new pending token
            # (last emitted) becomes the NEXT window's slot 0
            cache = self._commit_window(cache, aux, jnp.asarray(pos),
                                        jnp.asarray(n_commit))
            self.spec_stats["rounds"] += 1
            for i in range(b):
                row = emitted_rows[i]
                if not row:
                    continue
                self.spec_stats["proposed"] += q - 1
                self.spec_stats["accepted_drafts"] += int(n_matched[i])
                self.spec_stats["committed"] += len(row)
                out[i, n_out[i]:n_out[i] + len(row)] = row
                n_out[i] += len(row)
                pending[i] = row[-1]
                pos[i] += len(row)
                drafters[i].extend(row)
                if (eos_id is not None and row[-1] == eos_id) \
                        or n_out[i] >= mnt:
                    done[i] = True
        return [GenerationResult(out[i, :n_out[i]], lens[i], int(n_out[i]))
                for i in range(b)]

    def decode_throughput(self, batch_size: int, context_len: int,
                          n_steps: int = 32) -> float:
        """tokens/s of the steady-state decode loop (benchmark helper).

        Timed through the obs tracer (ISSUE 10) — the installed
        :class:`~repro.obs.trace.SpanTracer` when telemetry is on, a
        private one otherwise — so this benchmark cell and live serving
        metrics share one clock and one span code path instead of
        hand-rolled ``perf_counter`` bracketing."""
        from repro.obs import metrics as obs_metrics
        from repro.obs import trace as obs_trace
        tracer = obs_trace.active() or obs_trace.SpanTracer()
        prompts = [np.ones((context_len,), np.int32) for _ in range(batch_size)]
        toks = jnp.asarray(np.stack(prompts))
        logits, cache = self._prefill({"tokens": toks})
        next_tok = jnp.argmax(logits, -1).astype(jnp.int32)
        pos0 = jnp.full((batch_size,), context_len, jnp.int32)
        # warmup + compile
        lg, cache = self._decode(next_tok, cache, pos0)
        lg.block_until_ready()
        sid = tracer.begin("decode_throughput", "engine",
                           batch=batch_size, context=context_len,
                           steps=n_steps)
        for t in range(n_steps):
            lg, cache = self._decode(next_tok, cache, pos0 + 1 + t)
        lg.block_until_ready()
        dt = tracer.end(sid)
        tok_s = batch_size * n_steps / dt
        reg = obs_metrics.active()
        if reg is not None:
            reg.gauge("engine_decode_tokens_per_s",
                      "steady-state decode throughput (last probe)",
                      labelnames=("batch", "context")).set(
                tok_s, batch=batch_size, context=context_len)
        return tok_s
