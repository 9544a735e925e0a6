"""Configuration dataclasses for the SALS reproduction framework.

Everything that varies between runs — model architecture, SALS compression
settings, mesh/parallelism layout, training and serving hyper-parameters —
is expressed as a frozen dataclass here. Architecture files under
``repro/configs/`` instantiate :class:`ModelConfig`; launchers compose the
rest.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field, replace
from typing import Optional, Tuple


# ---------------------------------------------------------------------------
# Model architecture
# ---------------------------------------------------------------------------

FAMILIES = ("dense", "moe", "hybrid", "ssm", "encoder", "vlm")


@dataclass(frozen=True)
class ModelConfig:
    """Architecture description for one model.

    ``family`` selects the block structure:
      dense   — attention + gated MLP          (llama/qwen/granite/gemma/yi)
      moe     — attention + mixture-of-experts (llama4-scout, qwen3-moe)
      hybrid  — parallel attention ‖ SSM heads (hymba)
      ssm     — attention-free RWKV6 blocks    (rwkv6)
      encoder — bidirectional attention        (hubert)
      vlm     — dense LM + vision-prefix stub  (paligemma)
    """

    name: str
    family: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int

    # --- attention options -------------------------------------------------
    qkv_bias: bool = False
    rope_theta: float = 10_000.0
    use_rope: bool = True
    causal: bool = True
    attn_logit_softcap: float = 0.0

    # --- MLP ----------------------------------------------------------------
    mlp_act: str = "swiglu"  # swiglu | geglu

    # --- MoE ----------------------------------------------------------------
    n_experts: int = 0
    experts_per_token: int = 0
    moe_d_ff: int = 0          # per-expert hidden dim (0 -> use d_ff)
    n_shared_experts: int = 0  # llama4-style always-on shared expert
    moe_capacity_factor: float = 1.25   # Switch-style per-seq expert capacity

    # --- SSM / hybrid -------------------------------------------------------
    ssm_state: int = 0
    ssm_heads: int = 0         # hymba: mamba heads run in parallel with attn
    ssm_conv: int = 4
    rwkv_head_size: int = 64

    # --- embeddings / frontends --------------------------------------------
    tie_embeddings: bool = True
    frontend: str = "none"     # none | audio_stub | vision_stub
    vision_patches: int = 256  # number of prefix patch embeddings (vlm)

    norm_eps: float = 1e-5
    dtype: str = "bfloat16"

    # ----- derived ----------------------------------------------------------
    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        """Stacked multi-head key width — the SALS projection operates here."""
        return self.n_kv_heads * self.head_dim

    @property
    def group_size(self) -> int:
        return max(1, self.n_heads // max(1, self.n_kv_heads))

    @property
    def has_attention(self) -> bool:
        return self.family != "ssm"

    @property
    def is_decoder(self) -> bool:
        return self.family != "encoder"

    @property
    def expert_d_ff(self) -> int:
        return self.moe_d_ff or self.d_ff

    def param_count(self) -> int:
        """Analytic parameter count (embeddings + blocks + head)."""
        d, h = self.d_model, self.head_dim
        emb = self.vocab_size * d
        head = 0 if self.tie_embeddings else self.vocab_size * d
        per_layer = 0
        if self.family == "ssm":  # rwkv6: time-mix + channel-mix
            per_layer = 4 * d * d + d * self.d_ff * 2 + 6 * d  # approx
        else:
            attn = d * self.q_dim + 2 * d * self.kv_dim + self.q_dim * d
            if self.family == "moe":
                ff_in = 3 * self.d_model * self.expert_d_ff
                mlp = self.n_experts * ff_in + self.n_shared_experts * 3 * d * self.d_ff
                mlp += d * self.n_experts  # router
            else:
                mlp = 3 * d * self.d_ff
            if self.family == "hybrid":
                ssm_d = self.ssm_heads * self.head_dim
                mlp += 2 * d * ssm_d + ssm_d * d + ssm_d * (2 * self.ssm_state + 2)
            per_layer = attn + mlp
        return emb + head + self.n_layers * per_layer

    def active_param_count(self) -> int:
        """Params touched per token (MoE: only routed experts)."""
        if self.family != "moe":
            return self.param_count()
        d = self.d_model
        attn = d * self.q_dim + 2 * d * self.kv_dim + self.q_dim * d
        act_mlp = (self.experts_per_token + self.n_shared_experts) * 3 * d * self.expert_d_ff
        act_mlp += d * self.n_experts
        emb = self.vocab_size * d
        head = 0 if self.tie_embeddings else self.vocab_size * d
        return emb + head + self.n_layers * (attn + act_mlp)

    def reduced(self, **overrides) -> "ModelConfig":
        """A tiny same-family config for CPU smoke tests."""
        small = dict(
            n_layers=min(self.n_layers, 3),
            d_model=128,
            n_heads=4,
            n_kv_heads=min(self.n_kv_heads, 2) if self.n_kv_heads > 1 else 1,
            head_dim=32,
            d_ff=256,
            vocab_size=512,
            name=self.name + "-smoke",
        )
        if self.family == "moe":
            # high capacity factor: drop-free routing so reduced-config
            # prefill+decode exactly matches forward (tests)
            small.update(n_experts=4, experts_per_token=min(2, self.experts_per_token),
                         moe_d_ff=128, moe_capacity_factor=8.0)
        if self.family == "hybrid":
            small.update(ssm_heads=2, ssm_state=8)
        if self.family == "ssm":
            small.update(rwkv_head_size=16)
        if self.family == "vlm":
            small.update(vision_patches=16)
        small.update(overrides)
        return replace(self, **small)


# ---------------------------------------------------------------------------
# SALS (the paper's technique)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class SALSConfig:
    """Sparse Attention in Latent Space settings (paper §4, §5.1).

    ``rank_ratio``  d_r = r / kv_dim        (paper: 0.25 / 0.125)
    ``score_ratio`` r* = score_ratio · r    (paper: 0.5)
    ``n_critical``  top-k budget y          (paper: 432 @4k, doubled @32k)
    ``n_sink``      always-kept prefix x    (paper: 16)
    ``n_recent``    always-kept suffix z    (paper: 64; high-precision window)
    ``v_bits``      value-cache quant bits  (paper: 4b @25%, 2b @12.5%;
                    TPU-native int8/int4 used here, see DESIGN §7)
    """

    enabled: bool = True
    rank_ratio: float = 0.25
    score_ratio: float = 0.5
    n_critical: int = 432
    n_sink: int = 16
    n_recent: int = 64
    v_bits: int = 8
    v_group: int = 64
    k_latent_dtype: str = "bfloat16"   # "int8" = beyond-paper latent quant
    skip_layers_front: int = 2
    skip_layers_back: int = 1

    def rank(self, kv_dim: int) -> int:
        r = int(round(self.rank_ratio * kv_dim))
        return max(8, min(kv_dim, _round_to(r, 8)))

    def score_rank(self, kv_dim: int) -> int:
        r = self.rank(kv_dim)
        return max(8, _round_to(int(round(self.score_ratio * r)), 8))

    def n_selected(self, seq_len: int) -> int:
        """Total tokens attended per decode step."""
        return min(seq_len, self.n_sink + self.n_critical + self.n_recent)

    def sals_layer_mask(self, n_layers: int):
        """Per-layer bool list — True where SALS sparsification is active."""
        mask = []
        for i in range(n_layers):
            skip = i < self.skip_layers_front or i >= n_layers - self.skip_layers_back
            mask.append(not skip)
        return mask


def _round_to(x: int, m: int) -> int:
    return max(m, ((x + m - 1) // m) * m)


# Paper settings (§5): SALS-25% and SALS-12.5%
SALS_25 = SALSConfig(rank_ratio=0.25, v_bits=8, n_critical=432)
SALS_125 = SALSConfig(rank_ratio=0.125, v_bits=4, n_critical=432)


# ---------------------------------------------------------------------------
# Mesh / parallelism
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class MeshConfig:
    """Device mesh + sharding strategy.

    ``dist_mode`` for SALS decode:
      "global" — paper-faithful: scores all-gathered, one global top-k
      "local"  — beyond-paper: per-shard top-k + LSE merge (DESIGN §4)
    """

    shape: Tuple[int, ...] = (16, 16)
    axis_names: Tuple[str, ...] = ("data", "model")
    dist_mode: str = "local"
    pipeline_stages: int = 1           # >1 enables GPipe over leading axis
    seq_parallel: bool = True          # shard residual stream on model axis
    remat: str = "block"               # none | block | full

    @property
    def n_devices(self) -> int:
        n = 1
        for s in self.shape:
            n *= s
        return n

    @property
    def data_axes(self) -> Tuple[str, ...]:
        return tuple(a for a in self.axis_names if a != "model")

    @property
    def model_axis(self) -> str:
        return "model"


@dataclass(frozen=True)
class ShapeConfig:
    """One (input-shape) cell of the assigned grid."""

    name: str
    kind: str        # train | prefill | decode
    seq_len: int
    global_batch: int
    microbatches: int = 1  # grad-accumulation splits for train cells


SHAPES = {
    "train_4k": ShapeConfig("train_4k", "train", 4_096, 256),
    "prefill_32k": ShapeConfig("prefill_32k", "prefill", 32_768, 32),
    "decode_32k": ShapeConfig("decode_32k", "decode", 32_768, 128),
    "long_500k": ShapeConfig("long_500k", "decode", 524_288, 1),
}


# ---------------------------------------------------------------------------
# Train / serve
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class TrainConfig:
    steps: int = 300
    batch_size: int = 8
    seq_len: int = 256
    lr: float = 3e-4
    warmup_steps: int = 20
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    b1: float = 0.9
    b2: float = 0.95
    microbatches: int = 1
    checkpoint_every: int = 50
    keep_checkpoints: int = 3
    log_every: int = 10
    seed: int = 0
    grad_compression: str = "none"   # none | int8_ef
    ckpt_dir: str = "artifacts/ckpt"


@dataclass(frozen=True)
class ServeConfig:
    """``scheduler`` picks the batching discipline:
      "continuous" — slot-arena continuous batching: requests join a running
                     batch in empty slots between decode steps (per-slot
                     lengths, ragged per-row decode positions)
      "static"     — GPT-fast-style: fixed batches run prefill→drain
    ``pad_id`` right-pads ragged prompts (masked via per-slot lengths —
    pad tokens are never selectable nor attended).

    ``prefill_chunk`` is the fixed chunk width of the chunked prefill path:
    admission prefill runs as a loop over ONE compiled chunk HLO (the chunk
    offset is a traced scalar), so prompts of any length share one trace and
    peak activation memory is (1, chunk, d) instead of (1, S_prompt, d).
    ``max_seq_len`` must be a multiple of it (attention families).
    ``prefill_token_budget`` bounds how many prefill tokens the continuous
    scheduler spends between consecutive decode steps — resident sequences
    never stall longer than ~budget (rounded down to whole chunks, minimum
    one chunk) regardless of arriving prompt length.

    PAGED latent cache (ISSUE 5).  ``page_size`` > 0 switches the SALS
    segments' backing store from the dense ``(B, max_seq, ·)`` slot arena
    to a refcounted page pool (``core/pager.py``): per-token fields become
    ``(n_pages, page_size, ·)`` pools indexed through per-sequence page
    tables, so HBM is pinned per LIVE TOKEN (rounded up to a page) instead
    of per slot×max_seq, and same-prefix requests share one stored copy of
    their prefix pages (``prefix_cache``).

    Sizing rule: page-table overhead is ``4 / page_size`` bytes per token
    (one int32 table entry per page) — < 2% of the latent payload for any
    ``page_size`` ≥ 1 at the paper geometry (r·b_lat ≈ 2 KiB/token), so
    pick ``page_size`` by the score kernel's DMA burst (it streams one
    page per DMA; reconstruct copies an 8-row tile per selected token
    whatever the page size, and its scale chunk where a page holds 128
    rows or more) and prefix-sharing granularity
    (smaller pages share shorter common prefixes), NOT by metadata cost.
    ``n_pages`` (0 = auto: ``max_batch · max_seq_len / page_size``, the
    dense-equivalent capacity) sizes the pool; admission reserves a
    prompt's pages up front and decode growth may evict-to-requeue on
    exhaustion, so the pool bounds LIVE tokens, not slots.

    Validated at construction (not inside jit): ``max_seq_len`` must be a
    multiple of ``page_size``; ``page_size`` a multiple of
    ``prefill_chunk`` (prefix-resume boundaries are chunk-aligned); the
    pool must fit at least one max-length sequence.

    TWO-TIER pool (ISSUE 7).  ``hbm_pages`` > 0 splits the paged pool
    into an HBM hot tier and a host-memory cold tier
    (``core/tiering.py``): the device payload pools (full-r latents +
    quantized V) shrink to ``hbm_pages`` slots (+ trash), while a
    dedicated ``k_score`` device pool keeps the leading ``r*`` score
    columns of EVERY live page HBM-resident (the score pass is oblivious
    to tiering), so ``pool_pages`` — the LIVE capacity — is bounded by
    host RAM.  Selected-but-cold pages are fetched before the
    reconstruct kernel runs; ``tier_prefetch`` warms pages from the
    previous decode step's selection (the paper's stability insight —
    `benchmarks/overlap_score.py` measures the hit rate this predicts).
    0 = untiered PR 5 behavior (every page's payload HBM-resident)."""

    max_seq_len: int = 4096
    max_batch: int = 8
    max_new_tokens: int = 64
    temperature: float = 0.0
    sals: SALSConfig = field(default_factory=SALSConfig)
    seed: int = 0
    pad_id: int = 0
    scheduler: str = "continuous"     # continuous | static
    prefill_chunk: int = 32           # chunked-prefill step width (tokens)
    prefill_token_budget: int = 256   # prefill tokens between decode steps
    page_size: int = 0                # >0: paged latent cache (tokens/page)
    n_pages: int = 0                  # pool size (0 = max_batch·max_seq/ps)
    prefix_cache: bool = True         # COW prefix sharing (paged mode only)
    hbm_pages: int = 0                # >0: HBM hot-tier payload slots
    tier_prefetch: bool = True        # warm prev-step selection (tiered)
    # Each prefix-cache entry retains its registrant's DENSE single-request
    # cache + prefill scratch ((L, 1, max_seq, ·) — the append-only resume
    # state) on top of its pinned pool pages, so the entry COUNT bounds
    # HBM beyond the pool: LRU entries are evicted past this cap.
    prefix_cache_entries: int = 4
    # Deepest shareable prefix, in pages.  Prefill-resume needs a ring
    # snapshot per page boundary (the one non-append-only piece of prefill
    # state), captured during every chunked prefill — this cap bounds the
    # snapshots to prefix_share_pages × (L_sals, 1, n_recent, Hkv, dh)·2
    # per task instead of max_seq/page_size of them, and covers typical
    # system prompts (8 pages × page_size tokens) without trying to dedup
    # arbitrarily deep prompt bodies.
    prefix_share_pages: int = 8

    # --- fault tolerance (ISSUE 6) -----------------------------------------
    # Bounded admission queue: 0 = unbounded (legacy).  When full, "reject"
    # makes submit() raise QueueFull; "shed-oldest" cancels the OLDEST
    # pending request to admit the new one (freshness-biased shedding).
    max_queue: int = 0
    queue_policy: str = "reject"      # reject | shed-oldest
    # Per-request deadline in SCHEDULER STEPS from submission (0 = none).
    # Steps — not wall-clock — keep chaos tests deterministic; one step is
    # one decode iteration of the continuous loop.
    request_timeout_steps: int = 0
    # Per-request WALL-CLOCK deadline in milliseconds from submission
    # (0 = none).  Either deadline may fire — steps for deterministic
    # tests, wall-clock for production SLOs — and both sweep through the
    # same teardown path (fail-or-retry, pages released, callbacks fired).
    request_timeout_ms: float = 0.0
    # Transient per-request faults (injected faults, NaN logits, torn
    # admissions) retry up to this many times with exponential backoff in
    # scheduler steps: retry i waits retry_backoff_steps · 2^(i-1), capped.
    max_request_retries: int = 2
    retry_backoff_steps: int = 1
    retry_backoff_cap_steps: int = 16
    # Run audit_serving_state() every N scheduler steps (0 = off outside
    # teardowns; chaos tests set 1).  The audit is host-side bookkeeping —
    # O(pages + residents) — so small N is affordable even in production.
    audit_every: int = 0

    # --- SLO scheduling (ISSUE 8) ------------------------------------------
    # Number of priority classes; Request.priority must be in
    # [0, priority_classes).  Higher value = more urgent.  With > 1 class
    # the continuous scheduler preempts low-priority residents when a
    # strictly higher class is waiting and no slot is free.
    priority_classes: int = 1
    # What preemption does to the victim:
    #   "park"  — detach the slot but KEEP the pages (refcounts held);
    #             resume continues token-exact with no re-prefill.  Needs
    #             the paged cache (page_size > 0) when priority_classes > 1.
    #   "evict" — destructive evict-to-requeue (PR 5 machinery): pages
    #             released, request re-prefills from scratch.
    #   "none"  — never preempt; priorities only order admission.
    preempt_policy: str = "park"      # park | evict | none
    # Deficit-round-robin quantum (tokens per rotation turn) for per-tenant
    # fairness WITHIN a priority class.  A request's cost is
    # len(prompt) + max_new_tokens; larger quanta trade fairness
    # granularity for fewer rotation scans.
    tenant_quantum: int = 256
    # Per-tenant admission rate limit in tokens per scheduler step
    # (0 = unlimited).  Credit accrues while a tenant has pending work
    # (capped at 32 steps' worth) and admission debits the request cost —
    # credit may go negative, pacing bursts instead of rejecting them.
    tenant_rate: float = 0.0
    # Per-tenant cap on in-flight requests (PREFILLING + DECODING +
    # PARKED); 0 = uncapped.
    tenant_max_inflight: int = 0
    # Ring-buffer cap on the observability ledgers (pool_gauges,
    # admissions, prefill_chunks): keep only the most recent N rows.
    # 0 = unbounded (tests read full history); production should set this —
    # the ledgers otherwise grow one row per step/chunk forever.
    gauge_history: int = 0

    # --- speculative decoding (ISSUE 9) ------------------------------------
    # Verify-window width Q for self-speculative decoding (0 or 1 = off).
    # Each decode step drafts Q−1 tokens per row (n-gram prompt lookup,
    # serve/draft.py), runs ONE windowed decode HLO over [pending token +
    # drafts] — one latent selection serves the whole window — and commits
    # the longest matching prefix.  Greedy verify is token-exact with
    # sequential decode whenever n_critical covers the selectable range
    # (the window's one selection then IS each position's selection);
    # below that budget the amortized selection can drift from per-token
    # selection — the same approximation knob SALS itself turns.  Requires
    # Q <= sals.n_recent (the selection at the
    # window's last position must never cover uncommitted slots), an
    # attention family, and the untiered cache (the tiered hot-set
    # prefetch contract is per committed step).
    spec_window: int = 0

    def __post_init__(self):
        if self.max_queue < 0:
            raise ValueError("max_queue must be >= 0 (0 = unbounded)")
        if self.queue_policy not in ("reject", "shed-oldest"):
            raise ValueError(f"unknown queue_policy {self.queue_policy!r}")
        if self.request_timeout_steps < 0 or self.audit_every < 0:
            raise ValueError("request_timeout_steps / audit_every >= 0")
        if self.request_timeout_ms < 0:
            raise ValueError("request_timeout_ms must be >= 0 (0 = none)")
        if self.spec_window < 0 or self.spec_window > 8:
            raise ValueError("spec_window must be in [0, 8] (the windowed "
                             "kernels take q_len <= 8 query blocks)")
        if self.spec_window > 1:
            if self.sals.enabled and self.spec_window > self.sals.n_recent:
                raise ValueError(
                    f"spec_window {self.spec_window} > sals.n_recent "
                    f"{self.sals.n_recent}: the verify window's selection "
                    "mask would cover uncommitted cache slots")
            if self.hbm_pages:
                raise ValueError(
                    "speculative decoding needs the untiered cache: the "
                    "tiered hot-set prefetch contract is per committed "
                    "step (set hbm_pages=0 or spec_window=0)")
            if self.temperature > 0.0:
                raise ValueError(
                    "speculative decoding is greedy-only: the verify "
                    "accepts drafts by exact argmax match, which has no "
                    "sampled analogue here (set temperature=0.0 or "
                    "spec_window=0)")
        if (self.max_request_retries < 0 or self.retry_backoff_steps < 0
                or self.retry_backoff_cap_steps < 0):
            raise ValueError("retry knobs must be >= 0")
        if self.page_size < 0 or self.n_pages < 0:
            raise ValueError("page_size / n_pages must be >= 0")
        if self.hbm_pages < 0:
            raise ValueError("hbm_pages must be >= 0 (0 = untiered)")
        if self.priority_classes < 1:
            raise ValueError("priority_classes must be >= 1")
        if self.preempt_policy not in ("park", "evict", "none"):
            raise ValueError(f"unknown preempt_policy {self.preempt_policy!r}")
        if self.tenant_quantum < 1:
            raise ValueError("tenant_quantum must be >= 1")
        if self.tenant_rate < 0 or self.tenant_max_inflight < 0:
            raise ValueError("tenant_rate / tenant_max_inflight >= 0")
        if self.gauge_history < 0:
            raise ValueError("gauge_history must be >= 0 (0 = unbounded)")
        if (self.priority_classes > 1 and self.preempt_policy == "park"
                and self.page_size == 0):
            raise ValueError(
                "preempt_policy 'park' holds the victim's PAGES across the "
                "park and needs the paged latent cache (page_size > 0); "
                "dense arenas must use preempt_policy 'evict' or 'none'")
        if self.page_size == 0:
            if self.hbm_pages:
                raise ValueError("hbm_pages needs the paged latent cache "
                                 "(set page_size > 0)")
            return                            # dense slot arena: no paging
        if self.max_seq_len % self.page_size:
            raise ValueError(
                f"max_seq_len {self.max_seq_len} must be a multiple of "
                f"page_size {self.page_size} (page tables map whole pages)")
        if self.page_size % self.prefill_chunk:
            raise ValueError(
                f"page_size {self.page_size} must be a multiple of "
                f"prefill_chunk {self.prefill_chunk}: prefix-cache resume "
                "offsets are page boundaries and must land on chunk "
                "boundaries")
        if self.scheduler != "continuous":
            raise ValueError("the paged latent cache requires the "
                             "continuous scheduler (admission = page "
                             "reservation)")
        if self.n_pages and self.n_pages * self.page_size < self.max_seq_len:
            raise ValueError(
                f"n_pages {self.n_pages} × page_size {self.page_size} = "
                f"{self.n_pages * self.page_size} tokens cannot hold one "
                f"max_seq_len {self.max_seq_len} sequence")
        if self.hbm_pages:
            # every resident row pins its write page hot, and a demand
            # fetch needs at least one spillable slot on top of the pins
            if self.hbm_pages < self.max_batch + 1:
                raise ValueError(
                    f"hbm_pages {self.hbm_pages} must be >= max_batch + 1 "
                    f"= {self.max_batch + 1}: each resident pins its write "
                    "page hot and demand fetches need one spillable slot")
            if self.hbm_pages > self.pool_pages:
                raise ValueError(
                    f"hbm_pages {self.hbm_pages} exceeds the pool capacity "
                    f"{self.pool_pages} — the hot tier cannot outgrow the "
                    "pool (use the untiered pool instead)")

    @property
    def pool_pages(self) -> int:
        """Effective pool size (auto = dense-equivalent capacity)."""
        if not self.page_size:
            return 0
        return self.n_pages or (self.max_batch * self.max_seq_len
                                // self.page_size)


def asdict(cfg) -> dict:
    return dataclasses.asdict(cfg)
