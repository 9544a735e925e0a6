"""Logical-axis sharding: models annotate activations with *logical* axis
names; a context installed by the launcher maps them to mesh axes.

This keeps model code mesh-agnostic (the same ``mlp_apply`` runs on a laptop,
a 256-chip pod, or the 512-chip two-pod mesh) while the launcher controls the
parallelism layout per (arch × shape) cell.

Logical axes
------------
  batch         global batch                  -> ("pod","data") / ("data",)
  seq           in-block sequence             -> None (full within TP block)
  residual_seq  residual stream between blocks-> "model" (Megatron SP) | None
  embed         d_model                       -> None
  heads         query heads                   -> "model"
  kv_heads      kv heads                      -> "model" when divisible
  mlp           FFN hidden                    -> "model"
  experts       MoE expert dim                -> "model"
  vocab         vocabulary                    -> "model"
  kv_seq        cached sequence (decode)      -> "model" | ("data","model")
  latent        SALS latent rank r            -> None
"""
from __future__ import annotations

import contextlib
import threading
from dataclasses import dataclass, field
from typing import Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.config import MeshConfig, ShapeConfig

_state = threading.local()


@dataclass
class ShardingCtx:
    mesh: Mesh
    rules: dict

    def spec(self, logical: Tuple[Optional[str], ...]) -> P:
        parts = []
        for name in logical:
            if name is None:
                parts.append(None)
            else:
                parts.append(self.rules.get(name))
        return P(*parts)

    def sharding(self, logical) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec(logical))


def current_ctx() -> Optional[ShardingCtx]:
    return getattr(_state, "ctx", None)


@contextlib.contextmanager
def use_sharding(mesh: Mesh, rules: dict):
    prev = current_ctx()
    _state.ctx = ShardingCtx(mesh, rules)
    try:
        with mesh:
            yield _state.ctx
    finally:
        _state.ctx = prev


def constrain(x, logical: Tuple[Optional[str], ...]):
    """with_sharding_constraint by logical axis names; no-op outside a ctx."""
    ctx = current_ctx()
    if ctx is None:
        return x
    if x.ndim != len(logical):
        raise ValueError(f"rank mismatch: {x.shape} vs logical {logical}")
    return jax.lax.with_sharding_constraint(x, ctx.sharding(logical))


def logical_spec(logical) -> P:
    ctx = current_ctx()
    if ctx is None:
        return P(*([None] * len(logical)))
    return ctx.spec(logical)


def make_mesh(shape: Tuple[int, ...], axis_names: Tuple[str, ...],
              devices=None) -> Mesh:
    """A mesh whose axes are all ``Auto``: shardings are constraints the
    compiler propagates (``with_sharding_constraint`` and ``shard_map``
    work on them), not the explicit per-array types ``jax.make_mesh``
    now gives by default."""
    return jax.make_mesh(shape, axis_names,
                         axis_types=(jax.sharding.AxisType.Auto,)
                         * len(axis_names), devices=devices)


def mesh_axes_for(logical: str) -> Tuple[Tuple[str, ...], int]:
    """Physical mesh axes a logical axis maps to, and their combined size.

    Returns ((), 1) outside a sharding context or for an unsharded axis.
    Used by the grouped decode plan to decide whether the per-group kernels
    can run shard-locally (shard_map over these axes)."""
    ctx = current_ctx()
    if ctx is None:
        return (), 1
    rule = ctx.rules.get(logical)
    axes = (rule,) if isinstance(rule, str) else tuple(rule or ())
    sizes = dict(zip(ctx.mesh.axis_names, ctx.mesh.devices.shape))
    total = 1
    for a in axes:
        total *= sizes[a]
    return axes, total


# ---------------------------------------------------------------------------
# Default rule sets per run kind
# ---------------------------------------------------------------------------

def default_rules(mesh_cfg: MeshConfig, shape_cfg: Optional[ShapeConfig] = None) -> dict:
    """Logical->physical mapping for one (mesh, shape) cell."""
    axes = mesh_cfg.axis_names
    data_axes = tuple(a for a in axes if a not in ("model",))
    batch = data_axes if len(data_axes) > 1 else (data_axes[0] if data_axes else None)

    rules = {
        "batch": batch,
        "seq": None,
        "residual_seq": "model" if mesh_cfg.seq_parallel else None,
        "embed": None,
        "heads": "model",
        "kv_heads": None,
        "mlp": "model",
        "experts": "model",
        "vocab": "model",
        "kv_seq": "model",
        "kv_seq_full": None,   # skip-layer full-precision cache seq axis
        "latent": None,
    }

    if shape_cfg is not None and shape_cfg.kind == "decode":
        # decode: one-token steps — residual SP is pure overhead, and the
        # query heads must be REPLICATED: the SALS cache is sequence-sharded
        # (single-head latents), so head-sharded q would force XLA to
        # all-gather every selected-K block and the skip-layer caches to
        # co-locate the contraction (§Perf iteration A1: -70% collective
        # bytes on yi-9b×decode_32k).  One tiny q all-gather per layer
        # (B×H×dh ≈ 1 MiB) replaces per-cache gathers of 16 MiB..1 GiB.
        rules["residual_seq"] = None
        rules["heads"] = None
        if shape_cfg.global_batch == 1:
            # long-context single stream: spread the cache over everything
            # (incl. the skip-layer full caches — replicated they cost
            # ~1.6 GB/layer-pair at 500k and push granite/llama4 past HBM)
            rules["batch"] = None
            rules["kv_seq"] = tuple(axes)  # e.g. ("pod","data","model")
            rules["kv_seq_full"] = None    # build_decode overrides to
            # 'model' when the replicated skip cache would bust HBM
        else:
            rules["kv_seq"] = "model"
            rules["kv_seq_full"] = "model"
    return rules


# ---------------------------------------------------------------------------
# FSDP spec derivation (train): add 'data' sharding on top of the TP specs
# ---------------------------------------------------------------------------

def fsdp_specs(spec_tree, shape_tree, mesh: Mesh,
               fsdp_axis="data"):
    """ZeRO-3-style weight sharding: for every param, shard the largest
    still-unsharded dim over ``fsdp_axis`` (when divisible).  GSPMD then
    turns the DP gradient all-reduce into reduce-scatter + all-gather and
    the optimizer state inherits the sharding (ZeRO-1 for free).

    spec_tree: pytree of PartitionSpec (TP placements from *_specs(), or
    all-replicated for the pure-FSDP strategy).
    shape_tree: matching pytree of array shapes (from jax.eval_shape).
    fsdp_axis: one mesh axis name or a tuple (composite sharding, e.g.
    ("data", "model") = 256-way ZeRO-3); tuples degrade to their divisible
    prefix per param.
    """
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    axes = (fsdp_axis,) if isinstance(fsdp_axis, str) else tuple(fsdp_axis)

    def one(spec: P, shaped) -> P:
        shape = shaped.shape if hasattr(shaped, "shape") else tuple(shaped)
        parts = list(spec) + [None] * (len(shape) - len(spec))
        used = set()
        for p in parts:
            for a in ((p,) if isinstance(p, str) else (p or ())):
                used.add(a)
        free = tuple(a for a in axes if a not in used)
        best = None
        for i, (p, s) in enumerate(zip(parts, shape)):
            if p is not None:
                continue
            ax = list(free)
            while ax:                      # largest divisible prefix
                n = 1
                for a in ax:
                    n *= sizes[a]
                if s % n == 0 and s >= n:
                    break
                ax.pop()
            if ax and (best is None or s > shape[best[0]]):
                best = (i, ax)
        if best is not None:
            i, ax = best
            parts[i] = ax[0] if len(ax) == 1 else tuple(ax)
        return P(*parts)

    return jax.tree.map(one, spec_tree, shape_tree,
                        is_leaf=lambda x: isinstance(x, P))


def tree_shardings(mesh: Mesh, spec_tree):
    """PartitionSpec pytree -> NamedSharding pytree."""
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def sanitize_pspecs(spec_tree, shape_tree, mesh: Mesh):
    """Drop mesh axes from placements that don't divide the array dim.

    pjit rejects unevenly-sharded *arguments* (e.g. granite's 49155 vocab
    on a 16-way axis); this trims each placement from the right until the
    dim divides, falling back to replication.  Composite placements like
    ('pod','data','model') degrade gracefully to their divisible prefix.
    """
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def fix(spec: P, shaped) -> P:
        shape = shaped.shape if hasattr(shaped, "shape") else tuple(shaped)
        parts = list(spec) + [None] * (len(shape) - len(spec))
        out = []
        for dim, p in zip(shape, parts):
            if p is None:
                out.append(None)
                continue
            axes = (p,) if isinstance(p, str) else tuple(p)
            while axes:
                n = 1
                for a in axes:
                    n *= sizes[a]
                if dim % n == 0:
                    break
                axes = axes[:-1]
            if not axes:
                out.append(None)
            elif len(axes) == 1:
                out.append(axes[0])
            else:
                out.append(tuple(axes))
        return P(*out)

    return jax.tree.map(fix, spec_tree, shape_tree,
                        is_leaf=lambda x: isinstance(x, P))
