"""Production mesh construction.

Single pod:  (data=16, model=16)              — 256 chips (one v5e pod)
Multi-pod:   (pod=2, data=16, model=16)       — 512 chips across 2 pods

The 'model' axis carries TP/EP/SP collectives (intra-pod ICI only); 'data'
carries FSDP all-gather/reduce-scatter (intra-pod); 'pod' carries ONLY the
plain DP gradient all-reduce — the standard hierarchical layout that keeps
the slow cross-pod links off the per-layer critical path.

Defined as functions, not module constants: importing this module never
touches jax device state (the dry-run must set XLA_FLAGS first).
"""
from __future__ import annotations

import jax

from repro.config import MeshConfig
from repro.distributed.sharding import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh_config(*, multi_pod: bool = False, dist_mode: str = "local",
                     seq_parallel: bool = True) -> MeshConfig:
    return MeshConfig(
        shape=(2, 16, 16) if multi_pod else (16, 16),
        axis_names=("pod", "data", "model") if multi_pod else ("data", "model"),
        dist_mode=dist_mode,
        seq_parallel=seq_parallel,
    )


def make_host_mesh(max_devices: int = 0):
    """Degenerate mesh over the locally visible devices (CPU tests/examples).
    Shape (1, n) with the same axis names as the single-pod mesh."""
    n = len(jax.devices())
    if max_devices:
        n = min(n, max_devices)
    return make_mesh((1, n), ("data", "model"))
