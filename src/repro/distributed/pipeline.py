"""GPipe pipeline parallelism over a mesh axis (DESIGN §4, beyond-paper).

The layer stack is split into ``P`` contiguous stages mapped onto the
``pipe`` mesh axis (the 'pod' axis of the two-pod mesh: cross-pod links
carry exactly ONE (mb, seq, d) activation per tick — the point of PP at
pod scale).  Schedule is plain GPipe: M microbatches, T = M + P - 1 ticks,
bubble fraction (P-1)/T.

Implementation: ``shard_map`` manual over the pipe axis (model/data stay
auto → pjit TP/DP inside each stage), a ``lax.scan`` over ticks, and a
``ppermute`` ring push of the boundary activation each tick.  Backward is
jax autodiff through the scan + ppermute (reverse permutes), so the same
function trains.

The first stage reads microbatch embeddings; the last stage accumulates
per-microbatch mean-CE partials.  Stages are selected by masking on
``jax.lax.axis_index`` — every stage runs the same code (SPMD), with its
own slice of the stacked block params.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def stage_slice(tree, stage: int, n_stages: int, n_layers: int):
    """Slice stacked (L, ...) block params to one stage's layers."""
    per = n_layers // n_stages
    return jax.tree.map(lambda a: a[stage * per:(stage + 1) * per], tree)


def gpipe_loss(block_fn: Callable, embed_fn: Callable, head_loss_fn: Callable,
               axis: str = "pipe"):
    """Build a pipelined loss:  f(stage_blocks, io_params, batch) -> loss.

    block_fn(stage_blocks, x)      — run this stage's layer slice
    embed_fn(io_params, mb_batch)  — tokens -> x (stage 0 only)
    head_loss_fn(io_params, x, mb_batch) — final norm+CE (last stage only)

    stage_blocks: the CALLER passes the per-stage parameter slice via
    shard_map in_specs (leading axis = pipe).  io_params (embeddings, final
    norm) are replicated — they're small next to the blocks.
    batch: microbatched pytree with leading axis M.
    """

    def loss_fn(stage_blocks, io_params, batch):
        p = jax.lax.axis_size(axis)
        sid = jax.lax.axis_index(axis)
        m = jax.tree.leaves(batch)[0].shape[0]
        t_total = m + p - 1

        x0 = embed_fn(io_params, jax.tree.map(lambda a: a[0], batch))
        buf0 = jnp.zeros_like(x0)

        def tick(carry, t):
            buf, loss_sum = carry
            mb_idx = jnp.clip(t, 0, m - 1)
            mb = jax.tree.map(lambda a: a[mb_idx], batch)
            # stage 0 ingests microbatch t (if still in range)
            x_in = jnp.where(jnp.logical_and(sid == 0, t < m),
                             embed_fn(io_params, mb), buf)
            y = block_fn(stage_blocks, x_in)
            # last stage: microbatch (t - p + 1) completes this tick
            out_idx = jnp.clip(t - (p - 1), 0, m - 1)
            mb_out = jax.tree.map(lambda a: a[out_idx], batch)
            # (1,)-shaped, not scalar: rank-0 values crossing the shard_map
            # boundary as autodiff residuals trip the out-spec rank check
            # (they cannot concatenate along the pipe axis)
            mb_loss = head_loss_fn(io_params, y, mb_out).reshape(1)
            take = jnp.logical_and(sid == p - 1, t >= p - 1)
            loss_sum = loss_sum + jnp.where(take, mb_loss, 0.0)
            # push boundary activation to the next stage (ring; the wrap
            # edge P-1 -> 0 delivers zeros' worth of data that stage 0
            # overwrites with the next microbatch embedding)
            nxt = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % p) for i in range(p)])
            return (nxt, loss_sum), None

        (_, loss_sum), _ = jax.lax.scan(
            tick, (buf0, jnp.zeros((1,), jnp.float32)), jnp.arange(t_total))
        # everyone returns the last stage's mean loss
        loss = jax.lax.psum(
            jnp.where(sid == p - 1, loss_sum, 0.0), axis) / m
        return loss[0]

    return loss_fn


def bubble_fraction(n_micro: int, n_stages: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)
