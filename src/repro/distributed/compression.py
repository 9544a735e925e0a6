"""int8 error-feedback gradient compression (DESIGN §4, beyond-paper).

Under pure pjit the DP gradient reduction is implicit; to compress the
cross-replica traffic we drop to ``shard_map`` over the data axes and do the
reduction by hand:

    local grad -> (+ EF residual) -> per-tensor symmetric int8 quantize
    -> all_gather int8 codes + f32 scales over the data axes   (≈4× fewer
       bytes on the wire than an f32 ring all-reduce)
    -> dequantize + mean locally -> new residual = local - dequant(local)

The residual carries this step's quantization error into the next step
(error feedback), which keeps SGD/Adam convergence unbiased in practice.
Tensors smaller than ``MIN_COMPRESS`` elements ride the normal psum — scales
and norms dominate their traffic anyway.

``compressed_mean_grads`` is the shard_map body; ``wrap_grad_fn`` applies it
to a value_and_grad function's output inside an existing shard_map context.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

MIN_COMPRESS = 4096


def quantize_int8(g: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    a = jnp.max(jnp.abs(g))
    scale = jnp.maximum(a / 127.0, 1e-12)
    q = jnp.clip(jnp.round(g / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    return q.astype(jnp.float32) * scale


def compressed_mean_grads(grads, residual, axis_names: Tuple[str, ...]):
    """Inside shard_map: mean-reduce ``grads`` over ``axis_names`` with int8
    codes on the wire.  Returns (mean_grads, new_residual).

    grads/residual: local f32 pytrees (same structure).
    """
    n = 1
    for ax in axis_names:
        n *= jax.lax.axis_size(ax)

    def one(g, r):
        g = g.astype(jnp.float32)
        if g.size < MIN_COMPRESS:
            return jax.lax.pmean(g, axis_names), jnp.zeros_like(g)
        gc = g + r                                 # error feedback
        q, scale = quantize_int8(gc)
        deq_local = dequantize_int8(q, scale)
        new_r = gc - deq_local                      # local quantization error
        # gather int8 codes + scales from every shard, average locally
        qg = q
        sg = scale[None]
        for ax in axis_names:
            qg = jax.lax.all_gather(qg, ax, axis=0)
            sg = jax.lax.all_gather(sg, ax, axis=0)
        qg = qg.reshape(n, *g.shape)
        sg = sg.reshape(n, *([1] * g.ndim))
        mean = jnp.mean(qg.astype(jnp.float32) * sg, axis=0)
        return mean, new_r

    flat_g, treedef = jax.tree.flatten(grads)
    flat_r = treedef.flatten_up_to(residual)
    out = [one(g, r) for g, r in zip(flat_g, flat_r)]
    return (jax.tree.unflatten(treedef, [o[0] for o in out]),
            jax.tree.unflatten(treedef, [o[1] for o in out]))


def init_residual(params) -> dict:
    return jax.tree.map(
        lambda p: jnp.zeros(p.shape, jnp.float32) if p.size >= MIN_COMPRESS
        else jnp.zeros(p.shape, jnp.float32), params)


def wire_bytes(params, axis_size: int) -> Tuple[int, int]:
    """(compressed, uncompressed) bytes moved per reduction — bookkeeping."""
    comp = unc = 0
    for p in jax.tree.leaves(params):
        unc += 2 * p.size * 4                      # ring all-reduce ≈ 2N f32
        if p.size >= MIN_COMPRESS:
            comp += (axis_size - 1) * (p.size + 4)  # all_gather int8 + scale
        else:
            comp += 2 * p.size * 4
    return comp, unc
