"""Pallas TPU flash attention (train / prefill baseline — the paper's FA2
counterpart).

Blocked online-softmax attention with explicit VMEM tiling:

  grid = (B, H, Sq/bq, Sk/bk), kv axis innermost ("arbitrary" — sequential),
  q/k/v blocks of (bq|bk, dh) live in VMEM; running (m, l, acc) stats in VMEM
  scratch carried across the kv grid axis.  Fully-above-diagonal causal
  blocks are skipped with ``pl.when`` (no wasted MXU work), and the output
  tile is written once on the last kv step.

Block sizes default to 512×512 with dh up to 256 — working set
bq·dh + bk·dh + bq·bk + acc ≈ 1.5 MB ≪ 16 MB VMEM; matmul dims are
128-aligned for the MXU.

Validated on CPU via ``interpret=True`` against ``ref.attention_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode
from repro.kernels.ref import NEG_INF

DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_s, l_s, acc_s, *,
               causal: bool, softcap: float, scale: float, q_off: int,
               nk: int, bq: int, bk: int, prefix_len: int):
    i = pl.program_id(2)   # q block
    j = pl.program_id(3)   # kv block

    @pl.when(j == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    # skip blocks entirely above the causal diagonal (prefix columns live)
    live = jnp.asarray(True)
    if causal:
        live = ((j * bk) <= (q_off + i * bq + bq - 1)) | \
            jnp.asarray(j * bk < prefix_len)

    @pl.when(live)
    def _update():
        q = q_ref[0, 0].astype(jnp.float32)                 # (bq, dh)
        k = k_ref[0, 0].astype(jnp.float32)                 # (bk, dh)
        v = v_ref[0, 0].astype(jnp.float32)                 # (bk, dh)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # (bq, bk)
        if softcap:
            logits = softcap * jnp.tanh(logits / softcap)
        if causal:
            qp = q_off + i * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 0)
            kp = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            keep = qp >= kp
            if prefix_len:
                keep = keep | (kp < prefix_len)
            logits = jnp.where(keep, logits, NEG_INF)
        m_prev = m_s[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1))
        p = jnp.exp(logits - m_new[:, None])
        p = jnp.where(logits <= NEG_INF / 2, 0.0, p)
        alpha = jnp.exp(m_prev - m_new)
        l_s[:, 0] = l_s[:, 0] * alpha + jnp.sum(p, axis=-1)
        acc_s[...] = acc_s[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_s[:, 0] = m_new

    @pl.when(j == nk - 1)
    def _finish():
        o_ref[0, 0] = (acc_s[...] /
                       jnp.maximum(l_s[:, 0], 1e-30)[:, None]
                       ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "softcap", "prefix_len",
                                             "block_q", "block_k"))
def flash_attention_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                           causal: bool = True, softcap: float = 0.0,
                           prefix_len: int = 0,
                           block_q: int = DEFAULT_BLOCK_Q,
                           block_k: int = DEFAULT_BLOCK_K) -> jnp.ndarray:
    """q: (B, Sq, H, dh); k/v: (B, Sk, H, dh), GQA pre-expanded.

    Returns (B, Sq, H, dh) in q.dtype.  Sequence lengths are padded to the
    block size internally (masked via causal/softmax semantics).
    """
    b, sq, h, dh = q.shape
    sk = k.shape[1]
    bq = min(block_q, _ceil_mult(sq, 128) if sq >= 128 else sq)
    bk = min(block_k, _ceil_mult(sk, 128) if sk >= 128 else sk)

    sq_p, sk_p = _ceil_mult(sq, bq), _ceil_mult(sk, bk)
    qt = jnp.moveaxis(q, 2, 1)                       # (B, H, Sq, dh)
    kt = jnp.moveaxis(k, 2, 1)
    vt = jnp.moveaxis(v, 2, 1)
    if sq_p != sq:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, sq_p - sq), (0, 0)))
    if sk_p != sk:
        # padded kv rows: keys at +inf-distance — mask them via an explicit
        # causal guard (padded q rows attend to everything; discarded below)
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, sk_p - sk), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, sk_p - sk), (0, 0)))
    nq, nk = sq_p // bq, sk_p // bk
    q_off = sk - sq  # decode-style alignment when sq < sk

    if sk_p != sk and not causal:
        raise ValueError("kv padding requires causal masking")

    kernel = functools.partial(
        _fa_kernel, causal=causal or prefix_len > 0 or sk_p != sk,
        softcap=softcap, scale=dh ** -0.5, q_off=q_off, nk=nk, bq=bq, bk=bk,
        prefix_len=prefix_len)

    out = pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, dh), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, bk, dh), lambda b_, h_, i, j: (b_, h_, j, 0)),
            pl.BlockSpec((1, 1, bk, dh), lambda b_, h_, i, j: (b_, h_, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, dh),
                               lambda b_, h_, i, j: (b_, h_, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq_p, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),    # running max m
            pltpu.VMEM((bq, 1), jnp.float32),    # running sum l
            pltpu.VMEM((bq, dh), jnp.float32),   # output accumulator
        ],
        interpret=interpret_mode(),
        name="flash_attention",
    )(qt, kt, vt)
    out = jnp.moveaxis(out, 1, 2)
    return out[:, :sq]


def _ceil_mult(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m
