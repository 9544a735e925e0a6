"""Plain float32 reference of a dense GQA decoder served with SALS.

The published architecture (Mistral, Qwen2: RMSNorm, RoPE by halves,
grouped-query attention, SwiGLU MLP, optional QKV bias and tied head) in
straightforward ``jax.numpy`` at HIGHEST matmul precision, one layer at a
time, teacher-forced over a prompt and the tokens served after it.

Rows before the prompt's end attend exactly and causally: that is what
prefill computes.  Every later row (a decode step) attends as SALS
(arXiv:2510.24273, Algorithm 1) states it:

* the ``n_sink`` first and ``n_recent`` latest positions at full precision;
* of the rest, the ``n_critical`` with the largest latent score
  ``q̄·U[:, :r*] · (k·U)[:r*]``, where ``q̄`` sums the query heads of each
  KV group; their keys are rebuilt from the latent as ``(k·U)·Uᵀ`` and
  rotated at their own positions, and their values are read back from
  ``v_bits`` asymmetric group quantisation;
* one softmax over the union.

The skip layers (the first ``skip_layers_front`` and last
``skip_layers_back``) attend exactly at every row.  Cached tensors are
rounded to the configuration's storage types (bf16 keys, values, latents
and quantisation scales) where the configuration stores them; all
arithmetic is float32.

``fp8=True`` is the control: every linear layer and the LM head take
their operands through float8 e4m3 (activations scaled per row, weights
per output column), the step below the configuration's bfloat16.

The harness calls :func:`hidden` and :func:`head_stats`, the two names
every reference module gives it, with the canonical weights and projector
that the configuration's architecture module draws.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
NEG = -1e30
F8_MAX = 448.0
ROWS = 128                      # query rows per attention block


@dataclasses.dataclass(frozen=True)
class Spec:
    n_heads: int
    n_kv: int
    dh: int
    eps: float
    theta: float
    n_sink: int
    n_recent: int
    n_critical: int
    r_star: int
    v_bits: int
    v_group: int

    @property
    def group(self) -> int:
        return self.n_heads // self.n_kv

    @property
    def kvd(self) -> int:
        return self.n_kv * self.dh


def spec_for(arch, n_critical: int) -> Spec:
    s = arch.sals
    return Spec(arch.n_heads, arch.n_kv, arch.dh, arch.eps, arch.theta,
                int(s["n_sink"]), int(s["n_recent"]), int(n_critical),
                arch.r_star, int(s["v_bits"]), int(s["v_group"]))


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _fp8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _linear(x, w, fp8: bool):
    w = w.astype(jnp.float32)
    if fp8:
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.matmul(x, w, precision=HI)


def _rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def _rope(x, pos, theta):
    """x (T, h, dh), pos (T,): rotate the two halves (Llama convention)."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _quant_dequant(v, bits, group):
    """Asymmetric group quantisation, codes from f32 ranges, read back with
    the scale and zero as stored (bf16)."""
    t, c = v.shape
    vg = v.reshape(t, c // group, group)
    lo = jnp.min(vg, -1, keepdims=True)
    hi = jnp.max(vg, -1, keepdims=True)
    levels = (1 << bits) - 1
    scale = jnp.maximum((hi - lo) / levels, 1e-8)
    code = jnp.clip(jnp.round((vg - lo) / scale), 0, levels)
    return (code * _bf16(scale) + _bf16(lo)).reshape(t, c)


def _softmax_attend(lg, masks_values, sp: Spec):
    """lg (R, kv, g, T) masked logits; masks_values: [(mask (R,T), V (T,kv,dh))]
    — each key's value comes from the one mask that holds it."""
    m = jnp.max(lg, -1, keepdims=True)
    p = jnp.exp(lg - m)
    p = p / jnp.sum(p, -1, keepdims=True)
    o = 0.0
    for mask, v in masks_values:
        pm = jnp.where(mask[:, None, None, :], p, 0.0)
        o = o + jnp.einsum("rkgt,tkd->rkgd", pm, v, precision=HI)
    return o.reshape(o.shape[0], -1)


def _logits(q, k, sp: Spec):
    qg = q.reshape(q.shape[0], sp.n_kv, sp.group, sp.dh)
    return jnp.einsum("rkgd,tkd->rkgt", qg, k, precision=HI) * sp.dh ** -0.5


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _layer(x, lw, u, n_prompt, sp: Spec, sals: bool, fp8: bool):
    """One decoder layer over all rows ``x`` (T, d)."""
    t_len = x.shape[0]
    pos = jnp.arange(t_len)
    h = _rmsnorm(x, lw["attn_norm"], sp.eps)
    q, k, v = (_linear(h, lw[n], fp8) for n in ("wq", "wk", "wv"))
    if "bq" in lw:
        q = q + lw["bq"].astype(jnp.float32)
        k = k + lw["bk"].astype(jnp.float32)
        v = v + lw["bv"].astype(jnp.float32)
    q = q.reshape(t_len, sp.n_heads, sp.dh)
    k = k.reshape(t_len, sp.n_kv, sp.dh)
    v3 = v.reshape(t_len, sp.n_kv, sp.dh)
    q_r = _rope(q, pos, sp.theta)
    k_exact = _bf16(_rope(k, pos, sp.theta))        # stored post-RoPE
    v_exact = _bf16(v3)
    if sals:
        uf = u.astype(jnp.float32)
        k_region = _rope(_bf16(k), pos, sp.theta)   # stored pre-RoPE
        lat = _bf16(jnp.matmul(_bf16(k).reshape(t_len, -1), uf,
                               precision=HI))
        k_rec = _rope(jnp.matmul(lat, uf.T, precision=HI)
                      .reshape(t_len, sp.n_kv, sp.dh), pos, sp.theta)
        v_deq = _quant_dequant(_bf16(v), sp.v_bits, sp.v_group) \
            .reshape(t_len, sp.n_kv, sp.dh)
        lat_s = lat[:, :sp.r_star]
        u_s = uf[:, :sp.r_star]

    def exact(r0):
        rows = r0 + jnp.arange(ROWS)
        qb = jax.lax.dynamic_slice_in_dim(q_r, r0, ROWS)
        causal = pos[None, :] <= rows[:, None]
        lg = jnp.where(causal[:, None, None, :], _logits(qb, k_exact, sp),
                       NEG)
        return _softmax_attend(lg, [(causal, v_exact)], sp)

    def sparse(r0):
        rows = r0 + jnp.arange(ROWS)
        qp = jax.lax.dynamic_slice_in_dim(q, r0, ROWS)
        qb = jax.lax.dynamic_slice_in_dim(q_r, r0, ROWS)
        q_bar = qp.reshape(ROWS, sp.n_kv, sp.group, sp.dh).sum(2) \
            .reshape(ROWS, sp.kvd)
        score = jnp.matmul(jnp.matmul(q_bar, u_s, precision=HI), lat_s.T,
                           precision=HI)
        selectable = (pos[None, :] >= sp.n_sink) & \
            (pos[None, :] <= rows[:, None] - sp.n_recent)
        vals, idx = jax.lax.top_k(jnp.where(selectable, score, -jnp.inf),
                                  sp.n_critical)
        chosen = jnp.zeros((ROWS, t_len), bool).at[
            jnp.arange(ROWS)[:, None], idx].set(vals > -jnp.inf)
        region = ((pos[None, :] < sp.n_sink)
                  | (pos[None, :] > rows[:, None] - sp.n_recent)) \
            & (pos[None, :] <= rows[:, None])
        lg = jnp.where(region[:, None, None, :], _logits(qb, k_region, sp),
                       jnp.where(chosen[:, None, None, :],
                                 _logits(qb, k_rec, sp), NEG))
        o_s = _softmax_attend(lg, [(region, v_exact), (chosen, v_deq)], sp)
        return jnp.where((rows < n_prompt)[:, None], exact(r0), o_s)

    def block(r0):
        if not sals:
            return exact(r0)
        return jax.lax.cond(r0 + ROWS <= n_prompt, exact, sparse, r0)

    o = jax.lax.map(block, jnp.arange(0, t_len, ROWS))
    x = x + _linear(o.reshape(t_len, -1), lw["wo"], fp8)

    mb = next(b for b in (2048, 1024, 512, 256, ROWS) if t_len % b == 0)

    def mlp(xb):
        hb = _rmsnorm(xb, lw["mlp_norm"], sp.eps)
        g = _linear(hb, lw["w_gate"], fp8)
        up = _linear(hb, lw["w_up"], fp8)
        return xb + _linear(jax.nn.silu(g) * up, lw["w_down"], fp8)

    return jax.lax.map(mlp, x.reshape(t_len // mb, mb, -1)).reshape(x.shape)


@jax.jit
def _embed(table, tokens):
    return jnp.take(table, tokens, axis=0).astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(3,))
def _final(x, rows, g, eps):
    return _rmsnorm(jnp.take(x, rows, axis=0), g, eps)


LAYER_KEYS = ("attn_norm", "mlp_norm", "wq", "wk", "wv", "wo", "w_gate",
              "w_up", "w_down", "bq", "bk", "bv")


def final_hidden(w, u, sals_layers, sp: Spec, tokens: np.ndarray,
                 n_prompt: int, rows: np.ndarray, t_len: int, fp8=False):
    """Normalised final hidden states (len(rows), d) at ``rows`` of the
    sequence ``tokens`` (prompt of ``n_prompt``, then served tokens),
    computed over ``t_len`` positions (padded with token 0, never read
    by the rows that matter: attention is causal)."""
    if len(tokens) > t_len or t_len % ROWS:
        raise ValueError(f"{len(tokens)} tokens in {t_len} positions")
    toks = np.zeros((t_len,), np.int32)
    toks[:len(tokens)] = tokens
    x = _embed(w["embed"], jnp.asarray(toks))
    np_ = jnp.int32(n_prompt)
    for li, is_sals in enumerate(sals_layers):
        lw = {k: w[k][li] for k in LAYER_KEYS if k in w}
        x = _layer(x, lw, u[li] if is_sals else None, np_, sp, bool(is_sals),
                   bool(fp8))
    return _final(x, jnp.asarray(rows, jnp.int32), w["final_norm"], sp.eps)


def hidden(w, proj, arch, n_critical: int, tokens: np.ndarray,
           n_prompt: int, rows: np.ndarray, t_len: int, fp8=False):
    """:func:`final_hidden` of a configuration: its SALS layers, its
    projector, and a decode budget of ``n_critical`` tokens."""
    return final_hidden(w, proj["u"], arch.sals_layers,
                        spec_for(arch, n_critical), tokens, n_prompt, rows,
                        t_len, fp8)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _head_stats(hid, head, toks, tied: bool, fp8: bool):
    w = head.T if tied else head
    logits = _linear(hid, w, fp8)
    at = jnp.take_along_axis(logits, toks[:, None], axis=1)[:, 0]
    return jnp.max(logits, -1), at, jnp.argmax(logits, -1).astype(jnp.int32)


def head_stats(w, arch, hid, toks: np.ndarray, fp8=False) -> dict:
    """Per row: the largest logit, the logit of ``toks``, and the argmax."""
    head = w["embed"] if arch.tied else w["head"]
    out = {"max": [], "at": [], "argmax": []}
    step = 256
    for i in range(0, hid.shape[0], step):
        h = hid[i:i + step]
        t = np.zeros((h.shape[0],), np.int32)
        t[:len(toks[i:i + step])] = toks[i:i + step]
        mx, at, am = _head_stats(h, head, jnp.asarray(t), arch.tied,
                                 bool(fp8))
        out["max"].append(np.asarray(mx))
        out["at"].append(np.asarray(at))
        out["argmax"].append(np.asarray(am))
    return {k: np.concatenate(v)[:len(toks)] for k, v in out.items()}
