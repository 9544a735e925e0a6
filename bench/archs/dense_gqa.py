"""Architecture module of a dense GQA decoder (Mistral, Qwen2) served with
SALS: what the harness needs of a configuration whose file names this
module under ``"architecture"``.

Every architecture module gives the harness the same names:

``Arch(config)``            the sizes, by the names the reference and
                            ``counts`` use; ``vocab``, ``n_sals``,
                            ``param_count()`` and ``summary()`` at least;
``make_weights(arch, seed)``  every weight on the device, from the seed,
                            in the canonical layout the reference reads;
``make_projector(arch, w)``   the SALS projector, the benchmark's own;
``program_params(arch, w)``   the program's parameter tree over the same
                            device arrays;
``program_configs(arch, config, traffic)``  the program's
                            ``(ModelConfig, ServeConfig)`` for a cell;
``counts``                  operations and bytes the algorithm needs,
                            which the per-layer readers reach through
                            ``ctx.counts``.

Weights are made here, not by the program: one jitted call from the seed
draws every leaf on the device in the served dtype, in a canonical layout
(published semantics: the token embedding is added unscaled).  The token
embedding is drawn at standard deviation 1.0 where the head is untied, and
at ``d ** -0.5`` where ``tie_word_embeddings`` is true: that is the tied
head's own scale, which keeps the input token's logit from standing far
above the rest.  The SALS projector is made here too: per layer, the
leading eigenvectors of the key covariance that the random weights imply
for unit-variance normalised inputs (``W_kᵀ·diag(g²)·W_k + b·bᵀ``), which
is what a calibration over such inputs converges to.
"""
from __future__ import annotations

from bench import counts  # noqa: F401  (read by the harness as ``counts``)
from bench.model import seed_words


class Arch:
    """The sizes of one configuration, by the names the reference uses."""

    def __init__(self, c: dict):
        self.name = c["name"]
        self.d = int(c["hidden_size"])
        self.ff = int(c["intermediate_size"])
        self.n_heads = int(c["num_attention_heads"])
        self.n_kv = int(c["num_key_value_heads"])
        self.dh = int(c.get("head_dim") or self.d // self.n_heads)
        self.n_layers = int(c["num_hidden_layers"])
        self.vocab = int(c["vocab_size"])
        self.theta = float(c["rope_theta"])
        self.eps = float(c["rms_norm_eps"])
        self.tied = bool(c["tie_word_embeddings"])
        self.bias = bool(c.get("attention_bias", False)
                         or c.get("qkv_bias", False))
        self.dtype = c["torch_dtype"]
        s = c["sals"]
        self.sals = dict(s)
        self.kvd = self.n_kv * self.dh
        self.qd = self.n_heads * self.dh
        self.group = self.n_heads // self.n_kv
        self.rank = _round8(max(8, min(self.kvd, int(round(
            s["rank_ratio"] * self.kvd)))))
        self.r_star = _round8(max(8, int(round(s["score_ratio"]
                                               * self.rank))))
        front, back = s["skip_layers_front"], s["skip_layers_back"]
        self.sals_layers = [front <= i < self.n_layers - back
                            for i in range(self.n_layers)]

    @property
    def n_sals(self) -> int:
        return sum(self.sals_layers)

    @property
    def n_full(self) -> int:
        return self.n_layers - self.n_sals

    def summary(self) -> str:
        return (f"{self.param_count() / 1e9:.3f}B params, {self.n_layers} "
                f"layers ({self.n_sals} SALS, rank {self.rank}, score rank "
                f"{self.r_star})")

    def layer_params(self) -> int:
        return (self.d * (self.qd + 2 * self.kvd) + self.qd * self.d
                + 3 * self.d * self.ff)

    def matmul_params(self) -> int:
        """Parameters that every token multiplies: layers and LM head."""
        return self.n_layers * self.layer_params() + self.d * self.vocab

    def param_count(self) -> int:
        emb = self.vocab * self.d
        head = 0 if self.tied else self.vocab * self.d
        norms = (2 * self.n_layers + 1) * self.d
        bias = self.n_layers * (self.qd + 2 * self.kvd) if self.bias else 0
        return emb + head + norms + bias + self.n_layers * self.layer_params()


def _round8(x: int) -> int:
    return max(8, -(-x // 8) * 8)


def make_weights(arch: Arch, seed: int):
    """Every weight on the device, from the seed, in one jitted call.

    Canonical layout, stacked over layers: ``embed`` (V, d), drawn at unit
    variance where the head is untied (the published models add it
    unscaled) and at ``d ** -0.5`` where it is tied; ``head`` (d, V) unless
    tied; per layer ``wq wk wv wo w_gate w_up w_down`` (and ``bq bk bv``);
    norm gains ``attn_norm mlp_norm`` near 1, ``final_norm``."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(arch.dtype)
    L, d, ff, qd, kvd = arch.n_layers, arch.d, arch.ff, arch.qd, arch.kvd
    shapes = {
        "embed": ((arch.vocab, d), d ** -0.5 if arch.tied else 1.0),
        "wq": ((L, d, qd), d ** -0.5), "wk": ((L, d, kvd), d ** -0.5),
        "wv": ((L, d, kvd), d ** -0.5), "wo": ((L, qd, d), qd ** -0.5),
        "w_gate": ((L, d, ff), d ** -0.5), "w_up": ((L, d, ff), d ** -0.5),
        "w_down": ((L, ff, d), ff ** -0.5),
    }
    if not arch.tied:
        shapes["head"] = ((d, arch.vocab), d ** -0.5)
    if arch.bias:
        shapes.update(bq=((L, qd), 0.5), bk=((L, kvd), 0.5),
                      bv=((L, kvd), 0.5))
    gains = {"attn_norm": (L, d), "mlp_norm": (L, d), "final_norm": (d,)}
    names = sorted(shapes) + sorted(gains)

    def draw(key):
        keys = jax.random.split(key, len(names))
        out = {}
        for k, name in zip(keys, names):
            if name in shapes:
                shape, std = shapes[name]
                out[name] = (std * jax.random.normal(k, shape, jnp.float32)
                             ).astype(dt)
            else:
                out[name] = (1.0 + 0.1 * jax.random.normal(
                    k, gains[name], jnp.float32)).astype(dt)
        return out

    w0, w1 = (int(x) for x in seed_words(seed))
    key = jax.random.fold_in(jax.random.PRNGKey(w0), w1)
    return jax.jit(draw)(key)


def make_projector(arch: Arch, w) -> dict:
    """Per layer (kvd, r): leading eigenvectors, descending, of the key
    second moment for unit-variance normalised inputs; stored bf16."""
    import jax
    import jax.numpy as jnp

    def one(wk, g, bk):
        wk = wk.astype(jnp.float32) * g.astype(jnp.float32)[:, None]
        cov = wk.T @ wk
        if bk is not None:
            b = bk.astype(jnp.float32)
            cov = cov + jnp.outer(b, b)
        ev, vec = jnp.linalg.eigh(cov)            # ascending
        return vec[:, ::-1][:, :arch.rank], ev[::-1]

    def all_layers(wk, g, bk):
        with jax.default_matmul_precision("highest"):
            if bk is None:
                u, ev = jax.vmap(lambda a, b: one(a, b, None))(wk, g)
            else:
                u, ev = jax.vmap(one)(wk, g, bk)
        return {"u": u.astype(jnp.bfloat16), "eigvals": ev}

    return jax.jit(all_layers)(w["wk"], w["attn_norm"], w.get("bk"))


def program_params(arch: Arch, w) -> dict:
    """The program's parameter tree over the same device arrays.  The
    program multiplies the embedding by sqrt(d) as it looks it up, so its
    table is the canonical one divided by sqrt(d); with tied embeddings its
    logits are then the published ones over sqrt(d), which leaves every
    greedy token as it is."""
    import jax.numpy as jnp

    emb = (w["embed"].astype(jnp.float32) * arch.d ** -0.5).astype(
        w["embed"].dtype)
    embed = {"embedding": emb}
    if not arch.tied:
        embed["lm_head"] = w["head"]
    attn = {k: w[k] for k in ("wq", "wk", "wv", "wo")}
    if arch.bias:
        attn.update(bq=w["bq"], bk=w["bk"], bv=w["bv"])
    blocks = {"attn_norm": {"scale": w["attn_norm"]}, "attn": attn,
              "mlp_norm": {"scale": w["mlp_norm"]},
              "mlp": {k: w[k] for k in ("w_gate", "w_up", "w_down")}}
    return {"embed": embed, "blocks": blocks,
            "final_norm": {"scale": w["final_norm"]}}


def program_configs(arch: Arch, c: dict, traffic: dict):
    """(ModelConfig, ServeConfig) of the program for this cell.  Engine
    tuning knobs (prefill chunk and budget, gauges) keep their defaults."""
    from repro.config import ModelConfig, SALSConfig, ServeConfig

    mcfg = ModelConfig(
        name=arch.name, family="dense", n_layers=arch.n_layers, d_model=arch.d,
        n_heads=arch.n_heads, n_kv_heads=arch.n_kv, head_dim=arch.dh,
        d_ff=arch.ff, vocab_size=arch.vocab, qkv_bias=arch.bias,
        rope_theta=arch.theta, tie_embeddings=arch.tied, norm_eps=arch.eps,
        mlp_act="swiglu", dtype=arch.dtype)
    s = arch.sals
    sals = SALSConfig(
        rank_ratio=s["rank_ratio"], score_ratio=s["score_ratio"],
        n_critical=int(traffic["n_critical"]), n_sink=s["n_sink"],
        n_recent=s["n_recent"], v_bits=s["v_bits"], v_group=s["v_group"],
        k_latent_dtype=s["k_latent_dtype"],
        skip_layers_front=s["skip_layers_front"],
        skip_layers_back=s["skip_layers_back"])
    if sals.rank(mcfg.kv_dim) != arch.rank or \
            sals.score_rank(mcfg.kv_dim) != arch.r_star:
        raise ValueError("program and benchmark disagree on the SALS ranks")
    scfg = ServeConfig(max_seq_len=int(traffic["max_seq"]),
                       max_batch=int(traffic["batch"]), temperature=0.0,
                       sals=sals)
    return mcfg, scfg
