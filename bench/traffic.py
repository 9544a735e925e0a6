"""The one traffic generator.  ``bench/traffic/<name>.json`` holds a mix's
parameters; :func:`generate` turns them and a seed into requests.

Every seed gets the same multiset of sizes and of gaps between arrivals,
in its own order and with its own token ids: so seeds never change how
much work a mix holds, only when each piece of it arrives.

Kinds:

``resident``   ``batch`` sessions, all submitted at once before the
               window; contexts spread log-uniformly over
               [``context_min``, ``context_max``]; each may generate
               until ``max_seq`` (the window's end cancels it).
``open_loop``  independent users: Poisson arrivals at ``rate_per_s`` from
               ``lead_in_s`` before the window to its end; prompt and
               output lengths lognormal (median, sigma), clipped.  The
               sizes and gaps are the distributions' quantiles, shuffled.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List

import numpy as np

from bench.model import load_json, seed_words


@dataclasses.dataclass
class Planned:
    due_s: float              # due time, seconds from the window's start
    prompt: np.ndarray
    max_new_tokens: int


def load_traffic(name: str) -> dict:
    t = load_json("traffic", f"{name}.json")
    if t.get("kind") not in ("resident", "open_loop"):
        raise ValueError(f"traffic/{name}.json: unknown kind {t.get('kind')!r}")
    return t


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _lognormal(n: int, median: float, sigma: float, lo: int, hi: int
               ) -> np.ndarray:
    z = np.array([NormalDist().inv_cdf(p) for p in _quantiles(n)])
    return np.clip(np.round(median * np.exp(sigma * z)), lo, hi).astype(int)


def generate(t: dict, vocab: int, seed: int, seconds: float
             ) -> List[Planned]:
    rng = np.random.default_rng(seed_words(seed, 4))
    if t["kind"] == "resident":
        b = int(t["batch"])
        lo, hi = math.log(t["context_min"]), math.log(t["context_max"])
        ctx = np.round(np.exp(lo + (hi - lo) * _quantiles(b)) / 8) * 8
        ctx = rng.permutation(ctx.astype(int))
        return [Planned(0.0, rng.integers(1, vocab, int(c), dtype=np.int32),
                        int(t["max_seq"]) - int(c))
                for c in ctx]
    rate = float(t["rate_per_s"])
    lead = float(t["lead_in_s"])
    n = int(math.ceil(rate * (lead + seconds)))
    gaps = rng.permutation(-np.log1p(-_quantiles(n - 1)) / rate)
    due = np.concatenate([[0.0], np.cumsum(gaps)]) - lead
    p = t["prompt"]
    o = t["output"]
    plens = rng.permutation(_lognormal(n, p["median"], p["sigma"],
                                         p["min"], p["max"]))
    olens = rng.permutation(_lognormal(n, o["median"], o["sigma"],
                                         o["min"], o["max"]))
    return [Planned(float(due[i]),
                    rng.integers(1, vocab, int(plens[i]), dtype=np.int32),
                    int(olens[i]))
            for i in range(n)]
