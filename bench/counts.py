"""Operations and bytes that the algorithm needs, from shapes and live
lengths.  Nothing here reads the program: a kernel that streams more than
the live context, or fetches a tile where it needs a row, is charged what
the algorithm needs, so its share of the roofline shows the waste.

Per-term bytes follow the SALS traffic ledger (arXiv:2510.24273 §4.5):
score columns at the live length, one latent row and one quantised value
row per selected token, U_r once per layer and step, the sink and recent
window at full precision.  A decode step reads the weights once.
"""
from __future__ import annotations

from typing import Sequence

BF16 = 2


def v_row_bytes(arch) -> float:
    """Stored bytes of one quantised value row: codes, scale and zero."""
    s = arch.sals
    code = arch.kvd * s["v_bits"] / 8
    return code + 2 * BF16 * arch.kvd / s["v_group"]


def selected(arch, n_critical: int, live: int) -> int:
    """Tokens the top-k keeps: at most the selectable span."""
    s = arch.sals
    return max(0, min(n_critical, live - s["n_sink"] - s["n_recent"]))


def window(arch, live: int) -> int:
    s = arch.sals
    return min(live, s["n_sink"] + s["n_recent"])


# -- kernels, per row and SALS layer ----------------------------------------

def latent_topk(arch, live: int) -> tuple:
    """(ops, bytes) of scoring ``live`` cached latents on r* columns and
    keeping the best: the score dot products, the latent columns read."""
    ops = 2 * arch.r_star * live
    nbytes = live * arch.r_star * BF16 + arch.r_star * 4
    return ops, nbytes


def recon_attend(arch, n_critical: int, live: int) -> tuple:
    """(ops, bytes) of rebuilding one row's selected keys from their
    latents and attending over them: one latent row and one value row per
    selected token, and the row's query."""
    n = selected(arch, n_critical, live)
    ops = 2 * n * arch.rank * arch.kvd + 4 * arch.n_heads * arch.dh * n
    nbytes = n * (arch.rank * BF16 + v_row_bytes(arch)) + arch.qd * BF16
    return ops, nbytes


def recon_shared_bytes(arch) -> float:
    """Bytes the reconstruction reads once per SALS layer and step, however
    many rows it serves: the reconstruction matrix U_r."""
    return arch.kvd * arch.rank * BF16


# -- whole steps --------------------------------------------------------------

def decode_flops(arch, n_critical: int, lives: Sequence[int]) -> float:
    """FLOPs of one decode step over rows at context lengths ``lives``."""
    total = 0.0
    for live in lives:
        total += 2 * arch.matmul_params()
        total += arch.n_full * 4 * arch.n_heads * arch.dh * live
        total += arch.n_sals * (
            2 * arch.kvd * arch.rank                   # latent of the new key
            + 2 * arch.kvd * arch.r_star               # latent query
            + latent_topk(arch, live)[0]
            + recon_attend(arch, n_critical, live)[0]
            + 4 * arch.n_heads * arch.dh * window(arch, live))
    return total


def decode_bytes(arch, n_critical: int, lives: Sequence[int]) -> float:
    """HBM bytes one decode step needs: weights and U_r once, then per row
    the full layers' K/V and the SALS terms at the live length."""
    total = arch.param_count() * BF16 - arch.vocab * arch.d * BF16 \
        + len(lives) * arch.d * BF16       # embedding: only the rows used
    if lives:
        total += arch.n_sals * recon_shared_bytes(arch)
    for live in lives:
        total += arch.n_full * live * 2 * arch.kvd * BF16
        total += arch.n_sals * (
            latent_topk(arch, live)[1]
            + recon_attend(arch, n_critical, live)[1]
            + window(arch, live) * 2 * arch.kvd * BF16)
    if arch.tied:                          # the tied head reads the table
        total += arch.vocab * arch.d * BF16
    return total

