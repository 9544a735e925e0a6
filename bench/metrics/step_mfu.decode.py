"""FLOPs the algorithm needs for the decode steps of the traced window,
over the window's length times the chip's peak (%)."""
from bench import shares as _shares


def read(ctx):
    tot = _shares.decode_totals(ctx) if ctx.extract is not None else None
    if tot is None:
        return None
    window = ctx.extract["window_ns"] * 1e-9
    return 100.0 * tot[0] / (window * _shares.peaks(ctx)["bf16_flops_per_s"])
