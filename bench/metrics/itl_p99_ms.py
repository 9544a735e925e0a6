"""99th percentile of every gap between consecutive tokens of a request,
both inside the window, on the client's clock."""
import numpy as np


def read(ctx):
    gaps = ctx.gaps_ms()
    return float(np.percentile(gaps, 99)) if gaps else None
