"""Arithmetic that several per-layer metric readers share."""


def peaks(ctx):
    try:
        return ctx.peaks[ctx.device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device {ctx.device_kind!r} in "
                       "bench/peaks.json") from None


def decode_totals(ctx):
    """(FLOPs, bytes) the algorithm needs for the decode steps that ran
    inside the traced window, or None."""
    steps = ctx.traced_steps()
    if not steps or not ctx.extract["ops"]:
        return None
    nc = int(ctx.traffic["n_critical"])
    f = sum(ctx.counts.decode_flops(ctx.arch, nc, lives) for _, lives in steps)
    b = sum(ctx.counts.decode_bytes(ctx.arch, nc, lives) for _, lives in steps)
    return f, b


def idle_share(ctx):
    """Share of the traced window in which no operation ran on the device
    (%), averaged over the chips used."""
    ex = ctx.extract
    if ex is None or not ex["window_ns"]:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_ns(ex) / ex["window_ns"])


def kernel_share(ctx, kernel, per_row, per_step_bytes=0.0):
    """Roofline share (%) of ``kernel``: the least time its calls in the
    traced window could take, max(ops / peak, bytes / bandwidth), over the
    device time its events took.  ``per_row(live)`` gives a row's (ops,
    bytes) in one SALS layer; ``per_step_bytes`` is read once per SALS
    layer and step, whatever the rows."""
    if ctx.extract is None:
        return None
    ns, n = ctx.trace.kernel_ns(ctx.extract, kernel)
    steps = ctx.traced_steps()
    if not n or not steps:
        return None
    ops = nbytes = 0.0
    for _, lives in steps:
        if lives:
            nbytes += per_step_bytes * ctx.arch.n_sals
        for live in lives:
            o, b = per_row(live)
            ops += o * ctx.arch.n_sals
            nbytes += b * ctx.arch.n_sals
    pk = peaks(ctx)
    least = max(ops / pk["bf16_flops_per_s"], nbytes / pk["hbm_bytes_per_s"])
    return 100.0 * least / (ns * 1e-9)
