"""Device time of the decode program per step, in the trace."""


def read(ctx):
    if ctx.extract is None:
        return None
    ns, runs = ctx.trace.module_ns(ctx.extract, "_decode_impl")
    return ns / runs * 1e-6 if runs else None
