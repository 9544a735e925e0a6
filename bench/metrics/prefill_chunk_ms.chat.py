"""Device time of the chunked-prefill program per chunk, in the trace."""


def read(ctx):
    if ctx.extract is None:
        return None
    ns, runs = ctx.trace.module_ns(ctx.extract, "_prefill_chunk_impl")
    return ns / runs * 1e-6 if runs else None
