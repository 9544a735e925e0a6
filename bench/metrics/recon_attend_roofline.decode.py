"""Roofline share of the reconstruct-and-attend kernel (%): one latent and
one value row per selected token, U_r, and its ops, over its device time."""
from bench import shares as _shares


def read(ctx):
    nc = int(ctx.traffic["n_critical"])
    return _shares.kernel_share(
        ctx, "sals_recon_attend",
        lambda live: ctx.counts.recon_attend(ctx.arch, nc, live),
        ctx.counts.recon_shared_bytes(ctx.arch))
