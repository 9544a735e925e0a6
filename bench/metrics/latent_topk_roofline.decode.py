"""Roofline share of the latent score / top-k kernel (%): its ops and bytes
at the live context length over its device time."""
from bench import shares as _shares


def read(ctx):
    return _shares.kernel_share(
        ctx, "sals_latent_topk",
        lambda live: ctx.counts.latent_topk(ctx.arch, live))
