"""Share of the traced window in which no operation ran on the device (%),
averaged over the chips used."""
from bench import shares as _shares


def read(ctx):
    return _shares.idle_share(ctx)
