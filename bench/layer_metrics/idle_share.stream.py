"""Device idle share of the streamed fits, in %, averaged over the cell's
devices."""

from harness.layers import idle_share


def read(ctx):
    return idle_share(ctx)
