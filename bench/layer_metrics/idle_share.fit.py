"""Device idle share of the in-memory fits, in %: 1 - busy / traced window."""

from harness.layers import idle_share


def read(ctx):
    return idle_share(ctx)
