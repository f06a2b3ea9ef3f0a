"""Share of the streamed fit's roofline, in %: the least time of the
window's fits over the mean per-device busy time, the work spread over all
the cell's chips."""

from harness.layers import roofline_share


def read(ctx):
    return roofline_share(ctx)
