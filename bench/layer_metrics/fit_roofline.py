"""Share of the fit's roofline, in %: the least time of the window's fits
(harness.work, at the chip's peaks) over the device's busy time in them."""

from harness.layers import roofline_share


def read(ctx):
    return roofline_share(ctx)
