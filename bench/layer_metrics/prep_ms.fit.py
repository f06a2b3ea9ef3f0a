"""Prep time per fit, in ms: the program's ``plan.stage.*`` spans tagged
``kind=prep`` (ELL packing, effective edges), summed over the window and
divided by its fits.  Near 0 while the prepared graph is reused."""

from harness.layers import span_ms_per_fit


def read(ctx):
    return span_ms_per_fit(ctx, "plan.stage.", kind="prep")
