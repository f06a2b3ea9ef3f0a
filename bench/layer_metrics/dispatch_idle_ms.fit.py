"""Device idle time under the program's plan spans, in ms per fit: the
traced window's idle gaps whose innermost open host span is a ``plan.*``
span (a bucket's gathers, plane building, launch or write-back, the
degree pass, the plan's resolution), summed, divided by the devices and
by the window's fits.  None when no gap carries a ``plan.`` name, so
spans lost from the trace read as missing, not as 0."""


def read(ctx):
    r = ctx.reduced
    plan = [s for name, s in r.gaps if name.startswith("plan.")]
    if not plan or r.fits == 0:
        return None
    devices = max(len(r.busy_s), 1)
    return 1000.0 * sum(plan) / devices / r.fits
