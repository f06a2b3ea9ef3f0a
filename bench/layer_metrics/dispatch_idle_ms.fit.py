"""Device idle time under the program's plan spans, in ms per fit: the
traced window's idle time under a ``plan.*`` span as the innermost open
host span (a bucket's gathers, plane building, launch or write-back, the
degree pass, the plan's resolution), summed, divided by the devices and
by the window's fits.  Each idle gap is cut at the host spans' edges, so
a plan span gets its own part of a gap that begins or ends under another
span.  None when the window holds no ``plan.`` span, so spans lost from
the trace read as missing, not as 0."""


def read(ctx):
    r = ctx.reduced
    plan = [s for name, s in r.idle_under.items() if name.startswith("plan.")]
    if not plan or r.fits == 0:
        return None
    devices = max(len(r.busy_s), 1)
    return 1000.0 * sum(plan) / devices / r.fits
