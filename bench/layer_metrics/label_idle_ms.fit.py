"""Device idle time under a fit's label step, in ms per fit: the traced
window's idle gaps whose innermost open host span is ``plan.labels`` (the
label upload, the class weights, the dump-row extension and Z's
allocation of a bucketed fit), summed, divided by the devices and by the
window's fits.  None when no gap carries the name, so a program without
the span reads as missing, not as 0."""


def read(ctx):
    r = ctx.reduced
    labels = [s for name, s in r.gaps if name == "plan.labels"]
    if not labels or r.fits == 0:
        return None
    devices = max(len(r.busy_s), 1)
    return 1000.0 * sum(labels) / devices / r.fits
