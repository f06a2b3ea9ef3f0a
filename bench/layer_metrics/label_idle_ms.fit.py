"""Device idle time under a fit's label step, in ms per fit: the traced
window's idle time under ``plan.labels`` as the innermost open host span
(the label upload, the class weights, the dump-row extension and Z's
allocation of a bucketed fit), summed, divided by the devices and by the
window's fits.  Each idle gap is cut at the host spans' edges, so the
label step gets its own part of a gap that begins or ends under another
span, and 0 where the devices were busy all through it.  None when the
window holds no ``plan.labels`` span, so a program without the span
reads as missing, not as 0."""


def read(ctx):
    r = ctx.reduced
    if "plan.labels" not in r.idle_under or r.fits == 0:
        return None
    devices = max(len(r.busy_s), 1)
    return 1000.0 * r.idle_under["plan.labels"] / devices / r.fits
