"""Device idle time while the streamed fold waits on its window pipeline,
in ms per fit: the traced window's idle gaps whose innermost open host
span is a ``fold.prefetch_*`` span (the host's read, pad and stage of a
window, or the fold's wait for one), summed, divided by the devices and
by the window's fits.  None when no gap carries a ``fold.`` name, so
spans lost from the trace read as missing, not as 0."""


def read(ctx):
    r = ctx.reduced
    if r.fits == 0 or not any(name.startswith("fold.")
                              for name, _ in r.gaps):
        return None
    wait = sum(s for name, s in r.gaps if name.startswith("fold.prefetch_"))
    devices = max(len(r.busy_s), 1)
    return 1000.0 * wait / devices / r.fits
