"""Device idle share of the traced window, in %: 1 - (union of the device's
op intervals / window), averaged over the cell's devices."""


def read(w):
    return 100.0 * w.trace.idle_share(w.lo, w.hi)
