"""Device: the share of the traced window (host clock, first dispatch to
last raster on the host) in which no leaf op runs and no copy or
collective is in flight (``trace.py``'s busy time), %, on the idlest
chip."""


def read(run):
    busy = run.reduction.per_chip("busy").min()
    return 100.0 * (1.0 - busy / run.reduction.window_s)
