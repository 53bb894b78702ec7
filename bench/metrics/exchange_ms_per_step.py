"""Spike exchange: device time of the collectives (union of their
intervals) per simulated step, ms, on the slowest chip."""


def read(run):
    t = run.reduction.per_chip("exchange").max()
    return t / run.steps * 1e3 if t > 0 else None
