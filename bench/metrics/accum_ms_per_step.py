"""Accumulation kernel: device time of the Pallas custom calls per
simulated step, ms, on the slowest chip."""


def read(run):
    t = run.reduction.per_chip("accumulation").max()
    return t / run.steps * 1e3 if t > 0 else None
