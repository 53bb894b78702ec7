"""Neuron update: device time of the ops that are neither the kernel nor
collectives, per simulated step, ms, on the slowest chip."""


def read(run):
    t = run.reduction.per_chip("update").max()
    return t / run.steps * 1e3 if t > 0 else None
