"""Spike exchange: the part of the collectives' time in which no other op
runs on the chip, per simulated step, ms, on the slowest chip."""


def read(run):
    if run.reduction.per_chip("exchange").max() <= 0:
        return None
    return run.reduction.per_chip("exchange_exposed").max() / run.steps * 1e3
