"""Whole step: least time for the work the traced steps need
(accumulation, neuron state, spikes to other chips; ``work.py``) on the
busiest chip, over the traced window (host clock), %."""


def read(run):
    return 100.0 * run.work.step_least_s(run.peaks).max() / run.reduction.window_s
