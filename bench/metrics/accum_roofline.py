"""Accumulation kernel: least time for the accumulation the raster needs
(``work.py``) over the kernel's device time, %: the busiest chip's least
time against the slowest chip's kernel time."""


def read(run):
    t = run.reduction.per_chip("accumulation").max()
    if t <= 0:
        return None
    return 100.0 * run.work.accum_least_s(run.peaks).max() / t
