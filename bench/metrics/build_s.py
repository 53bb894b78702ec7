"""Network build: the program's Algorithm-1 partition, block-CSR tiling
and engine construction, seconds on the host clock."""


def read(run):
    return run.build_s
