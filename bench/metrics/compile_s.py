"""Executor compile: lowering and compiling one chunk's step, seconds, as
``DistributedSNN.compile`` returns it (a cache read after the first run)."""


def read(run):
    return run.compile_s
