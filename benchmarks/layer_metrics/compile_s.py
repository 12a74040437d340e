"""Seconds of the first call of the step: trace, lower, compile or load
from the persistent cache, and one step's run.  Host clock."""


def read(run):
    return run.compile_s
