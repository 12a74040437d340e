"""Gigabytes of one chip's memory the step program needs by the compiler's
account (``compiled.memory_analysis()``): arguments plus temporaries.  The
outputs alias the donated arguments."""


def read(run):
    m = run.module_memory
    return (m["argument"] + m["temp"]) / 1e9 if m else None
