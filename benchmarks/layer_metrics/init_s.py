"""Seconds in ``hvd.init`` + ``init_train_state`` + ``make_train_step`` and
placing the seeded weights.  Host clock, the benchmark's loop."""


def read(run):
    return run.init_s
