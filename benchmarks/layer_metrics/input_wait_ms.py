"""Mean milliseconds a step's ``next(feed)`` blocked the loop.  Host clock
round the call into ``data/loader``."""


def read(run):
    return sum(run.wait_s) / len(run.wait_s) * 1e3 if run.wait_s else None
