"""Device milliseconds a step has an all-reduce in flight, per chip: the
union of the intervals of ops whose opcode is ``all-reduce*`` on the core's
line and on the asynchronous line.  Device trace."""


def read(run):
    flight, _ = run.reduced.allreduce_seconds()
    return run.per_step_ms(flight) if flight > 0 else None
