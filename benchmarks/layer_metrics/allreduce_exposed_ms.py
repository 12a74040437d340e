"""The part of ``allreduce_ms`` during which no other op ran on that
chip's core: what the exchange costs the step.  Device trace."""


def read(run):
    flight, exposed = run.reduced.allreduce_seconds()
    return run.per_step_ms(exposed) if flight > 0 else None
