"""Mean milliseconds the host spent inside ``step(...)``: the
``make_train_step`` wrapper's enqueue.  Host clock round the call."""


def read(run):
    return (sum(run.dispatch_s) / len(run.dispatch_s) * 1e3
            if run.dispatch_s else None)
