"""Device time of the query kernels per read batch, from the trace: the
query modules' time over the read batches the traced stretch served."""


def read(run):
    if not run.trace:
        return None
    batches = run.trace.span_count("bench.read.")
    if not batches:
        return None
    return run.trace.stage_seconds("query") * 1e3 / batches
