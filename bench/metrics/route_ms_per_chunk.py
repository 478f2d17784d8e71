"""Device time of one execution of the route stage, from the trace."""


def read(run):
    return run.trace.stage_ms("route") if run.trace else None
