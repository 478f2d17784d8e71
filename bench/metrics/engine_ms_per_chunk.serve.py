"""Device time of one engine-stage execution in a serving cell, where it
is what a change waits for before it becomes visible."""


def read(run):
    return run.trace.stage_ms("engine") if run.trace else None
