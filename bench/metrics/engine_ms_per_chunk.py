"""Device time of one execution of the engine stage (one chunk: on-device
interning and every engine round), from the trace."""


def read(run):
    return run.trace.stage_ms("engine") if run.trace else None
