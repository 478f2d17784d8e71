"""Host time of one ``process()`` call inside the window: label hashing,
the fsynced journal append, packing and dispatch (it returns before the
device finishes)."""
import numpy as np


def read(run):
    times = run.span_times("bench.process", run.t0, run.t1)
    return 1e3 * float(np.mean(times)) if times else None
