"""Host time a read batch spends blocked on the device, inside the
window: the program's ``query.wait`` spans (fetching the kernels' answers
and the snapshot's reverse maps) over its read batches (root ``query.*``
spans) there.  A read due while an engine round runs waits for it.
Silent where the program records no spans."""


def read(run):
    obs = getattr(run.summ, "obs", None)
    if obs is None:
        return None
    spans = obs.spans("query.", run.t0, run.t1)
    batches = sum(1 for s in spans if s.parent_id == 0)
    if not batches:
        return None
    wait = sum(s.seconds for s in spans if s.name == "query.wait")
    return 1e3 * wait / batches
