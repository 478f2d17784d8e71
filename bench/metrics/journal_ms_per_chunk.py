"""Host time of the fsynced journal append per chunk inside the window:
the mean of the program's ``summarizer.journal`` spans there.  Silent
where the program records no spans."""


def read(run):
    obs = getattr(run.summ, "obs", None)
    if obs is None:
        return None
    spans = obs.spans("summarizer.journal", run.t0, run.t1)
    if not spans:
        return None
    return 1e3 * sum(s.seconds for s in spans) / len(spans)
