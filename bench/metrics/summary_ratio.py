"""phi / live edges at the epoch that holds every change of the window
(the run's final epoch, whose change count the schedule fixes), both
taken by the reference: the refold of the summary's partition over the
replayed edges, and the replay's edge count.  The check holds the
program's own phi to that refold."""


def read(run):
    ref = run.extra.get("reference")
    if not ref or not ref["live_edges"]:
        return None
    return ref["refold_phi"] / ref["live_edges"]
