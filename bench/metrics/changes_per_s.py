"""Changes whose epoch completed, over the time from the first timed
hand-off to the device's completion of the last chunk handed in (whole
chunks only)."""


def read(run):
    ex = run.extra
    if "changes" not in ex:
        return None
    return ex["changes"] / (ex["t_last_done"] - run.t0)
