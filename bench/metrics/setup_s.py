"""Set-up: process start to the window's start (the first timed hand-off,
or the start of the arrival schedule).  Stream generation, state build,
the compile or the compile-cache load, and warm-up all fall inside."""


def read(run):
    return run.t0 - run.t_start
