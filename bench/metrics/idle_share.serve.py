"""Share of the traced serving stretch (one chunk cycle) in which no
program ran on the device (1 - busy / window), in %."""


def read(run):
    if not run.trace or not run.trace.n_devices or run.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
