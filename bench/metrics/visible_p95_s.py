"""95th percentile, over every change that arrived inside the window, of
the completion on the device of the epoch that holds it minus its
scheduled arrival."""
import numpy as np


def read(run):
    if "visible_s" not in run.extra:
        return None
    return float(np.percentile(run.extra["visible_s"], 95))
