"""90th percentile over all steps of the window of the time from the loss
of the step before (for the first, the window's start) to the step's loss
being on the host, with the window's steps dispatched ahead."""

import numpy as np


def read(run):
    if not run["step_s"]:
        return None
    return float(np.percentile(run["step_s"], 90)) * 1e3
