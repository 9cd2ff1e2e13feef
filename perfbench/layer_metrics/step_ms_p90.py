"""90th percentile of the step-to-step interval over every step of the window,
unsmoothed: from one step's loss being on the host to the next's, as the
harness's watcher thread notes them. A sample is one step (150-500 ms), so the
host clock's half millisecond is up to 0.3 % of it."""

import numpy as np


def read(run):
    return float(np.percentile(run["step_ms"], 90)) if run["step_ms"] else None
