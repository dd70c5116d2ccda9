"""The 90th percentile of the window's step times: the interval between
consecutive calls of the benchmark's feed by the trainer, so input
preparation, dispatch and the step's gradient sync all count."""

import numpy as np


def read(rec):
    return float(np.percentile(rec["step_ms"], 90))
