"""95th percentile of the host-clock time of every ``step_once`` call in
the window, in milliseconds."""

import numpy as np


def read(ctx):
    return float(np.percentile(np.asarray(ctx["step_s"]) * 1e3, 95))
