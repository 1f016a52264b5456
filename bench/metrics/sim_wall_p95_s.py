"""95th percentile of the host seconds of every call of the window."""

import numpy as np


def read(w):
    if not w.calls:
        return None
    return float(np.percentile([c["wall_s"] for c in w.calls], 95))
