"""95th percentile of (append time - due time) over the window's chunks."""

import numpy as np


def read(obs):
    late = obs["feeder"].get("late_ms")
    return float(np.percentile(late, 95)) if late else None
