import numpy as np


def read(ctx, params):
    """Percentile of the time between consecutive step completions, all
    steps of the window; the first interval runs from the window's start."""
    gaps = np.diff(np.asarray([0.0] + list(ctx["done_at"])))
    return float(np.percentile(gaps, params["q"]) * 1e3)
