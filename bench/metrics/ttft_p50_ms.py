"""Median time to first token over every request completed in the window
(host clock).  A request's value is its static batch's
``RequestStats.ttft_wall_s``, shared by the batch's requests."""
import numpy as np


def read(run):
    return float(np.percentile([r["ttft_s"] for r in run.requests], 50)) * 1e3
