"""Median over every request completed in the window of its time per
output token after the first: the batch's decode wall time over the tokens
decoded after the first (host clock)."""
import numpy as np


def read(run):
    return float(np.percentile([r["tpot_s"] for r in run.requests], 50)) * 1e3
