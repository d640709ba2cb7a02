"""Median time to first token, in ms, over every request that arrived in
the window: from its scheduled arrival (open loop) or its send (closed
loop) to its first token."""
import numpy as np


def read(run):
    t = [r.first_token_s - r.arrival_s for r in run.window.arrived
         if r.first_token_s is not None]
    return float(np.percentile(t, 50)) * 1e3 if t else None
