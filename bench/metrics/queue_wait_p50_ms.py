"""Median wait in the scheduler's queue, in ms: from a request's arrival to
the start of the admission burst that takes it (the harness wraps the
engine's ``_admit_many``), over the window's admitted requests."""
import numpy as np


def read(run):
    starts = run.rec.admit_start
    t = [starts[r.rid] - r.arrival_s for r in run.window.arrived
         if r.rid in starts]
    return float(np.percentile(t, 50)) * 1e3 if t else None
