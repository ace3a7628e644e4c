"""95th percentile, over the window's admitted requests, of the host
time from a request's due time to the start of the `engine.step()` in
which it was admitted (scheduler and KV admission)."""
import math

import numpy as np


def read(run):
    w = [r.admitted - r.due for r in run.reqs if not math.isnan(r.admitted)]
    return float(np.percentile(w, 95)) if w else None
