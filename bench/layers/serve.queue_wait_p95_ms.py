"""Admission wait (``serve/scheduler``): the 95th percentile, in ms, of
the wait of every request due in the window from its ``submit()`` to the
engine step that first gave it a slot, as the program stamps them
(``_Request.t_submit`` and ``t_admit``).  Moves ``ttft_p95_ms``.  Nothing
to read where no request was admitted."""
import numpy as np


def read(run):
    if run["job"] != "serve" or not run["queue_waits_ms"]:
        return None
    w = np.sort(np.asarray(run["queue_waits_ms"], np.float64))
    return float(w[int(np.ceil(0.95 * len(w))) - 1])
