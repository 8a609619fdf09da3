"""The prompt feed (``ServeEngine``): the 95th percentile, in ms, of the
program's ``serve.prefill`` spans (a request's admission to its first
token) over the requests due in the window.  Moves ``ttft_p95_ms``.
Nothing to read where the program records no such span.

The spans are those the program's tracer holds, which the serve job keeps
on through its window in a traced run.  The engine numbers requests in
the order they were submitted, and the window submits them in the order
they are due, so the requests due in the window are the admitted ones
(``serve.queue``) with the lowest ids, as many as the window counted
admission waits for."""
import numpy as np


def read(run):
    if run["job"] != "serve" or not run["queue_waits_ms"]:
        return None
    from repro import obs
    events = obs.trace.get_tracer().events()

    def rid(e):
        return e.get("args", {}).get("rid")

    admitted = sorted(rid(e) for e in events
                      if e["name"] == "serve.queue" and rid(e) is not None)
    due = set(admitted[:len(run["queue_waits_ms"])])
    w = [e["dur"] / 1e3 for e in events
         if e["name"] == "serve.prefill" and rid(e) in due]
    if not w:
        return None
    w = np.sort(np.asarray(w, np.float64))
    return float(w[int(np.ceil(0.95 * len(w))) - 1])
