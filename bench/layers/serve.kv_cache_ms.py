"""Paged KV cache traffic (``serve/kv_cache``: the gather of each row's
pages into a contiguous view, and the scatter of each row's new keys and
values, handed back by the layer scan, into the donated pools): device
time of the ops under the ``kv_cache`` scope, per traced engine step, in
ms.  Moves ``itl_p95_ms``.  Nothing to read where no op carries the
scope."""
from bench import program_trace

SCOPE = "kv_cache"


def read(run):
    if run["job"] != "serve" or not run["steps"]:
        return None
    tr = program_trace.current()
    secs = tr.scope_seconds.get(SCOPE, 0.0) if tr else 0.0
    return 1e3 * secs / len(run["steps"]) if secs > 0 else None
