"""The per-row adapter gather (``ServeEngine``'s ``jnp.take`` of each rank
bucket's stacked adapters by tenant slot): device time of the ops under
the ``lora_gather`` scope, per traced engine step, in ms.  Moves
``itl_p95_ms``.  Nothing to read where no op carries the scope."""
from bench import program_trace

SCOPE = "lora_gather"


def read(run):
    if run["job"] != "serve" or not run["steps"]:
        return None
    tr = program_trace.current()
    secs = tr.scope_seconds.get(SCOPE, 0.0) if tr else 0.0
    return 1e3 * secs / len(run["steps"]) if secs > 0 else None
