"""The host's share of the engine step (``ServeEngine.step``): chip 0's
idle time inside the program's ``serve.step`` spans of the traced slice,
per span, in ms.  Moves ``itl_p95_ms``.  Nothing to read where the
program's spans are not in the device trace."""
from bench import program_trace


def read(run):
    if run["job"] != "serve":
        return None
    tr = program_trace.current()
    n, idle = tr.span_idle.get("serve.step", (0, 0.0)) if tr else (0, 0.0)
    return 1e3 * idle / n if n else None
