"""Attention in the train step (``models/attention``, forward and
backward, its q/k/v/o projections included): device time of the ops under
the ``attn`` scope, per traced step, in ms.  Moves
``train_tokens_per_s``.  Nothing to read where no op carries the scope."""
from bench import program_trace
from bench.jobs.train import TRACE_STEPS

SCOPE = "attn"


def read(run):
    if run["job"] != "train":
        return None
    tr = program_trace.current()
    secs = tr.scope_seconds.get(SCOPE, 0.0) if tr else 0.0
    return 1e3 * secs / TRACE_STEPS if secs > 0 else None
