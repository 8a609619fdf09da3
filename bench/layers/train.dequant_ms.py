"""XLA's dequantization of the packed base in the train step
(``models/modules.linear_apply``, forward and backward): device time of
the ops under the ``dequant`` scope, per traced step, in ms.  Moves
``train_tokens_per_s``.  Nothing to read where no op carries the scope."""
from bench import program_trace
from bench.jobs.train import TRACE_STEPS

SCOPE = "dequant"


def read(run):
    if run["job"] != "train":
        return None
    tr = program_trace.current()
    secs = tr.scope_seconds.get(SCOPE, 0.0) if tr else 0.0
    return 1e3 * secs / TRACE_STEPS if secs > 0 else None
