"""The MoE layer's routing and data movement in the train step: device
time of the ops under the ``route`` (router and top-k), ``dispatch``
(assignments sorted by expert, rows gathered into that order) and
``combine`` (rows gathered back to their tokens and weighted) scopes,
forward and backward, per traced step, in ms.  Moves
``train_tokens_per_s``.  Nothing to read where no op carries them."""
from bench import program_trace
from bench.jobs.train import TRACE_STEPS

SCOPES = ("route", "dispatch", "combine")


def read(run):
    if run["job"] != "train":
        return None
    tr = program_trace.current()
    secs = sum(tr.scope_seconds.get(s, 0.0) for s in SCOPES) if tr else 0.0
    return 1e3 * secs / TRACE_STEPS if secs > 0 else None
