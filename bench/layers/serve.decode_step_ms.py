"""One rank bucket's decode step (``ServeEngine``): the mean of the
program's ``serve.decode`` spans, which end on the host's copy of the
step's tokens, in ms.  Moves ``itl_p95_ms``."""


def read(run):
    spans = run.get("decode_spans_ms") or []
    return sum(spans) / len(spans) if spans else None
