"""The flash-attention kernel (``kernels/flash_attention``) in decode, as
a share of its roofline, in %: the least time its calls need (one layer
of a bucket: keys and values up to each row's length, bound by HBM
bandwidth) over the device time of its events in the trace.  Moves
``itl_p95_ms``."""
from bench import flops

KERNEL = "flash"


def read(run):
    if run["job"] != "serve":
        return None
    c, red = run["config"], run["trace"]
    secs, calls = red.kernel(KERNEL)
    if not calls or secs <= 0 or not run["steps"]:
        return None
    cap = run["bucket_capacity"]
    need, execs = 0.0, 0
    for step in run["steps"]:
        for _rank, lengths in step:
            # an empty slot still holds its one position
            rows = list(lengths) + [1] * (cap - len(lengths))
            need += flops.roofline_seconds(
                *flops.decode_attention_call(c, rows), run["peaks"])[0]
            execs += 1
    return 100.0 * need / execs * calls / secs
