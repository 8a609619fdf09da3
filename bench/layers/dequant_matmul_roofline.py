"""The packed-INT4 matmul kernel (``kernels/dequant_matmul``) in decode,
as a share of its roofline, in %: the least time its calls need (each
call a bucket's rows through one site, the larger of its FLOPs over the
bf16 peak and its bytes over HBM bandwidth; at decode batch sizes the
bytes bound it) over the device time of its events in the trace.  Moves
``itl_p95_ms``."""
from bench import flops

KERNEL = "dequant"


def read(run):
    if run["job"] != "serve":
        return None
    c, red = run["config"], run["trace"]
    secs, calls = red.kernel(KERNEL)
    if not calls or secs <= 0:
        return None
    rows = run["bucket_capacity"]
    sites = flops.site_shapes(c).values()
    per_call = sum(flops.roofline_seconds(
        *flops.dequant_matmul_call(c, rows, m, n), run["peaks"])[0]
        for m, n in sites) / len(sites)
    return 100.0 * per_call * calls / secs
