"""The whole decode step's share of the chip's bf16 peak over the traced
serving window, in %: the FLOPs every row processed needs (base, its
tenant's adapter, attention over its cache, head; prompt and output rows
alike, empty slots not counted) over the traced window's seconds and the
peak.  Moves ``itl_p95_ms``."""
from bench import flops


def read(run):
    if run["job"] != "serve" or not run["steps"] or not run["peaks"]:
        return None
    c, red = run["config"], run["trace"]
    total = 0.0
    for step in run["steps"]:
        for rank, lengths in step:
            total += sum(flops.decode_token_flops(c, rank, n)
                         for n in lengths)
    return 100.0 * total / red.window_s / run["peaks"]["bf16_flops_per_s"]
