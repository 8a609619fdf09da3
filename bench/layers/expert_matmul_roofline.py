"""The dropless MoE layer's grouped matmuls (``jax.lax.ragged_dot``, the
``ragged-dot`` ops of the trace) in the train step, as a share of their
roofline, in %: the least time their calls need (each call the larger of
its FLOPs over the bf16 peak and its bytes over HBM bandwidth, counted by
``bench.expert_flops`` for the forward, its recompute and the backward
of each traced step) over the device time of those ops in those steps.  Moves
``train_tokens_per_s``.  Nothing to read where the trace holds no such
op."""
from bench import expert_flops, flops

# the grouped matmuls' own ops; ``ragged-dot-metadata`` only lays out
# the groups
KERNEL = "ragged-dot-none"


def read(run):
    if run["job"] != "train" or not run["peaks"]:
        return None
    red = run["trace"]
    secs, calls = red.kernel(KERNEL)
    if not calls or secs <= 0:
        return None
    c = run["config"]
    per_step = int(c["train_batch"]) * run["seq_len"]
    least = sum(flops.roofline_seconds(f, b, run["peaks"])[0]
                for f, b in expert_flops.step_calls(c, per_step))
    return 100.0 * least * (run["tokens"] // per_step) / secs
