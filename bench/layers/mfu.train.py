"""The whole train step's share of the chip's bf16 peak over the traced
steps, in %: the forward and backward FLOPs a token needs (the base and
head only their input gradient, adapters both, no recomputation) times
the traced tokens, over the traced seconds and the peak.  Moves
``train_tokens_per_s``."""
from bench import flops


def read(run):
    if run["job"] != "train" or not run["peaks"]:
        return None
    c, red = run["config"], run["trace"]
    f = flops.train_token_flops(c, c["lora_rank"], run["seq_len"])
    return 100.0 * f * run["tokens"] / red.window_s \
        / run["peaks"]["bf16_flops_per_s"]
