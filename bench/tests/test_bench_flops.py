"""The operations and bytes of ``bench/flops.py``: the dense counts of
the published configuration as they were, and an MoE decoder's routed
experts and router counted by hand."""
import bench_tiny  # noqa: F401  (puts the repository on the path)

from bench import flops, harness

QWEN = harness.config_file("qwen3-1.7b")
# a decoder of 2 layers, d 8, 2 query heads and 1 key-value head of 4,
# 4 experts of width 6, top 2, vocabulary 10
MOE = {"n_layers": 2, "d_model": 8, "n_heads": 2, "n_kv_heads": 1,
       "head_dim": 4, "n_experts": 4, "top_k": 2, "d_ff_expert": 6,
       "vocab": 10, "quant": {"bits": 4, "group_size": 2}}


def test_dense_counts_of_qwen3_are_unchanged():
    assert flops.train_token_flops(QWEN, 64, 4096) == 8709816320.0
    assert flops.decode_token_flops(QWEN, 16, 100) == 3498704896.0
    assert flops.decode_token_flops(QWEN, 64, 384) == 3668443136.0
    assert flops.linear_macs(QWEN) == 1409286144
    assert flops.lora_macs(QWEN, 64) == 69730304
    assert list(flops.site_shapes(QWEN)) == [
        "attn.q", "attn.k", "attn.v", "attn.o", "mlp.gate", "mlp.up",
        "mlp.down"]
    assert flops.dequant_matmul_call(QWEN, 16, 2048, 6144) == \
        (402653184.0, 8126464.0)


def test_moe_counts_route_each_token_through_top_k_experts():
    # per layer: q 64 + k 32 + v 32 + o 64, router 8 x 4, and 2 experts
    # of gate 48 + up 48 + down 48
    assert flops.linear_macs(MOE) == 2 * (192 + 32 + 2 * 144)
    # rank 2: q 2(8+8), k and v 2(8+4), o 2(8+8); each routed expert's
    # gate, up and down 2(8+6)
    assert flops.lora_macs(MOE, 2) == 2 * (112 + 2 * 3 * 28)
    assert flops.site_shapes(MOE) == {
        "attn.q": (8, 8), "attn.k": (8, 4), "attn.v": (8, 4),
        "attn.o": (8, 8), "moe.gate": (8, 6), "moe.up": (8, 6),
        "moe.down": (6, 8)}
    # linear 1024, LoRA 560, attention 2 x 2 x 2 x 4 x 5, head 80
    assert flops.decode_token_flops(MOE, 2, 5) == 2.0 * (1024 + 560 + 160
                                                         + 80)
    # context (7 + 1) / 2: forward 1024 + 560 + 128 + 80, backward
    # 1024 + 2 x 560 + 2 x 128 + 80
    assert flops.train_token_flops(MOE, 2, 7) == 2.0 * (1792 + 2480)
