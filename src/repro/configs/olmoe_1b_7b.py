"""olmoe-1b-7b [arXiv:2409.02060, hf:allenai/OLMoE-1B-7B-0924]: 16L
d_model=2048 16H (kv=16) expert d_ff=1024, vocab=50304, MoE 64 experts
top-8, dropless, top-k weights not renormalized, qk-norm over the whole
q and k projections, untied head."""
import jax.numpy as jnp

from repro.models.transformer import ModelConfig


def config() -> ModelConfig:
    return ModelConfig(
        name="olmoe-1b-7b", family="moe",
        n_layers=16, d_model=2048, vocab=50304, vocab_pad_multiple=256,
        n_heads=16, n_kv_heads=16, head_dim=128, qk_norm=True,
        qk_norm_span="proj", rope_theta=1e4, rms_norm_eps=1e-5,
        n_experts=64, top_k=8, d_ff_expert=1024, norm_topk=False,
        capacity_factor=None,
        # the published config sets router_aux_loss_coef 0.01 but
        # output_router_logits false, so the fine-tune loss is
        # cross-entropy alone
        router_aux_weight=0.0,
        dtype=jnp.bfloat16,
    )


def smoke_config() -> ModelConfig:
    return ModelConfig(
        name="olmoe-1b-7b-smoke", family="moe",
        n_layers=2, d_model=64, vocab=512,
        n_heads=4, n_kv_heads=4, head_dim=16, qk_norm=True,
        qk_norm_span="proj", rms_norm_eps=1e-5,
        n_experts=4, top_k=2, d_ff_expert=32, norm_topk=False,
        capacity_factor=None, router_aux_weight=0.0,
        dtype=jnp.float32,
    )
