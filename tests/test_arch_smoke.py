"""Per-assigned-architecture smoke tests: reduced config, one forward/train
step and one decode step on CPU, asserting shapes + finiteness.  The FULL
configs are exercised by the dry-run only (results/dryrun)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ALIASES, get_config, get_smoke_config
from repro.launch.steps import build_state, make_train_step
from repro.models.parallel import LOCAL
from repro.models.transformer import (decode_step, forward, init_decode_cache,
                                      init_params, loss_fn)
from repro.optim import OptConfig

ARCHS = list(ALIASES)
RNG = np.random.default_rng(0)


def _batch(cfg, B=2, S=16):
    toks = jnp.asarray(RNG.integers(0, cfg.vocab, (B, S)), jnp.int32)
    batch = {"tokens": toks, "labels": toks}
    if cfg.family == "encdec":
        batch["enc_embeds"] = jnp.asarray(
            RNG.normal(size=(B, max(S // 4, 4), cfg.d_model)), jnp.float32)
    if cfg.frontend == "vision":
        batch["prefix_embeds"] = jnp.asarray(
            RNG.normal(size=(B, cfg.n_prefix, cfg.d_model)), jnp.float32)
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_forward_and_train_step(arch):
    cfg = get_smoke_config(arch)
    params = init_params(jax.random.PRNGKey(0), cfg)
    batch = _batch(cfg)
    logits, aux = forward(params, cfg, batch)
    assert logits.shape == (2, 16, cfg.vocab_padded)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))

    ocfg = OptConfig(lr=1e-3, trainable="all", total_steps=4)
    state = build_state(params, ocfg)
    step = jax.jit(make_train_step(cfg, ocfg, LOCAL))
    state, m = step(state, batch)
    l0 = float(m["loss"])
    state, m = step(state, batch)
    assert np.isfinite(l0) and np.isfinite(float(m["loss"]))
    assert float(m["loss"]) < l0 + 1.0   # no blow-up


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_decode_step(arch):
    cfg = get_smoke_config(arch)
    params = init_params(jax.random.PRNGKey(0), cfg)
    B, T = 2, 8
    cache = init_decode_cache(cfg, B, T)
    if cfg.family == "encdec":
        cache["enc_out"] = jnp.asarray(
            RNG.normal(size=(B, T, cfg.d_model)), cfg.dtype)
    toks = jnp.asarray(RNG.integers(0, cfg.vocab, (B, 1)), jnp.int32)
    logits, cache2 = decode_step(params, cfg, cache, toks)
    assert logits.shape == (B, cfg.vocab_padded)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))
    assert int(cache2["idx"]) == 1
    logits2, _ = decode_step(params, cfg, cache2, toks)
    assert bool(jnp.all(jnp.isfinite(logits2.astype(jnp.float32))))


def test_full_configs_match_assignment():
    """The exact published numbers from the assignment sheet."""
    c = get_config("qwen3-moe-30b-a3b")
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.vocab,
            c.n_experts, c.top_k) == (48, 2048, 32, 4, 151936, 128, 8)
    c = get_config("olmoe-1b-7b")
    assert (c.n_layers, c.d_model, c.n_experts, c.top_k, c.vocab) == \
        (16, 2048, 64, 8, 50304)
    assert (c.n_heads, c.n_kv_heads, c.head_dim, c.d_ff_expert,
            c.rope_theta, c.rms_norm_eps) == (16, 16, 128, 1024, 1e4, 1e-5)
    assert c.qk_norm and c.qk_norm_span == "proj" and not c.tie_embeddings
    assert c.capacity_factor is None and not c.norm_topk and c.router_aux_weight == 0.0
    c = get_config("qwen3-4b")
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff) == \
        (36, 2560, 32, 8, 9728) and c.qk_norm
    c = get_config("codeqwen1.5-7b")
    assert (c.n_layers, c.d_model, c.d_ff, c.vocab) == (32, 4096, 13440, 92416)
    assert c.attn_bias
    c = get_config("qwen3-1.7b")
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff) == \
        (28, 2048, 16, 8, 6144)
    c = get_config("minicpm-2b")
    assert (c.n_layers, c.d_model, c.n_heads, c.d_ff, c.vocab) == \
        (40, 2304, 36, 5760, 122753)
    c = get_config("zamba2-7b")
    assert (c.n_layers, c.d_model, c.n_heads, c.d_ff, c.vocab,
            c.ssm_state) == (81, 3584, 32, 14336, 32000, 64)
    c = get_config("seamless-m4t-medium")
    assert (c.n_layers, c.n_enc_layers, c.d_model, c.d_ff, c.vocab) == \
        (12, 12, 1024, 4096, 256206)
    c = get_config("mamba2-370m")
    assert (c.n_layers, c.d_model, c.ssm_state, c.vocab) == \
        (48, 1024, 128, 50280)
    c = get_config("pixtral-12b")
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff,
            c.vocab) == (40, 5120, 32, 8, 14336, 131072)


def test_vocab_padding_divisible_for_tp():
    for arch in ARCHS:
        c = get_config(arch)
        assert c.vocab_padded % 16 == 0, arch
        assert c.vocab_padded >= c.vocab
        assert c.vocab_padded - c.vocab < c.vocab_pad_multiple
