"""Model-zoo correctness: SSD math, decode<->prefill consistency, masks."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.attention import (AttnConfig, attn_apply, attn_decode,
                                    attn_init)
from repro.models.moe import MoEConfig, moe_apply, moe_init
from repro.models.ssm import (SSMConfig, mamba_apply, mamba_decode,
                              mamba_init, mamba_init_cache, ssd_chunked)
from repro.models.transformer import (ModelConfig, decode_step, forward,
                                      init_decode_cache, init_params)

RNG = np.random.default_rng(0)


def test_ssd_chunked_equals_recurrence():
    b, s, h, p, n = 2, 16, 3, 4, 5
    x = jnp.asarray(RNG.normal(size=(b, s, h, p)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.1, 0.9, size=(b, s, h)), jnp.float32)
    A = -jnp.asarray(RNG.uniform(0.5, 2.0, size=(h,)), jnp.float32)
    B = jnp.asarray(RNG.normal(size=(b, s, h, n)), jnp.float32)
    C = jnp.asarray(RNG.normal(size=(b, s, h, n)), jnp.float32)
    st = jnp.zeros((b, h, p, n))
    ys = []
    for t in range(s):
        decay = jnp.exp(dt[:, t] * A[None, :])
        st = st * decay[:, :, None, None] + jnp.einsum(
            "bh,bhn,bhp->bhpn", dt[:, t], B[:, t], x[:, t])
        ys.append(jnp.einsum("bhn,bhpn->bhp", C[:, t], st))
    y_ref = jnp.stack(ys, axis=1)
    for chunk in (4, 8, 16):
        y, fin = ssd_chunked(x, dt, A, B, C, chunk)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(fin), np.asarray(st), atol=1e-5)


def test_ssd_init_state_continuation():
    """Splitting a sequence across two ssd calls with state carry == one call."""
    b, s, h, p, n = 1, 16, 2, 4, 3
    x = jnp.asarray(RNG.normal(size=(b, s, h, p)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.1, 0.9, size=(b, s, h)), jnp.float32)
    A = -jnp.asarray(RNG.uniform(0.5, 2.0, size=(h,)), jnp.float32)
    B = jnp.asarray(RNG.normal(size=(b, s, h, n)), jnp.float32)
    C = jnp.asarray(RNG.normal(size=(b, s, h, n)), jnp.float32)
    y_full, st_full = ssd_chunked(x, dt, A, B, C, 4)
    y1, st1 = ssd_chunked(x[:, :8], dt[:, :8], A, B[:, :8], C[:, :8], 4)
    y2, st2 = ssd_chunked(x[:, 8:], dt[:, 8:], A, B[:, 8:], C[:, 8:], 4,
                          init_state=st1)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y_full), atol=1e-5)
    np.testing.assert_allclose(np.asarray(st2), np.asarray(st_full), atol=1e-5)


def test_mamba_decode_matches_prefill():
    cfg = SSMConfig(d_model=32, d_state=8, head_dim=8, n_groups=2, chunk=4)
    p = mamba_init(jax.random.PRNGKey(1), cfg, dtype=jnp.float32)
    x = jnp.asarray(RNG.normal(size=(2, 8, 32)), jnp.float32)
    y_full = mamba_apply(p, cfg, x)
    cache = mamba_init_cache(cfg, 2)
    outs = []
    for t in range(8):
        o, cache = mamba_decode(p, cfg, x[:, t:t + 1], cache)
        outs.append(o[:, 0])
    np.testing.assert_allclose(np.asarray(jnp.stack(outs, 1)),
                               np.asarray(y_full), atol=1e-4)


def test_attention_decode_matches_full():
    acfg = AttnConfig(d_model=32, n_heads=4, n_kv_heads=2, qk_norm=True,
                      rope_theta=1e4)
    p = attn_init(jax.random.PRNGKey(2), acfg, dtype=jnp.float32)
    x = jnp.asarray(RNG.normal(size=(2, 8, 32)), jnp.float32)
    y_full = attn_apply(p, acfg, x)
    cache = {"k": jnp.zeros((2, 8, 2, 8)), "v": jnp.zeros((2, 8, 2, 8)),
             "idx": jnp.zeros((), jnp.int32)}
    outs = []
    for t in range(8):
        o, cache, _ = attn_decode(p, acfg, x[:, t:t + 1], cache)
        outs.append(o[:, 0])
    np.testing.assert_allclose(np.asarray(jnp.stack(outs, 1)),
                               np.asarray(y_full), atol=1e-5)


def test_sliding_window_mask():
    acfg = AttnConfig(d_model=16, n_heads=2, n_kv_heads=2, sliding_window=3,
                      rope_theta=1e4)
    p = attn_init(jax.random.PRNGKey(3), acfg, dtype=jnp.float32)
    x = jnp.asarray(RNG.normal(size=(1, 12, 16)), jnp.float32)
    y = attn_apply(p, acfg, x)
    # position t must be insensitive to tokens before t - window + 1
    x2 = x.at[:, 0, :].set(100.0)
    y2 = attn_apply(p, acfg, x2)
    np.testing.assert_allclose(np.asarray(y[:, 6:]), np.asarray(y2[:, 6:]),
                               atol=1e-4)


def test_moe_capacity_drops_and_weights():
    cfg = MoEConfig(n_experts=4, top_k=2, d_model=16, d_ff=32,
                    capacity_factor=4.0)
    p = moe_init(jax.random.PRNGKey(4), cfg, dtype=jnp.float32)
    x = jnp.asarray(RNG.normal(size=(2, 8, 16)), jnp.float32)
    y, aux = moe_apply(p, cfg, x)
    assert y.shape == x.shape and bool(jnp.isfinite(aux["balance"]))
    # generous capacity: every token hits k experts; tiny capacity drops some
    cfg_tight = MoEConfig(n_experts=4, top_k=2, d_model=16, d_ff=32,
                          capacity_factor=0.5)
    y2, _ = moe_apply(p, cfg_tight, x)
    assert not np.allclose(np.asarray(y), np.asarray(y2))


def test_scan_equals_unrolled_forward():
    """scan_layers=True and False are the same function."""
    for family, kw in [("dense", {}),
                       ("ssm", dict(ssm_state=8, ssm_head_dim=16, ssm_chunk=8)),
                       ("hybrid", dict(n_layers=4, hybrid_attn_every=2,
                                       ssm_state=8, ssm_head_dim=16,
                                       ssm_chunk=8))]:
        base = dict(name="t", family=family, n_layers=4, d_model=32,
                    vocab=64, n_heads=4, n_kv_heads=2, d_ff=64,
                    dtype=jnp.float32)
        base.update(kw)
        cfg_scan = ModelConfig(**base, scan_layers=True)
        p = init_params(jax.random.PRNGKey(5), cfg_scan)
        toks = jnp.asarray(RNG.integers(0, 64, (2, 8)), jnp.int32)
        lg_scan, _ = forward(p, cfg_scan, {"tokens": toks})
        from repro.core.pipeline import to_eager_params
        cfg_un = ModelConfig(**base, scan_layers=False)
        pe = to_eager_params(p, cfg_scan)
        lg_un, _ = forward(pe, cfg_un, {"tokens": toks})
        np.testing.assert_allclose(np.asarray(lg_scan), np.asarray(lg_un),
                                   atol=2e-4, err_msg=family)


@pytest.mark.parametrize("family,kw", [
    ("dense", {}),
    # generous capacity: decode routes 1 token/step (never drops), so exact
    # equality with forward needs forward to not drop either
    ("moe", dict(n_experts=4, top_k=2, d_ff_expert=32, capacity_factor=8.0)),
    ("ssm", dict(ssm_state=8, ssm_head_dim=16, ssm_chunk=8)),
    ("hybrid", dict(n_layers=4, hybrid_attn_every=2, ssm_state=8,
                    ssm_head_dim=16, ssm_chunk=8, hybrid_window=8)),
])
def test_model_decode_matches_forward(family, kw):
    """Greedy logits from step-by-step decode == teacher-forced forward."""
    base = dict(name="t", family=family, n_layers=4, d_model=32, vocab=64,
                n_heads=4, n_kv_heads=2, d_ff=64, dtype=jnp.float32,
                scan_layers=True)
    base.update(kw)
    cfg = ModelConfig(**base)
    p = init_params(jax.random.PRNGKey(6), cfg)
    S = 8
    toks = jnp.asarray(RNG.integers(0, 64, (2, S)), jnp.int32)
    logits_full, _ = forward(p, cfg, {"tokens": toks})
    cache = init_decode_cache(cfg, 2, S)
    outs = []
    for t in range(S):
        lg, cache = decode_step(p, cfg, cache, toks[:, t:t + 1])
        outs.append(lg)
    logits_dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(logits_dec),
                               np.asarray(logits_full), atol=2e-3,
                               err_msg=family)


@pytest.mark.parametrize("tied", [True, False], ids=["tied", "untied"])
def test_head_as_wide_as_the_model_is_told_tied_from_untied(tied):
    """With as many vocabulary rows as model widths the head's weight is
    square, so only the model's ``tie_embeddings`` says whether it is the
    embedding (used transposed) or a head linear."""
    cfg = ModelConfig(name="t", family="dense", n_layers=1, d_model=32,
                      vocab=32, n_heads=4, n_kv_heads=2, d_ff=64,
                      tie_embeddings=tied, dtype=jnp.float32)
    p = init_params(jax.random.PRNGKey(6), cfg)
    toks = jnp.asarray(RNG.integers(0, 32, (2, 8)), jnp.int32)
    hidden, _ = forward(p, cfg, {"tokens": toks}, return_hidden=True)
    logits, _ = forward(p, cfg, {"tokens": toks})
    want = hidden @ (p["embed"]["w"].T if tied else p["head"]["w"])
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
