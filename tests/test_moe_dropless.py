"""The dropless MoE layer (OLMoE's routing) and the whole-projection
qk-norm, against plain ``jax.numpy`` on seeded random weights: every
routed row reaches its expert, the top-k weights stay as the softmax gave
them, the layer agrees under an expert-parallel mesh, its expert adapters
get the loop's gradients, and calibration records each expert's Gram from
the rows routed to it."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.attention import AttnConfig, attn_apply, attn_init
from repro.models.moe import MoEConfig, moe_apply, moe_init
from repro.utils import GramStore, capture_grams
from tests.util import run_with_devices

E, K, D, F, R = 8, 2, 16, 24, 4
CFG = MoEConfig(n_experts=E, top_k=K, d_model=D, d_ff=F, norm_topk=False,
                capacity_factor=None)


def _params(seed=0, skew=0.0):
    """Expert weights with nonzero LoRA factors; ``skew`` adds to the
    router's column of expert 0, so that most rows pick it."""
    p = moe_init(jax.random.PRNGKey(seed), CFG, dtype=jnp.float32,
                 lora_rank=R)
    kb = jax.random.PRNGKey(seed + 100)
    for i, name in enumerate(("gate", "up", "down")):
        p[name]["lora_b"] = 0.1 * jax.random.normal(
            jax.random.fold_in(kb, i), p[name]["lora_b"].shape)
    p["router"]["w"] = p["router"]["w"].at[:, 0].add(skew)
    return p


def _x(seed=1, shape=(2, 16, D)):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


def _routing(p, x, norm_topk=False):
    """Top-k expert ids and weights of each token, as published: softmax
    over all experts in f32, then top-k, renormalized only if asked."""
    xt = np.asarray(x, np.float64).reshape(-1, D)
    logits = xt @ np.asarray(p["router"]["w"], np.float64)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    topi = np.argsort(-probs, axis=-1, kind="stable")[:, :K]
    topw = np.take_along_axis(probs, topi, -1)
    if norm_topk:
        topw = topw / topw.sum(-1, keepdims=True)
    return topi, topw


def _expert(p, e, x):
    """Expert ``e``'s SwiGLU with its LoRA on rows ``x (n, D)``."""
    def lin(name, v):
        w = p[name]
        y = v @ w["w"][e]
        return y + (v @ w["lora_a"][e]) @ w["lora_b"][e].T
    return lin("down", jax.nn.silu(lin("gate", x)) * lin("up", x))


def _loop(p, x, norm_topk=False):
    """The layer as a loop over experts: each expert on the rows routed to
    it, weighted and added back to their tokens."""
    topi, topw = _routing(p, x, norm_topk)
    xt = x.reshape(-1, D)
    out = jnp.zeros_like(xt)
    for e in range(E):
        tok, slot = np.nonzero(topi == e)
        if len(tok):
            y = _expert(p, e, xt[tok]) * topw[tok, slot][:, None]
            out = out.at[tok].add(y.astype(jnp.float32))
    return out.reshape(x.shape)


def test_dropless_matches_a_per_expert_loop_where_capacity_would_drop():
    p, x = _params(skew=3.0), _x()
    topi, _ = _routing(p, x)
    counts = np.bincount(topi.ravel(), minlength=E)
    capacity = int(x.shape[0] * x.shape[1] * K * 1.25 / E)
    assert counts.max() > capacity            # the capacity path drops here
    y, aux = moe_apply(p, CFG, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(_loop(p, x)),
                               rtol=1e-5, atol=1e-5)
    assert float(aux["load_max"]) == pytest.approx(counts.max()
                                                   / counts.mean())
    dropped, _ = moe_apply(p, dataclasses.replace(CFG, capacity_factor=1.25), x)
    assert not np.allclose(np.asarray(dropped), np.asarray(y), atol=1e-3)


@pytest.mark.parametrize("norm_topk", [False, True])
def test_topk_weights_are_renormalized_only_where_asked(norm_topk):
    p, x = _params(seed=2), _x(seed=3)
    cfg = dataclasses.replace(CFG, norm_topk=norm_topk)
    y, _ = moe_apply(p, cfg, x)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(_loop(p, x, norm_topk)),
                               rtol=1e-5, atol=1e-5)
    other, _ = moe_apply(p, dataclasses.replace(cfg, norm_topk=not norm_topk),
                         x)
    assert not np.allclose(np.asarray(other), np.asarray(y), atol=1e-3)


def test_expert_lora_gradients_match_the_loop():
    p, x = _params(seed=4, skew=1.0), _x(seed=5)
    lora = {n: {k: p[n][k] for k in ("lora_a", "lora_b")}
            for n in ("gate", "up", "down")}
    cot = _x(seed=6)

    def merged(lo):
        return {**p, **{n: {**p[n], **lo[n]} for n in lo}}

    def program(lo):
        return jnp.sum(moe_apply(merged(lo), CFG, x)[0] * cot)

    def loop(lo):
        return jnp.sum(_loop(merged(lo), x) * cot)

    got, want = jax.grad(program)(lora), jax.grad(loop)(lora)
    for n in lora:
        for k in ("lora_a", "lora_b"):
            assert float(jnp.linalg.norm(want[n][k])) > 0
            np.testing.assert_allclose(np.asarray(got[n][k]),
                                       np.asarray(want[n][k]),
                                       rtol=1e-4, atol=1e-5,
                                       err_msg=f"{n}.{k}")


def test_calibration_records_each_experts_gram_of_its_rows():
    p, x = _params(seed=7, skew=2.0), _x(seed=8)
    store = GramStore()
    with capture_grams(store):
        moe_apply(p, CFG, x)
    topi, _ = _routing(p, x)
    xt = np.asarray(x, np.float64).reshape(-1, D)
    for path in ("gate", "up"):
        assert store.grams[path].shape == (E, D, D)
    for e in range(E):
        rows = xt[np.nonzero(topi == e)[0]]
        for path in ("gate", "up"):
            np.testing.assert_allclose(store.grams[path][e], rows.T @ rows,
                                       rtol=1e-5, atol=1e-4)
    assert store.grams["down"].shape == (E, F, F)


def _attn_cfg(span):
    return AttnConfig(d_model=32, n_heads=4, n_kv_heads=2, head_dim=8,
                      qk_norm=True, rope_theta=1e4, qk_norm_span=span,
                      norm_eps=1e-5)


def _plain_attention(p, x, cfg):
    """Causal attention with one RMSNorm over the whole q and k
    projections (their own scales), RoPE on rotated halves, GQA."""
    B, S, _ = x.shape
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd

    def rms(v, scale):
        return v / jnp.sqrt(jnp.mean(v * v, -1, keepdims=True)
                            + cfg.norm_eps) * scale

    def rope(v):
        inv = 1.0 / (cfg.rope_theta ** (np.arange(0, hd, 2) / hd))
        ang = np.arange(S)[:, None] * inv[None, :]
        cos, sin = np.cos(ang)[None, :, None], np.sin(ang)[None, :, None]
        a, b = v[..., :hd // 2], v[..., hd // 2:]
        return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)

    q = rms(x @ p["q"]["w"], p["q_norm"]["scale"]).reshape(B, S, H, hd)
    k = rms(x @ p["k"]["w"], p["k_norm"]["scale"]).reshape(B, S, Hkv, hd)
    v = (x @ p["v"]["w"]).reshape(B, S, Hkv, hd)
    q, k = rope(q), rope(k)
    k, v = (jnp.repeat(a, H // Hkv, axis=2) for a in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
    s = jnp.where(np.tril(np.ones((S, S), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    return o.reshape(B, S, H * hd) @ p["o"]["w"]


def test_whole_projection_qk_norm_matches_the_reference():
    cfg = _attn_cfg("proj")
    p = attn_init(jax.random.PRNGKey(9), cfg, dtype=jnp.float32)
    assert p["q_norm"]["scale"].shape == (32,)
    assert p["k_norm"]["scale"].shape == (16,)
    # scales that differ across heads, so no per-head norm can match
    ks = jax.random.split(jax.random.PRNGKey(10), 2)
    for name, kk in zip(("q_norm", "k_norm"), ks):
        n = p[name]["scale"].shape[0]
        p[name]["scale"] = 1.0 + 0.5 * jax.random.normal(kk, (n,))
    x = _x(seed=11, shape=(2, 8, 32))
    y = attn_apply(p, cfg, x)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(_plain_attention(p, x, cfg)),
                               rtol=1e-4, atol=1e-5)
    # the same weights normed per head (each head by its own RMS) differ
    head = _attn_cfg("head")
    ph = dict(p, q_norm={"scale": jnp.ones((8,))},
              k_norm={"scale": jnp.ones((8,))})
    pw = dict(p, q_norm={"scale": jnp.ones((32,))},
              k_norm={"scale": jnp.ones((16,))})
    assert not np.allclose(np.asarray(attn_apply(ph, head, x)),
                           np.asarray(attn_apply(pw, cfg, x)), atol=1e-3)


@pytest.mark.multidevice
def test_dropless_under_an_expert_mesh_matches_local():
    run_with_devices("""
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from repro.models.moe import MoEConfig, moe_init, moe_apply
        from repro.launch.mesh import make_local_mesh, pcontext_for
        cfg = MoEConfig(n_experts=8, top_k=2, d_model=32, d_ff=64,
                        norm_topk=False, capacity_factor=None)
        p = moe_init(jax.random.PRNGKey(0), cfg, dtype=jnp.float32,
                     lora_rank=4)
        p["router"]["w"] = p["router"]["w"].at[:, 3].add(2.0)
        for n in ("gate", "up", "down"):
            p[n]["lora_b"] = p[n]["lora_b"] + 0.1
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32))
        y_ref, aux_ref = moe_apply(p, cfg, x)
        mesh = make_local_mesh(2, 4)
        y, aux = moe_apply(p, cfg, x, pctx=pcontext_for(mesh))
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   atol=2e-5)
        assert float(aux["load_max"]) == float(aux_ref["load_max"])
        print("dropless EP == local")
    """)


def test_olmoe_smoke_calibrates_cloq_quantizes_and_trains():
    """The paper's pipeline on the dropless layer: calibration's
    per-expert Grams feed CLoQ for every expert of every layer, and the
    quantized model takes a LoRA step."""
    from repro.configs import get_smoke_config
    from repro.core.pipeline import (quantize_model, run_calibration,
                                     to_eager_params)
    from repro.core.recipe import QuantRecipe
    from repro.data import DataConfig, TokenStream
    from repro.launch.steps import build_state, make_train_step
    from repro.models.modules import QSpec
    from repro.models.parallel import LOCAL
    from repro.models.transformer import init_params
    from repro.optim import OptConfig
    cfg = get_smoke_config("olmoe-1b-7b")
    assert cfg.capacity_factor is None and not cfg.norm_topk and cfg.qk_norm_span == "proj"
    p = init_params(jax.random.PRNGKey(0), cfg)
    ds = TokenStream(DataConfig(vocab=cfg.vocab, seq_len=32, global_batch=2))
    batches = [ds.next_batch()]
    store = run_calibration(to_eager_params(p, cfg), cfg, batches)
    gram = store.grams["blocks.0.moe.gate"]
    assert gram.shape == (cfg.n_experts, cfg.d_model, cfg.d_model)
    # every routed row lands in one expert's Gram: k rows a token
    traces = np.trace(gram, axis1=1, axis2=2)
    assert (traces > 0).sum() >= cfg.top_k
    qspec = QSpec(bits=4, group_size=16, rank=4)
    qp, qcfg, _ = quantize_model(p, cfg, store,
                                 recipe=QuantRecipe.single("cloq", qspec))
    gate = qp["blocks"]["moe"]["gate"]
    assert gate["qcodes"].shape[:2] == (cfg.n_layers, cfg.n_experts)
    assert gate["lora_a"].shape == (cfg.n_layers, cfg.n_experts,
                                    cfg.d_model, 4)
    assert bool(jnp.all(jnp.isfinite(gate["lora_a"])))
    ocfg = OptConfig(lr=1e-3, trainable="lora", total_steps=2)
    step = jax.jit(make_train_step(qcfg, ocfg, LOCAL))
    state, m = step(build_state(qp, ocfg), ds.next_batch())
    assert np.isfinite(float(m["loss"]))
    assert float(m["moe_load_max"]) >= 1.0
    assert float(m["aux"]) > 0 and float(m["loss"]) == float(m["ce"])


def test_moe_scopes_reach_the_compiled_train_step():
    """``route``, ``dispatch`` and ``combine`` name the MoE layer's ops
    inside ``moe``, as the profiler's ``tf_op`` will show them."""
    import re
    from repro.configs import get_smoke_config
    from repro.launch.steps import build_state, make_train_step
    from repro.models.parallel import LOCAL
    from repro.models.transformer import init_params
    from repro.obs import names
    from repro.optim import OptConfig
    cfg = get_smoke_config("olmoe-1b-7b")
    ocfg = OptConfig(lr=1e-3, trainable="all", total_steps=2)
    state = build_state(init_params(jax.random.PRNGKey(0), cfg), ocfg)
    batch = {k: jnp.ones((2, 16), jnp.int32) for k in ("tokens", "labels")}
    text = jax.jit(make_train_step(cfg, ocfg, LOCAL)).lower(
        state, batch).compile().as_text()
    paths = set(re.findall(r'op_name="([^"]*)"', text))
    for name in ("route", "dispatch", "combine"):
        assert name in names.SCOPES
        assert any(re.search(rf"\bmoe/{name}\b", p) for p in paths), name
