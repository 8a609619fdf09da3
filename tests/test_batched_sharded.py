"""Distributed batched quantization engine: shard_map composed inside the
vmapped bucket (2 fake CPU devices, subprocess-isolated), the planner's
replicated fallback for non-divisible column counts, the stacked-MoE bucket
at model level, and streaming-order invariance of the bucket executor."""
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.batched import bucket_shards, make_spec
from repro.launch.mesh import make_model_mesh
from repro.models.modules import QSpec
from tests.util import parity_prelude, run_with_devices

# Parity helpers (tests/util.py), inlined into each subprocess (which only
# sees PYTHONPATH=src, not the tests package), plus the jax imports the
# snippets use.
_PARITY_HELPERS = "import jax, jax.numpy as jnp\n" + parity_prelude()


def test_bucket_shards_plan_rules():
    """Plan-time sharding decision: needs a mesh with the axis and a
    divisible column count (no method is forced replicated anymore)."""
    assert bucket_shards(48, "cloq", mesh=None) == 1
    assert bucket_shards(48, "loftq", mesh=None) == 1
    qspec = QSpec(bits=2, group_size=16, rank=4)
    spec = make_spec(32, 48, qspec, "cloq", has_gram=True)   # no mesh
    assert spec.n_shards == 1


@pytest.mark.multidevice
def test_sharded_bucket_parity_and_fallback():
    """One fused shard_map(vmap) bucket == the per-layer oracle, for every
    method (loftq now rides the Gram-trick sharded path too); a
    non-divisible column count falls back to the replicated executable
    (n_shards == 1) with identical results."""
    run_with_devices(_PARITY_HELPERS + textwrap.dedent("""
        from repro.core.batched import (LayerTask, plan_buckets,
                                        quantize_layer_batch)
        from repro.core.pipeline import _quantize_one
        from repro.models.modules import QSpec

        from repro.launch.mesh import make_model_mesh
        mesh = make_model_mesh(2)
        rng = np.random.default_rng(0)
        qspec = QSpec(bits=2, group_size=16, rank=8)

        def make_tasks(n_out, L=4, m=32):
            Ws = [jnp.asarray(rng.normal(size=(m, n_out)), jnp.float32)
                  for _ in range(L)]
            Hs = []
            for _ in range(L):
                X = rng.normal(size=(256, m)).astype(np.float32)
                Hs.append(jnp.asarray(X.T @ X))
            ks = jax.random.split(jax.random.PRNGKey(0), L)
            return [LayerTask(f"l{i}", None, W, H, k)
                    for i, (W, H, k) in enumerate(zip(Ws, Hs, ks))]

        for method in ("cloq", "gptq", "rtn", "qlora", "loftq"):
            tasks = make_tasks(48)
            spec = next(iter(plan_buckets(tasks, qspec, method, mesh=mesh)))
            assert spec.n_shards == 2, (method, spec.n_shards)
            got = quantize_layer_batch(tasks, qspec, method, mesh=mesh)
            for t, g in zip(tasks, got):
                want = _quantize_one(
                    t.W, t.H if method in ("cloq", "gptq") else None,
                    qspec, method, t.key)
                assert_leaves_close(g, want)
            print(method, "sharded parity ok")

        # non-divisible n: replicated fallback, same leaves as no-mesh run
        tasks = make_tasks(45)
        spec = next(iter(plan_buckets(tasks, qspec, "cloq", mesh=mesh)))
        assert spec.n_shards == 1
        got = quantize_layer_batch(tasks, qspec, "cloq", mesh=mesh)
        ref = quantize_layer_batch(tasks, qspec, "cloq")
        for g, r in zip(got, ref):
            for k in g:
                assert np.array_equal(np.asarray(g[k]), np.asarray(r[k])), k
        print("fallback ok")
    """), n_devices=2)


@pytest.mark.multidevice
def test_sharded_model_parity_moe():
    """quantize_model(engine='batched', mesh=...) on a 2-device mesh matches
    the sequential engine, including the stacked-MoE expert bucket."""
    run_with_devices(_PARITY_HELPERS + textwrap.dedent("""
        from repro.core.pipeline import quantize_model
        from repro.data import DataConfig, TokenStream
        from repro.models.modules import QSpec
        from repro.models.transformer import ModelConfig, init_params
        from repro.launch.mesh import make_model_mesh
        from repro.utils import tree_paths

        mesh = make_model_mesh()
        assert mesh.shape["model"] == 2
        cfg = ModelConfig(name="t", family="moe", n_layers=2, d_model=32,
                          vocab=128, n_heads=4, n_kv_heads=2, n_experts=4,
                          top_k=2, d_ff_expert=32, dtype=jnp.float32)
        qspec = QSpec(bits=4, group_size=16, rank=8)
        params = init_params(jax.random.PRNGKey(0), cfg)
        ds = TokenStream(DataConfig(vocab=128, seq_len=32, global_batch=2,
                                    seed=3))
        calib = [ds.next_batch()]
        msgs = []
        qp_b, _, _ = quantize_model(params, cfg, calib, qspec=qspec,
                                    engine="batched", mesh=mesh,
                                    progress=msgs.append)
        assert any("path=sharded shards=2" in m for m in msgs), msgs
        qp_s, _, _ = quantize_model(params, cfg, calib, qspec=qspec,
                                    engine="sequential")
        fb, fs = tree_paths(qp_b), tree_paths(qp_s)
        assert set(fb) == set(fs)
        byname = {}
        for k in fs:
            lin = k.rsplit(".", 1)[0]
            byname.setdefault(lin, {})[k.rsplit(".", 1)[1]] = None
        for lin, leaves in sorted(byname.items()):
            if not ("lora_a" in leaves or "qcodes" in leaves):
                continue
            g = {leaf: fb[f"{lin}.{leaf}"] for leaf in leaves}
            w = {leaf: fs[f"{lin}.{leaf}"] for leaf in leaves}
            assert_leaves_close(g, w)
        print("sharded model parity (moe) ok")
    """), n_devices=2)


@pytest.mark.multidevice
def test_sharded_site_lora_matches_unsharded():
    """cloq_site_lora under a 2-device mesh — one shard_map whose body
    vmaps cloq_lowrank_local over the call sites — matches the plain
    vmap-of-cloq_init path through the per-site A B^T products."""
    run_with_devices(_PARITY_HELPERS + textwrap.dedent("""
        from repro.core.cloq import cloq_site_lora

        rng = np.random.default_rng(0)
        m, n, S, r = 32, 48, 5, 8
        dW = jnp.asarray(rng.normal(size=(m, n)), jnp.float32)
        Hs = jnp.asarray(np.stack([
            (lambda X: X.T @ X)(rng.normal(size=(128, m)).astype(np.float32))
            for _ in range(S)]))
        from repro.launch.mesh import make_model_mesh
        mesh = make_model_mesh(2)

        A0, B0 = cloq_site_lora(Hs, dW, r)
        A1, B1 = cloq_site_lora(Hs, dW, r, mesh=mesh)
        assert A1.shape == (S, m, r) and B1.shape == (S, n, r)
        prod_rel = rel_fro(lora_product(A1, B1), lora_product(A0, B0))
        assert prod_rel <= 5e-3, prod_rel
        print("site_lora sharded parity ok:", prod_rel)
    """), n_devices=2)


def test_sequential_engine_rejects_mesh():
    import pytest
    from repro.core.pipeline import quantize_model
    from repro.data import DataConfig, TokenStream
    from repro.models.transformer import ModelConfig, init_params

    cfg = ModelConfig(name="t", family="dense", n_layers=1, d_model=32,
                      vocab=128, n_heads=4, n_kv_heads=2, d_ff=64,
                      dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    ds = TokenStream(DataConfig(vocab=128, seq_len=16, global_batch=2))
    mesh = make_model_mesh(1)
    with pytest.raises(ValueError, match="batched"):
        quantize_model(params, cfg, [ds.next_batch()],
                       engine="sequential", mesh=mesh)


def test_streaming_order_invariance():
    """Double-buffered streaming must not change any leaf: stream=True
    (stage bucket k+1 while k is in flight) vs stream=False (serialize on
    every bucket) produce bitwise-identical results across a multi-bucket,
    mixed-shape task list."""
    from repro.core.batched import LayerTask, plan_buckets, \
        quantize_layer_batch

    rng = np.random.default_rng(0)
    qspec = QSpec(bits=2, group_size=16, rank=4)

    tasks = []
    for shape, count, seed in (((32, 48), 3, 1), ((16, 24), 2, 2),
                               ((32, 16), 2, 3)):
        r = np.random.default_rng(seed)
        for i in range(count):
            W = jnp.asarray(r.normal(size=shape), jnp.float32)
            X = r.normal(size=(128, shape[0])).astype(np.float32)
            tasks.append(LayerTask(f"{shape}-{i}", None, W,
                                   jnp.asarray(X.T @ X),
                                   jax.random.PRNGKey(len(tasks))))
    assert len(plan_buckets(tasks, qspec, "cloq")) == 3
    streamed = quantize_layer_batch(tasks, qspec, "cloq", stream=True)
    serial = quantize_layer_batch(tasks, qspec, "cloq", stream=False)
    for a, b in zip(streamed, serial):
        assert set(a) == set(b)
        for k in a:
            assert np.array_equal(np.asarray(a[k]), np.asarray(b[k])), k
