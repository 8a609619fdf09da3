"""Cross-engine parity matrix.

Systematic grid — {sequential, batched, batched+2-device-sharded} ×
{rtn, qlora, loftq, gptq, cloq} × {square, tall, non-divisible-n,
stacked-MoE} — asserting codes/scales/zeros and the LoRA product against
the sequential per-layer oracle (`pipeline._quantize_one`) through the
shared helpers in `tests/util.py`.  This is the safety net behind the
sharded-engine completion: every method (LoftQ included) must ride the
fused shard_map(vmap) path and land on the oracle, and the one remaining
replicated fallback (non-divisible column count) must be exact.

The task data is generated by source shared verbatim between this process
and the fake-device subprocesses (``_TASK_SRC``), so every engine sees
bit-identical inputs.  Sharded cases run one subprocess per method (all
shapes inside), memoized so the 20 grid cases cost 5 subprocess launches.
"""
import textwrap

import numpy as np
import pytest

from repro.core.batched import GRAM_METHODS
from repro.models.modules import QSpec
from tests.util import (assert_leaves_close, parity_prelude,
                        run_with_devices)

METHODS = ("rtn", "qlora", "loftq", "gptq", "cloq")
SHAPE_KEYS = ("square", "tall", "nondiv", "moe")

QSPEC = QSpec(bits=2, group_size=16, rank=8)

# Shared verbatim with the subprocess: shape table + deterministic task
# generation.  "nondiv" (n=45) must trigger the replicated fallback on a
# 2-device mesh; "moe" flattens two stacked (E, m, n) weights into expert
# tasks exactly like pipeline._gather_tasks.
_TASK_SRC = """
import jax
import jax.numpy as jnp
import numpy as np

from repro.core.batched import LayerTask

SHAPES = {"square": (32, 32, None), "tall": (64, 32, None),
          "nondiv": (32, 45, None), "moe": (32, 48, 3)}
SEEDS = {"square": 10, "tall": 11, "nondiv": 12, "moe": 13}


def make_tasks(shape_key, L=3):
    m, n, E = SHAPES[shape_key]
    seed = SEEDS[shape_key]
    rng = np.random.default_rng(seed)

    def gram():
        X = rng.normal(size=(256, m)).astype(np.float32)
        return jnp.asarray(X.T @ X)

    tasks = []
    if E is None:
        keys = jax.random.split(jax.random.PRNGKey(seed), L)
        for i in range(L):
            W = jnp.asarray(rng.normal(size=(m, n)), jnp.float32)
            tasks.append(LayerTask(f"lin{i}", None, W, gram(), keys[i]))
    else:
        for s in range(2):
            Wstack = jnp.asarray(rng.normal(size=(E, m, n)), jnp.float32)
            keys = jax.random.split(jax.random.PRNGKey(100 + seed + s), E)
            for e in range(E):
                tasks.append(LayerTask(f"moe{s}", e, Wstack[e], gram(),
                                       keys[e]))
    return tasks
"""

_ns: dict = {}
exec(textwrap.dedent(_TASK_SRC), _ns)
make_tasks = _ns["make_tasks"]


def _oracle(tasks, method):
    from repro.core.pipeline import _quantize_one
    return [_quantize_one(t.W, t.H if method in GRAM_METHODS else None,
                          QSPEC, method, t.key) for t in tasks]


@pytest.mark.parametrize("shape", SHAPE_KEYS)
@pytest.mark.parametrize("method", METHODS)
def test_sequential_oracle_deterministic(method, shape):
    """The oracle itself is bitwise deterministic — the anchor the other
    two engines are measured against."""
    tasks = make_tasks(shape)
    a, b = _oracle(tasks, method), _oracle(tasks, method)
    for la, lb in zip(a, b):
        assert set(la) == set(lb)
        for k in la:
            assert np.array_equal(np.asarray(la[k]), np.asarray(lb[k])), k


@pytest.mark.parametrize("shape", SHAPE_KEYS)
@pytest.mark.parametrize("method", METHODS)
def test_batched_engine_matches_oracle(method, shape):
    from repro.core.batched import quantize_layer_batch
    tasks = make_tasks(shape)
    got = quantize_layer_batch(tasks, QSPEC, method)
    for t, g, want in zip(tasks, got, _oracle(tasks, method)):
        assert_leaves_close(g, want)


# ---------------------------------------------------------------------------
# Sharded third of the matrix: one 2-fake-device subprocess per method
# covering every shape (memoized), each case asserting its PARITY OK line.
# ---------------------------------------------------------------------------

_SHARDED_DRIVER = """
from repro.core.batched import GRAM_METHODS, plan_buckets, \\
    quantize_layer_batch
from repro.core.pipeline import _quantize_one
from repro.models.modules import QSpec

method = __METHOD__
from repro.launch.mesh import make_model_mesh
mesh = make_model_mesh(2)
qspec = QSpec(bits=2, group_size=16, rank=8)
for shape in SHAPES:
    tasks = make_tasks(shape)
    buckets = plan_buckets(tasks, qspec, method, mesh=mesh)
    shard_counts = {spec.n_shards for spec in buckets}
    expect = 1 if shape == "nondiv" else 2
    assert shard_counts == {expect}, (shape, shard_counts)
    got = quantize_layer_batch(tasks, qspec, method, mesh=mesh)
    for t, g in zip(tasks, got):
        want = _quantize_one(
            t.W, t.H if method in GRAM_METHODS else None,
            qspec, method, t.key)
        assert_leaves_close(g, want)
    print("PARITY OK", method, shape)
"""

# method -> stdout on success, or the AssertionError on failure (cached
# either way so a broken method's subprocess doesn't relaunch per shape)
_sharded_runs: dict[str, str | AssertionError] = {}


def _sharded_stdout(method: str) -> str:
    if method not in _sharded_runs:
        code = (parity_prelude() + textwrap.dedent(_TASK_SRC) +
                textwrap.dedent(_SHARDED_DRIVER).replace(
                    "__METHOD__", repr(method)))
        try:
            _sharded_runs[method] = run_with_devices(
                code, n_devices=2, timeout=600).stdout
        except AssertionError as e:
            _sharded_runs[method] = e
    res = _sharded_runs[method]
    if isinstance(res, AssertionError):
        raise res
    return res


@pytest.mark.multidevice
@pytest.mark.parametrize("shape", SHAPE_KEYS)
@pytest.mark.parametrize("method", METHODS)
def test_sharded_engine_matches_oracle(method, shape):
    """2-device shard_map(vmap) bucket == the sequential oracle, with the
    planner sharding every divisible bucket (loftq included — no
    replicated-method fallback remains) and falling back replicated only
    for the non-divisible column count."""
    assert f"PARITY OK {method} {shape}" in _sharded_stdout(method)


# ---------------------------------------------------------------------------
# Mixed-recipe row of the matrix: one heterogeneous QuantRecipe (three
# distinct (method, bits, rank) site rules + a skip) through task-level
# bucketing against the per-site sequential oracle, and through the full
# model-level drivers (batched vs sequential engine).  The sharded third +
# manifest round-trip live in tests/test_distributed.py
# (test_mixed_recipe_sharded_parity_and_manifest_restore), sharing
# _MIXED_SRC verbatim so every engine sees the same recipe and inputs.
# ---------------------------------------------------------------------------

_MIXED_SRC = """
from repro.core.recipe import QuantRecipe, SiteRule
from repro.models.modules import QSpec

# >=3 distinct (method, bits, rank) site rules + a skip: 2-bit rank-8 CLoQ
# MLPs, 4-bit rank-4 GPTQ attn.q, everything else 4-bit rank-2 RTN,
# mlp.down left dense.  All column counts divide 2 and 4 devices.
MIXED_RECIPE = QuantRecipe(rules=(
    SiteRule("*.mlp.down", skip=True),
    SiteRule("*.mlp.*", method="cloq", bits=2, rank=8),
    SiteRule("*.attn.q", method="gptq", bits=4, rank=4)),
    method="rtn", qspec=QSpec(bits=4, group_size=16, rank=2))


def mixed_model():
    import jax, jax.numpy as jnp
    from repro.data import DataConfig, TokenStream
    from repro.models.transformer import ModelConfig, init_params
    cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=32,
                      vocab=128, n_heads=4, n_kv_heads=2, d_ff=64,
                      dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    ds = TokenStream(DataConfig(vocab=128, seq_len=32, global_batch=2,
                                seed=3))
    return cfg, params, [ds.next_batch()]


def assert_mixed_trees_close(flat_got, flat_want, assert_leaves_close):
    import numpy as np
    assert set(flat_got) == set(flat_want)
    sites = sorted({k.rsplit(".", 1)[0] for k in flat_want
                    if k.endswith(".qcodes") or k.endswith(".lora_a")})
    for site in sites:
        leaves = {k.rsplit(".", 1)[1]: flat_want[k] for k in flat_want
                  if k.rsplit(".", 1)[0] == site}
        got = {k: flat_got[f"{site}.{k}"] for k in leaves}
        assert_leaves_close(got, leaves)
    rest = [k for k in flat_want if k.rsplit(".", 1)[0] not in sites]
    for k in rest:
        np.testing.assert_allclose(np.asarray(flat_got[k], np.float64),
                                   np.asarray(flat_want[k], np.float64),
                                   atol=1e-5, err_msg=k)
"""

_mixed_ns: dict = {}
exec(textwrap.dedent(_MIXED_SRC), _mixed_ns)
MIXED_RECIPE = _mixed_ns["MIXED_RECIPE"]
mixed_model = _mixed_ns["mixed_model"]
assert_mixed_trees_close = _mixed_ns["assert_mixed_trees_close"]


def test_mixed_recipe_tasks_match_per_site_oracle():
    """Heterogeneous tasks (sites resolved from MIXED_RECIPE over synthetic
    paths) bucket by their own specs and land on the per-site sequential
    oracle `_quantize_one(site.qspec, site.method)`."""
    import jax
    import jax.numpy as jnp
    from repro.core.batched import (LayerTask, plan_buckets,
                                    quantize_layer_batch)
    from repro.core.pipeline import _quantize_one

    paths = [f"blocks.{i}.{name}" for i in range(3)
             for name in ("mlp.up", "mlp.down", "attn.q", "attn.o")]
    sites = MIXED_RECIPE.resolve(paths)
    rng = np.random.default_rng(7)
    m = 32
    X = rng.normal(size=(256, m)).astype(np.float32)
    H = jnp.asarray(X.T @ X)
    keys = jax.random.split(jax.random.PRNGKey(7), len(paths))
    tasks = [LayerTask(p, None,
                       jnp.asarray(rng.normal(size=(m, 48)), jnp.float32),
                       H, k, site=sites[p])
             for p, k in zip(paths, keys) if not sites[p].skip]
    assert len(tasks) == 9      # 3 of 12 sites skipped

    buckets = plan_buckets(tasks)
    sigs = {(s.method, s.bits, s.rank) for s in buckets}
    assert sigs == {("cloq", 2, 8), ("gptq", 4, 4), ("rtn", 4, 2)}

    got = quantize_layer_batch(tasks)
    for t, g in zip(tasks, got):
        want = _quantize_one(
            t.W, t.H if t.site.method in GRAM_METHODS else None,
            t.site.qspec, t.site.method, t.key)
        assert_leaves_close(g, want)


def test_mixed_recipe_model_engines_agree():
    """quantize_model(recipe=MIXED_RECIPE): batched engine == sequential
    per-site engine, the skipped site keeps its dense `w` (no quantized or
    adapter leaves), and the plan actually mixes (>= 3 distinct bucket
    signatures)."""
    from repro.core.pipeline import quantize_model
    from repro.utils import tree_paths

    cfg, params, calib = mixed_model()
    msgs = []
    qp_b, cfg_b, _ = quantize_model(params, cfg, calib, recipe=MIXED_RECIPE,
                                    engine="batched", progress=msgs.append)
    qp_s, _, _ = quantize_model(params, cfg, calib, recipe=MIXED_RECIPE,
                                engine="sequential")
    flat_b, flat_s = tree_paths(qp_b), tree_paths(qp_s)
    assert_mixed_trees_close(flat_b, flat_s, assert_leaves_close)

    # the skipped mlp.down stayed dense in both engines
    for flat in (flat_b, flat_s):
        assert "blocks.mlp.down.w" in flat
        assert not any("mlp.down" in k and
                       k.endswith((".qcodes", ".lora_a")) for k in flat)
    # >= 3 distinct (method/bits/rank) buckets showed up in the plan
    assert len({m.split("] ")[1].split(" ")[0] for m in msgs}) >= 3
