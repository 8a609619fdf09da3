"""Calibrated bit-allocation subsystem (repro.core.allocate).

Covers the ISSUE-5 allocator contract: exact byte accounting (asserted
against ``quantized_param_shapes``), budgets never exceeded, proxy error
monotone non-increasing in budget, greedy == exhaustive at hull
breakpoints (synthetic <=3-site grids and the real swept model), the
emitted recipe running through the cross-engine parity asserts of
``tests/util.py``, and the sharded sweep path agreeing with the local one.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import allocate
from repro.core.allocate import (SiteGroup, budget_curve, site_bytes,
                                 solve_budget, solve_exhaustive)
from repro.core.pipeline import (allocate_plan, quantize_model,
                                 quantized_param_shapes, recipe_plan_bytes,
                                 run_calibration, to_eager_params)
from repro.core.recipe import QuantRecipe, SiteSpec
from repro.data import DataConfig, TokenStream
from repro.models.modules import QSpec
from repro.models.transformer import ModelConfig, init_params
from repro.utils import tree_paths
from tests.util import assert_leaves_close, run_with_devices

GRID = (("cloq", 2, 0), ("cloq", 2, 8), ("cloq", 4, 0), ("cloq", 4, 8))
BASE = QSpec(bits=4, group_size=16, rank=8)

_QUANT_LEAVES = ("qcodes", "scales", "zeros", "absmax", "lora_a", "lora_b")


@pytest.fixture(scope="module")
def small_model():
    cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=32,
                      vocab=128, n_heads=4, n_kv_heads=2, d_ff=64,
                      dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    ds = TokenStream(DataConfig(vocab=128, seq_len=32, global_batch=2,
                                seed=3))
    calib = [ds.next_batch()]
    store = run_calibration(to_eager_params(params, cfg), cfg, calib)
    return cfg, params, calib, store


@pytest.fixture(scope="module")
def swept_groups(small_model):
    """The real model's swept candidate tables (one sweep, reused)."""
    from repro.core.pipeline import _allocation_meta, _gather_tasks
    cfg, params, _, store = small_model
    from repro.core.pipeline import quantizable_linear_paths, _STACK_KEYS
    eparams = to_eager_params(params, cfg)
    sites = QuantRecipe.single("cloq", BASE).resolve(
        quantizable_linear_paths(eparams))
    tasks, _ = _gather_tasks(eparams, store, sites, seed=0)
    groups = allocate.group_sites(_allocation_meta(eparams, store),
                                  tuple(_STACK_KEYS))
    return allocate.sweep_sensitivity(tasks, groups, GRID, BASE, cfg.dtype)


def _uniform_bytes(cfg, bits, rank):
    return recipe_plan_bytes(cfg, QuantRecipe.single(
        "cloq", QSpec(bits=bits, group_size=16, rank=rank)))


# ---------------------------------------------------------------------------
# Byte accounting + budget feasibility.
# ---------------------------------------------------------------------------


def test_budget_never_exceeded_and_accounting_exact(small_model):
    """The allocation fits its budget, and its byte total is EXACTLY the
    serialized size of the quantized leaves quantized_param_shapes lays
    out for the emitted recipe."""
    cfg, params, _, store = small_model
    budget = (_uniform_bytes(cfg, 2, 0) + _uniform_bytes(cfg, 4, 8)) // 2
    alloc = allocate_plan(params, cfg, store, budget, grid=GRID, qspec=BASE)
    assert alloc.total_bytes <= budget
    # accounting path 1: the allocator's own per-group table
    assert sum(r["bytes"] for r in alloc.table) == alloc.total_bytes
    # accounting path 2: the abstract-shape evaluation of the same recipe
    assert recipe_plan_bytes(cfg, alloc.recipe) == alloc.total_bytes
    # accounting path 3: the actual quantized parameter layout
    shapes = quantized_param_shapes(cfg, recipe=alloc.recipe)
    layout_bytes = sum(
        int(np.prod(s.shape)) * jnp.dtype(s.dtype).itemsize
        for p, s in tree_paths(shapes).items()
        if p.rsplit(".", 1)[-1] in _QUANT_LEAVES)
    assert layout_bytes == alloc.total_bytes


def test_infeasible_budget_raises(small_model):
    cfg, params, _, store = small_model
    with pytest.raises(ValueError, match="infeasible"):
        allocate_plan(params, cfg, store, 16, grid=GRID, qspec=BASE)


def test_skip_candidate_costs_dense_bytes():
    spec = SiteSpec("cloq", QSpec(bits=2, group_size=16, rank=8), skip=True)
    assert site_bytes(64, 32, spec, jnp.float32) == 64 * 32 * 4
    assert site_bytes(64, 32, spec, jnp.bfloat16, experts=3) == 3 * 64 * 32 * 2


# ---------------------------------------------------------------------------
# Solver: monotonicity + greedy vs exhaustive.
# ---------------------------------------------------------------------------


def test_error_monotone_in_budget(small_model):
    cfg, params, _, store = small_model
    lo = _uniform_bytes(cfg, 2, 0)            # cheapest uniform plan
    hi = _uniform_bytes(cfg, 4, 8)            # priciest candidate everywhere
    budgets = [lo, (lo + hi) // 2, hi, 2 * hi]
    errs, bts = [], []
    for b in budgets:
        alloc = allocate_plan(params, cfg, store, b, grid=GRID, qspec=BASE)
        assert alloc.total_bytes <= b
        errs.append(alloc.total_error)
        bts.append(alloc.total_bytes)
    assert all(e1 >= e2 - 1e-9 for e1, e2 in zip(errs, errs[1:])), errs
    assert errs[0] > errs[-1]                 # budget actually buys error
    assert bts[-1] == bts[-2]                 # saturated beyond the grid max


def _toy_groups():
    """Three sites, hand-built convex (bytes, err) tables."""
    return [
        SiteGroup("a", ("a",), 1, 1, candidates=(None,) * 3,
                  bytes_=(100, 200, 400), errors=(30.0, 12.0, 5.0)),
        SiteGroup("b", ("b",), 1, 1, candidates=(None,) * 3,
                  bytes_=(100, 300, 600), errors=(50.0, 20.0, 10.0)),
        SiteGroup("c", ("c",), 1, 1, candidates=(None,) * 4,
                  bytes_=(50, 150, 151, 500), errors=(8.0, 4.0, 7.0, 2.0)),
    ]


def test_greedy_matches_exhaustive_toy_grid():
    """<=3-site grid (with a dominated candidate thrown in): the greedy
    equals brute force at every hull breakpoint and stays feasible at
    every in-between budget."""
    groups = _toy_groups()
    curve = budget_curve(groups)
    for budget, want_err in curve:
        greedy = solve_budget(groups, budget)
        exact = solve_exhaustive(groups, budget)
        g_err = sum(g.errors[c] for g, c in zip(groups, greedy))
        e_err = sum(g.errors[c] for g, c in zip(groups, exact))
        assert g_err == pytest.approx(e_err)
        assert g_err == pytest.approx(want_err)
        assert sum(g.bytes_[c] for g, c in zip(groups, greedy)) <= budget
    # off-breakpoint budgets: still feasible, never better than exhaustive
    for budget in (260, 431, 700):
        greedy = solve_budget(groups, budget)
        exact = solve_exhaustive(groups, budget)
        assert sum(g.bytes_[c] for g, c in zip(groups, greedy)) <= budget
        g_err = sum(g.errors[c] for g, c in zip(groups, greedy))
        e_err = sum(g.errors[c] for g, c in zip(groups, exact))
        assert g_err >= e_err - 1e-12


def test_greedy_matches_exhaustive_on_swept_model(swept_groups):
    """On the real swept sensitivities (3 site groups to keep the brute
    force tiny): greedy == exhaustive at every hull breakpoint."""
    groups = swept_groups[:3]
    for budget, _ in budget_curve(groups):
        greedy = solve_budget(groups, budget)
        exact = solve_exhaustive(groups, budget)
        g_err = sum(g.errors[c] for g, c in zip(groups, greedy))
        e_err = sum(g.errors[c] for g, c in zip(groups, exact))
        assert g_err == pytest.approx(e_err, rel=1e-9)


def test_dominated_candidates_never_chosen(swept_groups):
    """3-bit codes are stored unpacked (1 B/code), so INT3 is dominated by
    INT4 at equal-or-less cost — the hull must prune such candidates."""
    groups = [SiteGroup("x", ("x",), 1, 1, candidates=(None,) * 3,
                        bytes_=(100, 200, 200), errors=(9.0, 5.0, 3.0))]
    assert solve_budget(groups, 200) == [2]


# ---------------------------------------------------------------------------
# Emitted recipe: scan uniformity + cross-engine parity.
# ---------------------------------------------------------------------------


def test_recipe_scan_uniform_and_json_roundtrip(small_model):
    cfg, params, _, store = small_model
    budget = _uniform_bytes(cfg, 4, 8)
    alloc = allocate_plan(params, cfg, store, budget, grid=GRID, qspec=BASE)
    # scan-stacked model => layer-uniform glob rules, one per site template
    assert all(r.pattern.startswith("blocks.*.")
               for r in alloc.recipe.rules)
    rt = QuantRecipe.from_json(alloc.recipe.to_json())
    assert rt.to_dict() == alloc.recipe.to_dict()


def test_emitted_recipe_engine_parity(small_model):
    """The allocator's output is a first-class recipe: both engines
    quantize it to the same leaves (tests/util.py parity asserts)."""
    cfg, params, calib, store = small_model
    budget = (_uniform_bytes(cfg, 2, 0) + _uniform_bytes(cfg, 4, 8)) // 2
    alloc = allocate_plan(params, cfg, store, budget, grid=GRID, qspec=BASE)
    qp_b, _, _ = quantize_model(params, cfg, calib, recipe=alloc.recipe,
                                engine="batched")
    qp_s, _, _ = quantize_model(params, cfg, calib, recipe=alloc.recipe,
                                engine="sequential")
    flat_b = tree_paths(to_eager_params(qp_b, cfg))
    flat_s = tree_paths(to_eager_params(qp_s, cfg))
    assert set(flat_b) == set(flat_s)
    sites_seen = 0
    by_site: dict[str, dict] = {}
    for p in flat_s:
        leaf = p.rsplit(".", 1)[-1]
        if leaf in _QUANT_LEAVES:
            by_site.setdefault(p.rsplit(".", 1)[0], {})[leaf] = None
    for site, leaves in sorted(by_site.items()):
        got = {k: np.asarray(flat_b[f"{site}.{k}"]) for k in leaves}
        want = {k: np.asarray(flat_s[f"{site}.{k}"]) for k in leaves}
        assert_leaves_close(got, want)
        sites_seen += 1
    assert sites_seen >= 7                     # every site template covered


# ---------------------------------------------------------------------------
# Sharded sweep path.
# ---------------------------------------------------------------------------


@pytest.mark.multidevice
def test_sweep_sharded_matches_local():
    """evaluate_layer_batch under a 2-device mesh (fused shard_map eval
    buckets, scalar psum) returns the same proxy errors as the local
    path."""
    run_with_devices("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.core.batched import LayerTask, evaluate_layer_batch, \\
        plan_buckets
    from repro.core.recipe import SiteSpec
    from repro.models.modules import QSpec

    rng = np.random.default_rng(0)
    m, n, L = 32, 48, 3
    tasks = []
    for method, bits, rank in (("cloq", 2, 8), ("gptq", 4, 0),
                               ("loftq", 2, 8), ("rtn", 4, 8)):
        spec = SiteSpec(method, QSpec(bits=bits, group_size=16, rank=rank,
                                      method=method))
        for i in range(L):
            W = jnp.asarray(rng.normal(size=(m, n)), jnp.float32)
            X = rng.normal(size=(256, m)).astype(np.float32)
            tasks.append(LayerTask(f"{method}{i}", None, W,
                                   jnp.asarray(X.T @ X),
                                   jax.random.PRNGKey(i), site=spec))
    from repro.launch.mesh import make_model_mesh
    mesh = make_model_mesh(2)
    specs = list(plan_buckets(tasks, mesh=mesh, for_eval=True))
    assert all(s.n_shards == 2 for s in specs), specs
    local = evaluate_layer_batch(tasks)
    sharded = evaluate_layer_batch(tasks, mesh=mesh)
    for path_i, (a, b) in enumerate(zip(local, sharded)):
        assert abs(a - b) <= 1e-3 * max(abs(a), 1.0), (path_i, a, b)
    print("SWEEP PARITY OK")
    """, n_devices=2)
