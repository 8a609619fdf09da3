"""Request-level parity oracle + adapter-registry round-trip tests for the
multi-tenant serving engine (repro.serve).

The oracle: every op in the engine's decode step is row-independent for
dense models (stale KV pages are masked to an exact-zero softmax weight),
so a batched heterogeneous-adapter run must be **bit-identical**, token
for token, to a sequential one-request-at-a-time replay through the same
executables — including across an adapter hot-swap mid-run."""
import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.manager import save_tree
from repro.core.pipeline import quantize_model
from repro.core.recipe import QuantRecipe
from repro.models.modules import QSpec
from repro.models.transformer import ModelConfig, init_params
from repro.serve import (AdapterError, AdapterRegistry, ServeEngine,
                         adapters_from_tree, run_workload)
from repro.serve.registry import synthesize_adapters
from repro.utils import tree_paths

pytestmark = pytest.mark.serving


def _quantize(d_model=32, rank=4, seed=0):
    cfg = ModelConfig(name="serve-test", family="dense", n_layers=2,
                      d_model=d_model, vocab=64, n_heads=4, n_kv_heads=2,
                      d_ff=2 * d_model, dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(seed), cfg)
    rng = np.random.default_rng(seed)
    calib = [{"tokens": rng.integers(1, cfg.vocab, (2, 16))}]
    return quantize_model(
        params, cfg, calib,
        recipe=QuantRecipe.single("cloq", QSpec(bits=4, group_size=16,
                                                rank=rank)))[:2]


@pytest.fixture(scope="module")
def model():
    return _quantize()


def _registry(qp, ranks=(4, 8), per_rank=2, capacity=4):
    """Tenants t0..: round-robin over rank buckets, seeded adapters."""
    reg = AdapterRegistry.from_model(qp, capacity=capacity)
    base = adapters_from_tree(qp)
    names = []
    for i in range(per_rank * len(ranks)):
        name = f"t{i}"
        reg.register(name, synthesize_adapters(base, ranks[i % len(ranks)],
                                               seed=100 + i))
        names.append(name)
    return reg, names


def _engine(qp, qcfg, reg, **kw):
    kw.setdefault("page_size", 4)
    kw.setdefault("max_len", 24)
    kw.setdefault("bucket_capacity", 4)
    return ServeEngine(qp, qcfg, reg, **kw)


def test_batched_parity_mixed_ranks_and_tenants(model):
    """Heterogeneous batch (2 rank buckets, 4 tenants, staggered lengths)
    == sequential replay, bit-identical."""
    qp, qcfg = model
    reg, names = _registry(qp)
    reqs = [(names[i % len(names)], [1 + i, 2 + i, 3], 4 + i % 3)
            for i in range(8)]
    batched = run_workload(_engine(qp, qcfg, reg), reqs)
    sequential = run_workload(_engine(qp, qcfg, reg), reqs, sequential=True)
    assert batched == sequential
    for i, (_, prompt, max_new) in enumerate(reqs):
        assert len(batched[i]) == max_new


def test_parity_across_hot_swap(model):
    """Swap one tenant's adapters while ANOTHER tenant's request is in
    flight: the in-flight request is unaffected, the swapped tenant's next
    request uses the new weights — both bit-identical to replays."""
    qp, qcfg = model
    reg = AdapterRegistry.from_model(qp, capacity=4)
    base = adapters_from_tree(qp)
    old_a = synthesize_adapters(base, 4, seed=1)
    new_a = synthesize_adapters(base, 4, seed=2)
    b_ad = synthesize_adapters(base, 4, seed=3)
    reg.register("A", old_a)
    reg.register("B", b_ad)

    eng = _engine(qp, qcfg, reg)
    rid_b = eng.submit([5, 6], "B", max_new=14)
    rid_a1 = eng.submit([7], "A", max_new=3)
    done = set()
    for _ in range(40):                      # drain A1 while B is mid-flight
        done.update(eng.step())
        if rid_a1 in done:
            break
    assert rid_a1 in done and rid_b not in done
    reg.swap("A", new_a)                     # hot-swap mid-serve
    rid_a2 = eng.submit([8], "A", max_new=3)
    eng.run()

    # replay each request alone: A1 against the OLD adapters, A2 against
    # the new, B (whose flight spanned the swap) against its own unchanged
    # weights
    reg_old = AdapterRegistry.from_model(qp, capacity=4)
    reg_old.register("A", old_a)
    reg_old.register("B", b_ad)
    ref_a1 = run_workload(_engine(qp, qcfg, reg_old), [("A", [7], 3)])[0]
    ref_b = run_workload(_engine(qp, qcfg, reg_old), [("B", [5, 6], 14)])[0]
    reg_old.swap("A", new_a)
    ref_a2 = run_workload(_engine(qp, qcfg, reg_old), [("A", [8], 3)])[0]

    assert eng.result(rid_a1) == ref_a1
    assert eng.result(rid_b) == ref_b
    assert eng.result(rid_a2) == ref_a2


def test_registry_round_trip_base_bit_identical(model, tmp_path):
    """load -> serve -> evict -> reload from the same manifest: the packed
    base tree is bit-identical throughout (adapters never touch it)."""
    qp, qcfg = model
    save_tree(qp, str(tmp_path), 0)

    reg = AdapterRegistry.from_model(qp, capacity=2)
    eng = _engine(qp, qcfg, reg, bucket_capacity=2)
    snapshot = {p: np.asarray(leaf).copy()
                for p, leaf in tree_paths(eng._base).items()}

    for round_ in range(2):                  # load -> serve -> evict -> reload
        reg.load("tenant", str(tmp_path))
        out = run_workload(eng, [("tenant", [3, 4], 4)])
        assert len(out[0]) == 4
        reg.evict("tenant")

    after = tree_paths(eng._base)
    assert set(after) == set(snapshot)
    for p, leaf in after.items():
        np.testing.assert_array_equal(np.asarray(leaf), snapshot[p],
                                      err_msg=f"base leaf {p} mutated")
    # and the caller's tree was never touched either
    for p, leaf in tree_paths(qp).items():
        if p in snapshot:
            np.testing.assert_array_equal(np.asarray(leaf), snapshot[p])


def test_foreign_manifest_one_legible_error(model, tmp_path):
    """A checkpoint from a different model produces one AdapterError that
    names the mismatch — never a shape crash inside jit."""
    qp, _ = model
    reg = AdapterRegistry.from_model(qp, capacity=2)

    foreign_qp, _ = _quantize(d_model=48, rank=4, seed=7)
    save_tree(foreign_qp, str(tmp_path / "foreign"), 0)
    with pytest.raises(AdapterError, match="foreign or stale"):
        reg.load("bad", str(tmp_path / "foreign"))

    save_tree({"embed": {"w": np.zeros((4, 4), np.float32)}},
              str(tmp_path / "noadapter"), 0)
    with pytest.raises(AdapterError, match="no stacked LoRA adapter"):
        reg.load("bad", str(tmp_path / "noadapter"))

    with pytest.raises(AdapterError, match="no complete checkpoint"):
        reg.load("bad", str(tmp_path / "empty"))

    assert reg.tenants() == {}               # nothing half-registered


def test_evicted_tenant_rejected_with_legible_error(model):
    qp, qcfg = model
    reg, names = _registry(qp, ranks=(4,), per_rank=1)
    eng = _engine(qp, qcfg, reg)
    reg.evict(names[0])
    with pytest.raises(AdapterError, match="not registered"):
        eng.submit([1], names[0], max_new=2)


def test_kernel_path_matches_reference_tokens(model):
    """use_kernel=True (Pallas dequant + flash-decode with lengths) emits
    the same tokens as the jnp reference path on the same workload."""
    qp, qcfg = model
    reg, names = _registry(qp, ranks=(4,), per_rank=2)
    reqs = [(names[i % 2], [3 + i, 5], 4) for i in range(4)]
    out_k = run_workload(_engine(qp, qcfg, reg, use_kernel=True), reqs)
    out_r = run_workload(_engine(qp, qcfg, reg, use_kernel=False), reqs)
    assert out_k == out_r


def test_page_reuse_across_waves(model):
    """More requests than the pool can hold at once: the scheduler queues,
    pages recycle through the freelist, every request completes, and the
    allocator ends clean."""
    qp, qcfg = model
    reg, names = _registry(qp, ranks=(4,), per_rank=2)
    eng = _engine(qp, qcfg, reg, bucket_capacity=2, n_pages=7)
    reqs = [(names[i % 2], [1 + i], 8) for i in range(6)]
    batched = run_workload(eng, reqs)
    assert all(len(batched[i]) == 8 for i in range(6))
    alloc = eng.scheduler.allocator
    alloc.check()
    assert alloc.n_free == alloc.n_usable    # no leaked pages
    sequential = run_workload(
        _engine(qp, qcfg, reg, bucket_capacity=2, n_pages=7), reqs,
        sequential=True)
    assert batched == sequential


STEP_CHILDREN = ("serve.schedule", "serve.prepare", "serve.decode",
                 "serve.upload", "serve.dispatch", "serve.sync",
                 "serve.commit")


def test_engine_records_each_requests_phases_and_step_spans(model):
    """With the tracer on, every request leaves one ``serve.queue``,
    ``serve.prefill`` and ``serve.generate`` with its ``rid``, each the
    interval between the engine's own stamps, and each engine step is
    tiled by its host-boundary spans."""
    from repro.obs import metrics as obs_metrics
    from repro.obs import names as obs_names
    from repro.obs import trace as obs_trace

    qp, qcfg = model
    reg, names = _registry(qp)
    eng = _engine(qp, qcfg, reg, bucket_capacity=2)
    obs_trace.get_tracer().clear()
    obs_metrics.reset()
    obs_trace.enable()
    try:
        prompts = [[1 + i, 2, 3][:1 + i % 3] for i in range(5)]
        rids = [eng.submit(p, names[i % len(names)], 3)
                for i, p in enumerate(prompts)]
        eng.run()
    finally:
        obs_trace.disable()
    events = obs_trace.get_tracer().events()
    obs_trace.get_tracer().clear()
    phases = {}
    for e in events:
        if e["name"] in ("serve.queue", "serve.prefill", "serve.generate"):
            key = (e["name"], e["args"]["rid"])
            assert key not in phases
            phases[key] = e["dur"] / 1e6
    for rid in rids:
        r = eng._reqs[rid]
        want = {"serve.queue": r.t_admit - r.t_submit,
                "serve.prefill": r.t_first - r.t_admit,
                "serve.generate": r.t_finish - r.t_first}
        for name, secs in want.items():
            assert phases[(name, rid)] == pytest.approx(secs, abs=1e-9)
    names_seen = {e["name"] for e in events}
    assert set(STEP_CHILDREN) <= names_seen
    steps = [e for e in events if e["name"] == "serve.step"]
    assert len(steps) == eng.steps
    # prompt rows counted where they are fed: one per prompt token
    assert obs_metrics.counter(obs_names.SERVE_PROMPT_ROWS).value == sum(
        len(p) for p in prompts)
    obs_metrics.reset()


# --- KV write-back ----------------------------------------------------------

def _full_cache_writeback(eng):
    """The engine step with the plain write-back, as the reference: the
    layer scan re-emits each row's whole cache, the token just written is
    read back out of it at the row's length, then scattered."""
    from repro.models.parallel import LOCAL
    from repro.models.transformer import decode_step
    from repro.serve.engine import _with_adapters
    from repro.serve.kv_cache import gather_pages, scatter_token
    cfg, sites = eng.cfg, tuple(eng.registry.sites())

    def step(base, stacks, ad, k_pool, v_pool, pt, lens, toks):
        params = _with_adapters(base, stacks, ad, sites)
        cache = {"k": gather_pages(k_pool, pt),
                 "v": gather_pages(v_pool, pt), "idx": lens}
        logits, new = decode_step(params, cfg, cache, toks, pctx=LOCAL)
        L, B = new["k"].shape[:2]
        at = jnp.broadcast_to(lens[None, :, None, None, None],
                              (L, B, 1, *new["k"].shape[3:]))
        k_row, v_row = (jnp.take_along_axis(new[n], at, axis=2)[:, :, 0]
                        for n in ("k", "v"))
        nxt = jnp.argmax(logits[:, :cfg.vocab], axis=-1).astype(jnp.int32)
        return (nxt, scatter_token(k_pool, k_row, pt, lens),
                scatter_token(v_pool, v_row, pt, lens))
    return jax.jit(step)


def test_pool_writeback_matches_full_cache_reference(model):
    """Every engine step leaves the pools bit-identical to the full-cache
    write-back, and emits the same tokens: rows at mixed lengths, a write
    that opens a row's second page, and an empty slot that writes to the
    scratch page."""
    qp, qcfg = model
    reg, names = _registry(qp, ranks=(4,), per_rank=2)
    eng = _engine(qp, qcfg, reg, bucket_capacity=3)
    ref, exec_ = _full_cache_writeback(eng), eng._exec
    seen = []

    def checked(*args):
        want = [np.asarray(a) for a in ref(*args)]   # before the donation
        got = exec_(*args)
        for w, g in zip(want, got):
            np.testing.assert_array_equal(np.asarray(g), w)
        seen.append((np.asarray(args[5]), np.asarray(args[6])))
        return got

    eng._exec = checked
    eng.submit([1, 2, 3], names[0], max_new=4)
    eng.step()
    eng.step()
    eng.submit([4], names[1], max_new=3)
    eng.run()
    P = eng._page
    active = [(pt.any(axis=1), lens) for pt, lens in seen]
    assert any(((lens[a] % P == 0) & (lens[a] > 0)).any()
               for a, lens in active)                  # a page boundary
    assert any(len(set(lens[a])) > 1 for a, lens in active)
    assert all((~a).any() for a, _ in active)          # the scratch write


@pytest.mark.parametrize("path", ["jit", "compile_cache_miss",
                                  "compile_cache_hit"])
def test_engine_step_donates_its_pools(model, tmp_path, path):
    """The step writes the new rows into the pools in place: the arrays
    it was given are deleted after it, on the jitted path and on the
    persisted one, compiled or read back from disk."""
    qp, qcfg = model
    reg, names = _registry(qp, ranks=(4,), per_rank=1)
    cache = None if path == "jit" else str(tmp_path)
    if path == "compile_cache_hit":
        warm = _engine(qp, qcfg, reg, compile_cache=cache)
        warm.submit([1], names[0], max_new=1)
        warm.run()
    eng = _engine(qp, qcfg, reg, compile_cache=cache)
    k_pool, v_pool = eng._k_pool, eng._v_pool
    eng.submit([1, 2], names[0], max_new=2)
    eng.step()
    assert k_pool.is_deleted() and v_pool.is_deleted()
    if path == "compile_cache_hit":
        assert eng.compile_cache.hits >= 1 and eng.compile_cache.misses == 0
    eng.run()


def _hlo_shapes(text: str) -> dict:
    """Every instruction of an HLO module's text: name -> (dims, line)."""
    import re
    out = {}
    for m in re.finditer(r"^\s*(?:ROOT )?%(\S+) = \(?[a-z0-9]+\[([\d,]*)\]"
                         r".*$", text, re.M):
        dims = tuple(int(d) for d in m.group(2).split(",") if d)
        out[m.group(1)] = (dims, m.group(0))
    return out


def test_kv_cache_ops_read_no_gathered_cache(model):
    """No op under the ``kv_cache`` scope reads a whole ``(L, B, T, Hkv,
    hd)`` cache: only ``gather_pages`` makes one, and the new rows come
    out of the layer scan, not back out of the cache."""
    import re
    qp, qcfg = model
    reg, names = _registry(qp, ranks=(4,), per_rank=1)
    eng = _engine(qp, qcfg, reg, bucket_capacity=3)
    B = eng.bucket_capacity
    z = jnp.zeros((B,), jnp.int32)
    text = eng._exec.lower(
        eng._base, reg.stacks(reg.ranks()[0]), z, eng._k_pool, eng._v_pool,
        jnp.zeros((B, eng._maxp), jnp.int32), z,
        jnp.zeros((B, 1), jnp.int32)).compile().as_text()
    hd = qcfg.head_dim or qcfg.d_model // qcfg.n_heads
    full = (qcfg.n_layers, B, eng.max_len, qcfg.n_kv_heads, hd)
    assert full != eng._k_pool.shape
    shapes = _hlo_shapes(text)
    readers = []
    for name, (_, line) in shapes.items():
        if "/kv_cache/" not in line:
            continue
        args = re.search(r" [\w-]+\(([^)]*)\)", line.split(", metadata")[0])
        for arg in re.findall(r"%([\w.-]+)", args.group(1) if args else ""):
            if shapes.get(arg, ((),))[0] == full:
                readers.append(name)
    assert any(dims == full for dims, _ in shapes.values())  # gather_pages
    assert not readers, f"kv_cache ops read a full cache: {readers}"
