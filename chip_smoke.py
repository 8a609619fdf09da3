"""Run the CLoQ pipeline once on a TPU at qwen3-1.7b's full width.

    python chip_smoke.py                # one chip: every phase below
    python chip_smoke.py --four-chips   # four chips: sharded quantize only

One process, in this order:

1. device    refuse to run unless JAX's first device is a TPU;
2. kernels   the Pallas kernels of the main path against ``kernels/ref.py``,
             each compiled program checked for its Mosaic custom call;
3. quantize  calibrate and CLoQ-quantize (INT4, g=64, rank 64) through
             ``quantize_model``, with the cost model's memory gate;
4. train     5 LoRA steps on the packed base (``build_state`` /
             ``make_train_step``);
5. serve     8 requests from 4 tenants (ranks 16 and 64) through
             ``ServeEngine``, with the Pallas kernels on;
6. result    the last stdout line, ``{"ok": true, "device": {...}}``.

``--four-chips`` runs ``quantize_model(..., mesh=make_model_mesh(4))``
and the one-device engine on the same layers against one calibration
(its Grams feed both).  Both run without the cost model, so every
bucket fuses (sharded over the four chips, or vmapped on one); at
``FOUR_CHIP_LAYERS`` layers every fused bucket fits a chip.  Codes,
scales and zeros are held to the one-device engine's
(``tests/util.py:assert_leaves_close``); the sharded adapters are held
to Theorem 3.1's optimum on their own base (:func:`compare_engines`
says why).  The one-chip run holds layer 0 of every site to the same
optimum.

Weights and tokens are random, made from ``--seed``.  A failed phase
raises: the exit code is then non-zero and no result line is printed.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(REPO, "src"), REPO]

import jax                                              # noqa: E402
import jax.numpy as jnp                                 # noqa: E402
import numpy as np                                      # noqa: E402

from repro.launch.jax_cache import enable_compilation_cache  # noqa: E402

ARCH = "qwen3-1.7b"
# every layer of the published config; a cut to fewer is printed up front
N_LAYERS = 28
# the fused sharded bucket of all 28 down-projections needs 20.15 GB of
# HBM per chip (compiled for a described v5e); 8 layers fit, and the
# memory gate that would split a larger bucket runs it on one chip
FOUR_CHIP_LAYERS = 8
CALIB_BATCHES, CALIB_BATCH, CALIB_SEQ = 4, 8, 512
TRAIN_STEPS, TRAIN_BATCH, TRAIN_SEQ = 5, 4, 512
SERVE_RANKS, SERVE_TENANTS, SERVE_REQUESTS = (16, 64), 4, 8
SERVE_PROMPT, SERVE_MAX_NEW, SERVE_CACHE = 32, 16, 256
# relative Frobenius error allowed between a kernel and its reference:
# both round their output to bf16, and the kernel also rounds the
# dequantized weights to bf16 before the MXU (~2^-9 each)
KERNEL_TOL = 1e-2
# share of Theorem 3.1's optimal objective gain the adapters may miss,
# and the f32 rounding allowed above the optimum
OBJECTIVE_TOL, OBJECTIVE_OVER = 1e-2, 1e-3


class _CompileCount:
    """Backend compiles (and their seconds) seen through jax.monitoring."""

    def __init__(self) -> None:
        self.n, self.seconds, self.cache_hits = 0, 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += secs

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def _peak_bytes() -> int | None:
    return (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")


@contextlib.contextmanager
def _phase(name: str, compiles: _CompileCount):
    n0, s0 = compiles.n, compiles.seconds
    t0 = time.perf_counter()
    print(f"[{name}] start", flush=True)
    yield
    print(f"[{name}] done seconds={time.perf_counter() - t0:.1f} "
          f"compiles={compiles.n - n0} "
          f"compile_seconds={compiles.seconds - s0:.1f} "
          f"peak_bytes_in_use={_peak_bytes()}", flush=True)


def _rel_fro(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / (np.linalg.norm(want) + 1e-30))


def phase_device(n_chips: int) -> dict:
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print(f"device: platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']}", flush=True)
    if dev["platform"] != "tpu":
        raise SystemExit(f"no TPU: JAX's first device is {dev['platform']}; "
                         "this smoke never falls back to the CPU")
    if dev["count"] < n_chips:
        raise SystemExit(f"needs {n_chips} chips, JAX sees {dev['count']}")
    return dev


def _check_kernel(name: str, fn, ref_fn, args) -> None:
    compiled = jax.jit(fn).lower(*args).compile()
    if "tpu_custom_call" not in compiled.as_text():
        raise AssertionError(f"kernel {name}: the compiled program holds no "
                             "tpu_custom_call (interpreter or reference)")
    got = compiled(*args)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(ref_fn)(*args)
    err = _rel_fro(got, want)
    print(f"  kernel {name}: rel_err={err:.3e} tol={KERNEL_TOL:g}",
          flush=True)
    if not err <= KERNEL_TOL:
        raise AssertionError(f"kernel {name}: rel_err {err:.3e} > "
                             f"{KERNEL_TOL:g}")


def phase_kernels(seed: int) -> None:
    from repro.core.quantizer import pack_codes, quantize_int
    from repro.kernels import ops, ref

    rng = np.random.default_rng(seed)
    g = 64

    def packed_weight(K, N, bits):
        W = jnp.asarray(rng.normal(size=(K, N)) * 0.02, jnp.float32)
        codes, s, z = quantize_int(W, bits, g)
        return pack_codes(codes, bits), s, z

    for bits in (2, 4):
        for K, N in ((2048, 6144), (6144, 2048)):
            p, s, z = packed_weight(K, N, bits)
            for M in (8, 128):
                x = jnp.asarray(rng.normal(size=(M, K)), jnp.bfloat16)
                _check_kernel(
                    f"dequant_matmul int{bits} M={M} K={K} N={N}",
                    lambda x, p, s, z, b=bits: ops.dequant_matmul(
                        x, p, s, z, bits=b, group_size=g),
                    lambda x, p, s, z, b=bits: ref.dequant_matmul_ref(
                        x, p, s, z, bits=b, group_size=g),
                    (x, p, s, z))
    K, N, M, r = 2048, 6144, 128, 64
    p, s, z = packed_weight(K, N, 4)
    x = jnp.asarray(rng.normal(size=(M, K)), jnp.bfloat16)
    a = jnp.asarray(rng.normal(size=(K, r)) * 0.02, jnp.bfloat16)
    b = jnp.asarray(rng.normal(size=(N, r)) * 0.02, jnp.bfloat16)
    _check_kernel(
        f"dequant_matmul_lora int4 M={M} K={K} N={N} r={r}",
        lambda x, p, s, z, a, b: ops.dequant_matmul(
            x, p, s, z, bits=4, group_size=g, lora_a=a, lora_b=b),
        lambda x, p, s, z, a, b: ref.dequant_matmul_lora_ref(
            x, p, s, z, a, b, bits=4, group_size=g),
        (x, p, s, z, a, b))
    B, Hq, Hkv, T, d = 4, 16, 8, 256, 128
    q = jnp.asarray(rng.normal(size=(B, Hq, 1, d)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(B, Hkv, T, d)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(B, Hkv, T, d)), jnp.bfloat16)
    lengths = jnp.asarray([T, 1, 100, 177], jnp.int32)
    _check_kernel(
        f"flash_attention decode B={B} Hq={Hq} Hkv={Hkv} T={T} d={d}",
        lambda q, k, v, n: ops.flash_attention(q, k, v, causal=False,
                                               lengths=n),
        lambda q, k, v, n: ref.flash_attention_ref(q, k, v, causal=False,
                                                   lengths=n),
        (q, k, v, lengths))


def _calibration(cfg, seed: int) -> list[dict]:
    from repro.data import DataConfig, TokenStream
    stream = TokenStream(DataConfig(vocab=cfg.vocab, seq_len=CALIB_SEQ,
                                    global_batch=CALIB_BATCH, seed=seed))
    return [stream.next_batch() for _ in range(CALIB_BATCHES)]


def _recipe():
    from repro.core.recipe import QuantRecipe
    from repro.models.modules import QSpec
    return QuantRecipe.single("cloq", QSpec(bits=4, group_size=64, rank=64))


def _quantize(cfg, params: dict, calib, **kw):
    """``quantize_model`` of ``params`` against ``calib`` (batches, or the
    Grams of an earlier call); fails when any site left CLoQ for a
    fallback rung of the health ladder.  Returns ``(qp, qcfg, grams)``."""
    from repro.core.health import HealthReport
    from repro.core.pipeline import quantize_model

    report = HealthReport()
    t0 = time.perf_counter()
    qp, qcfg, store = quantize_model(
        params, cfg, calib, recipe=_recipe(), report=report,
        progress=lambda line: print(
            f"  t={time.perf_counter() - t0:.1f}s {line}", flush=True),
        **kw)
    jax.block_until_ready(qp)
    print(f"  {report.summary()}; counts={json.dumps(report.counts())}",
          flush=True)
    left = {s: r["status"] for s, r in report.records.items()
            if r["status"].startswith("fallback_")}
    if left:
        raise AssertionError(f"{len(left)} site(s) left CLoQ: {left}")
    return qp, qcfg, store


def check_cloq_objective(weights: dict, tree: dict, store,
                         layers) -> None:
    """Hold every CLoQ site's adapters to Theorem 3.1's optimum on the
    site's own base, at each of ``layers``.

    With ``H`` the regularized Gram, ``dW = W - Q`` and ``P = A B^T``,
    the adapters lower ``||X (P - dW)||_F^2`` by ``gain = 2 tr(P^T H dW)
    - tr(P^T H P)``, and no rank-r ``P`` gains more than ``best``, the sum
    of the r largest eigenvalues of ``dW^T H dW``.  ``gain / best`` must
    lie within ``OBJECTIVE_TOL`` below 1 (bf16 adapters and the TPU's
    subspace top-r lose ~1e-5) and ``OBJECTIVE_OVER`` above it (f32
    sums).  Unlike the adapters themselves, this is well defined when the
    top-r subspace is not: the whitened residual's spectrum is nearly
    flat.  ``weights`` and ``tree`` are flat paths of the dense and the
    quantized model; ``best`` is taken in f64 on the host."""
    from repro.core.cloq import regularize_gram
    from repro.core.quantizer import QuantConfig, dequantize_int, unpack_codes
    from repro.utils import solver_precision

    q = _recipe().qspec
    lam = QuantConfig(bits=q.bits, group_size=q.group_size).lambda_frac

    @jax.jit
    @solver_precision()
    def terms(W, codes, scales, zeros, H, A, B):
        dW = W - dequantize_int(unpack_codes(codes, q.bits, W.shape[0]),
                                scales, zeros, q.group_size)
        H = regularize_gram(H, lam)
        P = A @ B.T
        HdW = H @ dW
        gain = 2.0 * jnp.sum(P * HdW) - jnp.sum(P * (H @ P))
        # the smaller of dW^T H dW (n x n) and dW dW^T (m x m)
        side = dW.T @ HdW if dW.shape[1] <= dW.shape[0] else dW @ dW.T
        return gain, side

    sites = sorted(p[:-len(".qcodes")] for p in tree if p.endswith(".qcodes"))
    for site in sites:
        shares = []
        for i in layers:
            def leaf(k, dtype=None):
                a = np.asarray(tree[f"{site}.{k}"][i])
                return jnp.asarray(a if dtype is None else a.astype(dtype))
            H = store.grams[site.replace("blocks.", f"blocks.{i}.", 1)]
            W = weights[f"{site}.w"][i]
            gain, side = jax.device_get(terms(
                jnp.asarray(W, jnp.float32), leaf("qcodes"), leaf("scales"),
                leaf("zeros"), jnp.asarray(H), leaf("lora_a", np.float32),
                leaf("lora_b", np.float32)))
            side = np.asarray(side, np.float64)
            m, n = W.shape
            if n > m:
                # wide site: the eigenvalues of L^T (dW dW^T) L, H = L L^T
                H64 = np.asarray(H, np.float64)
                H64 += (lam * np.trace(H64) / m + 1e-8) * np.eye(m)
                L = np.linalg.cholesky(H64)
                side = L.T @ side @ L
            best = float(np.sort(np.linalg.eigvalsh(side))[-q.rank:].sum())
            shares.append(float(gain) / best)
        lo, hi = min(shares), max(shares)
        print(f"  {site}: objective gain / optimum over {len(shares)} "
              f"layers: min={lo:.6f} max={hi:.6f}", flush=True)
        if not (1.0 - OBJECTIVE_TOL <= lo and hi <= 1.0 + OBJECTIVE_OVER):
            raise AssertionError(
                f"{site}: CLoQ adapters reach {lo:.6f}..{hi:.6f} of the "
                f"optimal gain (allowed {1 - OBJECTIVE_TOL}.."
                f"{1 + OBJECTIVE_OVER})")


def phase_quantize(cfg, seed: int):
    from repro.core.costmodel import CostModel, calibrate
    from repro.models.transformer import init_params
    from repro.utils import tree_paths

    cal = calibrate(path=os.path.join(REPO, "results", "costcal-smoke.json"),
                    force=True)
    print(f"  memory_budget_bytes={cal.memory_budget_bytes:.0f}", flush=True)
    params = init_params(jax.random.PRNGKey(seed), cfg)
    qp, qcfg, store = _quantize(cfg, params, _calibration(cfg, seed),
                                cost_model=CostModel(cal), seed=seed)
    n_sites = sum(1 for path in tree_paths(qp) if path.endswith(".qcodes"))
    print(f"  quantized sites: {n_sites} stacked leaves", flush=True)
    # the subspace top-r at full width, against the exact optimum
    check_cloq_objective(tree_paths(params), tree_paths(qp), store, [0])
    return qp, qcfg


def phase_train(qp, qcfg, seed: int) -> list[float]:
    from repro.data import DataConfig, TokenStream
    from repro.launch.steps import build_state, make_train_step
    from repro.models.parallel import LOCAL
    from repro.optim import OptConfig

    ocfg = OptConfig(lr=1e-4, trainable="lora", total_steps=TRAIN_STEPS,
                     schedule="const")
    state = build_state(qp, ocfg)
    step_fn = jax.jit(make_train_step(qcfg, ocfg, LOCAL))
    stream = TokenStream(DataConfig(vocab=qcfg.vocab, seq_len=TRAIN_SEQ,
                                    global_batch=TRAIN_BATCH, seed=seed + 1))
    losses = []
    for i in range(TRAIN_STEPS):
        t0 = time.perf_counter()
        state, metrics = step_fn(state, stream.next_batch())
        loss = float(metrics["loss"])
        losses.append(loss)
        print(f"  step {i} loss={loss:.6f} "
              f"grad_norm={float(metrics['grad_norm']):.6f} "
              f"seconds={time.perf_counter() - t0:.3f}", flush=True)
    if not np.all(np.isfinite(losses)):
        raise FloatingPointError(f"non-finite training loss: {losses}")
    return losses


def phase_serve(qp, qcfg, seed: int) -> int:
    from repro.serve import AdapterRegistry, ServeEngine, adapters_from_tree
    from repro.serve.registry import synthesize_adapters

    reg = AdapterRegistry.from_model(qp, capacity=SERVE_TENANTS)
    base = adapters_from_tree(qp)
    tenants = [f"tenant-{i}" for i in range(SERVE_TENANTS)]
    for i, name in enumerate(tenants):
        reg.register(name, synthesize_adapters(
            base, SERVE_RANKS[i % len(SERVE_RANKS)], seed=seed + 100 + i))
    rng = np.random.default_rng(seed)
    reqs = [(tenants[i % SERVE_TENANTS],
             rng.integers(1, qcfg.vocab, SERVE_PROMPT).tolist())
            for i in range(SERVE_REQUESTS)]

    def engine():
        # one bucket row per request of a rank bucket, padded to 8 rows:
        # the dequant kernel tiles 8 rows at a time
        return ServeEngine(qp, qcfg, reg, page_size=16,
                           max_len=SERVE_CACHE, bucket_capacity=8,
                           use_kernel=True)

    eng = engine()
    rids = [eng.submit(prompt, tenant, SERVE_MAX_NEW)
            for tenant, prompt in reqs]
    t0 = time.perf_counter()
    eng.run()
    outs = [eng.result(r) for r in rids]
    tokens = sum(len(o) for o in outs)
    print(f"  served {len(outs)} requests, {tokens} tokens, "
          f"{eng.steps} engine steps, seconds={time.perf_counter() - t0:.3f}",
          flush=True)
    bad = [i for i, o in enumerate(outs)
           if len(o) != SERVE_MAX_NEW or not all(0 <= t < qcfg.vocab
                                                 for t in o)]
    if bad:
        raise AssertionError(f"requests {bad} did not finish with "
                             f"{SERVE_MAX_NEW} in-vocab tokens")
    # the engine's parity contract: a request replayed alone through a
    # fresh engine yields its batched tokens bit for bit
    for i in range(len(SERVE_RANKS)):
        alone = engine()
        rid = alone.submit(reqs[i][1], reqs[i][0], SERVE_MAX_NEW)
        alone.run()
        if alone.result(rid) != outs[i]:
            raise AssertionError(f"request {i} replayed alone diverged from "
                                 "its batched tokens")
    print(f"  replay parity: {len(SERVE_RANKS)} requests bit-identical "
          "alone and batched", flush=True)
    return tokens


def compare_engines(got: dict, ref: dict) -> None:
    """Hold the codes, scales and zeros of the quantized tree ``got``
    (flat paths) to the one-device batched engine's ``ref`` with
    ``assert_leaves_close``.

    The adapters are not compared with ``ref``'s: CLoQ's adapters are a
    function of the base, and two compiled programs that round
    differently (a TPU tiles an n/4-wide matmul unlike an n-wide one)
    flip a few codes within the flip budget.  A flipped code moves
    ``W - Q`` by a whole quantization step, and with the nearly flat
    spectrum of the whitened residual the best rank-r subspace moves
    with it, so the adapters of two engines are not comparable with each
    other (:func:`check_cloq_objective` holds each to its own optimum).
    The engine-to-engine product is printed, not held."""
    from tests.util import assert_leaves_close, lora_product, rel_fro

    def leaves(tree, site, keys):
        out = {k: np.asarray(tree[f"{site}.{k}"]) for k in keys}
        return {k: v if v.dtype == np.uint8 else v.astype(np.float32)
                for k, v in out.items()}

    base = ("qcodes", "scales", "zeros")
    lora = ("lora_a", "lora_b")
    sites = sorted(p.rsplit(".", 1)[0] for p in ref if p.endswith(".qcodes"))
    pairs = []
    for site in sites:
        g, w = leaves(got, site, base), leaves(ref, site, base)
        gl, wl = leaves(got, site, lora), leaves(ref, site, lora)
        engine_rel = rel_fro(lora_product(gl["lora_a"], gl["lora_b"]),
                             lora_product(wl["lora_a"], wl["lora_b"]))
        print(f"  {site}: "
              f"code_flip_frac={np.mean(g['qcodes'] != w['qcodes']):.3e} "
              f"scales_rel={rel_fro(g['scales'], w['scales']):.3e} "
              f"zeros_rel={rel_fro(g['zeros'], w['zeros']):.3e} "
              f"engine_lora_product_rel={engine_rel:.3e}", flush=True)
        pairs.append((g, w))
    for g, w in pairs:
        assert_leaves_close(g, w)
    print(f"  {len(sites)} stacked sites: codes, scales and zeros held to "
          "the one-device engine (assert_leaves_close)", flush=True)


def four_chips(cfg, seed: int) -> None:
    from repro.launch.mesh import make_model_mesh
    from repro.models.transformer import init_params
    from repro.utils import tree_paths

    params = init_params(jax.random.PRNGKey(seed), cfg)
    ref, _, store = _quantize(cfg, params, _calibration(cfg, seed),
                              seed=seed)
    sh, _, _ = _quantize(cfg, params, store, seed=seed,
                         mesh=make_model_mesh(4))
    sh, ref = tree_paths(sh), tree_paths(ref)
    compare_engines(sh, ref)
    check_cloq_objective(tree_paths(params), sh, store, range(cfg.n_layers))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded quantize engine on four "
                         "chips and compare it with the one-device engine")
    args = ap.parse_args(argv)
    print(f"compile cache: {enable_compilation_cache()}", flush=True)
    compiles = _CompileCount()
    t_all = time.perf_counter()
    with _phase("device", compiles):
        dev = phase_device(4 if args.four_chips else 1)
    from repro.configs import get_config
    cfg = get_config(ARCH, n_layers=(FOUR_CHIP_LAYERS if args.four_chips
                                     else N_LAYERS))
    print(f"model: {ARCH} d_model={cfg.d_model} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab} layers={cfg.n_layers} of "
          f"{get_config(ARCH).n_layers}", flush=True)
    if args.four_chips:
        with _phase("quantize-four-chips", compiles):
            four_chips(cfg, args.seed)
    else:
        with _phase("kernels", compiles):
            phase_kernels(args.seed)
        with _phase("quantize", compiles):
            qp, qcfg = phase_quantize(cfg, args.seed)
        with _phase("train", compiles):
            losses = phase_train(qp, qcfg, args.seed)
        with _phase("serve", compiles):
            tokens = phase_serve(qp, qcfg, args.seed)
        print(f"summary: losses={json.dumps(losses)} tokens_served={tokens}",
              flush=True)
    print(f"total: seconds={time.perf_counter() - t_all:.1f} "
          f"compiles={compiles.n} compile_seconds={compiles.seconds:.1f} "
          f"persistent_cache_hits={compiles.cache_hits} "
          f"peak_bytes_in_use={_peak_bytes()}", flush=True)
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
