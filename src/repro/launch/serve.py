"""Multi-tenant serving CLI (continuous batching over repro.serve).

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --smoke \
        --requests 16 --max-new 32 --tenants 4 --ranks 8,16

Attention-cache families (dense/moe) serve through
:class:`repro.serve.engine.ServeEngine`: per-tenant CLoQ adapter pairs in
an :class:`~repro.serve.registry.AdapterRegistry` (synthetic perturbations
of the base's calibrated adapters by default; ``--adapter name=DIR`` hot-
loads real checkpoint manifests), iteration-level admission/retirement,
rank-bucketed batched adapter einsums, and a paged KV cache.

SSM/hybrid/enc-dec families keep the legacy fixed-slot loop (their decode
state is not a paged attention cache)."""
from __future__ import annotations

import argparse
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs import get_config, get_smoke_config
from repro.obs import log as obs_log
from repro.obs import metrics as obs_metrics
from repro.obs import names as obs_names
from repro.core.pipeline import quantize_model
from repro.core.recipe import QuantRecipe, load_plan
from repro.data import DataConfig, TokenStream
from repro.launch.jax_cache import enable_compilation_cache
from repro.launch.steps import make_decode_step
from repro.models.modules import QSpec
from repro.models.parallel import LOCAL
from repro.models.transformer import init_decode_cache, init_params


def _build_quantized(args, cfg, params):
    recipe = None
    if args.recipe:
        recipe = load_plan(args.recipe)
    elif args.method != "none":
        recipe = QuantRecipe.single(
            args.method,
            QSpec(bits=args.bits, group_size=16 if args.smoke else 64,
                  rank=8 if args.smoke else 64, method=args.method))
    if recipe is not None:
        dcfg = DataConfig(vocab=cfg.vocab, seq_len=64, global_batch=2,
                          seed=args.seed,
                          kind="encdec" if cfg.family == "encdec" else
                          ("vlm" if cfg.frontend == "vision" else "lm"),
                          enc_len=16, n_prefix=cfg.n_prefix,
                          d_model=cfg.d_model)
        calib = [TokenStream(dcfg).next_batch()]
        params, cfg, _ = quantize_model(
            params, cfg, calib, recipe=recipe,
            cost_model=args.cost_cal or None,
            compile_cache=args.compile_cache or None)
    return cfg, params


def _serve_multitenant(args, cfg, params) -> int:
    from repro.serve import (AdapterRegistry, ServeEngine,
                             adapters_from_tree)
    from repro.serve.registry import synthesize_adapters

    base_ad = adapters_from_tree(params)
    if not base_ad:
        return -1                       # no adapter sites -> legacy loop
    registry = AdapterRegistry.from_model(params, capacity=args.batch)
    ranks = ([int(r) for r in args.ranks.split(",") if r]
             or [next(iter(base_ad.values()))["lora_a"].shape[2]])
    n_tenants = args.tenants or args.batch * len(ranks)
    tenants = []
    for i in range(n_tenants):
        name = f"tenant-{i}"
        registry.register(name, synthesize_adapters(
            base_ad, ranks[i % len(ranks)], seed=args.seed + i))
        tenants.append(name)
    for spec in args.adapter:           # hot-load real adapter checkpoints
        name, _, directory = spec.partition("=")
        registry.load(name, directory)
        tenants.append(name)

    engine = ServeEngine(params, cfg, registry, page_size=args.page_size,
                         max_len=args.cache_len, bucket_capacity=args.batch,
                         use_kernel=args.kernel,
                         compile_cache=args.compile_cache or None)
    rng = np.random.default_rng(args.seed)
    t0 = time.time()
    rids = [engine.submit([int(rng.integers(1, cfg.vocab))],
                          tenants[i % len(tenants)], args.max_new)
            for i in range(args.requests)]
    engine.run()
    dt = time.time() - t0
    # summary derived from the metrics registry, not recounted by hand:
    # the engine increments serve.* as it admits/decodes/retires
    reg = obs_metrics.get_registry()
    toks = reg.counter(obs_names.SERVE_TOKENS).value
    done = reg.counter(obs_names.SERVE_FINISHED).value
    steps = reg.counter(obs_names.SERVE_STEPS).value
    lats = sorted(engine.latency(r) for r in rids)
    p50 = lats[len(lats) // 2]
    obs_log.info("serve", requests=f"{done}/{args.requests}",
                 steps=steps, tokens=toks, s=dt, tok_s=toks / dt,
                 tenants=len(tenants),
                 rank_buckets=",".join(map(str, registry.ranks())),
                 p50_ms=p50 * 1e3)
    if engine.compile_cache is not None:
        obs_log.info("serve", "decode",
                     cache_hits=reg.counter(obs_names.CACHE_HITS).value,
                     cache_misses=reg.counter(
                         obs_names.CACHE_MISSES).value)
    return 0


def _serve_legacy(args, cfg, params) -> int:
    """Fixed-slot refill loop for families without a paged attention
    cache (ssm/hybrid/encdec) — the pre-engine serving path."""
    B = args.batch
    cache = init_decode_cache(cfg, B, args.cache_len)
    if cfg.family == "encdec":
        cache["enc_out"] = jnp.zeros((B, args.cache_len, cfg.d_model),
                                     cfg.dtype)
    step = jax.jit(make_decode_step(cfg, LOCAL))

    rng = np.random.default_rng(args.seed)
    queue = [int(rng.integers(1, cfg.vocab)) for _ in range(args.requests)]
    slots = [None] * B             # (request_id, tokens_left) or None
    current = np.zeros((B, 1), np.int32)
    done, req_id = 0, 0
    t0 = time.time()
    steps = 0
    while done < args.requests:
        for s in range(B):          # refill free slots
            if slots[s] is None and queue:
                first = queue.pop(0)
                slots[s] = [req_id, args.max_new]
                current[s, 0] = first
                req_id += 1
        logits, cache = step(params, cache, jnp.asarray(current))
        nxt = jax.device_get(jnp.argmax(logits, axis=-1))
        steps += 1
        for s in range(B):
            if slots[s] is None:
                continue
            slots[s][1] -= 1
            current[s, 0] = int(nxt[s]) % cfg.vocab
            if slots[s][1] <= 0:
                done += 1
                slots[s] = None
        if steps > args.requests * args.max_new + 16:
            break
    dt = time.time() - t0
    toks = steps * B
    obs_log.info("serve", requests=f"{done}/{args.requests}", steps=steps,
                 slot_tokens=toks, s=dt, tok_s=toks / dt)
    return 0


def main(argv=None) -> int:
    enable_compilation_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True)
    p.add_argument("--smoke", action="store_true")
    p.add_argument("--method", default="cloq")
    p.add_argument("--recipe", default="",
                   help="QuantRecipe JSON — or a bucket-manifest JSON "
                        "embedding one (checkpoint meta / auto-allocated "
                        "plan); overrides --method/--bits")
    p.add_argument("--bits", type=int, default=4)
    p.add_argument("--batch", type=int, default=4,
                   help="slots per rank bucket (legacy loop: slot count)")
    p.add_argument("--cache-len", type=int, default=128)
    p.add_argument("--requests", type=int, default=8)
    p.add_argument("--max-new", type=int, default=16)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--tenants", type=int, default=0,
                   help="synthetic tenants (0 = batch x #ranks)")
    p.add_argument("--ranks", default="",
                   help="comma list of adapter ranks, one bucket each "
                        "(default: the base recipe's rank)")
    p.add_argument("--page-size", type=int, default=8)
    p.add_argument("--kernel", action="store_true",
                   help="Pallas dequant + flash-decode kernels")
    p.add_argument("--adapter", action="append", default=[],
                   metavar="NAME=DIR",
                   help="hot-load a tenant adapter checkpoint (repeatable)")
    p.add_argument("--compile-cache", default="", metavar="DIR",
                   help="persist AOT executables (quantization buckets + "
                        "decode step) under DIR; a second start with the "
                        "same DIR deserializes instead of retracing")
    p.add_argument("--cost-cal", default="", metavar="FILE",
                   help="cost-model calibration JSON (repro.core.costmodel "
                        "calibrate output) driving the bucket planner's "
                        "sharded/replicated/sequential choice")
    p.add_argument("--trace-out", default="", metavar="FILE",
                   help="write a chrome-trace/Perfetto span timeline "
                        "(quantize buckets + serve steps/decodes) to FILE; "
                        "REPRO_TRACE_SYNC=1 fences async dispatch")
    p.add_argument("--metrics-out", default="", metavar="FILE",
                   help="write the metrics-registry snapshot to FILE "
                        "(defaults to results/metrics-serve.json when "
                        "--trace-out is set)")
    args = p.parse_args(argv)

    metrics_out = args.metrics_out or (
        obs.default_metrics_path("serve") if args.trace_out else "")
    with obs.session(args.trace_out or None, metrics_out or None):
        cfg = (get_smoke_config(args.arch) if args.smoke
               else get_config(args.arch))
        params = init_params(jax.random.PRNGKey(args.seed), cfg)
        cfg, params = _build_quantized(args, cfg, params)

        if cfg.family in ("dense", "moe") and cfg.scan_layers:
            rc = _serve_multitenant(args, cfg, params)
            if rc >= 0:
                return rc
        return _serve_legacy(args, cfg, params)


if __name__ == "__main__":
    sys.exit(main())
