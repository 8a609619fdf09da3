"""Fine-tuning driver with fault tolerance.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-4b --smoke \
        --method cloq --bits 2 --steps 50

Fault tolerance (DESIGN.md §4):
  * checkpoint every ``--ckpt-every`` steps (atomic, retained, async) with
    the data-iterator state inside ``meta``;
  * ``--resume`` restores the newest checkpoint and reshards it onto the
    *current* mesh (elastic restart after resizing the data axis);
  * SIGTERM/SIGINT triggers a synchronous final checkpoint (preemption);
  * straggler detection: per-step wall time is tracked against the running
    median; steps slower than ``--straggler-factor`` x median are logged
    with the step index (on a real cluster this feeds the requeue policy —
    single-process simulation documented in DESIGN.md).
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import statistics
import sys
import time

import jax
import jax.numpy as jnp

from repro import obs
from repro.checkpoint import CheckpointManager
from repro.obs import log as obs_log
from repro.obs import metrics as obs_metrics
from repro.obs import names as obs_names
from repro.obs import trace as obs_trace
from repro.configs import get_config, get_smoke_config
from repro.core.pipeline import (allocate_plan, quantization_manifest,
                                 quantize_model)
from repro.core.recipe import QuantRecipe, load_plan
from repro.data import DataConfig, TokenStream
from repro.launch.jax_cache import enable_compilation_cache
from repro.launch.steps import build_state, make_train_step
from repro.models.modules import QSpec
from repro.models.parallel import LOCAL
from repro.models.transformer import init_params
from repro.optim import OptConfig, merge_params
from repro.utils import tree_paths, set_path


def parse_args(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True)
    p.add_argument("--smoke", action="store_true",
                   help="reduced config (CPU-runnable)")
    p.add_argument("--method", default="cloq",
                   choices=["cloq", "gptq", "loftq", "qlora", "rtn", "none"])
    p.add_argument("--recipe", default="",
                   help="path to a QuantRecipe JSON — or a bucket-manifest "
                        "JSON embedding one (per-site mixed-precision "
                        "plan; overrides --method/--bits/--group-size/"
                        "--rank/--split)")
    p.add_argument("--auto-allocate", action="store_true",
                   help="derive the recipe from calibration sensitivities "
                        "under --budget-mb (repro.core.allocate: vmapped "
                        "sweep + budgeted knapsack solve)")
    p.add_argument("--budget-mb", type=float, default=0.0,
                   help="total quantized-site byte budget for "
                        "--auto-allocate, in MiB")
    p.add_argument("--bits", type=int, default=4)
    p.add_argument("--group-size", type=int, default=64)
    p.add_argument("--rank", type=int, default=64)
    p.add_argument("--split", default="paper")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--schedule", default="cosine",
                   choices=["const", "linear", "cosine", "wsd"])
    p.add_argument("--seq-len", type=int, default=128)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--calib-batches", type=int, default=4)
    p.add_argument("--pretrain-steps", type=int, default=0,
                   help="optional full-precision warm start (smoke demos)")
    p.add_argument("--ckpt-dir", default="")
    p.add_argument("--ckpt-every", type=int, default=50)
    p.add_argument("--resume", action="store_true")
    p.add_argument("--resume-quant", default="", metavar="DIR",
                   help="journal the quantization pass into DIR (one atomic "
                        "commit per completed bucket) and, on restart, skip "
                        "buckets already committed there — resumable "
                        "quantization for preemptible jobs; the health "
                        "report lands at DIR/health.json")
    p.add_argument("--straggler-factor", type=float, default=3.0)
    p.add_argument("--compile-cache", default="", metavar="DIR",
                   help="persist AOT bucket executables under DIR; a "
                        "restart with the same DIR deserializes instead of "
                        "retracing (pairs well with --resume-quant)")
    p.add_argument("--cost-cal", default="", metavar="FILE|auto",
                   help="cost-model calibration driving the bucket "
                        "planner's sharded/replicated/sequential choice: a "
                        "calibration JSON, or 'auto' to microbenchmark this "
                        "host once and cache the result "
                        "(repro.core.costmodel.calibrate)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--trace-out", default="", metavar="FILE",
                   help="write a chrome-trace/Perfetto span timeline of "
                        "the run to FILE (load at https://ui.perfetto.dev; "
                        "REPRO_TRACE_SYNC=1 fences async dispatch at span "
                        "close)")
    p.add_argument("--metrics-out", default="", metavar="FILE",
                   help="write the metrics-registry snapshot to FILE "
                        "(defaults to results/metrics-train.json when "
                        "--trace-out is set)")
    return p.parse_args(argv)


def main(argv=None) -> int:
    enable_compilation_cache()
    args = parse_args(argv)
    metrics_out = args.metrics_out or (
        obs.default_metrics_path("train") if args.trace_out else "")
    with obs.session(args.trace_out or None, metrics_out or None):
        return _run(args)


def _run(args) -> int:
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.smoke and args.group_size > cfg.d_model:
        args.group_size = min(args.group_size, 16)

    key = jax.random.PRNGKey(args.seed)
    params = init_params(key, cfg)

    kind = ("encdec" if cfg.family == "encdec"
            else "vlm" if cfg.frontend == "vision" else "lm")
    dcfg = DataConfig(vocab=cfg.vocab, seq_len=args.seq_len,
                      global_batch=args.batch, seed=args.seed, kind=kind,
                      enc_len=max(args.seq_len // 4, 8),
                      n_prefix=cfg.n_prefix, d_model=cfg.d_model)
    stream = TokenStream(dcfg)

    if args.pretrain_steps:
        ocfg0 = OptConfig(lr=3e-3, trainable="all",
                          total_steps=args.pretrain_steps, schedule="cosine")
        st0 = build_state(params, ocfg0)
        fn0 = jax.jit(make_train_step(cfg, ocfg0, LOCAL))
        for _ in range(args.pretrain_steps):
            st0, m0 = fn0(st0, stream.next_batch())
        params = merge_params(st0["train"], st0["frozen"])
        obs_log.info("pretrain", steps=args.pretrain_steps,
                     loss=float(m0["loss"]))

    if args.auto_allocate and args.recipe:
        raise SystemExit("--auto-allocate derives the recipe; it conflicts "
                         "with an explicit --recipe")
    if args.auto_allocate and args.method == "none":
        raise SystemExit("--auto-allocate conflicts with --method none")
    if args.budget_mb and not args.auto_allocate:
        raise SystemExit("--budget-mb only applies with --auto-allocate")
    recipe = None
    if args.recipe:
        recipe = load_plan(args.recipe)
    elif args.method != "none" and not args.auto_allocate:
        recipe = QuantRecipe.single(
            args.method, QSpec(bits=args.bits, group_size=args.group_size,
                               rank=args.rank, method=args.method,
                               split=args.split))
    calib = None
    if args.auto_allocate:
        if args.budget_mb <= 0:
            raise SystemExit("--auto-allocate needs --budget-mb > 0")
        from repro.core.allocate import default_grid
        base = QSpec(bits=args.bits, group_size=args.group_size,
                     rank=args.rank, method=args.method, split=args.split)
        calib = [stream.next_batch() for _ in range(args.calib_batches)]
        t0 = time.time()
        # candidate bits x ranks around the CLI method (27-candidate full
        # grid only when explicitly scripted through the API)
        alloc = allocate_plan(params, cfg, calib,
                              int(args.budget_mb * 2**20),
                              grid=default_grid(methods=(args.method,)),
                              qspec=base)
        obs_log.info("allocate", "solved", s=time.time() - t0)
        print(alloc.summary())
        recipe = alloc.recipe
    # handlers installed BEFORE quantization: a SIGTERM mid-quantization
    # must stop the engine at the next bucket boundary (journaled buckets
    # are already committed), not fall through to the default handler
    stop = {"flag": False}

    def on_signal(signum, frame):
        stop["flag"] = True

    signal.signal(signal.SIGTERM, on_signal)
    signal.signal(signal.SIGINT, on_signal)

    manifest = None
    if recipe is not None:
        from repro.core.health import HealthReport, QuantPreempted
        if calib is None:
            calib = [stream.next_batch() for _ in range(args.calib_batches)]
        cost_model = None
        if args.cost_cal:
            from repro.core.costmodel import CostModel, calibrate
            cal = (calibrate() if args.cost_cal == "auto"
                   else args.cost_cal)
            cost_model = CostModel.coerce(cal)
        t0 = time.time()
        journal_dir = args.resume_quant or None
        report = HealthReport()
        try:
            params, cfg, _ = quantize_model(
                params, cfg, calib, recipe=recipe, report=report,
                journal_dir=journal_dir,
                cost_model=cost_model,
                compile_cache=args.compile_cache or None,
                should_stop=(lambda: stop["flag"]) if journal_dir else None)
        except QuantPreempted as e:
            obs_log.warn(
                "preempt-quant",
                f"signal received — buckets 0..{e.bucket} committed to "
                f"{journal_dir}; rerun with the same --resume-quant to "
                "continue")
            return 0
        obs_log.info("quantize", rules=len(recipe.rules),
                     default=f"{recipe.method}/{recipe.qspec.bits}b",
                     s=time.time() - t0)
        obs_log.info("quantize", report.summary())
        # production checkpoints carry the bucket manifest (recipe
        # included) so restores on any mesh can rebuild per-leaf shardings
        # without the planner (checkpoint.manager.manifest_shardings)
        manifest = quantization_manifest(cfg, recipe=recipe,
                                         cost_model=cost_model)
        trainable = "lora"
    else:
        trainable = "all"

    ocfg = OptConfig(lr=args.lr, trainable=trainable, total_steps=args.steps,
                     schedule=args.schedule)
    state = build_state(params, ocfg)
    step_fn = jax.jit(make_train_step(cfg, ocfg, LOCAL))

    ckpt = None
    start_step = 0
    if args.ckpt_dir:
        ckpt = CheckpointManager(args.ckpt_dir, keep=3, every=args.ckpt_every)
        if args.resume and ckpt.latest_step() is not None:
            tree, meta = ckpt.restore()
            flat = tree_paths(tree)
            rebuilt: dict = {}
            for pth, leaf in flat.items():
                set_path(rebuilt, pth, jnp.asarray(leaf))
            state = rebuilt
            stream.load_state_dict(meta["data"])
            start_step = meta["step"]
            obs_log.info("resume", f"step {start_step}")

    step_hist = obs_metrics.histogram(obs_names.TRAIN_STEP_TIME)
    step_count = obs_metrics.counter(obs_names.TRAIN_STEPS)
    times: list[float] = []
    for step in range(start_step, args.steps):
        t0 = time.time()
        with obs_trace.span("train.step", step=step):
            state, metrics = step_fn(state, stream.next_batch())
            # fence the async dispatch: the step time below must measure
            # device compute, not XLA enqueue (reprolint BENCH)
            jax.block_until_ready(metrics)
        dt = time.time() - t0
        step_hist.observe(dt)
        step_count.inc()
        if len(times) >= 5:
            med = statistics.median(times[-50:])
            if dt > args.straggler_factor * med:
                obs_log.warn(
                    "straggler",
                    f"step {step} took {dt:.3f}s (median {med:.3f}s) "
                    "— would requeue on cluster")
        times.append(dt)
        if step % 10 == 0 or step == args.steps - 1:
            obs_log.info("step", i=step, loss=float(metrics["loss"]),
                         lr=float(metrics["lr"]),
                         gnorm=float(metrics["grad_norm"]),
                         ms=dt * 1e3)
        if ckpt is not None:
            ckpt.maybe_save(step + 1, state,
                            {"data": stream.state_dict(), "step": step + 1},
                            manifest=manifest)
        if stop["flag"]:
            obs_log.warn("preempt",
                         f"signal received — checkpointing at {step + 1}")
            if ckpt is not None:
                # pinned: retention GC must never collect the preemption
                # checkpoint, however many routine saves follow on restart
                ckpt.maybe_save(step + 1, state,
                                {"data": stream.state_dict(),
                                 "step": step + 1}, force=True,
                                manifest=manifest, pin=True)
                ckpt.wait()
            return 0
    if ckpt is not None:
        ckpt.maybe_save(args.steps, state,
                        {"data": stream.state_dict(), "step": args.steps},
                        force=True, manifest=manifest)
        ckpt.wait()
    obs_log.info("done", json.dumps({"final_loss": float(metrics["loss"])}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
