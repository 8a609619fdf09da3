"""End-to-end model quantization + LoRA-initialization driver.

``quantize_model`` converts a dense param tree into the paper's deployment
form: every block linear replaced by {qcodes, scales, zeros, lora_a, lora_b},
with the base quantized by MagR→OPTQ against calibration Grams and the LoRA
adapters initialized by CLoQ's closed form (or a baseline method).

The primary signature is declarative::

    quantize_model(params, cfg, calib, recipe=QuantRecipe(
        rules=(SiteRule("blocks.0.*", skip=True),        # left dense
               SiteRule("*.mlp.*", bits=2, rank=32),     # 2-bit MLPs
               SiteRule("*.attn.*", bits=4, rank=16)),   # 4-bit attention
        method="cloq", qspec=QSpec(bits=4, rank=16)))    # everything else

The :class:`repro.core.recipe.QuantRecipe` resolves every quantization
site to a frozen per-site ``(method, qspec | skip)`` ONCE, at plan time
(first-match-wins; see :mod:`repro.core.recipe`), and the per-site specs
are threaded through task gathering, bucket planning, and both engines —
one run can mix CLoQ/LoftQ/QLoRA/RTN/GPTQ at different bit-widths and
ranks across buckets.  The legacy global pair
``quantize_model(method=..., qspec=...)`` still works as a zero-rule
recipe via a deprecation shim.

Calibration runs the model *eagerly* (``scan_layers=False``) so the
name-scope capture hooks see concrete activations.  The zamba2-style shared
block gets ONE quantized base from the pooled Gram and per-site LoRA from
per-site Grams — CLoQ's data-driven init extended to weight-shared
architectures (beyond-paper; DESIGN.md §5).

Engines
-------
``engine="batched"`` (default) is the **batched quantization engine**
(:mod:`repro.core.batched`): quantization sites are flattened to per-layer
tasks — each stacked MoE weight ``(E, m, n)`` contributes E expert tasks, a
natural bucket — then grouped by ``(m, n, method, bits, group_size, rank,
split, …)``.  Each bucket stacks its ``(W, H)`` pairs and runs the full
MagR→OPTQ→CLoQ (or baseline) stack under one ``jax.jit(jax.vmap(...))``
executable: one trace, one dispatch, all layers of the bucket factorized in
parallel.  All shape-dependent branching (OPTQ sweep block, MagR gate) is
resolved at *plan* time so the traced cores stay vmap-safe.  Per-site PRNG
keys are split in path order, exactly like the sequential loop, so random
LoRA inits agree bit-for-bit.

On a multi-device mesh (``quantize_model(..., mesh=...)``) the batched
engine additionally column-shards each bucket over the ``model`` axis —
``shard_map`` composed *inside* the vmapped bucket — and streams buckets
(double-buffered host staging).  See :mod:`repro.core.batched` and
``docs/architecture.md``.

``engine="sequential"`` is the original per-layer Python loop, kept as the
fallback and as the numerical-parity oracle (``tests/test_batched.py``
asserts both engines produce allclose leaves, including the stacked-MoE
case).

Methods:
    cloq       MagR -> OPTQ -> closed-form (A, B)          [the paper]
    gptq       OPTQ -> standard LoRA init (A~N, B=0)       [GPTQ-LoRA]
    loftq      data-free AltMin on ||Q + AB^T - W||        [LoftQ]
    qlora      NF4 RTN -> standard LoRA init               [QLoRA]
    rtn        INT RTN -> standard LoRA init
"""
from __future__ import annotations

import dataclasses
import os
import warnings
from typing import Callable, Iterable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import faults, health
from repro.core.batched import (GRAM_METHODS, LayerTask, bucket_shards,
                                magr_alpha, make_spec, plan_buckets,
                                plan_manifest, quantize_layer_batch)
from repro.core.recipe import QuantRecipe, SiteSpec
from repro.core.cloq import cloq_init, cloq_site_lora, regularize_gram
from repro.core.loftq import loftq_init, qlora_init
from repro.core.magr import magr_preprocess
from repro.core.optq import optq_quantize
from repro.core.quantizer import (QuantConfig, dequantize_int, pack_codes,
                                  quantize_int, unpack_codes)
from repro.models.modules import QSpec
from repro.obs import metrics as obs_metrics
from repro.obs import names as obs_names
from repro.obs import trace as obs_trace
from repro.models.transformer import ModelConfig, forward
from repro.utils import (GramStore, capture_grams, get_path, set_path,
                         solver_precision, tree_paths)

Array = jax.Array

# param paths NOT quantized even though they hold a 2-D "w"
_SKIP_SUFFIXES = ("embed.w", "head.w", "router.w")


def qspec_to_qcfg(q: QSpec) -> QuantConfig:
    return QuantConfig(bits=q.bits, group_size=q.group_size)


def unstack_blocks(stacked, n: int) -> dict:
    return {str(i): jax.tree.map(lambda a: a[i], stacked) for i in range(n)}


def stack_blocks(d: dict):
    ks = sorted(d, key=int)
    return jax.tree.map(lambda *xs: jnp.stack(xs), *[d[k] for k in ks])


_STACK_KEYS = {"blocks": "n_layers", "enc_blocks": "n_enc_layers",
               "dec_blocks": "n_layers", "cross": "n_layers"}


def to_eager_params(params: dict, cfg: ModelConfig) -> dict:
    """Unstack scan-stacked block params into per-layer dicts."""
    if not cfg.scan_layers:
        return params
    out = dict(params)
    for key, nattr in _STACK_KEYS.items():
        if key in params:
            out[key] = unstack_blocks(params[key], getattr(cfg, nattr))
    return out


def to_scan_params(params: dict, cfg: ModelConfig) -> dict:
    out = dict(params)
    for key in _STACK_KEYS:
        if key in params and isinstance(params[key], dict) and \
                all(k.isdigit() for k in params[key]):
            out[key] = stack_blocks(params[key])
    return out


def quantizable_linear_paths(params: dict) -> list[str]:
    """Paths of linear subtrees (ending at the dict holding 'w') that are
    quantization targets: 2-D or stacked-3-D weights inside blocks."""
    out = []
    for path, leaf in tree_paths(params).items():
        if not path.endswith(".w"):
            continue
        if any(path.endswith(sfx) for sfx in _SKIP_SUFFIXES):
            continue
        if "conv" in path.rsplit(".", 2)[-2]:
            continue
        if not hasattr(leaf, "ndim") or leaf.ndim not in (2, 3):
            continue
        if not any(seg in path for seg in
                   ("blocks.", "shared.", "cross.")):
            continue
        out.append(path[: -len(".w")])
    return sorted(out)


def run_calibration(params: dict, cfg: ModelConfig, batches: Iterable[dict],
                    *, report: "health.HealthReport | None" = None
                    ) -> GramStore:
    """Eager forward passes accumulating per-linear Grams.

    Hardened against bad calibration data: every batch accumulates into its
    own scratch store and is merged only when all Gram updates it produced
    are finite — a batch with NaN/Inf activations is skipped and logged
    (``report.event`` + a ``RuntimeWarning``) instead of silently poisoning
    every downstream site.  Raises when batches were supplied but every one
    was skipped/dropped: a zero-sample GramStore would make each
    Gram-consuming site fail individually and far less legibly."""
    eager_cfg = dataclasses.replace(cfg, scan_layers=False, quant=None)
    store = GramStore()
    n_in = n_used = 0
    for i, batch in enumerate(batches):
        n_in += 1
        batch = faults.corrupt_batch(i, batch)        # calib_nan/calib_drop
        if batch is faults.DROPPED:
            obs_metrics.counter(obs_names.CALIB_BATCHES_SKIPPED).inc()
            if report is not None:
                report.event(f"calibration batch {i} dropped")
            continue
        scratch = GramStore()
        with capture_grams(scratch):
            forward(params, eager_cfg, batch)
        faults.poison_grams(i, scratch)               # calib_nan (post)
        if not scratch.all_finite():
            obs_metrics.counter(obs_names.CALIB_BATCHES_SKIPPED).inc()
            msg = (f"calibration batch {i} produced non-finite activations"
                   " — batch skipped")
            warnings.warn(msg, RuntimeWarning, stacklevel=2)
            if report is not None:
                report.event(msg)
            continue
        store.merge(scratch)
        n_used += 1
        obs_metrics.counter(obs_names.CALIB_BATCHES_USED).inc()
    if n_in and not n_used:
        raise RuntimeError(
            f"calibration produced a zero-sample GramStore: all {n_in} "
            "batches were skipped (non-finite activations) or dropped — "
            "fix the calibration data, or use a data-free method")
    return store


def _scope_for(lin_path: str) -> str:
    """Map a param path to the calibration capture scope."""
    if lin_path.startswith("shared.block."):
        return "shared." + lin_path[len("shared.block."):]
    if lin_path.startswith("cross."):
        # param "cross.{i}.xattn.{name}" captured under scope
        # "dec_blocks.{i}.cross.{name}"
        _, idx, _, name = lin_path.split(".")
        return f"dec_blocks.{idx}.cross.{name}"
    return lin_path


def _site_gram(store: GramStore, scope_path: str, target: str):
    """Gram read with the fault-injection hook applied
    (:func:`repro.core.faults.corrupt_gram`).  Both engines read every
    site's Gram through here (keyed by the *param* path), so an armed
    ``gram_*`` injection corrupts the same site identically in each —
    the cross-engine fault matrix depends on it."""
    return faults.corrupt_gram(target, store.grams.get(scope_path))


def _shared_site_grams(store: GramStore, lin_path: str):
    """Per-site Grams of a weight-shared linear plus their pooled sum."""
    rest = lin_path[len("shared.block."):]          # e.g. attn.q
    site_paths = sorted(k for k in store.grams
                        if k.startswith("sites.") and
                        k.endswith(".shared." + rest))
    pooled = None
    for sp in site_paths:
        g = store.grams[sp]
        pooled = g.copy() if pooled is None else pooled + g
    pooled = faults.corrupt_gram(lin_path, pooled)
    return rest, site_paths, pooled


def _shared_base_dequant(newlin: dict, m: int, qspec: QSpec) -> Array:
    """Dequantize the shared base once — it is identical for every site."""
    codes = unpack_codes(newlin["qcodes"], qspec.bits, m)
    return dequantize_int(codes, newlin["scales"], newlin["zeros"],
                          qspec.group_size)


@solver_precision()     # f32 solver products, as the batched core
def _quantize_one(W: Array, H: Array | None, qspec: QSpec, method: str,
                  key: Array):
    """Quantize one (m, n) weight. Returns dict of new leaves."""
    qcfg = qspec_to_qcfg(qspec)
    m, n = W.shape
    W = jnp.asarray(W, jnp.float32)
    if method == "cloq":
        assert H is not None, "cloq needs calibration Grams"
        H = jnp.asarray(H, jnp.float32)
        # traced alpha (same arithmetic as the batched core: f32, no host
        # sync) so both engines quantize identically
        Wp = magr_preprocess(W, H, alpha=magr_alpha(H, m),
                             iters=20) if qspec.bits <= 4 else W
        Qd, Qc, s, z = optq_quantize(Wp, H, qcfg)
        # one lambda_frac governs both OPTQ's damping (inside optq_quantize)
        # and CLoQ's Gram regularization — exactly like the batched core, so
        # the health ladder's re-damp rung reaches every factorization
        A, B = cloq_init(regularize_gram(H, qcfg.lambda_frac), W - Qd,
                         qspec.rank, qspec.split)
        return {"qcodes": pack_codes(Qc, qspec.bits), "scales": s, "zeros": z,
                "lora_a": A, "lora_b": B}
    if method == "gptq":
        assert H is not None
        Qd, Qc, s, z = optq_quantize(W, jnp.asarray(H, jnp.float32), qcfg)
        A = jax.random.normal(key, (m, qspec.rank), jnp.float32) / np.sqrt(m)
        B = jnp.zeros((n, qspec.rank), jnp.float32)
        return {"qcodes": pack_codes(Qc, qspec.bits), "scales": s, "zeros": z,
                "lora_a": A, "lora_b": B}
    if method == "loftq":
        Qd, A, B, qstate = loftq_init(W, qcfg, qspec.rank, iters=5)
        codes, s, z = qstate
        return {"qcodes": pack_codes(codes, qspec.bits), "scales": s,
                "zeros": z, "lora_a": A, "lora_b": B}
    if method == "qlora":
        Qd, A, B, qstate = qlora_init(W, qcfg, qspec.rank, key)
        codes, absmax = qstate
        return {"qcodes": pack_codes(codes, 4), "absmax": absmax,
                "lora_a": A, "lora_b": B}
    if method == "rtn":
        codes, s, z = quantize_int(W, qspec.bits, qspec.group_size)
        A = jax.random.normal(key, (m, qspec.rank), jnp.float32) / np.sqrt(m)
        B = jnp.zeros((n, qspec.rank), jnp.float32)
        return {"qcodes": pack_codes(codes, qspec.bits), "scales": s,
                "zeros": z, "lora_a": A, "lora_b": B}
    raise ValueError(f"unknown method {method}")


def _cast_for_model(leaves: dict, dtype) -> dict:
    out = {}
    for k, v in leaves.items():
        if k in ("lora_a", "lora_b"):
            out[k] = v.astype(dtype)
        else:
            out[k] = v
    return out


def _set_site_lora(new_params: dict, rest: str, As, Bs, dtype) -> None:
    sl = dict(get_path(new_params, "shared.site_lora"))
    sl[rest.replace(".", "_")] = {"lora_a": jnp.asarray(As).astype(dtype),
                                  "lora_b": jnp.asarray(Bs).astype(dtype)}
    set_path(new_params, "shared.site_lora", sl)


# ---------------------------------------------------------------------------
# Sequential engine: the original per-layer loop (fallback + parity oracle).
# ---------------------------------------------------------------------------


def _quantize_model_sequential(eparams: dict, store: GramStore,
                               sites: dict[str, SiteSpec], seed: int,
                               cfg: ModelConfig, new_params: dict,
                               progress: Callable[[str], None] | None,
                               mesh=None, shard_axis: str = "model", *,
                               policy=None, report=None, journal=None,
                               should_stop=None) -> None:
    assert mesh is None, "quantize_model rejects mesh+sequential up front"
    assert journal is None, "quantize_model rejects journal+sequential"
    guarded = policy is not None and policy.enabled
    if guarded and report is None:
        report = health.HealthReport()

    def guard(W, H, leaves, sub, site, path, expert=None):
        """Per-layer health check + ladder: the same criterion, oracle and
        (W, H, key, spec) as the batched engine's bucket check, so a healed
        site is bit-identical across engines."""
        if not guarded:
            return leaves
        spec = make_spec(W.shape[0], W.shape[1], site.qspec, site.method,
                         H is not None)
        report.checked += 1
        obs_metrics.counter(obs_names.HEALTH_CHECKED).inc()
        if health.check_single(W, leaves, spec, policy):
            return leaves
        return health.heal_task(W, H, sub, spec, policy, report, path,
                                expert)

    key = jax.random.PRNGKey(seed)
    for i, lin_path in enumerate(quantizable_linear_paths(eparams)):
        # PRNG keys split per quantizable path — skipped sites included —
        # so key assignment is independent of the recipe's skip rules and
        # identical across engines
        key, sub = jax.random.split(key)
        site = sites[lin_path]
        if site.skip:
            if progress:
                progress(f"[{i}] {lin_path} skipped (left dense)")
            continue
        qspec, method = site.qspec, site.method
        lin = dict(get_path(eparams, lin_path))
        W = lin.pop("w")
        is_shared = lin_path.startswith("shared.block.")
        scope_path = _scope_for(lin_path)
        if progress:
            progress(f"[{i}] {lin_path} {tuple(W.shape)} "
                     f"{method}/{qspec.bits}b/r{qspec.rank}")

        if W.ndim == 3:        # stacked MoE experts (E, m, n)
            H = _site_gram(store, scope_path, lin_path)  # (E, D, D) or None
            E = W.shape[0]
            keys = jax.random.split(sub, E)
            outs = []
            for e in range(E):
                He = None if H is None else H[e]
                lv = _quantize_one(W[e], He, qspec, method, keys[e])
                outs.append(guard(W[e], He, lv, keys[e], site, lin_path, e))
            if any(o is None for o in outs):
                # a stacked MoE site is one leaf tree: an expert degraded
                # to dense forces the whole stacked site dense
                report.event(f"{lin_path}: expert degraded to dense — "
                             "whole stacked site left dense")
                continue
            newlin = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
        elif is_shared:
            # pooled Gram for the shared base; per-site Grams for site LoRA
            rest, site_paths, pooled = _shared_site_grams(store, lin_path)
            newlin = _quantize_one(W, pooled, qspec, method, sub)
            newlin = guard(W, pooled, newlin, sub, site, lin_path)
            if newlin is None:
                continue                       # shared base left dense
            A0, B0 = newlin.pop("lora_a"), newlin.pop("lora_b")
            As, Bs = [], []
            if method == "cloq" and site_paths:
                # the shared base Qd is identical for every site: hoisted
                Qd = _shared_base_dequant(newlin, W.shape[0], qspec)
                for sp in site_paths:
                    Hs_raw = faults.corrupt_gram(sp, store.grams[sp])
                    Hs = jnp.asarray(Hs_raw, jnp.float32)
                    A_s, B_s = cloq_init(regularize_gram(Hs), W - Qd,
                                         qspec.rank, qspec.split)
                    if guarded and not (
                            bool(jnp.all(jnp.isfinite(A_s)))
                            and bool(jnp.all(jnp.isfinite(B_s)))):
                        A_s, B_s = health.heal_site_lora(
                            Hs_raw, jnp.asarray(W, jnp.float32) - Qd,
                            qspec.rank, qspec.split, policy, report,
                            lin_path, sp)
                    As.append(A_s)
                    Bs.append(B_s)
            else:
                As = [A0] * len(site_paths)
                Bs = [B0] * len(site_paths)
            if As:
                _set_site_lora(new_params, rest, jnp.stack(As),
                               jnp.stack(Bs), cfg.dtype)
        else:
            H = _site_gram(store, scope_path, lin_path)
            newlin = _quantize_one(W, H, qspec, method, sub)
            newlin = guard(W, H, newlin, sub, site, lin_path)
            if newlin is None:
                continue                       # degraded to dense: keep w
        keep = {k: v for k, v in lin.items()}     # bias etc.
        keep.update(_cast_for_model(newlin, cfg.dtype))
        set_path(new_params, lin_path, keep)


# ---------------------------------------------------------------------------
# Batched engine: flatten sites to tasks, bucket by shape, jit(vmap) each.
# ---------------------------------------------------------------------------


def _gather_tasks(eparams: dict, store: GramStore,
                  sites: dict[str, SiteSpec], seed: int):
    """Flatten every (non-skipped) quantization site into a LayerTask
    carrying its resolved SiteSpec, splitting PRNG keys in path order
    exactly like the sequential loop (bit-for-bit random-init parity;
    skipped sites consume a key but produce no task)."""
    tasks: list[LayerTask] = []
    groups: list[dict] = []
    key = jax.random.PRNGKey(seed)
    for lin_path in quantizable_linear_paths(eparams):
        key, sub = jax.random.split(key)
        site = sites[lin_path]
        if site.skip:
            continue
        lin = dict(get_path(eparams, lin_path))
        W = lin.pop("w")
        g = {"path": lin_path, "keep": lin, "W": W, "kind": "dense",
             "site": site, "tasks": []}
        if W.ndim == 3:        # stacked MoE experts: a natural bucket
            g["kind"] = "moe"
            H = _site_gram(store, _scope_for(lin_path), lin_path)
            keys = jax.random.split(sub, W.shape[0])
            for e in range(W.shape[0]):
                g["tasks"].append(len(tasks))
                tasks.append(LayerTask(lin_path, e, W[e],
                                       None if H is None else H[e], keys[e],
                                       site=site))
        elif lin_path.startswith("shared.block."):
            g["kind"] = "shared"
            rest, site_paths, pooled = _shared_site_grams(store, lin_path)
            g["rest"], g["site_paths"] = rest, site_paths
            g["tasks"].append(len(tasks))
            tasks.append(LayerTask(lin_path, None, W, pooled, sub,
                                   site=site))
        else:
            g["tasks"].append(len(tasks))
            tasks.append(LayerTask(lin_path, None, W,
                                   _site_gram(store, _scope_for(lin_path),
                                              lin_path),
                                   sub, site=site))
        groups.append(g)
    return tasks, groups


def _quantize_model_batched(eparams: dict, store: GramStore,
                            sites: dict[str, SiteSpec], seed: int,
                            cfg: ModelConfig, new_params: dict,
                            progress: Callable[[str], None] | None,
                            mesh=None, shard_axis: str = "model", *,
                            policy=None, report=None, journal=None,
                            should_stop=None, cost_model=None,
                            compile_cache=None) -> None:
    tasks, groups = _gather_tasks(eparams, store, sites, seed)
    results = quantize_layer_batch(tasks, progress=progress,
                                   mesh=mesh, axis=shard_axis,
                                   policy=policy, report=report,
                                   journal=journal, should_stop=should_stop,
                                   cost_model=cost_model,
                                   compile_cache=compile_cache)
    guarded = policy is not None and policy.enabled
    for g in groups:
        qspec, method = g["site"].qspec, g["site"].method
        if g["kind"] == "moe":
            outs = [results[i] for i in g["tasks"]]
            if any(o is None for o in outs):
                # a stacked MoE site is one leaf tree: an expert degraded
                # to dense forces the whole stacked site dense
                if report is not None:
                    report.event(f"{g['path']}: expert degraded to dense "
                                 "— whole stacked site left dense")
                continue
            newlin = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
        else:
            res = results[g["tasks"][0]]
            if res is None:
                continue                      # degraded to dense: keep w
            newlin = dict(res)
        if g["kind"] == "shared":
            A0, B0 = newlin.pop("lora_a"), newlin.pop("lora_b")
            site_paths = g["site_paths"]
            if site_paths:
                if method == "cloq":
                    W = jnp.asarray(g["W"], jnp.float32)
                    Qd = _shared_base_dequant(newlin, W.shape[0], qspec)
                    dW = W - Qd
                    Hs_raw = [faults.corrupt_gram(sp, store.grams[sp])
                              for sp in site_paths]
                    Hs = jnp.stack([jnp.asarray(h, jnp.float32)
                                    for h in Hs_raw])
                    # same plan-time gate as the bucket planner: shard the
                    # per-site solves over the mesh when n divides the axis
                    site_mesh = mesh if bucket_shards(
                        dW.shape[1], method, mesh, shard_axis) > 1 else None
                    As, Bs = cloq_site_lora(Hs, dW, qspec.rank, qspec.split,
                                            mesh=site_mesh, axis=shard_axis)
                    if guarded:
                        As_h, Bs_h = np.asarray(As), np.asarray(Bs)
                        bad = [s for s in range(len(site_paths))
                               if not (np.isfinite(As_h[s]).all()
                                       and np.isfinite(Bs_h[s]).all())]
                        if bad:
                            As_l, Bs_l = list(As), list(Bs)
                            for s in bad:
                                As_l[s], Bs_l[s] = health.heal_site_lora(
                                    Hs_raw[s], dW, qspec.rank, qspec.split,
                                    policy, report, g["path"],
                                    site_paths[s])
                            As, Bs = jnp.stack(As_l), jnp.stack(Bs_l)
                else:
                    As = jnp.stack([A0] * len(site_paths))
                    Bs = jnp.stack([B0] * len(site_paths))
                _set_site_lora(new_params, g["rest"], As, Bs, cfg.dtype)
        keep = {k: v for k, v in g["keep"].items()}     # bias etc.
        keep.update(_cast_for_model(newlin, cfg.dtype))
        set_path(new_params, g["path"], keep)


_ENGINES = {"batched": _quantize_model_batched,
            "sequential": _quantize_model_sequential}


def _check_scan_uniform(sites: dict[str, SiteSpec], cfg: ModelConfig) -> None:
    """Scan-stacked containers re-stack per-layer leaves after
    quantization, which requires every layer of a container to share one
    leaf structure — i.e. a recipe that is layer-uniform within each
    stacked container.  Depth-dependent plans (skip block 0, 2-bit the
    deep half, …) need ``scan_layers=False``.  Fail at plan time with the
    offending container instead of deep inside ``to_scan_params``."""
    if not cfg.scan_layers:
        return
    groups: dict[tuple[str, str], set[SiteSpec]] = {}
    for p, s in sites.items():
        segs = p.split(".")
        if segs[0] in _STACK_KEYS and len(segs) > 1 and segs[1].isdigit():
            groups.setdefault((segs[0], ".".join(segs[2:])), set()).add(s)
    for (container, rest), specs in sorted(groups.items()):
        if len(specs) > 1:
            raise ValueError(
                f"recipe resolves layers of the scan-stacked container "
                f"{container!r} to {len(specs)} different specs at "
                f"{container}.<i>.{rest}; scan stacking needs layer-uniform "
                "rules — use a config with scan_layers=False for "
                "depth-dependent plans")


def _coerce_recipe(recipe: QuantRecipe | None, method: str | None,
                   qspec: QSpec | None, cfg: ModelConfig,
                   caller: str) -> QuantRecipe:
    """Back-compat shim: the legacy global ``(method, qspec)`` pair becomes
    a zero-rule recipe (every site resolves to the defaults).  Explicitly
    passing the legacy kwargs warns; mixing them with ``recipe=`` is an
    error."""
    if recipe is not None:
        if method is not None or qspec is not None:
            raise ValueError(f"{caller}: pass either recipe= or the legacy "
                             "(method=, qspec=) pair, not both")
        return recipe
    if method is not None or qspec is not None:
        warnings.warn(
            f"{caller}(method=, qspec=) is deprecated: the global pair is "
            "the zero-rule recipe QuantRecipe(method=..., qspec=...); pass "
            "recipe= for per-site mixed-precision plans",
            DeprecationWarning, stacklevel=3)
    return QuantRecipe.single(method or "cloq",
                              qspec or cfg.quant or QSpec())


def quantize_model(params: dict, cfg: ModelConfig,
                   calib_batches: "list[dict] | GramStore",
                   *, recipe: QuantRecipe | None = None,
                   method: str | None = None, qspec: QSpec | None = None,
                   seed: int = 0, engine: str = "batched",
                   progress: Callable[[str], None] | None = None,
                   mesh=None, shard_axis: str = "model",
                   policy: "health.HealthPolicy | None" = None,
                   report: "health.HealthReport | None" = None,
                   journal_dir: str | None = None,
                   should_stop: Callable[[], bool] | None = None,
                   cost_model=None, compile_cache=None):
    """Quantize all block linears of ``params``.

    ``recipe`` (the primary input — :class:`repro.core.recipe.QuantRecipe`)
    declares per-site mixed-precision plans: ordered glob/regex rules over
    eager param paths resolving to per-site ``(method, qspec)`` overrides
    or ``skip``, first match wins.  All sites are resolved once, up front;
    each distinct resolved spec becomes its own bucket in the batched
    engine, so one call can mix methods, bit-widths, and ranks.  The
    legacy ``method=``/``qspec=`` pair still works as a zero-rule recipe
    (deprecation shim).

    ``engine`` selects the batched bucket engine (default) or the
    sequential per-layer fallback; both produce the same leaves (see module
    docstring).

    ``mesh`` (batched engine only) runs each bucket column-sharded over
    ``shard_axis``: one fused shard_map(vmap) program per bucket instead of
    per-layer sharded dispatches, with buckets whose column count doesn't
    divide the axis falling back to replicated execution
    (:mod:`repro.core.batched`).  Leaves of sharded buckets come back as
    committed sharded arrays; ``lora_a`` stays replicated.

    ``policy`` — the numerical health guards
    (:class:`repro.core.health.HealthPolicy`), **on by default**: every
    quantized slice is checked (finiteness + proxy-error blowup vs an RTN
    baseline) and failing slices walk the degradation ladder instead of
    landing as NaN leaves.  Pass ``HealthPolicy(enabled=False)`` to opt
    out.  ``report`` collects the per-site ladder records and run events
    (one is created internally when omitted; pass your own to inspect it).

    ``journal_dir`` (batched engine only) makes the run resumable: every
    completed bucket is committed synchronously to a
    :class:`repro.checkpoint.manager.QuantJournal` under that directory,
    and a restarted call with the same plan skips committed buckets,
    returning their leaves bit-identical.  The health report is saved to
    ``<journal_dir>/health.json``.  ``should_stop`` is polled at every
    bucket boundary (after the commit); returning True raises
    :class:`repro.core.health.QuantPreempted` — the clean SIGTERM path of
    ``launch/train.py``.

    ``cost_model`` (batched engine only) — a
    :class:`repro.core.costmodel.CostModel` (or calibration/path its
    ``coerce`` accepts): each bucket's execution path (replicated /
    sharded / sequential) is chosen from calibrated predicted time instead
    of the divisibility gate.  ``compile_cache`` (batched engine only) — a
    :class:`repro.core.compile_cache.CompileCache` or directory path:
    bucket executables persist to disk keyed on the plan fingerprint, so
    repeat process starts deserialize instead of retracing.

    Returns (new_params in the input (scan/eager) layout, new_cfg with
    ``quant=`` set to the recipe's default qspec, gram_store).  Skipped
    sites keep their dense ``w`` leaf — as do sites the health ladder
    degraded to dense; ``linear_apply`` dequantizes each quantized site
    from its own stored shapes, so mixed bit-widths need no per-site
    config at apply time.

    ``calib_batches`` may instead be the populated
    :class:`~repro.utils.GramStore` a previous call returned: two engines
    then quantize against the same Grams without calibrating twice."""
    if engine not in _ENGINES:
        raise ValueError(f"unknown engine {engine!r}; options "
                         f"{tuple(_ENGINES)}")
    if mesh is not None and engine != "batched":
        # fail before the (expensive) calibration pass, not after
        raise ValueError("mesh sharding is only supported by the batched "
                         "engine; use engine='batched' or drop mesh=")
    if journal_dir is not None and engine != "batched":
        raise ValueError("journaled (resumable) quantization requires the "
                         "batched engine's bucket streaming; use "
                         "engine='batched' or drop journal_dir=")
    if (cost_model is not None or compile_cache is not None) \
            and engine != "batched":
        raise ValueError("cost_model=/compile_cache= drive the batched "
                         "engine's bucket planner/executables; use "
                         "engine='batched' or drop them")
    policy = health.HealthPolicy() if policy is None else policy
    report = health.HealthReport() if report is None else report
    journal = None
    if journal_dir is not None:
        from repro.checkpoint.manager import QuantJournal
        journal = QuantJournal(journal_dir)
    recipe = _coerce_recipe(recipe, method, qspec, cfg, "quantize_model")
    eparams = to_eager_params(params, cfg)
    sites = recipe.resolve(quantizable_linear_paths(eparams))
    _check_scan_uniform(sites, cfg)
    if isinstance(calib_batches, GramStore):
        store = calib_batches
    else:
        with obs_trace.span("quant.calibrate", batches=len(calib_batches)):
            # grams land host-side (device_get in GramStore.add): no fence
            store = run_calibration(eparams, cfg, calib_batches,
                                    report=report)
    new_params = jax.tree.map(lambda a: a, eparams)   # structural copy
    extra = ({"cost_model": cost_model, "compile_cache": compile_cache}
             if engine == "batched" else {})
    with obs_trace.span("quant.model", engine=engine,
                        sites=len(sites)) as sp:
        _ENGINES[engine](eparams, store, sites, seed, cfg, new_params,
                         progress, mesh, shard_axis, policy=policy,
                         report=report, journal=journal,
                         should_stop=should_stop, **extra)
        sp.sync(new_params)
    if journal_dir is not None:
        report.save(os.path.join(journal_dir, "health.json"))
    new_cfg = dataclasses.replace(cfg, quant=recipe.qspec)
    if cfg.scan_layers:
        new_params = to_scan_params(new_params, cfg)
    return new_params, new_cfg, store


# ---------------------------------------------------------------------------
# Calibrated bit allocation: derive the QuantRecipe instead of writing it
# (repro.core.allocate — sensitivity sweep + budget solver).
# ---------------------------------------------------------------------------


def _allocation_meta(eparams: dict, store: GramStore
                     ) -> dict[str, tuple[int, int, int, int]]:
    """Per-site geometry for the allocator's byte accounting:
    ``{path: (m, n, experts, lora_sites)}``.  Stacked MoE weights multiply
    everything by E; weight-shared linears store one base plus one adapter
    pair per recorded call site."""
    meta: dict[str, tuple[int, int, int, int]] = {}
    for lin_path in quantizable_linear_paths(eparams):
        W = get_path(eparams, lin_path)["w"]
        if W.ndim == 3:
            E, m, n = W.shape
            meta[lin_path] = (m, n, E, 1)
        elif lin_path.startswith("shared.block."):
            m, n = W.shape
            _, site_paths, _ = _shared_site_grams(store, lin_path)
            meta[lin_path] = (m, n, 1, len(site_paths))
        else:
            m, n = W.shape
            meta[lin_path] = (m, n, 1, 1)
    return meta


def allocate_plan(params: dict, cfg: ModelConfig, calib, budget_bytes: int,
                  *, grid=None, qspec: QSpec | None = None,
                  include_skip: bool = False, seed: int = 0,
                  mesh=None, shard_axis: str = "model",
                  progress: Callable[[str], None] | None = None):
    """Solve for a mixed-precision plan under a byte budget.

    Stage 1 sweeps every quantization site over the candidate ``grid``
    (``(method, bits, rank)`` tuples; :func:`repro.core.allocate.
    default_grid` when ``None``), computing each candidate's
    calibration-weighted proxy error ``tr(E^T H E)`` through the batched
    engine — one fused ``jit(vmap)`` bucket per ``(shape x candidate)``
    slab, sharded over ``mesh`` where the planner allows.  Stage 2 picks
    one candidate per site (scan-uniform group) minimizing total proxy
    error subject to exact serialized bytes <= ``budget_bytes``.

    Args:
        calib: calibration batches, or an already-populated
            :class:`~repro.utils.GramStore` (e.g. from a previous
            :func:`run_calibration`) to reuse without re-running the model.
        qspec: base :class:`QSpec` the candidates inherit
            ``group_size``/``split`` from (default ``cfg.quant``).
        include_skip: add the leave-dense candidate per site.

    Returns a :class:`repro.core.allocate.Allocation`; its ``.recipe`` is
    ready for ``quantize_model(recipe=...)``."""
    from repro.core import allocate
    base = qspec or cfg.quant or QSpec()
    eparams = to_eager_params(params, cfg)
    store = (calib if isinstance(calib, GramStore)
             else run_calibration(eparams, cfg, calib))
    # every site participates in the sweep: resolve a zero-rule recipe
    # (per-candidate specs are substituted task-by-task in the sweep)
    sites = QuantRecipe.single(base.method or "cloq", base).resolve(
        quantizable_linear_paths(eparams))
    tasks, _ = _gather_tasks(eparams, store, sites, seed)
    scan_containers = tuple(_STACK_KEYS) if cfg.scan_layers else ()
    return allocate.build_allocation(
        tasks, _allocation_meta(eparams, store), budget_bytes, base, grid,
        cfg.dtype, scan_containers=scan_containers,
        include_skip=include_skip, mesh=mesh, axis=shard_axis,
        progress=progress)


def allocate_recipe(params: dict, cfg: ModelConfig, calib,
                    budget_bytes: int, *, grid=None,
                    qspec: QSpec | None = None,
                    include_skip: bool = False, seed: int = 0,
                    mesh=None, shard_axis: str = "model",
                    progress: Callable[[str], None] | None = None
                    ) -> QuantRecipe:
    """:func:`allocate_plan` returning just the emitted
    :class:`QuantRecipe` — the budget-optimal mixed-precision plan, ready
    for ``quantize_model(recipe=...)`` or ``--recipe plan.json``."""
    return allocate_plan(params, cfg, calib, budget_bytes, grid=grid,
                         qspec=qspec, include_skip=include_skip, seed=seed,
                         mesh=mesh, shard_axis=shard_axis,
                         progress=progress).recipe


# ---------------------------------------------------------------------------
# Abstract quantized parameter shapes + bucket manifest (dry-run: no
# allocation, no compute, no calibration).
# ---------------------------------------------------------------------------


def _abstract_eager_shapes(cfg: ModelConfig):
    """ShapeDtypeStruct tree of the dense eager params (no allocation)."""
    from repro.models.transformer import init_params
    eager_cfg = dataclasses.replace(cfg, scan_layers=False)
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0),
                                                eager_cfg))
    return jax.tree.map(lambda s: s, shapes)


def _abstract_tasks(eshapes: dict,
                    sites: dict[str, SiteSpec]) -> list[LayerTask]:
    """Flatten quantization sites of an abstract shape tree into
    ShapeDtypeStruct-backed :class:`LayerTask`s carrying their resolved
    SiteSpecs — same site discovery and ordering as :func:`_gather_tasks`
    (skipped sites produce no task), so planning them reproduces the real
    engine's buckets exactly (the planner only reads ``W.shape``,
    ``H is not None``, and the site spec)."""
    SDS = jax.ShapeDtypeStruct
    tasks: list[LayerTask] = []
    for lin_path in quantizable_linear_paths(eshapes):
        site = sites[lin_path]
        if site.skip:
            continue
        W = get_path(eshapes, lin_path)["w"]
        has_gram = site.method in GRAM_METHODS
        if W.ndim == 3:
            E, m, n = W.shape
            for e in range(E):
                tasks.append(LayerTask(
                    lin_path, e, SDS((m, n), jnp.float32),
                    SDS((m, m), jnp.float32) if has_gram else None, None,
                    site=site))
        else:
            m, n = W.shape
            tasks.append(LayerTask(
                lin_path, None, SDS((m, n), jnp.float32),
                SDS((m, m), jnp.float32) if has_gram else None, None,
                site=site))
    return tasks


def quantization_manifest(cfg: ModelConfig, method: str | None = None,
                          qspec: QSpec | None = None, *,
                          recipe: QuantRecipe | None = None, mesh=None,
                          shard_axis: str = "model", cost_model=None,
                          _eshapes: dict | None = None) -> dict:
    """Bucket manifest of a ``quantize_model`` run, built from abstract
    shapes alone — no calibration, no weights, no device compute.

    Runs the very same planner (:func:`repro.core.batched.plan_buckets`)
    over ShapeDtypeStruct tasks, so the returned manifest (bucket specs
    with shard counts, task -> bucket assignment, param-tree paths) is
    exactly the plan the batched engine executes for this
    ``(cfg, recipe, mesh)``.  The manifest also records:

    * ``recipe`` — the serialized :class:`QuantRecipe`, so a production
      checkpoint carries the full mixed-precision plan it was built from;
    * ``site_lora`` — one entry per weight-shared linear (``shared.block``
      sites), so ``checkpoint.manager.manifest_shardings`` can lay out the
      per-site adapter stacks (``shared.site_lora.*``) on a new mesh
      without re-running ``launch.shardings.param_specs``.

    The legacy positional ``(method, qspec)`` pair is accepted as a
    zero-rule recipe.  Hand the result to
    ``checkpoint.manager.save_tree(..., manifest=...)`` so later restores
    can rebuild per-bucket shardings without re-running the planner
    (``checkpoint.manager.manifest_shardings``)."""
    if recipe is None:
        recipe = QuantRecipe.single(method or "cloq",
                                    qspec or cfg.quant or QSpec())
    elif method is not None or qspec is not None:
        raise ValueError("quantization_manifest: pass either recipe= or "
                         "the legacy (method, qspec) pair, not both")
    eshapes = _abstract_eager_shapes(cfg) if _eshapes is None else _eshapes
    sites = recipe.resolve(quantizable_linear_paths(eshapes))
    _check_scan_uniform(sites, cfg)
    tasks = _abstract_tasks(eshapes, sites)
    from repro.core.costmodel import CostModel
    buckets = plan_buckets(tasks, mesh=mesh, axis=shard_axis,
                           cost_model=CostModel.coerce(cost_model))
    manifest = plan_manifest(tasks, buckets, axis=shard_axis)
    manifest["recipe"] = recipe.to_dict()
    manifest["site_lora"] = [
        {"name": p[len("shared.block."):].replace(".", "_"),
         "n": int(get_path(eshapes, p)["w"].shape[-1]),
         "method": s.method}
        for p, s in sites.items()
        if p.startswith("shared.block.") and not s.skip]
    if cfg.scan_layers:
        # the saved param layout stacks these containers over layers: record
        # them so manifest_shardings can alias each eager task path to its
        # scan-stacked form (one extra unsharded leading dim)
        manifest["stacked"] = [k for k in _STACK_KEYS if k in eshapes]
    return manifest


def recipe_plan_bytes(cfg: ModelConfig, recipe: QuantRecipe) -> int:
    """Exact serialized bytes of all quantization sites under ``recipe``,
    evaluated from abstract shapes alone (no weights, no calibration) —
    the allocator's byte accounting (:func:`repro.core.allocate.
    site_bytes`) applied to a whole plan.  Skipped sites count their dense
    weight.  Used by the dry-run ``--budget-mb`` validation and asserted
    equal to the :func:`quantized_param_shapes` layout in tests."""
    from repro.core.allocate import site_bytes
    eshapes = _abstract_eager_shapes(cfg)
    sites = recipe.resolve(quantizable_linear_paths(eshapes))
    total = 0
    for lin_path, site in sites.items():
        W = get_path(eshapes, lin_path)["w"]
        experts, (m, n) = (1, W.shape) if W.ndim == 2 else \
            (W.shape[0], W.shape[1:])
        lora_sites = 1
        if lin_path.startswith("shared.block."):
            sl = eshapes.get("shared", {}).get("site_lora", {})
            name = lin_path[len("shared.block."):].replace(".", "_")
            lora_sites = (sl[name]["lora_a"].shape[0]
                          if name in sl else 0)
        total += site_bytes(m, n, site, cfg.dtype, experts, lora_sites)
    return total


def _quant_leaf_shapes(m: int, n: int, qspec: QSpec, dtype,
                       lead: tuple = (), method: str = "cloq") -> dict:
    SDS = jax.ShapeDtypeStruct
    g = m if qspec.group_size is None else qspec.group_size
    bits = 4 if method == "qlora" else qspec.bits       # NF4 is always 4-bit
    mp = m * bits // 8 if bits in (2, 4) else m
    out = {
        "qcodes": SDS(lead + (mp, n), jnp.uint8),
        "lora_a": SDS(lead + (m, qspec.rank), dtype),
        "lora_b": SDS(lead + (n, qspec.rank), dtype),
    }
    if method == "qlora":
        out["absmax"] = SDS(lead + (m // g, n), jnp.float32)
    else:
        out["scales"] = SDS(lead + (m // g, n), jnp.float32)
        out["zeros"] = SDS(lead + (m // g, n), jnp.float32)
    return out


def quantized_param_shapes(cfg: ModelConfig, *, method: str | None = None,
                           recipe: QuantRecipe | None = None,
                           mesh=None, shard_axis: str = "model",
                           with_manifest: bool = False):
    """ShapeDtypeStruct tree of the post-quantization param layout, built
    without running calibration or allocating anything.

    ``recipe`` resolves per-site specs exactly like ``quantize_model``:
    each site's leaf shapes follow its own resolved ``(bits, group_size,
    rank)``, skipped sites keep their dense ``w``, and the weight-shared
    block's ``shared.site_lora`` stacks take the resolved rank.  Without a
    recipe, the global ``cfg.quant`` (+ ``method``) pair is used as a
    zero-rule recipe.

    With ``with_manifest=True``, also returns the bucket manifest of the
    plan the batched engine would execute for ``(cfg, recipe, mesh)`` —
    ``(shapes, manifest)`` — i.e. :func:`quantization_manifest` evaluated
    on the same abstract shapes, ready to be saved next to a checkpoint of
    this layout."""
    if recipe is None:
        assert cfg.quant is not None, "cfg.quant must be set"
        recipe = QuantRecipe.single(method or "cloq", cfg.quant)
    shapes = _abstract_eager_shapes(cfg)
    sites = recipe.resolve(quantizable_linear_paths(shapes))
    _check_scan_uniform(sites, cfg)
    manifest = (quantization_manifest(cfg, recipe=recipe, mesh=mesh,
                                      shard_axis=shard_axis,
                                      _eshapes=shapes)
                if with_manifest else None)
    for lin_path, site in sites.items():
        if site.skip:
            continue                         # dense w stays in place
        qspec = site.qspec
        lin = dict(get_path(shapes, lin_path))
        W = lin.pop("w")
        if W.ndim == 3:
            E, m, n = W.shape
            newlin = _quant_leaf_shapes(m, n, qspec, cfg.dtype, (E,),
                                        site.method)
        else:
            m, n = W.shape
            newlin = _quant_leaf_shapes(m, n, qspec, cfg.dtype,
                                        method=site.method)
        if lin_path.startswith("shared.block."):
            newlin.pop("lora_a")
            newlin.pop("lora_b")
            # the per-site adapter stacks take the resolved rank
            sl_name = lin_path[len("shared.block."):].replace(".", "_")
            sl = get_path(shapes, "shared.site_lora")
            if sl_name in sl:
                S = sl[sl_name]["lora_a"].shape[0]
                sl[sl_name] = {
                    "lora_a": jax.ShapeDtypeStruct((S, m, qspec.rank),
                                                   cfg.dtype),
                    "lora_b": jax.ShapeDtypeStruct((S, n, qspec.rank),
                                                   cfg.dtype)}
        lin.update(newlin)
        set_path(shapes, lin_path, lin)
    if cfg.scan_layers:
        def stack_shapes(subtree, L):
            return jax.tree.map(
                lambda s: jax.ShapeDtypeStruct((L,) + s.shape, s.dtype), subtree)
        for key, nattr in _STACK_KEYS.items():
            if key in shapes:
                per_layer = shapes[key]["0"]
                shapes[key] = stack_shapes(per_layer, getattr(cfg, nattr))
    if with_manifest:
        return shapes, manifest
    return shapes
