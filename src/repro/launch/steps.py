"""Step builders shared by train.py / serve.py / dryrun.py.

``make_train_step`` builds the pjit-able LoRA fine-tuning step (frozen
quantized base + trainable adapters, AdamW, schedule).  ``abstract_*``
variants build ShapeDtypeStruct pytrees for the dry-run (no allocation).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.pipeline import quantized_param_shapes
from repro.models.parallel import PContext
from repro.models.transformer import (ModelConfig, aux_zero, decode_step,
                                      forward, init_decode_cache, init_params,
                                      lm_logits, loss_fn)
from repro.optim import (OptConfig, adamw_init, adamw_update, make_schedule,
                         merge_params, partition_params, trainable_mask)
from repro.launch.shardings import cache_specs, param_specs

Array = jax.Array

SHAPE_CELLS = {
    "train_4k": dict(kind="train", seq=4096, batch=256),
    "prefill_32k": dict(kind="prefill", seq=32768, batch=32),
    "decode_32k": dict(kind="decode", seq=32768, batch=128),
    "long_500k": dict(kind="decode", seq=524288, batch=1),
}

# quantized/structural leaves never trained even in "all" mode
_NEVER_TRAIN = ("qcodes", "scales", "zeros", "absmax")


def cell_applicable(cfg: ModelConfig, cell: str) -> tuple[bool, str]:
    if cell == "long_500k" and cfg.family not in ("ssm", "hybrid"):
        return False, ("full-attention arch: 500k decode needs sub-quadratic "
                       "attention (skip per assignment; DESIGN.md §5)")
    return True, ""


def full_trainable_mask(params, mode: str):
    mask = trainable_mask(params, mode)
    from repro.utils import tree_paths, set_path
    out: dict = {}
    for pth, m in tree_paths(mask).items():
        if pth.rsplit(".", 1)[-1] in _NEVER_TRAIN:
            m = False
        set_path(out, pth, m)
    return out


def build_state(params, ocfg: OptConfig):
    mask = full_trainable_mask(params, ocfg.trainable)
    train_p, frozen_p = partition_params(params, mask)
    return {"train": train_p, "frozen": frozen_p, "opt": adamw_init(train_p)}


def make_train_step(cfg: ModelConfig, ocfg: OptConfig, pctx: PContext,
                    window: int | None = None):
    schedule = make_schedule(ocfg.schedule, ocfg.lr, ocfg.total_steps,
                             ocfg.warmup_frac)
    k = max(ocfg.microbatch, 1)

    def train_step(state, batch):
        def loss_of(tp, b):
            params = merge_params(tp, state["frozen"])
            return loss_fn(params, cfg, b, pctx=pctx, window=window)

        if k > 1:
            # gradient accumulation over k microbatches via lax.scan: the
            # backward of microbatch i completes before i+1 starts, so peak
            # activation memory is 1/k of the monolithic step (§Perf lever).
            # NOTE for cost accounting: the scan body holds ~all step FLOPs
            # and is counted once by cost_analysis — compare FLOPs against
            # the k=1 variant (identical math).
            mb = jax.tree.map(
                lambda a: a.reshape(k, a.shape[0] // k, *a.shape[1:]), batch)

            def body(acc, b):
                (l, (ce, aux)), g = jax.value_and_grad(
                    loss_of, has_aux=True)(state["train"], b)
                acc = jax.tree.map(jnp.add, acc,
                                   (g, {"l": l, "ce": ce, "aux": aux}))
                return acc, None

            zero_g = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state["train"])
            zeros = (zero_g, {"l": jnp.zeros(()), "ce": jnp.zeros(()),
                              "aux": aux_zero(cfg)})
            (grads, sums), _ = jax.lax.scan(body, zeros, mb)
            grads = jax.tree.map(lambda g: g / k, grads)
            # each aux term, the MoE load included, is the microbatches' mean
            loss, ce = sums["l"] / k, sums["ce"] / k
            aux = jax.tree.map(lambda a: a / k, sums["aux"])
        else:
            (loss, (ce, aux)), grads = jax.value_and_grad(
                loss_of, has_aux=True)(state["train"], batch)
        new_tp, new_opt, m = adamw_update(grads, state["opt"], state["train"],
                                          ocfg, schedule)
        metrics = {"loss": loss, "ce": ce, "aux": aux["balance"], **m}
        if "load_max" in aux:
            metrics["moe_load_max"] = aux["load_max"]
        return {"train": new_tp, "frozen": state["frozen"],
                "opt": new_opt}, metrics

    return train_step


def make_prefill_step(cfg: ModelConfig, pctx: PContext,
                      last_only: bool = False):
    """``last_only``: serving-honest prefill — only the final position's
    logits are computed (the (B, S, V) logits tensor is pure waste when
    prefill feeds a decode loop; §Perf lever)."""
    def prefill(params, batch):
        if last_only:
            hidden, _ = forward(params, cfg, batch, pctx=pctx,
                                return_hidden=True)
            return lm_logits(params, cfg, hidden[:, -1:, :])
        logits, _ = forward(params, cfg, batch, pctx=pctx)
        return logits

    return prefill


def make_decode_step(cfg: ModelConfig, pctx: PContext):
    def step(params, cache, tokens):
        return decode_step(params, cfg, cache, tokens, pctx=pctx)

    return step


# ---------------------------------------------------------------------------
# Abstract (ShapeDtypeStruct) builders for the dry-run.
# ---------------------------------------------------------------------------


def abstract_params(cfg: ModelConfig, recipe=None):
    """Abstract (ShapeDtypeStruct) param tree: dense when ``cfg.quant`` is
    unset, else the quantized layout — per-site when a
    :class:`repro.core.recipe.QuantRecipe` is given (mixed bit-widths,
    ranks, skipped-dense sites)."""
    if recipe is not None:
        return quantized_param_shapes(cfg, recipe=recipe)
    if cfg.quant is not None:
        return quantized_param_shapes(cfg)
    return jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))


def abstract_state(cfg: ModelConfig, ocfg: OptConfig, recipe=None):
    pshapes = abstract_params(cfg, recipe)
    return jax.eval_shape(lambda ps: build_state(ps, ocfg), pshapes)


def batch_specs(cfg: ModelConfig, cell: str):
    """ShapeDtypeStructs for one input batch of the given shape cell."""
    SDS = jax.ShapeDtypeStruct
    c = SHAPE_CELLS[cell]
    B, S = c["batch"], c["seq"]
    if c["kind"] == "decode":
        return {"tokens": SDS((B, 1), jnp.int32)}
    if cfg.family == "encdec":
        batch = {"tokens": SDS((B, S), jnp.int32),
                 "enc_embeds": SDS((B, S // 4, cfg.d_model), jnp.float32)}
    elif cfg.frontend == "vision":
        text = S - cfg.n_prefix
        batch = {"tokens": SDS((B, text), jnp.int32),
                 "prefix_embeds": SDS((B, cfg.n_prefix, cfg.d_model),
                                      jnp.float32)}
    else:
        batch = {"tokens": SDS((B, S), jnp.int32)}
    if c["kind"] == "train":
        batch["labels"] = SDS(batch["tokens"].shape, jnp.int32)
    return batch


def abstract_cache(cfg: ModelConfig, cell: str, kv_dtype=None):
    c = SHAPE_CELLS[cell]
    return jax.eval_shape(
        lambda: init_decode_cache(cfg, c["batch"], c["seq"], dtype=kv_dtype))


def batch_pspecs(cfg: ModelConfig, cell: str, data_axes) -> dict:
    dp = data_axes
    c = SHAPE_CELLS[cell]
    specs = {}
    for name in batch_specs(cfg, cell):
        nd = {"tokens": 2, "labels": 2, "enc_embeds": 3, "prefix_embeds": 3}[name]
        bspec = dp if c["batch"] > 1 else None
        specs[name] = P(*([bspec] + [None] * (nd - 1)))
    return specs


def state_pspecs(state_shapes, mesh=None) -> dict:
    return {"train": param_specs(state_shapes["train"], mesh),
            "frozen": param_specs(state_shapes["frozen"], mesh),
            "opt": {"mu": param_specs(state_shapes["opt"]["mu"], mesh),
                    "nu": param_specs(state_shapes["opt"]["nu"], mesh),
                    "step": P()}}


def named(tree, mesh):
    return jax.tree.map(
        lambda sp: NamedSharding(mesh, sp), tree,
        is_leaf=lambda x: isinstance(x, P))
