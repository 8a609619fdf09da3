"""Mixture-of-Experts block with expert parallelism.

EP scheme (TPU-native, DESIGN.md §4): expert weights are sharded over the
``model`` mesh axis.  Inside ``shard_map`` each (data, model) cell routes its
*local* tokens to the experts it *locally owns* (sort-based dispatch into a
static (E_local, C, D) capacity buffer) and the per-shard partial outputs are
combined with one ``psum`` over the model axis — communication identical to
a standard TP all-reduce, no all-to-all required.  Tokens beyond per-expert
capacity are dropped (standard capacity-factor semantics).

Without a mesh (unit tests / CPU), the same code runs with E_local = E and
no collectives.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.core.quantizer import dequantize_int, unpack_codes
from repro.models.modules import QSpec
from repro.utils import current_scope, record_activation, scope

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_model: int
    d_ff: int                      # per-expert hidden
    capacity_factor: float = 1.25
    norm_topk: bool = True         # renormalize selected probs (qwen3 style)
    router_aux_weight: float = 0.01


def moe_init(key, cfg: MoEConfig, *, dtype=jnp.bfloat16,
             lora_rank: int = 0) -> dict:
    ks = jax.random.split(key, 4)
    E, D, F = cfg.n_experts, cfg.d_model, cfg.d_ff

    def stack(k, m, n):
        w = jax.random.normal(k, (E, m, n), jnp.float32) / jnp.sqrt(m)
        return w.astype(dtype)

    p = {
        "router": {"w": (jax.random.normal(ks[0], (D, E), jnp.float32)
                         * 0.02).astype(jnp.float32)},
        "gate": {"w": stack(ks[1], D, F)},
        "up": {"w": stack(ks[2], D, F)},
        "down": {"w": stack(ks[3], F, D)},
    }
    if lora_rank:
        ka, kb = jax.random.split(ks[0])
        for name, m, n in (("gate", D, F), ("up", D, F), ("down", F, D)):
            p[name]["lora_a"] = (jax.random.normal(ka, (E, m, lora_rank),
                                 jnp.float32) / jnp.sqrt(m)).astype(dtype)
            p[name]["lora_b"] = jnp.zeros((E, n, lora_rank), dtype)
    return p


def _expert_matmul(pd: dict, buf: Array, qspec: QSpec | None) -> Array:
    """buf (E, C, m) @ per-expert weights (E, m, n) -> (E, C, n)."""
    if "qcodes" in pd:
        assert qspec is not None
        m = buf.shape[-1]
        if "absmax" in pd:                     # NF4 (QLoRA baseline)
            from repro.core.quantizer import dequantize_nf4
            group = m // pd["absmax"].shape[-2]
            with scope("dequant"):
                codes = jax.vmap(lambda c: unpack_codes(c, 4, m))(
                    pd["qcodes"])
                w = jax.vmap(lambda c, a: dequantize_nf4(
                    c, a, group, dtype=buf.dtype))(codes, pd["absmax"])
        else:
            # bits/group derived from the stored shapes (per-site recipes
            # may quantize expert stacks differently; see modules.packed_bits)
            from repro.models.modules import packed_bits
            bits = packed_bits(pd["qcodes"].shape[-2], m)
            group = m // pd["scales"].shape[-2]
            with scope("dequant"):
                codes = jax.vmap(lambda c: unpack_codes(c, bits, m))(
                    pd["qcodes"])
                w = jax.vmap(lambda c, s, z: dequantize_int(
                    c, s, z, group, dtype=buf.dtype))(
                        codes, pd["scales"], pd["zeros"])
    else:
        w = pd["w"].astype(buf.dtype)
    y = jnp.einsum("ecm,emn->ecn", buf, w)
    if "lora_a" in pd:
        with scope("lora"):
            a = pd["lora_a"].astype(buf.dtype)
            b = pd["lora_b"].astype(buf.dtype)
            y = y + jnp.einsum("ecr,enr->ecn",
                               jnp.einsum("ecm,emr->ecr", buf, a), b)
    return y


def _route(router_w: Array, xt: Array, cfg: MoEConfig):
    """Returns (topw (T,k) f32, topi (T,k) i32, aux_loss scalar)."""
    logits = (xt.astype(jnp.float32) @ router_w).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                 # (T, E)
    topw, topi = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk:
        topw = topw / (jnp.sum(topw, axis=-1, keepdims=True) + 1e-9)
    # Switch-style load-balance aux loss: E * sum_e f_e * P_e
    E = cfg.n_experts
    f = jnp.mean(jax.nn.one_hot(topi[:, 0], E, dtype=jnp.float32), axis=0)
    P = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(f * P)
    return topw, topi, aux


def _dispatch_compute_combine(p: dict, cfg: MoEConfig, xt: Array,
                              topw: Array, topi: Array, capacity: int,
                              e_start: Array | int, e_local: int,
                              qspec: QSpec | None) -> Array:
    """Route local tokens to locally-owned experts [e_start, e_start+e_local).

    Static-shape sort-based dispatch into an (E_local, C, D) buffer."""
    T, D = xt.shape
    k = cfg.top_k
    flat_e = topi.reshape(-1)                                # (T*k,) global ids
    flat_w = topw.reshape(-1)
    local_e = flat_e - e_start                               # local expert ids
    mine = (local_e >= 0) & (local_e < e_local)
    local_e = jnp.where(mine, local_e, e_local)              # overflow bucket
    # position within expert, by stable sort over local expert id
    sort_idx = jnp.argsort(local_e, stable=True)             # (T*k,)
    sorted_e = local_e[sort_idx]
    counts = jnp.bincount(local_e, length=e_local + 1)
    starts = jnp.cumsum(counts) - counts
    pos_in_e = jnp.arange(T * k) - starts[sorted_e]
    keep = (pos_in_e < capacity) & (sorted_e < e_local)
    dest = jnp.where(keep, sorted_e * capacity + pos_in_e, e_local * capacity)
    token_id = sort_idx // k
    buf = jnp.zeros((e_local * capacity + 1, D), xt.dtype)
    buf = buf.at[dest].set(xt[token_id])   # overflow row (last) is discarded
    buf = buf[:-1].reshape(e_local, capacity, D)

    with scope("gate"):
        record_activation(current_scope(), buf, keep_leading=True)
        g = _expert_matmul(p["gate"], buf, qspec)
    with scope("up"):
        record_activation(current_scope(), buf, keep_leading=True)
        u = _expert_matmul(p["up"], buf, qspec)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(buf.dtype) * u
    with scope("down"):
        record_activation(current_scope(), h, keep_leading=True)
        yb = _expert_matmul(p["down"], h, qspec)             # (E_l, C, D)

    y_flat = jnp.concatenate(
        [yb.reshape(e_local * capacity, D), jnp.zeros((1, D), yb.dtype)], 0)
    contrib = y_flat[dest] * (flat_w[sort_idx] * keep)[:, None].astype(yb.dtype)
    out = jnp.zeros((T, D), yb.dtype).at[token_id].add(contrib)
    return out


def moe_capacity(cfg: MoEConfig, tokens_local: int) -> int:
    c = int(tokens_local * cfg.top_k * cfg.capacity_factor / cfg.n_experts)
    return max(c, 4)


def moe_apply(p: dict, cfg: MoEConfig, x: Array, *,
              qspec: QSpec | None = None, pctx=None) -> tuple[Array, Array]:
    """Returns (y (B,S,D), aux_loss scalar)."""
    B, S, D = x.shape
    xt = x.reshape(B * S, D)

    if pctx is None or pctx.mesh is None:
        topw, topi, aux = _route(p["router"]["w"], xt, cfg)
        C = moe_capacity(cfg, xt.shape[0])
        y = _dispatch_compute_combine(p, cfg, xt, topw, topi, C, 0,
                                      cfg.n_experts, qspec)
        return y.reshape(B, S, D), aux

    from jax.sharding import PartitionSpec as P

    mesh = pctx.mesh
    dp, mp = pctx.data_axes, pctx.model_axis
    n_model = 1
    for ax in ([mp] if isinstance(mp, str) else mp):
        n_model *= mesh.shape[ax]
    n_data = 1
    for ax in ([dp] if isinstance(dp, str) else dp):
        n_data *= mesh.shape[ax]
    e_local = cfg.n_experts // n_model
    C = moe_capacity(cfg, (B * S) // n_data)

    def expert_spec(leaf_ndim):
        return P(mp, *([None] * (leaf_ndim - 1)))

    ew_specs = jax.tree.map(lambda a: expert_spec(a.ndim),
                            {k: p[k] for k in ("gate", "up", "down")})

    def local_fn(router_w, ew, xt_l):
        topw, topi, aux = _route(router_w, xt_l, cfg)
        ax_idx = jax.lax.axis_index(mp)
        y = _dispatch_compute_combine(ew, cfg, xt_l, topw, topi, C,
                                      ax_idx * e_local, e_local, qspec)
        y = jax.lax.psum(y, mp)
        aux = jax.lax.pmean(aux, dp)
        return y, aux

    fn = jax.shard_map(local_fn, mesh=mesh,
                       in_specs=(P(None, None), ew_specs, P(dp, None)),
                       out_specs=(P(dp, None), P()),
                       check_vma=False)
    y, aux = fn(p["router"]["w"], {k: p[k] for k in ("gate", "up", "down")}, xt)
    return y.reshape(B, S, D), aux
