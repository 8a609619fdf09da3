"""Mixture-of-Experts block with expert parallelism.

Two dispatches of the routed rows to the experts:

* capacity (a ``capacity_factor``): sort-based dispatch into a static
  ``(E_local, C, D)`` buffer; tokens beyond an expert's capacity are
  dropped (standard capacity-factor semantics, qwen3-moe's entry);
* dropless (``capacity_factor`` None, OLMoE): the ``T * k`` assignments are
  sorted by expert and each expert's rows run through grouped matmuls
  (``jax.lax.ragged_dot`` with the per-expert counts as group sizes), so
  no token is ever dropped.

EP scheme (TPU-native, DESIGN.md §4): expert weights are sharded over the
``model`` mesh axis.  Inside ``shard_map`` each (data, model) cell routes its
*local* tokens to the experts it *locally owns* (``e_start``, ``e_local``)
and the per-shard partial outputs are combined with one ``psum`` over the
model axis — communication identical to a standard TP all-reduce, no
all-to-all required.

Without a mesh (unit tests / CPU), the same code runs with E_local = E and
no collectives.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.quantizer import (byte_order, dequantize_nf4,
                                  dequantize_packed_byte_order, unpack_codes)
from repro.models.modules import QSpec, packed_bits
from repro.utils import current_scope, record_activation, scope

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_model: int
    d_ff: int                      # per-expert hidden
    # None: dropless, grouped matmuls over every routed row
    capacity_factor: float | None = 1.25
    norm_topk: bool = True         # renormalize selected probs (qwen3 style)


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


def _weight_and_input(pd: dict, x: Array, qspec: QSpec | None
                      ) -> tuple[Array, Array]:
    """The ``(E, m, n)`` weight stack of one expert site in ``x``'s dtype,
    dequantized where it is packed, and ``x (..., m)`` with its features
    in the weight's row order.  A packed INT stack dequantizes in the
    packed bytes' row order (``dequantize_packed_byte_order``): unpacking
    to row order interleaves rows, a relayout that XLA runs on f32 codes,
    scales and zeros once a product's backward asks for the weight
    transposed, 2 GiB of temporaries a site at OLMoE's size."""
    m, dtype = x.shape[-1], x.dtype
    if "qcodes" not in pd:
        return pd["w"].astype(dtype), x
    assert qspec is not None
    if "absmax" in pd:                     # NF4 (QLoRA baseline)
        group = m // pd["absmax"].shape[-2]
        with scope("dequant"):
            codes = jax.vmap(lambda c: unpack_codes(c, 4, m))(pd["qcodes"])
            return jax.vmap(lambda c, a: dequantize_nf4(
                c, a, group, dtype=dtype))(codes, pd["absmax"]), x
    # bits derived from the stored shapes (per-site recipes may quantize
    # expert stacks differently; see modules.packed_bits)
    bits = packed_bits(pd["qcodes"].shape[-2], m)
    with scope("dequant"):
        w = dequantize_packed_byte_order(pd["qcodes"], pd["scales"],
                                         pd["zeros"], bits, m, dtype)
    return w, byte_order(x, bits)


def _expert_matmul(pd: dict, buf: Array, qspec: QSpec | None) -> Array:
    """buf (E, C, m) @ per-expert weights (E, m, n) -> (E, C, n)."""
    w, xw = _weight_and_input(pd, buf, qspec)
    y = jnp.einsum("ecm,emn->ecn", xw, w)
    if "lora_a" in pd:
        with scope("lora"):
            a = pd["lora_a"].astype(buf.dtype)
            b = pd["lora_b"].astype(buf.dtype)
            y = y + jnp.einsum("ecr,enr->ecn",
                               jnp.einsum("ecm,emr->ecr", buf, a), b)
    return y


def _grouped_matmul(pd: dict, xs: Array, sizes: Array,
                    qspec: QSpec | None) -> Array:
    """xs (N, m), rows sorted by expert with ``sizes[e]`` rows for expert
    ``e``, each through its own expert's weights -> (N, n); rows past
    ``sum(sizes)`` come out zero."""
    w, xw = _weight_and_input(pd, xs, qspec)
    y = jax.lax.ragged_dot(xw, w, sizes)
    if "lora_a" in pd:
        with scope("lora"):
            a = pd["lora_a"].astype(xs.dtype)
            b = jnp.swapaxes(pd["lora_b"], 1, 2).astype(xs.dtype)
            y = y + jax.lax.ragged_dot(jax.lax.ragged_dot(xs, a, sizes), b,
                                       sizes)
    return y


def _route(router_w: Array, xt: Array, cfg: MoEConfig):
    """Returns (topw (T,k) f32, topi (T,k) i32, aux_loss scalar, counts
    (E,) i32: the rows routed to each expert)."""
    with scope("route"):
        logits = jnp.dot(xt.astype(jnp.float32),
                         router_w.astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        probs = jax.nn.softmax(logits, axis=-1)             # (T, E)
        topw, topi = jax.lax.top_k(probs, cfg.top_k)
        if cfg.norm_topk:
            topw = topw / (jnp.sum(topw, axis=-1, keepdims=True) + 1e-9)
        # Switch-style load-balance aux loss: E * sum_e f_e * P_e
        E = cfg.n_experts
        f = jnp.mean(jax.nn.one_hot(topi[:, 0], E, dtype=jnp.float32),
                     axis=0)
        P = jnp.mean(probs, axis=0)
        aux = E * jnp.sum(f * P)
        counts = jnp.bincount(topi.reshape(-1), length=E)
    return topw, topi, aux, counts


def _load_max(counts: Array) -> Array:
    """The busiest expert's routed rows over the mean."""
    return (jnp.max(counts) / jnp.mean(counts)).astype(jnp.float32)


def _dropless(p: dict, cfg: MoEConfig, xt: Array, topw: Array, topi: Array,
              e_start: Array | int, e_local: int,
              qspec: QSpec | None) -> Array:
    """Every assignment of the local tokens to a locally-owned expert
    [e_start, e_start+e_local) through grouped matmuls; assignments to
    other experts sort past the held groups and add nothing."""
    T, D = xt.shape
    k = cfg.top_k
    with scope("dispatch"):
        local_e = topi.reshape(-1) - e_start                  # (T*k,)
        mine = (local_e >= 0) & (local_e < e_local)
        local_e = jnp.where(mine, local_e, e_local)           # sorts last
        order = jnp.argsort(local_e, stable=True)
        sizes = jnp.bincount(local_e, length=e_local)         # held only
        # row i of the (T*k, D) token-major copy is token i // k
        xs = _permute(jnp.repeat(xt, k, axis=0), order)

    with scope("gate"):
        record_activation(current_scope(), xs, groups=sizes)
        g = _grouped_matmul(p["gate"], xs, sizes, qspec)
    with scope("up"):
        record_activation(current_scope(), xs, groups=sizes)
        u = _grouped_matmul(p["up"], xs, sizes, qspec)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(xs.dtype) * u
    with scope("down"):
        record_activation(current_scope(), h, groups=sizes)
        ys = _grouped_matmul(p["down"], h, sizes, qspec)      # (T*k, D)

    with scope("combine"):
        y = _permute(ys, jnp.argsort(order)).reshape(T, k, D)
        w = topw * mine.reshape(T, k)
        out = jnp.einsum("tkd,tk->td", y.astype(jnp.float32), w)
    return out.astype(xt.dtype)


def _permute(a: Array, idx: Array) -> Array:
    """``a[idx]`` for a permutation ``idx``: each row is read once, so the
    gradient is a scatter without collisions."""
    return a.at[idx].get(unique_indices=True, mode="promise_in_bounds")


def _dispatch_compute_combine(p: dict, cfg: MoEConfig, xt: Array,
                              topw: Array, topi: Array, capacity: int,
                              e_start: Array | int, e_local: int,
                              qspec: QSpec | None) -> Array:
    """Route local tokens to locally-owned experts [e_start, e_start+e_local).

    Static-shape sort-based dispatch into an (E_local, C, D) buffer."""
    T, D = xt.shape
    k = cfg.top_k
    with scope("dispatch"):
        flat_e = topi.reshape(-1)                            # (T*k,) global
        flat_w = topw.reshape(-1)
        local_e = flat_e - e_start                           # local ids
        mine = (local_e >= 0) & (local_e < e_local)
        local_e = jnp.where(mine, local_e, e_local)          # overflow bucket
        # position within expert, by stable sort over local expert id
        sort_idx = jnp.argsort(local_e, stable=True)         # (T*k,)
        sorted_e = local_e[sort_idx]
        counts = jnp.bincount(local_e, length=e_local + 1)
        starts = jnp.cumsum(counts) - counts
        pos_in_e = jnp.arange(T * k) - starts[sorted_e]
        keep = (pos_in_e < capacity) & (sorted_e < e_local)
        dest = jnp.where(keep, sorted_e * capacity + pos_in_e,
                         e_local * capacity)
        token_id = sort_idx // k
        buf = jnp.zeros((e_local * capacity + 1, D), xt.dtype)
        buf = buf.at[dest].set(xt[token_id])   # overflow row (last) dropped
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

    with scope("combine"):
        y_flat = jnp.concatenate(
            [yb.reshape(e_local * capacity, D), jnp.zeros((1, D), yb.dtype)],
            0)
        contrib = y_flat[dest] * (flat_w[sort_idx]
                                  * keep)[:, None].astype(yb.dtype)
        return jnp.zeros((T, D), yb.dtype).at[token_id].add(contrib)


def moe_capacity(cfg: MoEConfig, tokens_local: int) -> int:
    c = int(tokens_local * cfg.top_k * cfg.capacity_factor / cfg.n_experts)
    return max(c, 4)


def moe_apply(p: dict, cfg: MoEConfig, x: Array, *,
              qspec: QSpec | None = None, pctx=None) -> tuple[Array, dict]:
    """Returns (y (B,S,D), aux): ``aux["balance"]`` the load-balance
    loss term, ``aux["load_max"]`` the busiest expert's routed rows over
    the mean."""
    B, S, D = x.shape
    xt = x.reshape(B * S, D)

    def experts(ew, xt_l, topw, topi, e_start, e_local, tokens_local):
        if cfg.capacity_factor is None:
            return _dropless(ew, cfg, xt_l, topw, topi, e_start, e_local,
                             qspec)
        return _dispatch_compute_combine(
            ew, cfg, xt_l, topw, topi, moe_capacity(cfg, tokens_local),
            e_start, e_local, qspec)

    if pctx is None or pctx.mesh is None:
        topw, topi, aux, counts = _route(p["router"]["w"], xt, cfg)
        y = experts(p, xt, topw, topi, 0, cfg.n_experts, xt.shape[0])
        return y.reshape(B, S, D), {"balance": aux,
                                    "load_max": _load_max(counts)}

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

    def expert_spec(leaf_ndim):
        return P(mp, *([None] * (leaf_ndim - 1)))

    ew_specs = jax.tree.map(lambda a: expert_spec(a.ndim),
                            {k: p[k] for k in ("gate", "up", "down")})

    def local_fn(router_w, ew, xt_l):
        topw, topi, aux, counts = _route(router_w, xt_l, cfg)
        ax_idx = jax.lax.axis_index(mp)
        y = experts(ew, xt_l, topw, topi, ax_idx * e_local, e_local,
                    (B * S) // n_data)
        y = jax.lax.psum(y, mp)
        aux = jax.lax.pmean(aux, dp)
        return y, aux, _load_max(jax.lax.psum(counts, dp))

    fn = jax.shard_map(local_fn, mesh=mesh,
                       in_specs=(P(None, None), ew_specs, P(dp, None)),
                       out_specs=(P(dp, None), P(), P()),
                       check_vma=False)
    y, aux, load = fn(p["router"]["w"],
                      {k: p[k] for k in ("gate", "up", "down")}, xt)
    return y.reshape(B, S, D), {"balance": aux, "load_max": load}
