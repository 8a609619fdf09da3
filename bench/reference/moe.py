"""Plain float32 reference of an OLMoE-style decoder (arXiv:2409.02060)
with LoRA on a dequantized INT-b base.

It follows the published description: RMSNorm before attention and before
the experts; one RMSNorm over the whole query projection and one over the
whole key projection, before they are split into heads; rotary embedding
with the rotate-half convention; multi-head attention; a router whose
softmax over all experts in float32 gives each token's top-k experts,
weighted by those probabilities as they are (no renormalization); a
SwiGLU expert each; RMSNorm before an untied head.  Every RMSNorm uses the
published ``rms_norm_eps``, 1e-5.  It imports nothing of the program:
every weight is drawn again from the seed by ``bench.weights``, one layer
at a time.  Each linear computes ``x W + (x A) B^T`` with ``W`` the
dequantized base, each expert with its own adapters.

Departures from the published model, none of which changes the result:
every expert runs on every row, and the experts a token did not choose
are weighted by zero; the norms' scales are ones (the benchmark draws
them so); the embedding, head and router come from the seed.

A ``Precision`` object does every matrix product, the router's included:
:data:`EXACT` runs them in float32 at ``highest``, :data:`FP8` in
float8_e4m3 with one scale per tensor.  It provides what
``bench.jobs.common.reference`` lists.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights
from bench.reference.dense import (EXACT, FP8, Precision, embedding,  # noqa: F401
                                   head, linear, rope)

# OLMoE's published rms_norm_eps, which every RMSNorm here uses
EPS = 1e-5
ATTN = ("attn.q", "attn.k", "attn.v", "attn.o")
EXPERTS = ("moe.gate", "moe.up", "moe.down")


@dataclasses.dataclass(frozen=True)
class Dims:
    n_layers: int
    d_model: int
    n_heads: int
    head_dim: int
    n_experts: int
    top_k: int
    d_ff_expert: int
    vocab: int
    vocab_padded: int
    rope_theta: float
    tie_embeddings: bool
    group_size: int
    bits: int

    @classmethod
    def of(cls, c: dict) -> "Dims":
        mult = c.get("vocab_pad_multiple", 1)
        assert c["n_kv_heads"] == c["n_heads"], "OLMoE's attention is MHA"
        assert c["qk_norm"] and c["qk_norm_span"] == "proj"
        assert not c["norm_topk"] and c["capacity_factor"] is None
        assert c["rms_norm_eps"] == EPS
        return cls(c["n_layers"], c["d_model"], c["n_heads"], c["head_dim"],
                   c["n_experts"], c["top_k"], c["d_ff_expert"], c["vocab"],
                   -(-c["vocab"] // mult) * mult, float(c["rope_theta"]),
                   bool(c["tie_embeddings"]),
                   c["quant"]["group_size"], c["quant"]["bits"])

    def site_shape(self, site: str) -> tuple[int, int]:
        d, hq, f = self.d_model, self.n_heads * self.head_dim, self.d_ff_expert
        return {"attn.q": (d, hq), "attn.k": (d, hq), "attn.v": (d, hq),
                "attn.o": (hq, d), "moe.gate": (d, f), "moe.up": (d, f),
                "moe.down": (f, d)}[site]


def sites(dims: Dims) -> dict:
    """``{site: (lead, m, n)}``: the attention projections, and each
    expert site stacked over the ``E`` experts."""
    out = {s: ((), *dims.site_shape(s)) for s in ATTN}
    out.update({s: ((dims.n_experts,), *dims.site_shape(s))
                for s in EXPERTS})
    return out


def base_layer(key, dims: Dims, layer) -> dict:
    """Layer ``layer``'s base: ``{site: W (m, n) f32}`` for attention, the
    router ``(d, E)``, and for each expert site its packed draw, codes
    ``(E, m, n)`` with scales and zeros ``(E, m/g, n)``, which
    :func:`block` dequantizes one expert at a time."""
    out = {}
    for site in ATTN:
        m, n = dims.site_shape(site)
        lv = weights.site_layer(key, f"blocks.{site}", layer, m, n,
                                dims.group_size, dims.bits, 0, (),
                                jnp.bfloat16)
        out[site] = weights.dequant(lv["qcodes"], lv["scales"], lv["zeros"],
                                    dims.group_size)
    for site in EXPERTS:
        m, n = dims.site_shape(site)
        lv = weights.site_layer(key, f"blocks.{site}", layer, m, n,
                                dims.group_size, dims.bits, 0,
                                (dims.n_experts,), jnp.bfloat16)
        out[site] = (lv["qcodes"], lv["scales"], lv["zeros"])
    path = "blocks.moe.router.w"
    out["router"] = weights.dense_leaf(
        jax.random.fold_in(weights.leaf_key(key, path), layer), path,
        (dims.d_model, dims.n_experts), jnp.float32, dims.vocab)
    return out


def rmsnorm(x):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS)


def final_norm(x):
    """The RMSNorm before the head (its scale is ones)."""
    return rmsnorm(x)


def block(prec: Precision, dims: Dims, x, base: dict, lora: dict):
    """One OLMoE layer on x (N, T, d); ``lora[site]`` is (A, B) or
    missing, an expert site's with the expert axis after the row axis."""
    N, T, _ = x.shape
    H, hd = dims.n_heads, dims.head_dim
    h = rmsnorm(x)
    q = rmsnorm(linear(prec, h, base["attn.q"], lora.get("attn.q")))
    k = rmsnorm(linear(prec, h, base["attn.k"], lora.get("attn.k")))
    v = linear(prec, h, base["attn.v"], lora.get("attn.v"))
    q = rope(q.reshape(N, T, H, hd), dims.rope_theta)
    k = rope(k.reshape(N, T, H, hd), dims.rope_theta)
    v = v.reshape(N, T, H, hd)
    s = prec.einsum("nqhd,nkhd->nhqk", q, k) / np.sqrt(hd)
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = prec.einsum("nhqk,nkhd->nqhd", p, v).reshape(N, T, H * hd)
    x = x + linear(prec, o, base["attn.o"], lora.get("attn.o"))

    h = rmsnorm(x)
    probs = jax.nn.softmax(prec.einsum("ntd,de->nte", h, base["router"]),
                           axis=-1)
    kth = jax.lax.top_k(probs, dims.top_k)[0][..., -1:]
    gates = jnp.where(probs >= kth, probs, 0.0)          # (N, T, E)

    def expert_lora(site):
        ad = lora.get(site)
        if ad is None:
            return None
        return tuple(jnp.moveaxis(a, 1, 0) for a in ad)   # (E, N, ., r)

    def expert(y, inp):
        leaves, ads, gate = inp

        def lin(site, z):
            codes, scales, zeros = leaves[site]
            w = weights.dequant(codes, scales, zeros, dims.group_size)
            return linear(prec, z, w, ads[site])

        out = lin("moe.down", jax.nn.silu(lin("moe.gate", h))
                  * lin("moe.up", h))
        return y + gate[..., None] * out, None

    leaves = {s: base[s] for s in EXPERTS}
    ads = {s: expert_lora(s) for s in EXPERTS}
    y, _ = jax.lax.scan(jax.checkpoint(expert), jnp.zeros_like(x),
                        (leaves, ads, jnp.moveaxis(gates, -1, 0)))
    return x + y
