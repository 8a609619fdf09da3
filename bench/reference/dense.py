"""Plain float32 reference of a Qwen3-style decoder with LoRA on a
dequantized INT-b base.

It follows the published description (RMSNorm before attention and MLP,
RMSNorm on each query and key head, rotary embedding with the rotate-half
convention, grouped-query attention, SwiGLU MLP, RMSNorm before a head
that may be tied to the embedding) and imports nothing of the program:
every weight is drawn again from the seed by ``bench.weights``, one layer
at a time.  Each linear computes ``x W + (x A) B^T`` with ``W`` the
dequantized base.

A ``Precision`` object does every matrix product: :data:`EXACT` runs them
in float32 at ``highest``; :data:`FP8` casts both operands to float8_e4m3
with one scale per tensor, the lower precision that the control runs.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights

SITES = ("attn.q", "attn.k", "attn.v", "attn.o", "mlp.gate", "mlp.up",
         "mlp.down")
EPS = 1e-6
F8_MAX = 448.0


@dataclasses.dataclass(frozen=True)
class Precision:
    name: str

    def cast(self, x):
        """``x`` rounded to this precision (float8_e4m3 with one scale per
        tensor); differentiation sees the rounding as the identity."""
        if self.name == "exact":
            return x
        s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
        q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
        return x + jax.lax.stop_gradient(q - x)

    def einsum(self, spec: str, a, b):
        return jnp.einsum(spec, self.cast(a.astype(jnp.float32)),
                          self.cast(b.astype(jnp.float32)),
                          precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)


EXACT = Precision("exact")
FP8 = Precision("fp8")


@dataclasses.dataclass(frozen=True)
class Dims:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    vocab_padded: int
    rope_theta: float
    tie_embeddings: bool
    group_size: int
    bits: int

    @classmethod
    def of(cls, c: dict) -> "Dims":
        mult = c.get("vocab_pad_multiple", 1)
        return cls(c["n_layers"], c["d_model"], c["n_heads"],
                   c["n_kv_heads"], c["head_dim"], c["d_ff"], c["vocab"],
                   -(-c["vocab"] // mult) * mult, float(c["rope_theta"]),
                   bool(c["tie_embeddings"]), c["quant"]["group_size"],
                   c["quant"]["bits"])

    def site_shape(self, site: str) -> tuple[int, int]:
        d, q, kv = (self.d_model, self.n_heads * self.head_dim,
                    self.n_kv_heads * self.head_dim)
        return {"attn.q": (d, q), "attn.k": (d, kv), "attn.v": (d, kv),
                "attn.o": (q, d), "mlp.gate": (d, self.d_ff),
                "mlp.up": (d, self.d_ff), "mlp.down": (self.d_ff, d)}[site]


def base_layer(key, dims: Dims, layer) -> dict:
    """Layer ``layer``'s dequantized base ``{site: W (m, n) f32}``."""
    out = {}
    for site in SITES:
        m, n = dims.site_shape(site)
        leaves = weights.site_layer(key, f"blocks.{site}", layer, m, n,
                                    dims.group_size, dims.bits, 0, (),
                                    jnp.bfloat16)
        out[site] = weights.dequant(leaves["qcodes"], leaves["scales"],
                                    leaves["zeros"], dims.group_size)
    return out


def embedding(key, dims: Dims) -> jax.Array:
    """(V_pad, d) embedding as served (bf16), in f32."""
    return weights.dense_leaf(weights.leaf_key(key, "embed.w"), "embed.w",
                              (dims.vocab_padded, dims.d_model),
                              jnp.bfloat16, dims.vocab).astype(jnp.float32)


def head(key, dims: Dims) -> jax.Array:
    """(d, V) output head over the published vocabulary, in f32."""
    if dims.tie_embeddings:
        return embedding(key, dims)[:dims.vocab].T
    w = weights.dense_leaf(weights.leaf_key(key, "head.w"), "head.w",
                           (dims.d_model, dims.vocab_padded), jnp.bfloat16,
                           dims.vocab)
    return w.astype(jnp.float32)[:, :dims.vocab]


def rmsnorm(x):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS)


def rope(x, theta: float):
    """x (N, T, H, hd): rotate the halves by position."""
    T, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def linear(prec: Precision, x, w, lora=None):
    """x (N, T, m) @ W (m, n) plus ``(x A) B^T``; ``lora`` is (A, B) with a
    leading row axis N (one adapter per row) or none."""
    y = prec.einsum("btm,mn->btn", x, w)
    if lora is not None:
        a, b = lora
        y = y + prec.einsum("btr,bnr->btn",
                            prec.einsum("btm,bmr->btr", x, a), b)
    return y


def block(prec: Precision, dims: Dims, x, base: dict, lora: dict):
    """One decoder layer on x (N, T, d); ``lora[site]`` is (A, B) or
    missing."""
    N, T, _ = x.shape
    H, Hkv, hd = dims.n_heads, dims.n_kv_heads, dims.head_dim
    h = rmsnorm(x)
    q = linear(prec, h, base["attn.q"], lora.get("attn.q"))
    k = linear(prec, h, base["attn.k"], lora.get("attn.k"))
    v = linear(prec, h, base["attn.v"], lora.get("attn.v"))
    q = rope(rmsnorm(q.reshape(N, T, H, hd)), dims.rope_theta)
    k = rope(rmsnorm(k.reshape(N, T, Hkv, hd)), dims.rope_theta)
    v = v.reshape(N, T, Hkv, hd)
    rep = H // Hkv
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    s = prec.einsum("nqhd,nkhd->nhqk", q, k) / np.sqrt(hd)
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = prec.einsum("nhqk,nkhd->nqhd", p, v).reshape(N, T, H * hd)
    x = x + linear(prec, o, base["attn.o"], lora.get("attn.o"))
    h = rmsnorm(x)
    g = linear(prec, h, base["mlp.gate"], lora.get("mlp.gate"))
    u = linear(prec, h, base["mlp.up"], lora.get("mlp.up"))
    return x + linear(prec, jax.nn.silu(g) * u, base["mlp.down"],
                      lora.get("mlp.down"))
