"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.quantizer import dequantize_int, unpack_codes

Array = jax.Array


def dequant_matmul_ref(x: Array, packed: Array, scales: Array, zeros: Array,
                       *, bits: int, group_size: int) -> Array:
    """y = x @ ((codes - z) * s).  x (M, K); packed (K*bits/8, N)."""
    K = x.shape[-1]
    codes = unpack_codes(packed, bits, K)
    w = dequantize_int(codes, scales, zeros, group_size, dtype=jnp.float32)
    return (x.astype(jnp.float32) @ w).astype(x.dtype)


def dequant_matmul_lora_ref(x: Array, packed: Array, scales: Array,
                            zeros: Array, lora_a: Array, lora_b: Array, *,
                            bits: int, group_size: int) -> Array:
    """y = x @ Wq + (x @ A) @ B^T, fused."""
    base = dequant_matmul_ref(x, packed, scales, zeros, bits=bits,
                              group_size=group_size).astype(jnp.float32)
    xa = x.astype(jnp.float32) @ lora_a.astype(jnp.float32)
    return (base + xa @ lora_b.astype(jnp.float32).T).astype(x.dtype)


def gram_ref(x: Array) -> Array:
    """H = X^T X in f32.  x (T, D)."""
    x32 = x.astype(jnp.float32)
    return x32.T @ x32


def flash_attention_ref(q: Array, k: Array, v: Array, *, causal: bool = True,
                        lengths: Array | None = None) -> Array:
    """q (B, Hq, Sq, d); k/v (B, Hkv, Sk, d); GQA by head grouping; softmax
    f32.  ``causal`` masks key ``j`` for query ``i`` when ``j > i``;
    ``lengths`` (B,) masks keys at ``j >= lengths[b]``."""
    B, Hq, Sq, d = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    rep = Hq // Hkv
    kk = jnp.repeat(k, rep, axis=1)
    vv = jnp.repeat(v, rep, axis=1)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        kk.astype(jnp.float32)) / jnp.sqrt(d)
    kpos = jnp.arange(Sk)[None, None, None, :]
    if causal:
        logits = jnp.where(kpos <= jnp.arange(Sq)[None, None, :, None],
                           logits, -1e30)
    if lengths is not None:
        logits = jnp.where(kpos < lengths[:, None, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vv.astype(jnp.float32))
    return out.astype(q.dtype)
