"""Minimal functional module system: init fns return nested param dicts,
apply fns consume them.  No flax dependency.

Linear layers are the quantization surface: ``linear_apply`` transparently
handles dense bf16 weights, packed-quantized weights (OPTQ/CLoQ state), and
LoRA adapters, and records calibration activations when inside a
``capture_grams`` context (eager only).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.quantizer import dequantize_int, unpack_codes
from repro.utils import current_scope, record_activation, scope

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class QSpec:
    """Static quantization spec threaded through model configs."""
    bits: int = 4
    group_size: int = 64
    rank: int = 64
    method: str = "cloq"          # cloq | loftq | rtn | gptq | qlora(nf4)
    split: str = "paper"
    use_kernel: bool = False      # Pallas dequant-matmul (tests/benchmarks)


def _init_dense(key, m, n, dtype, scale=None):
    scale = (1.0 / jnp.sqrt(m)) if scale is None else scale
    return (jax.random.normal(key, (m, n), jnp.float32) * scale).astype(dtype)


def linear_init(key, m: int, n: int, *, dtype=jnp.bfloat16, bias: bool = False,
                lora_rank: int = 0, scale=None) -> dict:
    keys = jax.random.split(key, 3)
    p = {"w": _init_dense(keys[0], m, n, dtype, scale)}
    if bias:
        p["b"] = jnp.zeros((n,), dtype)
    if lora_rank:
        p["lora_a"] = (jax.random.normal(keys[1], (m, lora_rank), jnp.float32)
                       / jnp.sqrt(m)).astype(dtype)
        p["lora_b"] = jnp.zeros((n, lora_rank), dtype)
    return p


def packed_bits(mp: int, m: int) -> int:
    """Bit-width of a packed ``qcodes`` leaf, inferred from its row count
    (``m`` in-features packed to ``mp`` uint8 rows).  2-/4-bit codes pack
    4/2 per byte; unpacked rows (3-/8-bit storage) are returned as 8 —
    ``unpack_codes`` is the identity for both, so dequantization is
    unambiguous.  Quantized leaves are therefore self-describing: mixed
    bit-widths (per-site QuantRecipe plans) need no per-layer config at
    apply time."""
    if mp * 4 == m:
        return 2
    if mp * 2 == m:
        return 4
    if mp != m:
        raise ValueError(f"qcodes rows {mp} do not match in-features {m}")
    return 8


def _group_of(meta: Array, m: int) -> int:
    """Group size recovered from a (m/g, n) scales/absmax leaf."""
    return m // meta.shape[-2]


def linear_apply(p: dict, x: Array, qspec: QSpec | None = None) -> Array:
    """y = x @ W (+ LoRA path + bias). W may be dense or packed-quantized.

    Each quantized site dequantizes from its OWN stored shapes (bit-width
    via :func:`packed_bits`, group size from the scales/absmax rows), so a
    model quantized with a heterogeneous :class:`repro.core.recipe.
    QuantRecipe` — 2-bit MLPs next to 4-bit attention — runs with the one
    global ``qspec`` only gating the Pallas kernel path."""
    # the calibration key is read above; the scopes below only name ops
    record_activation(current_scope(), x)
    m = x.shape[-1]
    if "qcodes" in p:
        assert qspec is not None, "quantized params need a QSpec"
        if "absmax" in p:                      # NF4 (QLoRA baseline)
            from repro.core.quantizer import dequantize_nf4
            with scope("dequant"):
                codes = unpack_codes(p["qcodes"], 4, m)
                w = dequantize_nf4(codes, p["absmax"],
                                   _group_of(p["absmax"], m), x.dtype)
            y = x @ w
        else:
            bits = packed_bits(p["qcodes"].shape[-2], m)
            group = _group_of(p["scales"], m)
            if qspec.use_kernel:
                from repro.kernels import ops as kops
                y = kops.dequant_matmul(x, p["qcodes"], p["scales"],
                                        p["zeros"], bits=bits,
                                        group_size=group)
            else:
                with scope("dequant"):
                    codes = unpack_codes(p["qcodes"], bits, m)
                    w = dequantize_int(codes, p["scales"], p["zeros"],
                                       group, dtype=x.dtype)
                y = x @ w
    else:
        y = x @ p["w"].astype(x.dtype)
    if "lora_a" in p:
        with scope("lora"):
            a = p["lora_a"].astype(x.dtype)
            b = p["lora_b"].astype(x.dtype)
            if a.ndim == 3:
                # per-request adapters (serving): a (B, m, r), b (B, n, r)
                # — one gathered einsum over the whole batch, never a row
                # loop
                y = y + jnp.einsum("bsr,bnr->bsn",
                                   jnp.einsum("bsm,bmr->bsr", x, a), b)
            else:
                y = y + (x @ a) @ b.T
    if "b" in p:
        y = y + p["b"].astype(y.dtype)
    return y


def rmsnorm_init(d: int, dtype=jnp.bfloat16) -> dict:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm_apply(p: dict, x: Array, eps: float = 1e-6) -> Array:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)


def layernorm_init(d: int, dtype=jnp.bfloat16) -> dict:
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layernorm_apply(p: dict, x: Array, eps: float = 1e-5) -> Array:
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


def embedding_init(key, vocab: int, d: int, dtype=jnp.bfloat16) -> dict:
    w = jax.random.normal(key, (vocab, d), jnp.float32) * 0.02
    return {"w": w.astype(dtype)}


def embedding_apply(p: dict, tokens: Array) -> Array:
    with scope("embed"):
        return jnp.take(p["w"], tokens, axis=0)


def lm_head_apply(p: dict, x: Array, *, tied: bool) -> Array:
    """Logits. ``p`` is the embedding ({'w': (V, d)}) where ``tied``, else
    the head linear ({'w': (d, V)}); the caller, who knows the model's
    ``tie_embeddings``, says which (a square ``w`` cannot tell)."""
    with scope("lm_head"):
        w = p["w"].astype(x.dtype)
        return x @ w.T if tied else x @ w
