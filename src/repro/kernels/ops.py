"""Public jit'd wrappers around the Pallas kernels.

The wrappers choose how a kernel runs from the backend, once, here: on a
TPU it lowers through Mosaic, and on any other backend (the CPU test
suite) it runs in the Pallas interpreter.  No caller passes
``interpret``; the raw kernels take it explicitly, so a compile test can
lower them for a described TPU from a CPU host.  Wrappers validate shapes
and fall back to the pure-jnp reference for shapes the tiling cannot
cover (non-multiple dims), so they are safe to call from model code.
"""
from __future__ import annotations

import math

import jax

from repro.kernels import ref
from repro.kernels.dequant_matmul import dequant_matmul as _dqmm
from repro.kernels.dequant_matmul import dequant_matmul_lora as _dqmm_lora
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.gram import gram as _gram

Array = jax.Array


def interpret_mode() -> bool:
    """True unless the default backend is a TPU: the only place the
    repository decides between Mosaic and the Pallas interpreter."""
    return jax.default_backend() != "tpu"


def _pack_factor(bits: int) -> int:
    return 8 // bits if bits in (2, 4) else 1


def dequant_matmul(x: Array, packed: Array, scales: Array, zeros: Array, *,
                   bits: int, group_size: int, lora_a: Array | None = None,
                   lora_b: Array | None = None) -> Array:
    K = x.shape[-1]
    N = packed.shape[-1]
    g = K if group_size is None else group_size
    M = math.prod(x.shape[:-1]) if x.ndim > 1 else 1
    tileable = (K % g == 0 and packed.shape[0] * _pack_factor(bits) == K)
    # tiles need M, N, K covered by block multiples; fall back otherwise
    if not tileable or M % 8 or N % 128 or K % g:
        if lora_a is not None:
            return ref.dequant_matmul_lora_ref(
                x, packed, scales, zeros, lora_a, lora_b, bits=bits,
                group_size=group_size)
        return ref.dequant_matmul_ref(x, packed, scales, zeros, bits=bits,
                                      group_size=group_size)
    bm = 128 if M % 128 == 0 else (8 if M % 8 == 0 else M)
    if lora_a is not None:
        return _dqmm_lora(x, packed, scales, zeros, lora_a, lora_b, bits=bits,
                          group_size=group_size, bm=bm,
                          interpret=interpret_mode())
    return _dqmm(x, packed, scales, zeros, bits=bits, group_size=group_size,
                 bm=bm, interpret=interpret_mode())


def gram(x: Array) -> Array:
    D = x.shape[-1]
    T = math.prod(x.shape[:-1])
    if D % 128 or T % 8:
        return ref.gram_ref(x.reshape(-1, D))
    bt = 512 if T % 512 == 0 else (8 if T % 8 == 0 else T)
    return _gram(x, bt=bt, interpret=interpret_mode())


def _seq_tileable(S: int) -> bool:
    # one block of the whole sequence, or whole 128-row blocks
    return S <= 128 or S % 128 == 0


def flash_attention(q: Array, k: Array, v: Array, *, causal: bool = True,
                    lengths: Array | None = None) -> Array:
    """q (B, Hq, Sq, d); k/v (B, Hkv, Sk, d).  ``lengths`` (B,) masks keys
    at ``kpos >= lengths[b]`` (the serving decode path)."""
    d = q.shape[-1]
    Sq, Sk = q.shape[2], k.shape[2]
    if d % 8 or not (_seq_tileable(Sq) and _seq_tileable(Sk)):
        return ref.flash_attention_ref(q, k, v, causal=causal,
                                       lengths=lengths)
    return _flash(q, k, v, causal=causal, lengths=lengths,
                  interpret=interpret_mode())
