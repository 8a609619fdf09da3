"""Pallas TPU kernel: fused dequantize(packed INT2/INT4/INT8) x matmul
(+ optional fused LoRA second path).

TPU mapping (DESIGN.md §3): grid (M/bm, N/bn, K/bk) with the K loop
innermost ("arbitrary" semantics, accumulation in an f32 VMEM scratch).
Packed uint8 words stream HBM->VMEM at bits/8 bytes per weight — the whole
point of the paper's deployment; unpacking is a VPU shift/mask on an int32
upcast, group scales/zeros broadcast across their 64-row groups, and the
dequantized bf16 tile feeds the MXU.  Block shapes default to MXU-aligned
(bm, bk, bn) = (128, 512, 128); bk is a multiple of eight groups (or all
of K), so the (bk / g, bn) scale and zero tiles meet the TPU's
eight-sublane block rule and align with the weight tiles.

The fused-LoRA variant accumulates x@A (bm x r) in a second scratch during
the same K sweep and adds (x@A)@B^T on the final K step — one pass over x
for base + adapter (beyond-paper optimization, EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


def _unpack_tile(words: Array, bits: int) -> Array:
    """(bk/pack, bn) uint8 -> (bk, bn) int32 codes (pack along rows)."""
    if bits == 8:
        return words.astype(jnp.int32)
    per = 8 // bits
    mask = (1 << bits) - 1
    w32 = words.astype(jnp.int32)
    parts = [(w32 >> (bits * j)) & mask for j in range(per)]
    stacked = jnp.stack(parts, axis=1)            # (bk/pack, per, bn)
    return stacked.reshape(words.shape[0] * per, words.shape[1])


def _k_block(K: int, g: int, want: int) -> int:
    """K tile: the largest multiple of ``8 * g`` that divides ``K`` and is
    at most ``max(want, 8 * g)``, else all of ``K``.  A scale/zero block
    then holds a multiple of eight group rows or the whole array, which
    Mosaic requires of a block's second-to-last dimension."""
    step = 8 * g
    b = (max(want, step) // step) * step
    while b >= step:
        if K % b == 0:
            return b
        b -= step
    return K


def _dequant_tile(words: Array, s: Array, z: Array, bits: int,
                  group: int) -> Array:
    """-> (bk, bn) bf16 dequantized weights."""
    codes = _unpack_tile(words, bits)             # (bk, bn) int32
    reps = codes.shape[0] // s.shape[0]
    s_full = jnp.repeat(s, reps, axis=0)
    z_full = jnp.repeat(z, reps, axis=0)
    return ((codes.astype(jnp.float32) - z_full) * s_full)


def _kernel(x_ref, w_ref, s_ref, z_ref, o_ref, acc, *, bits, group, nk):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    w = _dequant_tile(w_ref[...], s_ref[...], z_ref[...], bits, group)
    x = x_ref[...].astype(jnp.float32)
    acc[...] += jax.lax.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _done():
        o_ref[...] = acc[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bits", "group_size", "bm", "bn",
                                             "bk", "interpret"))
def dequant_matmul(x: Array, packed: Array, scales: Array, zeros: Array, *,
                   bits: int, group_size: int, bm: int = 128, bn: int = 128,
                   bk: int = 512, interpret: bool) -> Array:
    """y = x @ dequant(packed).  x (..., K); packed (K*bits/8, N)."""
    orig_shape = x.shape
    K = x.shape[-1]
    x2 = x.reshape(-1, K)
    M = x2.shape[0]
    N = packed.shape[1]
    g = K if group_size is None else group_size
    pack = 8 // bits if bits in (2, 4) else 1
    bm = min(bm, M)
    bn = min(bn, N)
    bk = _k_block(K, g, bk)
    nk = K // bk

    grid = (M // bm, N // bn, nk)
    out = pl.pallas_call(
        functools.partial(_kernel, bits=bits, group=g, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda m, n, k: (m, k)),
            pl.BlockSpec((bk // pack, bn), lambda m, n, k: (k, n)),
            pl.BlockSpec((bk // g, bn), lambda m, n, k: (k, n)),
            pl.BlockSpec((bk // g, bn), lambda m, n, k: (k, n)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda m, n, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x2, packed, scales, zeros)
    return out.reshape(*orig_shape[:-1], N)


def _kernel_lora(x_ref, w_ref, s_ref, z_ref, a_ref, b_ref, o_ref, acc, xa,
                 *, bits, group, nk):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        xa[...] = jnp.zeros_like(xa)

    w = _dequant_tile(w_ref[...], s_ref[...], z_ref[...], bits, group)
    x = x_ref[...].astype(jnp.float32)
    acc[...] += jax.lax.dot(x, w, preferred_element_type=jnp.float32)
    xa[...] += jax.lax.dot(x, a_ref[...].astype(jnp.float32),
                           preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _done():
        lora = jax.lax.dot(xa[...], b_ref[...].astype(jnp.float32).T,
                           preferred_element_type=jnp.float32)
        o_ref[...] = (acc[...] + lora).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bits", "group_size", "bm", "bn",
                                             "bk", "interpret"))
def dequant_matmul_lora(x: Array, packed: Array, scales: Array, zeros: Array,
                        lora_a: Array, lora_b: Array, *, bits: int,
                        group_size: int, bm: int = 128, bn: int = 128,
                        bk: int = 512, interpret: bool) -> Array:
    """Fused y = x @ Wq + (x @ A) @ B^T — one sweep over x."""
    orig_shape = x.shape
    K = x.shape[-1]
    x2 = x.reshape(-1, K)
    M, N = x2.shape[0], packed.shape[1]
    r = lora_a.shape[1]
    g = K if group_size is None else group_size
    pack = 8 // bits if bits in (2, 4) else 1
    bm, bn, bk = min(bm, M), min(bn, N), _k_block(K, g, bk)
    nk = K // bk

    grid = (M // bm, N // bn, nk)
    out = pl.pallas_call(
        functools.partial(_kernel_lora, bits=bits, group=g, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda m, n, k: (m, k)),
            pl.BlockSpec((bk // pack, bn), lambda m, n, k: (k, n)),
            pl.BlockSpec((bk // g, bn), lambda m, n, k: (k, n)),
            pl.BlockSpec((bk // g, bn), lambda m, n, k: (k, n)),
            pl.BlockSpec((bk, r), lambda m, n, k: (k, 0)),
            pl.BlockSpec((bn, r), lambda m, n, k: (n, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda m, n, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32),
                        pltpu.VMEM((bm, r), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x2, packed, scales, zeros, lora_a, lora_b)
    return out.reshape(*orig_shape[:-1], N)
