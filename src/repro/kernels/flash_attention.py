"""Pallas TPU kernel: causal GQA flash attention (prefill hot-spot).

Online-softmax blocking (Dao et al., adapted to TPU): grid
(B*Hq, Sq/bq, Sk/bk) with the key loop innermost; running max m, running
sum l, and the (bq x d) output accumulator live in VMEM scratch.  Key
blocks above the causal diagonal, or past a sequence's length, skip their
compute; the grid is static, so they are still visited.

GQA: the q-head grid index maps to kv head q_head // (Hq // Hkv) via the
BlockSpec index_map — no repeated K/V materialization.

``lengths`` (B,) adds per-sequence key masking: keys at ``kpos >=
lengths[b]`` are dropped for every query of sequence ``b`` (without
``lengths``, every sequence holds all ``Sk`` keys).  This is the
serving integration point — the paged-KV decode path hands the kernel each
request's token count so one batch can mix requests at different progress.
Every sequence must have length >= 1 (an all-masked first block would make
the online softmax renormalize from nothing); decode always satisfies this
because the current token is written before attention runs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array
NEG_INF = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc, *,
            scale, causal, bq, bk, nk, hq):
    kb = pl.program_id(2)
    qb = pl.program_id(1)
    length = len_ref[pl.program_id(0) // hq]      # scalar-prefetched (SMEM)

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc[...] = jnp.zeros_like(acc)

    # key blocks past the sequence's length contribute nothing; with
    # causal masking, neither do blocks strictly above the diagonal band
    run = kb * bk < length
    if causal:
        run = run & ((kb * bk) <= (qb * bq + bq - 1))

    @pl.when(run)
    def _step():
        q = q_ref[0].astype(jnp.float32) * scale            # (bq, d)
        k = k_ref[0].astype(jnp.float32)                    # (bk, d)
        s = jax.lax.dot(q, k.T, preferred_element_type=jnp.float32)
        kpos = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        if causal:
            qpos = qb * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        s = jnp.where(kpos < length, s, NEG_INF)
        m_prev = m_scr[...]                                  # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)
        acc[...] = acc[...] * alpha + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(kb == nk - 1)
    def _done():
        o_ref[0] = (acc[...] / jnp.maximum(l_scr[...], 1e-30)
                    ).astype(o_ref.dtype)


def _block(size: int, want: int) -> int:
    """Largest divisor of ``size`` that is <= ``want`` (static shapes need
    bq | Sq and bk | Sk; serving cache lengths are not always 128-multiples)."""
    b = min(want, size)
    while size % b:
        b -= 1
    return b


@functools.partial(jax.jit,
                   static_argnames=("causal", "bq", "bk", "interpret"))
def flash_attention(q: Array, k: Array, v: Array, *, causal: bool = True,
                    bq: int = 128, bk: int = 128, interpret: bool,
                    lengths: Array | None = None) -> Array:
    """q (B, Hq, Sq, d); k/v (B, Hkv, Sk, d) -> (B, Hq, Sq, d).

    ``lengths`` (B,) int32: optional per-sequence valid key count (keys at
    ``kpos >= lengths[b]`` are masked for all of b's queries); must be
    >= 1 everywhere.  It reaches the kernel through scalar prefetch
    (SMEM), so the index maps and the mask read it without a VMEM block."""
    B, Hq, Sq, d = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    rep = Hq // Hkv
    bq = _block(Sq, bq)
    bk = _block(Sk, bk)
    nq, nk = Sq // bq, Sk // bk
    scale = 1.0 / (d ** 0.5)
    if lengths is None:
        lengths = jnp.full((B,), Sk, jnp.int32)

    q4 = q.reshape(B * Hq, Sq, d)
    k4 = k.reshape(B * Hkv, Sk, d)
    v4 = v.reshape(B * Hkv, Sk, d)

    def kv_map(h, qb, kb, lens):
        return (h // rep, kb, 0)

    def q_map(h, qb, kb, lens):
        return (h, qb, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B * Hq, nq, nk),
        in_specs=[pl.BlockSpec((1, bq, d), q_map),
                  pl.BlockSpec((1, bk, d), kv_map),
                  pl.BlockSpec((1, bk, d), kv_map)],
        out_specs=pl.BlockSpec((1, bq, d), q_map),
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, d), jnp.float32)])
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal, bq=bq, bk=bk,
                          nk=nk, hq=Hq),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * Hq, Sq, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lengths.astype(jnp.int32), q4, k4, v4)
    return out.reshape(B, Hq, Sq, d)
