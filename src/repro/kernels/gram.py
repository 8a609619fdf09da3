"""Pallas TPU kernel: blocked Gram accumulation H = X^T X (f32).

The calibration hot-spot: every CLoQ/OPTQ layer consumes an (m x m) Gram of
potentially millions of calibration tokens.  Grid (D/bi, D/bj, T/bt) with
the token loop innermost; X tiles stream through VMEM once per (i, j) pair
and accumulate on the MXU in f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


def _kernel(xi_ref, xj_ref, o_ref, acc, *, nt):
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    xi = xi_ref[...].astype(jnp.float32)
    xj = xj_ref[...].astype(jnp.float32)
    acc[...] += jax.lax.dot(xi.T, xj, preferred_element_type=jnp.float32)

    @pl.when(t == nt - 1)
    def _done():
        o_ref[...] = acc[...]


@functools.partial(jax.jit, static_argnames=("bi", "bj", "bt", "interpret"))
def gram(x: Array, *, bi: int = 128, bj: int = 128, bt: int = 512,
         interpret: bool) -> Array:
    """H = X^T X.  x (..., D) flattened over leading dims."""
    D = x.shape[-1]
    x2 = x.reshape(-1, D)
    T = x2.shape[0]
    bi, bj, bt = min(bi, D), min(bj, D), min(bt, T)
    nt = T // bt
    grid = (D // bi, D // bj, nt)
    return pl.pallas_call(
        functools.partial(_kernel, nt=nt),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt, bi), lambda i, j, t: (t, i)),
            pl.BlockSpec((bt, bj), lambda i, j, t: (t, j)),
        ],
        out_specs=pl.BlockSpec((bi, bj), lambda i, j, t: (i, j)),
        out_shape=jax.ShapeDtypeStruct((D, D), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bi, bj), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x2, x2)
