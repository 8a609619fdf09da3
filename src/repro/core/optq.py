"""OPTQ/GPTQ layer-wise post-training quantization in JAX.

Solves  min_{Q in grid} ||X (Q - W)||_F^2  with the blocked
Cholesky error-compensation sweep of Frantar et al. (2022), adapted to the
``y = X @ W`` convention: ``W`` is ``(m, n)``, the sweep runs over the input
dim ``m`` (rows), and all ``n`` output columns are compensated jointly
(vectorized) — they are independent given the shared Gram ``H = X^T X``.

TPU adaptation (DESIGN.md §3): the ``n`` dim is embarrassingly parallel, so
:func:`optq_quantize_sharded` runs the same sweep under ``shard_map`` with
``W`` column-sharded over the model axis — distributed OPTQ with zero
communication (H is replicated).  The shard-local body is the same
:func:`optq_quantize_core` the batched engine vmaps, so sharding and
batching compose: one bucket of L same-shape layers runs as a single
``shard_map`` whose body vmaps the sweep over its ``(L, m, n_local)``
column shard (``repro.core.batched.run_bucket_sharded``).

Static per-group quantization grids (GPTQ ``static_groups=True``) are
computed up front from the (MagR-preprocessed) weights, which keeps the
sweep JAX-friendly and deterministic under ``act_order``.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.core.linalg import cholesky_lower, tri_inv_lower
from repro.core.quantizer import QuantConfig, quant_params, stable_round
from repro.utils import scope

Array = jax.Array


def dampen(H: Array, lambda_frac: float) -> Array:
    m = H.shape[0]
    lam = lambda_frac * jnp.trace(H) / m
    return H + (lam + 1e-8) * jnp.eye(m, dtype=H.dtype)


def inv_cholesky_upper(H: Array) -> Array:
    """Upper-triangular U with H^{-1} = U^T @ U (torch ``cholesky(upper=True)``
    of the inverse — the factor GPTQ's sweep consumes row-by-row).

    With ``J`` the order reversal, ``Lr = chol(J H J)`` gives
    ``H = (J Lr J)(J Lr J)^T`` with ``J Lr J`` upper-triangular, so
    ``U = J Lr^{-1} J``: one Cholesky and one triangular inverse, and no
    explicit ``H^{-1}``."""
    return tri_inv_lower(cholesky_lower(H[::-1, ::-1]))[::-1, ::-1]


@partial(jax.jit, static_argnames=("bits", "block_size", "act_order"))
def _optq_core(W: Array, H: Array, srow: Array, zrow: Array, *, bits: int,
               block_size: int, act_order: bool):
    """Blocked GPTQ sweep.  ``srow``/``zrow`` are per-row (m, n) grids.

    Requires ``m % block_size == 0`` (caller guarantees)."""
    m, n = W.shape
    bs = block_size
    if act_order:
        perm = jnp.argsort(-jnp.diag(H))
        inv_perm = jnp.argsort(perm)
        W, H = W[perm], H[perm][:, perm]
        srow, zrow = srow[perm], zrow[perm]

    U = inv_cholesky_upper(H)
    dU = jnp.diag(U)
    rows = jnp.arange(m)
    maxq = 2.0 ** bits - 1.0

    def body(carry, blk):
        Wc = carry
        start = blk * bs
        Wblk = jax.lax.dynamic_slice(Wc, (start, 0), (bs, n))
        sblk = jax.lax.dynamic_slice(srow, (start, 0), (bs, n))
        zblk = jax.lax.dynamic_slice(zrow, (start, 0), (bs, n))
        dblk = jax.lax.dynamic_slice(dU, (start,), (bs,))
        Ubb = jax.lax.dynamic_slice(U, (start, start), (bs, bs))

        def inner(i, st):
            Wb, Qdb, Qcb, Err = st
            w_i, s_i, z_i = Wb[i], sblk[i], zblk[i]
            q = jnp.clip(stable_round(w_i / s_i) + z_i, 0.0, maxq)
            dq = (q - z_i) * s_i
            err = (w_i - dq) / dblk[i]
            u = Ubb[i] * (jnp.arange(bs) > i)          # rows after i in block
            Wb = Wb - u[:, None] * err[None, :]
            Qdb = Qdb.at[i].set(dq)
            Qcb = Qcb.at[i].set(q.astype(jnp.uint8))
            Err = Err.at[i].set(err)
            return Wb, Qdb, Qcb, Err

        # init from Wblk (not fresh zeros) so shard_map vma tracking matches
        init = (Wblk, Wblk * 0.0, (Wblk * 0.0).astype(jnp.uint8), Wblk * 0.0)
        _, Qdb, Qcb, Err = jax.lax.fori_loop(0, bs, inner, init)

        # lazy tail update for rows >= start + bs
        Ublk = jax.lax.dynamic_slice(U, (start, 0), (bs, m))   # (bs, m)
        tail = (rows >= start + bs).astype(W.dtype)
        Wc = Wc - (Ublk.T @ Err) * tail[:, None]
        return Wc, (Qdb, Qcb)

    _, (Qd_blocks, Qc_blocks) = jax.lax.scan(body, W, jnp.arange(m // bs))
    Qd = Qd_blocks.reshape(m, n)
    Qc = Qc_blocks.reshape(m, n)

    if act_order:
        Qd, Qc = Qd[inv_perm], Qc[inv_perm]
    return Qd, Qc


def _per_row_grids(scales: Array, zeros: Array, m: int, group_size: int | None):
    g = m if group_size is None else int(group_size)
    return jnp.repeat(scales, g, axis=0), jnp.repeat(zeros, g, axis=0)


def pick_block(m: int, block_size: int) -> int:
    """Largest divisor of ``m`` that is <= ``block_size`` (sweep block).

    Shape-only: resolve at *plan* time so the traced core below stays free
    of data-dependent Python branching (vmap/batching safe)."""
    if m % block_size == 0:
        return block_size
    for b in range(min(block_size, m), 0, -1):
        if m % b == 0:
            return b
    return m


@scope("optq")
def optq_quantize_core(W: Array, H: Array, cfg: QuantConfig,
                       scales: Array | None = None,
                       zeros: Array | None = None):
    """Vmap- and shard_map-safe OPTQ sweep: pure traced ops, no host syncs,
    no shape fallbacks.  ``cfg.block_size`` must already divide ``m`` —
    resolve it with :func:`pick_block` at plan time.  Every op is
    per-column given the replicated ``H`` (grids, damping, sweep), so a
    column shard of ``W`` yields exactly the corresponding shard of every
    output with zero communication.  Returns
    (Q_dequant (m,n) f32, codes uint8, scales, zeros)."""
    W = jnp.asarray(W, jnp.float32)
    H = dampen(jnp.asarray(H, jnp.float32), cfg.lambda_frac)
    if scales is None or zeros is None:
        scales, zeros = quant_params(W, cfg.bits, cfg.group_size)
    srow, zrow = _per_row_grids(scales, zeros, W.shape[0], cfg.group_size)
    Qd, Qc = _optq_core(W, H, srow, zrow, bits=cfg.bits,
                        block_size=cfg.block_size, act_order=cfg.act_order)
    return Qd, Qc, scales, zeros


def optq_quantize(W: Array, H: Array, cfg: QuantConfig,
                  scales: Array | None = None, zeros: Array | None = None):
    """OPTQ sweep.  Returns (Q_dequant (m,n) f32, codes uint8, scales, zeros).

    ``H`` is the *undamped* Gram; damping is applied here.
    Grids are static per group, computed from ``W`` unless provided.
    """
    bs = pick_block(W.shape[0], cfg.block_size)
    if bs != cfg.block_size:
        cfg = dataclasses.replace(cfg, block_size=bs)
    return optq_quantize_core(W, H, cfg, scales, zeros)


def cholesky_factor_finite(H: Array, lambda_frac: float = 0.01) -> bool:
    """Host-side diagnostic: does the *damped* Gram admit a finite Cholesky
    factor?  ``inv_cholesky_upper`` silently yields NaN on (effectively)
    non-PSD input and the sweep propagates it into every code of the layer
    — this is the check the health guards use to name that failure mode
    (``repro.core.health.diagnose``) instead of reporting a generic
    non-finite output."""
    U = inv_cholesky_upper(dampen(jnp.asarray(H, jnp.float32), lambda_frac))
    return bool(jnp.all(jnp.isfinite(U)))


def optq_error(X: Array, W: Array, Qd: Array) -> float:
    """||X(Q - W)||_F — the calibrated objective (for tests/benchmarks)."""
    return float(jnp.linalg.norm(X @ (Qd - W)))


def gram_error(H: Array, D: Array) -> float:
    """sqrt(Tr(D^T H D)) = ||X D||_F given H = X^T X (avoids materializing X)."""
    v = jnp.einsum("ij,ik,kj->", D, H, D)
    return float(jnp.sqrt(jnp.maximum(v, 0.0)))


def optq_quantize_sharded(W: Array, H: Array, cfg: QuantConfig, mesh,
                          axis: str = "model"):
    """Distributed OPTQ: columns (output channels) sharded over ``axis``.

    H is replicated; the sweep needs no communication (columns independent).
    The shard-local body is :func:`optq_quantize_core` — grids, damping and
    the sweep are all per-column, so each shard computes exactly the columns
    it owns.  The sweep block is resolved here (plan time) so the traced
    core is shard_map- *and* vmap-safe; the batched engine reuses the same
    core inside one fused program per bucket
    (:func:`repro.core.batched.run_bucket_sharded`).

    Returns ``(Qd (m, n), codes uint8, scales (m/g, n), zeros (m/g, n))``
    with every leaf except ``H`` column-sharded over ``axis``.
    """
    from jax.sharding import PartitionSpec as P

    W = jnp.asarray(W, jnp.float32)
    H = jnp.asarray(H, jnp.float32)
    bs = pick_block(W.shape[0], cfg.block_size)
    if bs != cfg.block_size:
        cfg = dataclasses.replace(cfg, block_size=bs)

    def local(Wl, H_):
        return optq_quantize_core(Wl, H_, cfg)

    col = P(None, axis)
    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(col, P(None, None)),
                       out_specs=(col, col, col, col))
    return fn(W, H)
