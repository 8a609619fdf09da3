"""LoftQ baseline (Li et al., 2023): data-free alternating Q/low-rank init.

    min_{Q, A, B}  || Q + A B^T - W ||_F^2                    (paper eq. 6)

AltMin: Q <- quant(W - A B^T);  (A, B) <- SVD_r(W - Q), split as
A = U_r S_r^{1/2}, B = V_r S_r^{1/2} (LoftQ's choice). Default 5 iterations.
Supports the uniform INT grid (to compare heads-up with CLoQ) and NF4.

Distributed: the RTN quantization inside each AltMin round is per output
column, and the SVD of the full-width residual ``W - Q`` is recovered
exactly from a column shard via the same Gram trick CLoQ's sharded solve
uses (:func:`svd_lowrank_topr`: ``G = (W-Q)(W-Q)^T`` psummed, ``eigh``
replicated, ``V`` shard-local) — so :func:`loftq_init` runs column-sharded
inside the batched engine's ``shard_map`` with one ``(m, m)`` psum per
AltMin round, and LoftQ no longer forces the replicated bucket fallback.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.linalg import sym_topr
from repro.core.quantizer import (QuantConfig, dequantize_int, dequantize_nf4,
                                  quantize_int, quantize_nf4)
from repro.utils import scope

Array = jax.Array


def _rtn_roundtrip(W: Array, cfg: QuantConfig):
    if cfg.fmt == "nf4":
        codes, absmax = quantize_nf4(W, cfg.group_size)
        return dequantize_nf4(codes, absmax, cfg.group_size), (codes, absmax)
    codes, s, z = quantize_int(W, cfg.bits, cfg.group_size)
    return dequantize_int(codes, s, z, cfg.group_size), (codes, s, z)


def svd_lowrank_topr(dW_local: Array, rank: int, axis: str | None = None):
    """Top-``rank`` SVD factors of the full-width ``dW`` from a column shard.

    Same Gram trick as :func:`repro.core.cloq.cloq_lowrank_local` with
    ``R = I``:

        G = dW dW^T          -- psum over ``axis`` when given (m x m)
        top-r eigh(G) -> U, S^2   -- replicated across shards
        V_local = dW_l^T U S^{-1}   -- shard-local

    Returns ``(U (m, r), S (r,), V_local (n_local, r))`` with ``U``/``S``
    identical on every shard.  Safe under both ``shard_map`` (the psum is
    the only communication) and ``vmap`` (the batched engine maps it over a
    stacked ``(L, m, n_local)`` bucket — the psum reduces an ``(L, m, m)``
    stack in one collective).  Without ``axis`` every column is at hand,
    and a tall ``dW`` (``n < m``) is factored through the
    smaller ``(n, n)`` Gram of its transpose."""
    if axis is None and dW_local.shape[1] < dW_local.shape[0]:
        V, S, U = svd_lowrank_topr(dW_local.T, rank)
        return U, S, V
    G = dW_local @ dW_local.T
    if axis is not None:
        G = jax.lax.psum(G, axis)
    top, U = sym_topr(G, rank)
    S = jnp.sqrt(jnp.maximum(top, 1e-30))
    V_l = (dW_local.T @ U) / S[None, :]                 # (n_local, r)
    return U, S, V_l


@scope("loftq")
def loftq_init(W: Array, cfg: QuantConfig, rank: int, iters: int = 5,
               axis: str | None = None):
    """Returns (Q_dequant, A, B, qstate) after ``iters`` AltMin rounds.

    Vmap-safe: the AltMin loop is a static Python unroll of traced ops, so
    the batched engine maps it across a stacked ``(L, m, n)`` bucket.

    With ``axis`` set, ``W`` is a column shard inside a ``shard_map`` body:
    the RTN round-trip is already per-column, and the rank-r factors of the
    full-width ``W - Q`` come from :func:`svd_lowrank_topr` — one
    ``(m, m)`` psum per AltMin round.  ``A`` comes back replicated, ``B``
    and ``qstate`` cover the local columns."""
    W = jnp.asarray(W, jnp.float32)
    m, n = W.shape
    A = jnp.zeros((m, rank), jnp.float32)
    B = jnp.zeros((n, rank), jnp.float32)
    Qd, qstate = _rtn_roundtrip(W, cfg)
    for _ in range(iters):
        Qd, qstate = _rtn_roundtrip(W - A @ B.T, cfg)
        if axis is None:
            U_f, S_f, Vt = jnp.linalg.svd(W - Qd, full_matrices=False)
            U, S, V = U_f[:, :rank], S_f[:rank], Vt[:rank, :].T
        else:
            U, S, V = svd_lowrank_topr(W - Qd, rank, axis)
        rt = jnp.sqrt(S)
        A = U * rt[None, :]
        B = V * rt[None, :]
    return Qd, A, B, qstate


def qlora_init(W: Array, cfg: QuantConfig, rank: int, key: Array | None = None):
    """QLoRA baseline: NF4 RTN quantization + standard LoRA init
    (A ~ N(0, 1/m) Kaiming-ish, B = 0) — zero perturbation at start."""
    W = jnp.asarray(W, jnp.float32)
    m, n = W.shape
    nf4_cfg = QuantConfig(bits=4, group_size=cfg.group_size, fmt="nf4")
    Qd, qstate = _rtn_roundtrip(W, nf4_cfg)
    key = jax.random.PRNGKey(0) if key is None else key
    A = jax.random.normal(key, (m, rank), jnp.float32) / jnp.sqrt(m)
    B = jnp.zeros((n, rank), jnp.float32)
    return Qd, A, B, qstate


def gptq_lora_init(Qd: Array, m: int, n: int, rank: int,
                   key: Array | None = None):
    """GPTQ-LoRA baseline: OPTQ base (computed by caller) + zero LoRA init."""
    key = jax.random.PRNGKey(0) if key is None else key
    A = jax.random.normal(key, (m, rank), jnp.float32) / jnp.sqrt(m)
    B = jnp.zeros((n, rank), jnp.float32)
    return A, B
