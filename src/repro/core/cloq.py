"""CLoQ (Theorem 3.1): closed-form calibrated LoRA initialization.

Given the regularized calibration Gram ``H = X^T X + lambda*I`` and the
quantization residual ``dW = W - Q``, the optimal rank-r adapters minimizing

    || X (A B^T - dW) ||_F^2

are any factorization of ``R^{-1} LR_r(R dW)`` where ``R = S_H^{1/2} U_H^T``
is the non-symmetric root of ``H`` (H = R^T R) and ``LR_r`` the best rank-r
approximation (Eckart–Young).  Exactly two symmetric eigendecompositions:
``eigh(H)`` (m x m) for the root, and the top-r SVD of ``R dW`` through the
``eigh`` of its smaller Gram (:func:`repro.core.loftq.svd_lowrank_topr`) —
independent of the calibration-set size.

Splits of ``A B^T = R^{-1} U_{:r} S_{:r} V_{:r}^T`` (paper Table 7):
    "paper" : A = R^{-1} U S,      B = V        (best; default)
    "bsigma": A = R^{-1} U,        B = V S
    "sqrt"  : A = R^{-1} U S^1/2,  B = V S^1/2

:func:`cloq_init_sharded` is the TPU-scale variant: ``dW`` column-sharded
over the model axis, the SVD of ``R dW`` computed exactly via the Gram trick
(one m x m psum per layer) — see DESIGN.md §3.  Its shard-local body is
:func:`cloq_lowrank_local`, which is **both** shard_map- and vmap-safe, so
the batched quantization engine (:mod:`repro.core.batched`) maps it over a
stacked ``(L, m, n_local)`` bucket *inside* a ``shard_map`` — one fused
program per bucket, one ``(L, m, m)`` psum of communication.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.linalg import cholesky_lower, tri_inv_lower
from repro.utils import scope

Array = jax.Array

SPLITS = ("paper", "bsigma", "sqrt")
# diagonal floor of the Cholesky root, in units of the mean eigenvalue:
# ten times what an f32 Cholesky of a rank-deficient Gram needs
CHOL_FLOOR = 1e-5


def regularize_gram(H: Array, lambda_frac: float = 0.01) -> Array:
    m = H.shape[0]
    lam = lambda_frac * jnp.trace(H) / m
    return H + (lam + 1e-8) * jnp.eye(m, dtype=H.dtype)


def chol_root(H: Array):
    """The TPU's root of :func:`gram_root`: ``R = L^T`` from the Cholesky
    factor of ``H + CHOL_FLOOR * tr(H)/m * I``.  The floor lets an
    unregularized, rank-deficient H factor in f32, so ``Rinv`` acts as
    the pseudo-inverse path of Theorem 3.1's remark; on a regularized
    Gram it adds a thousandth of the damping."""
    m = H.shape[0]
    L = cholesky_lower(H + CHOL_FLOOR * jnp.trace(H) / m
                       * jnp.eye(m, dtype=H.dtype))
    return L.T, tri_inv_lower(L).T


@scope("cloq")
def gram_root(H: Array, eps: float = 1e-10):
    """A root R with H = R^T R, plus its inverse.

    Theorem 3.1's adapters do not depend on which root: any two differ by
    an orthogonal factor that the SVD of ``R dW`` absorbs.  On a TPU the
    root is the Cholesky factor (:func:`chol_root`; an ``m x m``
    eigendecomposition there is minutes to compile or to run).
    Elsewhere it is the paper's ``R = S^{1/2} U^T`` from ``eigh(H)``,
    where a rank-deficient H has its eigenvalues floored at
    ``eps * max_eig`` so that ``Rinv`` acts as the pseudo-inverse path of
    the theorem's remark."""
    H = jnp.asarray(H, jnp.float32)

    def eig_root(h):
        evals, evecs = jnp.linalg.eigh(h)
        floor = eps * jnp.maximum(evals[-1], 1e-30)
        sq = jnp.sqrt(jnp.maximum(evals, floor))
        return sq[:, None] * evecs.T, evecs * (1.0 / sq)[None, :]

    return jax.lax.platform_dependent(H, tpu=chol_root, default=eig_root)


def split_factors(RinvU: Array, S: Array, V: Array, split: str):
    if split == "paper":
        return RinvU * S[None, :], V
    if split == "bsigma":
        return RinvU, V * S[None, :]
    if split == "sqrt":
        rt = jnp.sqrt(S)
        return RinvU * rt[None, :], V * rt[None, :]
    raise ValueError(f"unknown split {split!r}; options {SPLITS}")


@partial(jax.jit, static_argnames=("rank", "split"))
def cloq_init(H: Array, dW: Array, rank: int, split: str = "paper"):
    """Closed-form (A, B) minimizing ||X (A B^T - dW)||_F^2.

    ``H`` must already be regularized (Algorithm 1 input).  Returns
    (A (m,r), B (n,r)).  Vmap-safe: only ``rank``/``split`` are static, so
    the batched engine maps it over stacked (H, dW) buckets (and the
    shared-block driver over per-site Grams with a fixed dW)."""
    R, Rinv = gram_root(H)
    return cloq_lowrank_local(R, Rinv, jnp.asarray(dW, jnp.float32), rank,
                              split)


def lowrank_objective(H: Array, dW: Array, A: Array, B: Array) -> float:
    """||X (A B^T - dW)||_F given H = X^T X (no X materialization)."""
    D = A @ B.T - dW
    v = jnp.einsum("ij,ik,kj->", D, H, D)
    return float(jnp.sqrt(jnp.maximum(v, 0.0)))


def discrepancy_norms(H: Array, Q: Array, A: Array, B: Array, W: Array):
    """Paper Fig. 2 quantities: ||X(Q + AB^T - W)|| in Frobenius and spectral
    norm (spectral computed on R D, since ||XD||_2 = ||R D||_2)."""
    D = Q + A @ B.T - W
    R, _ = gram_root(H)
    RD = R @ D
    fro = float(jnp.linalg.norm(RD))
    spec = float(jnp.linalg.norm(RD, ord=2))
    return fro, spec


@scope("cloq")
def cloq_lowrank_local(R: Array, Rinv: Array, dW_local: Array, rank: int,
                       split: str = "paper", axis: str | None = None):
    """Shard-local body of the Gram-trick CLoQ solve.

    Computes the exact top-``rank`` factorization of ``R^{-1} LR_r(R dW)``
    from a **column shard** ``dW_local`` (m, n_local) of the residual:

        G = (R dW)(R dW)^T        -- psum over ``axis`` when given (m x m)
        eigh(G) -> U, S^2         -- replicated across shards
        V_local = (R dW)_l^T U S^{-1}   -- shard-local

    Args:
        R, Rinv:  (m, m) non-symmetric Gram root and inverse
                  (:func:`gram_root` of the *regularized* Gram), replicated.
        dW_local: (m, n_local) local column shard of ``W - Q``.
        rank:     adapter rank r (static).
        split:    one of :data:`SPLITS` (static).
        axis:     mesh axis name to all-reduce the m x m Gram over; ``None``
                  means ``dW_local`` already holds all columns (single
                  device / replicated fallback).

    Returns ``(A (m, r) replicated, B_local (n_local, r))``.

    Safe under both ``shard_map`` (the psum is the only communication) and
    ``vmap`` (the batched engine maps it over a stacked ``(L, m, n_local)``
    bucket inside one ``shard_map`` — psum then reduces a ``(L, m, m)``
    stack in one collective).  With ``axis=None`` it is the whole solve of
    :func:`cloq_init`.  Tests compare the ``A B^T`` product, the
    well-defined quantity.  The Gram-trick core is shared with LoftQ
    (:func:`repro.core.loftq.svd_lowrank_topr`) — this is the ``R != I``
    instance."""
    from repro.core.loftq import svd_lowrank_topr
    M_l = R @ dW_local                                  # (m, n_local)
    U, S, V_l = svd_lowrank_topr(M_l, rank, axis)
    return split_factors(Rinv @ U, S, V_l, split)


def cloq_site_lora(Hs: Array, dW: Array, rank: int, split: str = "paper",
                   mesh=None, axis: str = "model",
                   lambda_frac: float = 0.01):
    """Per-site CLoQ adapters of a weight-shared block: one Theorem-3.1
    solve per call site against the site's own Gram, with the residual
    ``dW = W - Q`` of the (pooled-Gram) shared base fixed.

    Args:
        Hs:    (S, m, m) stacked per-site *unregularized* Grams.
        dW:    (m, n) shared quantization residual.
        rank:  adapter rank r (static).
        split: one of :data:`SPLITS` (static).
        mesh:  optional ``jax.sharding.Mesh``.  Without one, the solve is a
               plain vmap of :func:`cloq_init` over the site Grams (dense
               SVD per site).  With one, ``dW`` is column-sharded over
               ``axis`` and the solve runs as ONE ``shard_map`` whose body
               vmaps :func:`cloq_lowrank_local` over the sites — the per-
               site ``gram_root``s are replicated compute and the S Gram
               psums fuse into a single ``(S, m, m)`` collective.  The
               caller must ensure ``n`` divides the axis (the engine's
               planner gate, :func:`repro.core.batched.bucket_shards`).
        axis:  mesh axis name.

    Returns ``(As (S, m, r), Bs (S, n, r))``; under a mesh ``Bs`` comes
    back column-sharded and ``As`` replicated."""
    dW = jnp.asarray(dW, jnp.float32)
    Hs = jnp.asarray(Hs, jnp.float32)
    if mesh is None:
        return jax.vmap(
            lambda H: cloq_init(regularize_gram(H, lambda_frac), dW, rank,
                                split))(Hs)
    from jax.sharding import PartitionSpec as P

    Rs, Rinvs = jax.vmap(
        lambda H: gram_root(regularize_gram(H, lambda_frac)))(Hs)

    def local(Rs_, Rinvs_, dW_l):
        return jax.vmap(lambda R, Rinv: cloq_lowrank_local(
            R, Rinv, dW_l, rank, split, axis))(Rs_, Rinvs_)

    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(P(None, None, None), P(None, None, None),
                                 P(None, axis)),
                       out_specs=(P(None, None, None), P(None, axis, None)))
    return fn(Rs, Rinvs, dW)


def cloq_init_sharded(H: Array, dW: Array, rank: int, mesh,
                      axis: str = "model", split: str = "paper"):
    """Distributed CLoQ: ``dW`` (m, n) column-sharded over ``axis``.

    Per-layer wrapper over :func:`cloq_lowrank_local` (exact Gram-trick
    SVD).  Communication: one m*m f32 all-reduce per layer.  The batched
    engine fuses L of these into a single program — see
    :func:`repro.core.batched.run_bucket_sharded`.
    """
    from jax.sharding import PartitionSpec as P

    R, Rinv = gram_root(jnp.asarray(H, jnp.float32))
    dW = jnp.asarray(dW, jnp.float32)

    def local(R_, Rinv_, dW_l):
        return cloq_lowrank_local(R_, Rinv_, dW_l, rank, split, axis)

    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(P(None, None), P(None, None), P(None, axis)),
                       out_specs=(P(None, None), P(axis, None)))
    return fn(R, Rinv, dW)
