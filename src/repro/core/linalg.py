"""Dense factorizations of the quantize engine, chosen where a program is
lowered.

On a TPU the stock solvers fail a quantize job at the solver sizes of a
1-2B model.  XLA's default (QDWH) ``eigh`` compiles for 171 s at
2048 x 2048, and its Cholesky and triangular-solve expanders for ~35 s
each at 6144 (compiled for a described v5e on an 8-core host).  The
Jacobi ``eigh``, which compiles in seconds, runs for seconds at 2048 and
minutes at 6144 (one v5e).  So on a TPU:

* :func:`cholesky_lower` and :func:`tri_inv_lower` run a blocked
  algorithm in a ``fori_loop`` whose body holds one ``BLOCK``-sized
  factorization, so the program does not grow with ``m``;
* :func:`sym_topr` finds the top eigenpairs of a symmetric PSD matrix by
  block subspace iteration (``TOPR_ITERS`` steps, ``2 * rank`` columns,
  Cholesky-QR) and a Rayleigh-Ritz solve of that small block: matmuls
  the MXU runs, and one ``eigh`` small enough for Jacobi.

Elsewhere (the CPU test suite) each is the stock ``jnp`` call.  All are
vmap- and shard_map-safe.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.scipy.linalg import solve_triangular

Array = jax.Array

BLOCK = 128
TOPR_ITERS = 100


def _orth(Y: Array) -> Array:
    """Orthonormal columns spanning ``Y`` (shifted Cholesky-QR, twice)."""
    k = Y.shape[1]
    for _ in range(2):
        C = Y.T @ Y
        C = C + 1e-7 * jnp.trace(C) / k * jnp.eye(k, dtype=Y.dtype)
        Y = solve_triangular(jnp.linalg.cholesky(C), Y.T, lower=True).T
    return Y


def _topr_subspace(G: Array, rank: int) -> tuple[Array, Array]:
    """Block subspace iteration on ``2 * rank`` columns, then the
    Rayleigh-Ritz ``eigh`` of the small block (at most 256 wide for the
    ranks LoRA uses, which a TPU solves with Jacobi)."""
    m = G.shape[0]
    k = min(m, 2 * rank)
    if k == m:
        w, v = jnp.linalg.eigh(G)
    else:
        start = jax.random.normal(jax.random.PRNGKey(0), (m, k), G.dtype)
        Q = jax.lax.fori_loop(0, TOPR_ITERS, lambda i, Q: _orth(G @ Q),
                              _orth(G @ start))
        w, v = jnp.linalg.eigh(Q.T @ G @ Q)
        v = Q @ v
    return w[::-1][:rank], v[:, ::-1][:, :rank]


def sym_topr(G: Array, rank: int) -> tuple[Array, Array]:
    """Top-``rank`` ``(eigenvalues, eigenvectors)``, descending, of a
    symmetric positive semi-definite matrix."""
    def stock(g):
        w, v = jnp.linalg.eigh(g)
        return w[::-1][:rank], v[:, ::-1][:, :rank]
    return jax.lax.platform_dependent(
        G, tpu=lambda g: _topr_subspace(g, rank), default=stock)


def _blocked(m: int) -> bool:
    return m > BLOCK and m % BLOCK == 0


def _cholesky_blocked(H: Array) -> Array:
    """Right-looking blocked Cholesky: step ``k`` factors the diagonal
    block, solves the column panel below it, and subtracts the panel's
    outer product from the trailing matrix (masked full-size updates)."""
    m, b = H.shape[0], BLOCK
    below_of = jnp.arange(m)[:, None]

    def step(k, carry):
        A, L = carry
        s = k * b
        Lkk = jnp.linalg.cholesky(jax.lax.dynamic_slice(A, (s, s), (b, b)))
        col = jax.lax.dynamic_slice(A, (0, s), (m, b))
        panel = solve_triangular(Lkk, col.T, lower=True).T      # A_ik Lkk^-T
        panel = jnp.where(below_of >= s + b, panel, 0.0)
        A = A - panel @ panel.T
        L = jax.lax.dynamic_update_slice(
            L, jax.lax.dynamic_update_slice(panel, Lkk, (s, 0)), (0, s))
        return A, L

    _, L = jax.lax.fori_loop(0, m // b, step, (H, jnp.zeros_like(H)))
    return L


def _tri_inv_blocked(L: Array) -> Array:
    """Inverse of a lower-triangular matrix by block rows:
    ``X_kk = L_kk^-1`` and ``X_k,<k = -X_kk (L_k,<k X_<k,<k)``; rows of
    ``X`` at and after block ``k`` are still zero when step ``k`` reads
    it, so one masked full-width product gives the sum over ``i < k``."""
    m, b = L.shape[0], BLOCK
    cols = jnp.arange(m)[None, :]
    eye = jnp.eye(b, dtype=L.dtype)

    def step(k, X):
        s = k * b
        row = jax.lax.dynamic_slice(L, (s, 0), (b, m))
        Xkk = solve_triangular(jax.lax.dynamic_slice(L, (s, s), (b, b)), eye,
                               lower=True)
        Xrow = -Xkk @ (jnp.where(cols < s, row, 0.0) @ X)
        Xrow = jax.lax.dynamic_update_slice(Xrow, Xkk, (0, s))
        return jax.lax.dynamic_update_slice(X, Xrow, (s, 0))

    return jax.lax.fori_loop(0, m // b, step, jnp.zeros_like(L))


def cholesky_lower(H: Array) -> Array:
    """Lower ``L`` with ``H = L L^T`` (NaN where ``H`` is not positive
    definite, as ``jnp.linalg.cholesky``)."""
    if not _blocked(H.shape[0]):
        return jnp.linalg.cholesky(H)
    return jax.lax.platform_dependent(H, tpu=_cholesky_blocked,
                                      default=jnp.linalg.cholesky)


def tri_inv_lower(L: Array) -> Array:
    """``L^-1`` of a lower-triangular ``L``."""
    eye = jnp.eye(L.shape[0], dtype=L.dtype)

    def stock(L_):
        return solve_triangular(L_, eye, lower=True)
    if not _blocked(L.shape[0]):
        return stock(L)
    return jax.lax.platform_dependent(L, tpu=_tri_inv_blocked, default=stock)
