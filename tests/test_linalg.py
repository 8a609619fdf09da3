"""The quantize engine's factorizations (``repro.core.linalg``).

On a TPU, ``cholesky_lower`` and ``tri_inv_lower`` run blocked
``fori_loop`` algorithms and ``sym_topr`` runs subspace iteration; these
tests run those bodies on the CPU against the stock solvers, and check
the OPTQ factor and the CLoQ adapters built from them."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.linalg import (BLOCK, _cholesky_blocked, _topr_subspace,
                               _tri_inv_blocked, cholesky_lower, sym_topr,
                               tri_inv_lower)
from repro.core.optq import inv_cholesky_upper


def _spd(m, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(2 * m, m)) * np.geomspace(3.0, 0.1, m)
    return jnp.asarray(X.T @ X + 0.01 * np.eye(m), jnp.float32)


@pytest.mark.parametrize("blocks", [2, 3])
def test_blocked_cholesky_matches_stock(blocks):
    H = _spd(blocks * BLOCK)
    L = np.asarray(_cholesky_blocked(H), np.float64)
    ref = np.asarray(jnp.linalg.cholesky(H), np.float64)
    assert np.allclose(np.triu(L, 1), 0.0)
    np.testing.assert_allclose(L, ref, rtol=1e-3, atol=1e-3 * np.abs(ref).max())


@pytest.mark.parametrize("blocks", [2, 3])
def test_blocked_tri_inverse_matches_stock(blocks):
    L = jnp.linalg.cholesky(_spd(blocks * BLOCK, seed=1))
    X = np.asarray(_tri_inv_blocked(L), np.float64)
    np.testing.assert_allclose(X @ np.asarray(L, np.float64),
                               np.eye(L.shape[0]), atol=2e-3)
    assert np.allclose(np.triu(X, 1), 0.0)


def test_blocked_cholesky_not_positive_definite_is_nan():
    H = _spd(2 * BLOCK).at[BLOCK + 3, BLOCK + 3].set(-1.0)
    assert not np.isfinite(np.asarray(_cholesky_blocked(H))).all()


@pytest.mark.parametrize("m", [48, 2 * BLOCK])
def test_inv_cholesky_upper_factors_inverse(m):
    """U upper with U^T U = H^-1, on the stock path (m=48) and at a
    blocked size."""
    H = _spd(m, seed=2)
    U = np.asarray(inv_cholesky_upper(H), np.float64)
    assert np.allclose(np.tril(U, -1), 0.0) and (np.diag(U) > 0).all()
    Hinv = np.linalg.inv(np.asarray(H, np.float64))
    np.testing.assert_allclose(U.T @ U, Hinv, rtol=1e-3,
                               atol=1e-3 * np.abs(Hinv).max())


def test_stock_paths_off_tpu():
    H = _spd(2 * BLOCK, seed=3)
    np.testing.assert_array_equal(np.asarray(cholesky_lower(H)),
                                  np.asarray(jnp.linalg.cholesky(H)))
    L = cholesky_lower(H)
    np.testing.assert_allclose(np.asarray(tri_inv_lower(L) @ L),
                               np.eye(L.shape[0]), atol=2e-3)
    w, v = sym_topr(H, 8)
    w_ref = np.linalg.eigvalsh(np.asarray(H, np.float64))[::-1][:8]
    np.testing.assert_allclose(np.asarray(w), w_ref, rtol=1e-4)
    assert v.shape == (H.shape[0], 8)


@pytest.mark.parametrize("spectrum", ["flat", "decaying"])
def test_subspace_topr_matches_eigh(spectrum):
    """Top-16 of a 512 x 512 Gram: eigenvalues to f32 precision and the
    same invariant subspace, on a flat (random) and a decaying spectrum."""
    rng = np.random.default_rng(4)
    M = rng.normal(size=(512, 1024))
    if spectrum == "decaying":
        M *= np.geomspace(10.0, 0.1, 512)[:, None]
    G = jnp.asarray(M @ M.T, jnp.float32)
    w, V = _topr_subspace(G, 16)
    w_ref, V_ref = np.linalg.eigh(np.asarray(G, np.float64))
    w_ref, V_ref = w_ref[::-1][:16], V_ref[:, ::-1][:, :16]
    np.testing.assert_allclose(np.asarray(w, np.float64), w_ref, rtol=1e-4)
    V = np.asarray(V, np.float64)
    assert np.linalg.norm(V @ V.T - V_ref @ V_ref.T) < 1e-3


def test_cloq_adapters_with_the_tpu_solvers_match_stock():
    """cloq_lowrank_local on the TPU's root (Cholesky) and top-r solver
    (subspace iteration), run on the CPU, gives the stock path's A B^T."""
    from repro.core import cloq
    from repro.core.loftq import svd_lowrank_topr
    rng = np.random.default_rng(5)
    m, n, r = 256, 384, 16
    X = rng.normal(size=(1024, m)) * np.geomspace(5.0, 0.2, m)
    H = cloq.regularize_gram(jnp.asarray(X.T @ X, jnp.float32))
    dW = jnp.asarray(rng.normal(size=(m, n)), jnp.float32)
    A, B = cloq.cloq_init(H, dW, r)
    L = _cholesky_blocked(H)
    R, Rinv = L.T, _tri_inv_blocked(L).T
    M = R @ dW
    G = M @ M.T
    top, U = _topr_subspace(G, r)
    S = jnp.sqrt(top)
    A_t, B_t = cloq.split_factors(Rinv @ U, S, (M.T @ U) / S[None, :],
                                  "paper")
    want = np.asarray(A @ B.T, np.float64)
    got = np.asarray(A_t @ B_t.T, np.float64)
    assert np.linalg.norm(got - want) / np.linalg.norm(want) < 1e-3
    assert svd_lowrank_topr(M, r)[1].shape == (r,)
