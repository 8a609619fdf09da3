"""Theorem 3.1 and CLoQ-core properties (the paper's central math)."""
import jax.numpy as jnp
import numpy as np
import pytest
from tests._hypothesis_compat import given, settings, st

from repro.core.cloq import (chol_root, cloq_init, cloq_lowrank_local,
                             discrepancy_norms, gram_root, lowrank_objective,
                             regularize_gram, split_factors)
from repro.core.magr import magr_preprocess, project_l1_ball, prox_linf
from repro.core.optq import optq_quantize, gram_error
from repro.core.quantizer import QuantConfig, rtn


def _case(seed, m=48, n=64, t=256):
    rng = np.random.default_rng(seed)
    W = jnp.asarray(rng.normal(size=(m, n)), jnp.float32)
    X = jnp.asarray(rng.normal(size=(t, m)), jnp.float32)
    H = regularize_gram(X.T @ X)
    return W, X, H


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2**31 - 1), st.sampled_from([2, 4, 8, 16]))
def test_theorem31_attains_optimum(seed, r):
    """Closed form achieves exactly the Eckart-Young optimum of ||R(AB^T-dW)||."""
    W, X, H = _case(seed)
    dW = W - rtn(W, QuantConfig(bits=2, group_size=16))
    A, B = cloq_init(H, dW, r)
    R, _ = gram_root(H)
    S = jnp.linalg.svd(R @ dW, compute_uv=False)
    opt = float(jnp.sqrt(jnp.sum(S[r:] ** 2)))
    got = lowrank_objective(H, dW, A, B)
    assert got <= opt * (1 + 1e-3) + 1e-4


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_cloq_beats_naive_svd_init(seed):
    """Data-aware init <= data-free SVD(dW) init in the calibrated norm."""
    W, X, H = _case(seed)
    dW = W - rtn(W, QuantConfig(bits=2, group_size=16))
    r = 8
    A, B = cloq_init(H, dW, r)
    U, S, Vt = jnp.linalg.svd(dW, full_matrices=False)
    A_n, B_n = U[:, :r] * S[:r], Vt[:r].T
    assert lowrank_objective(H, dW, A, B) <= \
        lowrank_objective(H, dW, A_n, B_n) * (1 + 1e-4)


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_splits_same_product(seed):
    W, X, H = _case(seed)
    dW = W - rtn(W, QuantConfig(bits=2, group_size=16))
    prods = []
    for sp in ("paper", "bsigma", "sqrt"):
        A, B = cloq_init(H, dW, 8, sp)
        prods.append(A @ B.T)
    np.testing.assert_allclose(np.asarray(prods[0]), np.asarray(prods[1]),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(prods[0]), np.asarray(prods[2]),
                               atol=1e-4)


def test_gram_root_identity():
    _, _, H = _case(0)
    R, Rinv = gram_root(H)
    np.testing.assert_allclose(np.asarray(R.T @ R), np.asarray(H),
                               rtol=2e-4, atol=2e-3)
    eye = np.asarray(R @ Rinv)
    np.testing.assert_allclose(eye, np.eye(H.shape[0]), atol=1e-3)


def test_rank_deficient_gram_pseudoinverse_path():
    """X rank-deficient: the eigenvalue-floored Rinv still yields finite,
    improving adapters (Theorem 3.1 remark)."""
    rng = np.random.default_rng(1)
    m, n, t = 32, 24, 12          # t < m  => H rank-deficient
    W = jnp.asarray(rng.normal(size=(m, n)), jnp.float32)
    X = jnp.asarray(rng.normal(size=(t, m)), jnp.float32)
    H = X.T @ X                   # deliberately unregularized
    dW = W - rtn(W, QuantConfig(bits=2, group_size=16))
    A, B = cloq_init(H, dW, 4)
    assert bool(jnp.all(jnp.isfinite(A))) and bool(jnp.all(jnp.isfinite(B)))
    assert lowrank_objective(H, dW, A, B) <= gram_error(H, dW) + 1e-3


@pytest.mark.parametrize("m,t", [(32, 12), (256, 64)])
def test_rank_deficient_gram_cholesky_root(m, t):
    """The TPU's Cholesky root (run here on the CPU) keeps the remark's
    contract: finite adapters within 1e-3 of the eigen root's objective
    on an unregularized, rank-deficient H, and the same product on a
    regularized one."""
    rng = np.random.default_rng(3)
    W = jnp.asarray(rng.normal(size=(m, m // 2)), jnp.float32)
    X = jnp.asarray(rng.normal(size=(t, m)), jnp.float32)
    dW = W - rtn(W, QuantConfig(bits=2, group_size=16))
    for H in (X.T @ X, regularize_gram(X.T @ X)):
        R, Rinv = chol_root(H)
        A, B = cloq_lowrank_local(R, Rinv, dW, 4)
        assert bool(jnp.all(jnp.isfinite(A))) and bool(jnp.all(jnp.isfinite(B)))
        A_e, B_e = cloq_init(H, dW, 4)
        ref = lowrank_objective(H, dW, A_e, B_e)
        assert lowrank_objective(H, dW, A, B) <= ref * (1 + 1e-3) + 1e-4
    prod, want = np.asarray(A @ B.T), np.asarray(A_e @ B_e.T)
    assert np.linalg.norm(prod - want) / np.linalg.norm(want) < 1e-2


def test_discrepancy_cloq_below_rtn_and_loftq():
    """Fig. 2 ordering: CLoQ discrepancy < LoftQ < plain RTN.

    Anisotropic activations (power-law feature spectrum, the realistic LLM
    regime that calibration exploits): CLoQ spends its rank budget on the
    data-weighted directions, LoftQ cannot."""
    from repro.core.loftq import loftq_init
    rng = np.random.default_rng(2)
    m, n = 64, 96
    W = jnp.asarray(rng.normal(size=(m, n)), jnp.float32)
    aniso = jnp.asarray(np.geomspace(10.0, 0.1, m), jnp.float32)
    X = jnp.asarray(rng.normal(size=(1024, m)), jnp.float32) * aniso[None, :]
    H = regularize_gram(X.T @ X)
    qcfg = QuantConfig(bits=2, group_size=16)
    Qd, _, _, _ = optq_quantize(W, X.T @ X, qcfg)
    A, B = cloq_init(H, W - Qd, 16)
    fro_cloq, _ = discrepancy_norms(H, Qd, A, B, W)
    Ql, Al, Bl, _ = loftq_init(W, qcfg, 16, iters=5)
    fro_loftq, _ = discrepancy_norms(H, Ql, Al, Bl, W)
    Q_rtn = rtn(W, qcfg)
    zero = jnp.zeros((m, 16)), jnp.zeros((n, 16))
    fro_rtn, _ = discrepancy_norms(H, Q_rtn, *zero, W)
    assert fro_cloq < fro_loftq < fro_rtn * 1.01


# ---------------------------- MagR ----------------------------------------


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2**31 - 1), st.floats(0.5, 50.0))
def test_l1_projection_properties(seed, radius):
    rng = np.random.default_rng(seed)
    v = jnp.asarray(rng.normal(size=(40, 8)) * 5, jnp.float32)
    p = project_l1_ball(v, radius)
    l1 = np.abs(np.asarray(p)).sum(0)
    assert np.all(l1 <= radius * (1 + 1e-4))
    # projection is identity inside the ball
    small = jnp.asarray(rng.normal(size=(40, 8)) * radius / 200, jnp.float32)
    np.testing.assert_allclose(np.asarray(project_l1_ball(small, radius)),
                               np.asarray(small), atol=1e-6)


def test_prox_linf_shrinks_max():
    rng = np.random.default_rng(0)
    v = jnp.asarray(rng.normal(size=(64, 4)), jnp.float32)
    p = prox_linf(v, 5.0)
    assert np.all(np.abs(np.asarray(p)).max(0) <=
                  np.abs(np.asarray(v)).max(0) + 1e-6)


def test_magr_reduces_linf_keeps_calibrated_output():
    rng = np.random.default_rng(3)
    m, n = 64, 48
    W = jnp.asarray(rng.normal(size=(m, n)), jnp.float32)
    # inject outliers (MagR's target)
    W = W.at[0, :].mul(8.0)
    X = jnp.asarray(rng.normal(size=(512, m)), jnp.float32)
    H = X.T @ X
    Wt = magr_preprocess(W, H, alpha=0.01 * float(jnp.trace(H) / m), iters=30)
    assert float(jnp.max(jnp.abs(Wt))) < float(jnp.max(jnp.abs(W)))
    rel = float(jnp.linalg.norm(X @ (Wt - W)) / jnp.linalg.norm(X @ W))
    assert rel < 0.05


def test_apiq_lite_converges_to_cloq_closed_form():
    """Gradient descent on the calibrated objective converges to Theorem
    3.1's closed form — the paper's 'no backprop needed' claim."""
    from repro.core.apiq_lite import apiq_lite_init
    rng = np.random.default_rng(0)
    m, n, r = 48, 64, 6
    W = jnp.asarray(rng.normal(size=(m, n)), jnp.float32)
    aniso = jnp.asarray(np.geomspace(5.0, 0.2, m), jnp.float32)
    X = jnp.asarray(rng.normal(size=(1024, m)), jnp.float32) * aniso[None, :]
    H = regularize_gram(X.T @ X)
    dW = W - rtn(W, QuantConfig(bits=2, group_size=16))
    A_c, B_c = cloq_init(H, dW, r)
    obj_c = lowrank_objective(H, dW, A_c, B_c)
    A_a, B_a, _ = apiq_lite_init(H, dW, r, steps=800)
    obj_a = lowrank_objective(H, dW, A_a, B_a)
    assert obj_c <= obj_a * 1.01          # closed form is the optimum
    assert obj_a <= obj_c * 1.10          # and GD approaches it
