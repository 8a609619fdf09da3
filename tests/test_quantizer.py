"""Property-based tests for the uniform INT quantizer, packing, and NF4."""
import jax.numpy as jnp
import numpy as np
import pytest
from tests._hypothesis_compat import given, settings, st

from repro.core.quantizer import (NF4_LEVELS, QuantConfig, byte_order,
                                  dequantize_int, dequantize_nf4,
                                  dequantize_packed_byte_order,
                                  pack_codes, quant_params,
                                  quantize_int, quantize_nf4, rtn,
                                  quant_state_size_bytes, unpack_codes)

BITS = st.sampled_from([2, 3, 4, 8])
DIMS = st.sampled_from([(16, 8), (64, 32), (128, 16), (32, 96)])


@st.composite
def weight_case(draw):
    bits = draw(BITS)
    m, n = draw(DIMS)
    g = draw(st.sampled_from([None, 8, 16, m]))
    seed = draw(st.integers(0, 2**31 - 1))
    scale = draw(st.floats(1e-3, 1e3))
    rng = np.random.default_rng(seed)
    w = rng.normal(size=(m, n)).astype(np.float32) * scale
    return bits, g, jnp.asarray(w)


@settings(max_examples=30, deadline=None)
@given(weight_case())
def test_roundtrip_error_bounded_by_half_scale(case):
    bits, g, w = case
    codes, s, z = quantize_int(w, bits, g)
    wd = dequantize_int(codes, s, z, g)
    # per-group |w - dq| <= delta/2 (+eps): nearest-grid-point property
    m, n = w.shape
    gs = m if g is None else g
    err = jnp.abs(wd - w).reshape(m // gs, gs, n)
    bound = s[:, None, :] / 2 + 1e-5 * jnp.maximum(jnp.abs(w).max(), 1.0)
    assert bool(jnp.all(err <= bound))


@settings(max_examples=30, deadline=None)
@given(weight_case())
def test_codes_in_range_and_zero_point_valid(case):
    bits, g, w = case
    codes, s, z = quantize_int(w, bits, g)
    assert int(codes.max()) <= 2**bits - 1
    assert bool(jnp.all(z >= 0)) and bool(jnp.all(z <= 2**bits - 1))
    assert bool(jnp.all(s > 0))


@settings(max_examples=30, deadline=None)
@given(weight_case())
def test_pack_unpack_exact(case):
    bits, g, w = case
    if bits not in (2, 4):
        return
    codes, _, _ = quantize_int(w, bits, g)
    packed = pack_codes(codes, bits)
    assert packed.shape[0] == codes.shape[0] * bits // 8
    assert bool(jnp.all(unpack_codes(packed, bits, codes.shape[0]) == codes))


@pytest.mark.parametrize("bits", [2, 3, 4, 8])
def test_byte_order_dequant_is_a_permutation_of_row_order(bits):
    """A stack of packed weights dequantized in byte order, times an input
    whose features are put in that order, is the row-order product; the
    weight itself is the row-order one with its rows permuted."""
    m, n, g, E = 32, 12, 8, 3
    rng = np.random.default_rng(bits)
    ws = [quantize_int(jnp.asarray(rng.normal(size=(m, n)), jnp.float32),
                       bits, g) for _ in range(E)]
    packed = jnp.stack([pack_codes(c, bits) for c, _, _ in ws])
    scales = jnp.stack([s for _, s, _ in ws])
    zeros = jnp.stack([z for _, _, z in ws])
    rows = jnp.stack([dequantize_int(c, s, z, g) for c, s, z in ws])
    w = dequantize_packed_byte_order(packed, scales, zeros, bits, m)
    assert w.shape == rows.shape
    idx = np.asarray(byte_order(jnp.arange(m), bits))
    np.testing.assert_array_equal(np.asarray(w), np.asarray(rows)[:, idx])
    x = jnp.asarray(rng.normal(size=(E, 5, m)), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(jnp.einsum("ecm,emn->ecn", byte_order(x, bits), w)),
        np.asarray(jnp.einsum("ecm,emn->ecn", x, rows)),
        rtol=1e-5, atol=1e-5)


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_quantizing_grid_points_is_exact(seed):
    """w already on the grid => RTN reproduces it exactly."""
    rng = np.random.default_rng(seed)
    w = rng.normal(size=(32, 16)).astype(np.float32)
    cfg = QuantConfig(bits=4, group_size=16)
    wq = rtn(w, cfg)
    wq2 = rtn(wq, cfg)
    np.testing.assert_allclose(np.asarray(wq2), np.asarray(wq), atol=1e-6)


def test_nf4_levels_and_roundtrip():
    assert NF4_LEVELS.shape == (16,)
    assert float(NF4_LEVELS[0]) == -1.0 and float(NF4_LEVELS[-1]) == 1.0
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=(64, 32)), jnp.float32)
    codes, absmax = quantize_nf4(w, 16)
    wd = dequantize_nf4(codes, absmax, 16)
    # NF4 error bounded by half the largest level gap x absmax
    gaps = np.diff(np.asarray(NF4_LEVELS))
    bound = float(gaps.max()) / 2 * np.asarray(absmax).repeat(16, 0) + 1e-6
    assert np.all(np.abs(np.asarray(wd - w)) <= bound)


def test_quant_state_size_accounting():
    cfg2 = QuantConfig(bits=2, group_size=64)
    cfg16 = QuantConfig(bits=8, group_size=64)
    m, n = 4096, 4096
    s2 = quant_state_size_bytes(m, n, cfg2)
    s8 = quant_state_size_bytes(m, n, cfg16)
    dense = m * n * 2  # bf16
    # 2-bit codes + f32 scale/zero per 64-group ~= 3 bits/weight effective
    assert s2 < dense / 4
    assert s2 < s8


def test_group_divisibility_error():
    w = jnp.zeros((30, 8))
    with pytest.raises(ValueError):
        quant_params(w, 4, 16)
