"""The main path's Pallas kernels, compiled for a described TPU v5e.

Each test lowers a kernel with ``interpret=False`` at qwen3-1.7b widths
(d_model 2048, d_ff 6144, 16 query / 8 KV heads of 128) and compiles it
with the TPU compiler for a ``v5e:2x2`` topology that is described, not
attached.  The compiler refuses block shapes the chip cannot tile and
kernels that overrun its fast memory; the compiled text must hold the
Mosaic ``tpu_custom_call``, so neither the interpreter nor a reference
stood in.  Nothing runs, so nothing here says anything about results or
times.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.dequant_matmul import dequant_matmul, dequant_matmul_lora
from repro.kernels.flash_attention import flash_attention
from repro.kernels.gram import gram
from repro.core.cloq import cloq_init, regularize_gram
from repro.core.optq import inv_cholesky_upper

D_MODEL, D_FF, HQ, HKV, HEAD = 2048, 6144, 16, 8, 128
GROUP, RANK = 64, 64


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler or library lock held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compilation cache off: a
    compile for a described chip is written to it but cannot be read back
    without the chip."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compiled_text(fn, sharding, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("m_rows", [8, 128])
@pytest.mark.parametrize("k,n", [(D_MODEL, D_FF), (D_FF, D_MODEL)])
@pytest.mark.parametrize("bits", [2, 4])
def test_dequant_matmul_compiles(one_chip, bits, k, n, m_rows):
    pack = 8 // bits
    text = _compiled_text(
        lambda x, p, s, z: dequant_matmul(
            x, p, s, z, bits=bits, group_size=GROUP, bm=min(128, m_rows),
            interpret=False),
        one_chip, ((m_rows, k), jnp.bfloat16), ((k // pack, n), jnp.uint8),
        ((k // GROUP, n), jnp.float32), ((k // GROUP, n), jnp.float32))
    assert "tpu_custom_call" in text


def test_dequant_matmul_lora_compiles(one_chip):
    k, n, m_rows = D_MODEL, D_FF, 128
    text = _compiled_text(
        lambda x, p, s, z, a, b: dequant_matmul_lora(
            x, p, s, z, a, b, bits=4, group_size=GROUP, interpret=False),
        one_chip, ((m_rows, k), jnp.bfloat16), ((k // 2, n), jnp.uint8),
        ((k // GROUP, n), jnp.float32), ((k // GROUP, n), jnp.float32),
        ((k, RANK), jnp.bfloat16), ((n, RANK), jnp.bfloat16))
    assert "tpu_custom_call" in text


def test_flash_attention_decode_with_lengths_compiles(one_chip):
    b, t = 4, 256
    text = _compiled_text(
        lambda q, k, v, n: flash_attention(q, k, v, causal=False, lengths=n,
                                           interpret=False),
        one_chip, ((b, HQ, 1, HEAD), jnp.bfloat16),
        ((b, HKV, t, HEAD), jnp.bfloat16), ((b, HKV, t, HEAD), jnp.bfloat16),
        ((b,), jnp.int32))
    assert "tpu_custom_call" in text


def test_flash_attention_prefill_compiles(one_chip):
    s = 512
    text = _compiled_text(
        lambda q, k, v: flash_attention(q, k, v, causal=True,
                                        interpret=False),
        one_chip, ((1, HQ, s, HEAD), jnp.bfloat16),
        ((1, HKV, s, HEAD), jnp.bfloat16), ((1, HKV, s, HEAD), jnp.bfloat16))
    assert "tpu_custom_call" in text


def test_gram_compiles(one_chip):
    text = _compiled_text(lambda x: gram(x, interpret=False), one_chip,
                          ((4096, D_MODEL), jnp.bfloat16))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("m,n", [(D_MODEL, D_FF // 6), (D_FF, D_MODEL)])
def test_cloq_solvers_stay_small_on_tpu(one_chip, m, n):
    """On a TPU the CLoQ solve and OPTQ's factor hold no eigensolver wider
    than the subspace block (2 x rank): a full-width QDWH eigh compiles
    for minutes, and a full-width Jacobi runs for minutes."""
    def solve(H, W):
        return (cloq_init(regularize_gram(H), W, RANK),
                inv_cholesky_upper(regularize_gram(H)))
    lowered = jax.jit(solve).lower(
        jax.ShapeDtypeStruct((m, m), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((m, n), jnp.float32, sharding=one_chip))
    eighs = [ln for ln in lowered.as_text().splitlines()
             if "stablehlo.custom_call @Eigh(" in ln]
    block = f"tensor<{2 * RANK}x{2 * RANK}xf32>"
    assert eighs and all(f"({block})" in ln for ln in eighs), eighs
    lowered.compile()
