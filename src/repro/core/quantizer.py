"""Uniform INT-b quantizer (asymmetric, group-wise) + NF4, with bit packing.

Conventions
-----------
Weights follow the paper's ``y = X @ W`` layout: ``W`` has shape ``(m, n)``
with ``m`` = in-features (reduction dim) and ``n`` = out-features.
Quantization groups run along the **input** dim (axis 0), matching OPTQ's
sweep order, with ``group_size=64`` default; ``group_size=None`` means
per-(output-)channel, i.e. one group spanning the whole column.

Storage layout of a quantized linear layer (all arrays jnp):
    qweight : packed codes. int2/int4/int8 pack 4/2/1 codes per uint8 along
              axis 0 -> shape (m*bits/8, n) uint8.  3-bit codes are stored
              unpacked as uint8 (documented TPU packing note in DESIGN.md).
    scales  : (m/g, n) f32   (delta)
    zeros   : (m/g, n) f32   (integer zero-point z, stored as f32)

``dequant(qweight, scales, zeros)`` returns ``delta * (q - z)`` in the
requested dtype.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

# NF4 grid from the QLoRA paper (Dettmers et al., 2023), appendix E.
NF4_LEVELS = jnp.array(
    [
        -1.0, -0.6961928009986877, -0.5250730514526367, -0.39491748809814453,
        -0.28444138169288635, -0.18477343022823334, -0.09105003625154495, 0.0,
        0.07958029955625534, 0.16093020141124725, 0.24611230194568634,
        0.33791524171829224, 0.44070982933044434, 0.5626170039176941,
        0.7229568362236023, 1.0,
    ],
    dtype=jnp.float32,
)


@dataclasses.dataclass(frozen=True)
class QuantConfig:
    bits: int = 4
    group_size: int | None = 64      # None => per-output-channel
    fmt: str = "int"                 # "int" | "nf4"
    act_order: bool = False          # OPTQ activation ordering
    magr: bool = True                # MagR preprocessing before OPTQ
    magr_alpha: float = 1e-3
    magr_iters: int = 20
    lambda_frac: float = 0.01        # damping: lambda = frac * tr(H)/m
    block_size: int = 128            # OPTQ sweep block

    def codes_per_byte(self) -> int:
        return {2: 4, 3: 1, 4: 2, 8: 1}[self.bits]

    @property
    def n_levels(self) -> int:
        return 2 ** self.bits


def stable_round(x: Array) -> Array:
    """Round-half-up with the decision boundary nudged off exact midpoints.

    MagR's l-inf prox clamps a column's positive and negative extremes to
    *exactly equal* magnitudes, which puts quantization ratios like
    ``-wmin/scale`` exactly on ``k + 0.5``.  There, ``jnp.round``'s
    half-even tie-break depends on 1-ulp differences between differently
    fused XLA programs (the batched vmap engine vs the per-layer path), and
    OPTQ's error compensation cascades a single flipped tie into many
    changed codes.  Shifting the boundary by ``eps`` removes all structural
    mass from the decision point, so every program variant rounds
    identically.  1e-5 is ~30x the worst ulp jitter at 4-bit code
    magnitudes (ties live at x <= 15.5, jitter ~ x * 1e-7) while keeping
    the nearest-grid-point bound |w - dq| <= (0.5 + 1e-5) * scale inside
    the roundtrip property test's slack (max|w| >= 1.5 * scale)."""
    return jnp.floor(x + (0.5 + 1e-5))


def _group_reshape(w: Array, group_size: int | None):
    m, n = w.shape
    g = m if group_size is None else int(group_size)
    if m % g:
        raise ValueError(f"in-features {m} not divisible by group {g}")
    return w.reshape(m // g, g, n), g


def quant_params(w: Array, bits: int, group_size: int | None = 64):
    """Asymmetric min/max scale+zero per group. Returns (scales, zeros)."""
    wg, _ = _group_reshape(jnp.asarray(w, jnp.float32), group_size)
    wmin = jnp.min(wg, axis=1)
    wmax = jnp.max(wg, axis=1)
    # force zero into range (standard asym quant; keeps z in [0, 2^b-1])
    wmin = jnp.minimum(wmin, 0.0)
    wmax = jnp.maximum(wmax, 0.0)
    scale = (wmax - wmin) / (2**bits - 1)
    scale = jnp.maximum(scale, 1e-9)
    zero = jnp.clip(stable_round(-wmin / scale), 0, 2**bits - 1)
    return scale, zero


def quantize_int(w: Array, bits: int, group_size: int | None = 64,
                 scales: Array | None = None, zeros: Array | None = None):
    """Round-to-nearest INT quantization. Returns (codes uint8 (m,n), scales, zeros)."""
    w = jnp.asarray(w, jnp.float32)
    if scales is None or zeros is None:
        scales, zeros = quant_params(w, bits, group_size)
    wg, g = _group_reshape(w, group_size)
    q = jnp.clip(stable_round(wg / scales[:, None, :]) + zeros[:, None, :],
                 0, 2**bits - 1)
    codes = q.reshape(w.shape).astype(jnp.uint8)
    return codes, scales, zeros


def dequantize_int(codes: Array, scales: Array, zeros: Array,
                   group_size: int | None = 64, dtype=jnp.float32) -> Array:
    m, n = codes.shape
    g = m if group_size is None else int(group_size)
    cg = codes.reshape(m // g, g, n).astype(jnp.float32)
    w = (cg - zeros[:, None, :]) * scales[:, None, :]
    return w.reshape(m, n).astype(dtype)


def quantize_column_entry(w_rows: Array, row_idx, scales: Array, zeros: Array,
                          bits: int, group_size: int | None, m: int) -> Array:
    """Quantize->dequantize a single row i of W (shape (n,)) with its group's
    static params; used inside the OPTQ sweep. ``row_idx`` may be traced."""
    g = m if group_size is None else int(group_size)
    gi = row_idx // g
    s = jax.lax.dynamic_index_in_dim(scales, gi, axis=0, keepdims=False)
    z = jax.lax.dynamic_index_in_dim(zeros, gi, axis=0, keepdims=False)
    q = jnp.clip(stable_round(w_rows / s) + z, 0, 2**bits - 1)
    return (q - z) * s


# -------------------------- bit packing -----------------------------------


def pack_codes(codes: Array, bits: int) -> Array:
    """Pack uint8 codes (values < 2^bits) along axis 0 into uint8 words.

    int8/int3 pass through unpacked (3-bit packing is documented as a TPU
    storage optimization; codes remain <8 so uint8 is a safe container)."""
    codes = codes.astype(jnp.uint8)
    per = {2: 4, 4: 2}.get(bits)
    if per is None:
        return codes
    m, n = codes.shape
    if m % per:
        raise ValueError(f"rows {m} not divisible by pack factor {per}")
    c = codes.reshape(m // per, per, n)
    word = jnp.zeros((m // per, n), jnp.uint8)
    for j in range(per):
        word = word | (c[:, j, :] << (bits * j))
    return word


def unpack_codes(packed: Array, bits: int, m: int) -> Array:
    per = {2: 4, 4: 2}.get(bits)
    if per is None:
        return packed
    mask = jnp.uint8(2**bits - 1)
    parts = [((packed >> (bits * j)) & mask) for j in range(per)]
    c = jnp.stack(parts, axis=1)  # (m//per, per, n)
    return c.reshape(m, packed.shape[-1])


def dequantize_packed_byte_order(packed: Array, scales: Array, zeros: Array,
                                 bits: int, m: int,
                                 dtype=jnp.float32) -> Array:
    """``dequantize_int(unpack_codes(packed, bits, m), ...)`` for packed
    INT codes ``(..., m / per, n)`` with groups ``(..., m / g, n)``, where
    ``per`` codes share a byte, but with the ``m`` rows in byte order:
    first the rows whose codes sit in each byte's lowest bits (``0, per,
    2 per, ...``), then the next bits' (``1, per + 1, ...``), and so on.
    Each part dequantizes straight from the packed bytes, with no
    interleave of rows: a product with the weight takes its input in the
    same order (:func:`byte_order`)."""
    per = {2: 4, 4: 2}.get(bits, 1)
    *lead, rows, n = packed.shape
    group = m // scales.shape[-2]

    def spread(a):          # (..., m/g, n) -> one part's (..., m/per, n)
        a = jnp.expand_dims(a, -2)
        return jnp.broadcast_to(a, (*lead, m // group, group // per, n)
                                ).reshape(*lead, rows, n)

    scales, zeros = spread(scales), spread(zeros)
    mask = jnp.uint8(2**bits - 1)
    parts = [((packed >> (bits * j)) & mask) if per > 1 else packed
             for j in range(per)]
    return jnp.concatenate([((c.astype(jnp.float32) - zeros) * scales)
                            .astype(dtype) for c in parts], axis=-2)


def byte_order(x: Array, bits: int) -> Array:
    """``x``'s last axis, of ``m`` features, in the row order of
    :func:`dequantize_packed_byte_order`, so that ``byte_order(x) @ w``
    with that weight is ``x @ dequantize_int(...)``."""
    per = {2: 4, 4: 2}.get(bits, 1)
    *lead, m = x.shape
    return jnp.swapaxes(x.reshape(*lead, m // per, per), -1, -2
                        ).reshape(*lead, m)


# ----------------------------- NF4 -----------------------------------------


def quantize_nf4(w: Array, group_size: int | None = 64):
    """NF4 (QLoRA): absmax-normalized nearest-level codes per group.

    Returns (codes uint8 (m,n) in [0,16), absmax (m/g, n))."""
    w = jnp.asarray(w, jnp.float32)
    wg, g = _group_reshape(w, group_size)
    absmax = jnp.maximum(jnp.max(jnp.abs(wg), axis=1), 1e-9)
    norm = wg / absmax[:, None, :]
    dist = jnp.abs(norm[..., None] - NF4_LEVELS)          # (G,g,n,16)
    codes = jnp.argmin(dist, axis=-1).astype(jnp.uint8)
    return codes.reshape(w.shape), absmax


def dequantize_nf4(codes: Array, absmax: Array, group_size: int | None = 64,
                   dtype=jnp.float32) -> Array:
    m, n = codes.shape
    g = m if group_size is None else int(group_size)
    cg = codes.reshape(m // g, g, n)
    w = NF4_LEVELS[cg] * absmax[:, None, :]
    return w.reshape(m, n).astype(dtype)


# ------------------------ convenience: RTN round-trip ----------------------


def rtn(w: Array, cfg: QuantConfig) -> Array:
    """Round-to-nearest dequantized weights (data-free baseline)."""
    if cfg.fmt == "nf4":
        codes, absmax = quantize_nf4(w, cfg.group_size)
        return dequantize_nf4(codes, absmax, cfg.group_size)
    codes, s, z = quantize_int(w, cfg.bits, cfg.group_size)
    return dequantize_int(codes, s, z, cfg.group_size)


def quant_state_size_bytes(m: int, n: int, cfg: QuantConfig) -> int:
    """Storage cost of the quantized layer (codes + scales + zeros)."""
    g = m if cfg.group_size is None else cfg.group_size
    code_bytes = m * n if cfg.bits in (3, 8) else m * n * cfg.bits // 8
    meta = (m // g) * n * 4 * 2
    return code_bytes + meta
