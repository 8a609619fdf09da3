"""Operations and bytes that the model's work needs, from shapes alone.

These count what the algorithm requires, not what the program happens to
execute: a later change to the program leaves them as they are.  ``c`` is
a configuration file (``bench/configs/*.json``) of a dense decoder.
"""
from __future__ import annotations


def site_shapes(c: dict) -> dict:
    """``{site: (in, out)}`` of one layer's linears."""
    d, hd = c["d_model"], c["head_dim"]
    q, kv = c["n_heads"] * hd, c["n_kv_heads"] * hd
    return {"attn.q": (d, q), "attn.k": (d, kv), "attn.v": (d, kv),
            "attn.o": (q, d), "mlp.gate": (d, c["d_ff"]),
            "mlp.up": (d, c["d_ff"]), "mlp.down": (c["d_ff"], d)}


def linear_macs(c: dict) -> int:
    """Multiply-adds of every layer's base linears, per token."""
    return c["n_layers"] * sum(m * n for m, n in site_shapes(c).values())


def lora_macs(c: dict, rank: int) -> int:
    """Multiply-adds of ``x A B^T`` at every site, per token."""
    return c["n_layers"] * sum(rank * (m + n)
                               for m, n in site_shapes(c).values())


def attention_macs(c: dict, context: float) -> float:
    """Multiply-adds of scores and weighted values for one query that
    attends to ``context`` positions, over all layers."""
    return 2 * c["n_layers"] * c["n_heads"] * c["head_dim"] * context


def head_macs(c: dict) -> int:
    return c["d_model"] * c["vocab"]


def decode_token_flops(c: dict, rank: int, context: int) -> float:
    """One token through the model at a position with ``context``
    positions in its cache (itself included)."""
    return 2.0 * (linear_macs(c) + lora_macs(c, rank)
                  + attention_macs(c, context) + head_macs(c))


def train_token_flops(c: dict, rank: int, seq_len: int) -> float:
    """Forward and backward per token of a causal row of ``seq_len``:
    the frozen base and head need only the gradient of their input, the
    adapters that and their own; attention's backward is twice its
    forward; nothing recomputed counts."""
    ctx = (seq_len + 1) / 2.0
    fwd = (linear_macs(c) + lora_macs(c, rank) + attention_macs(c, ctx)
           + head_macs(c))
    bwd = (linear_macs(c) + 2 * lora_macs(c, rank)
           + 2 * attention_macs(c, ctx) + head_macs(c))
    return 2.0 * (fwd + bwd)


def dequant_matmul_call(c: dict, rows: int, m: int, n: int) -> tuple:
    """(flops, bytes) of one packed-INT4 matmul of ``rows`` bf16 rows:
    codes (4 bits a weight), f32 scales and zeros per group, bf16 input
    and output."""
    g = c["quant"]["group_size"]
    flops = 2.0 * rows * m * n
    nbytes = m * n * c["quant"]["bits"] / 8 + 2 * 4 * (m // g) * n \
        + 2 * rows * (m + n)
    return flops, nbytes


def decode_attention_call(c: dict, lengths: list) -> tuple:
    """(flops, bytes) of one layer's decode attention over rows whose
    caches hold ``lengths`` positions: bf16 keys and values up to each
    row's length, one bf16 query and output per row and head."""
    H, Hkv, hd = c["n_heads"], c["n_kv_heads"], c["head_dim"]
    flops = sum(4.0 * H * hd * n for n in lengths)
    nbytes = sum(2 * 2 * Hkv * hd * n for n in lengths) \
        + 2 * 2 * H * hd * len(lengths)
    return flops, nbytes


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> tuple:
    """Least time the chip needs, and which bound sets it."""
    tf = flops / peaks["bf16_flops_per_s"]
    tb = nbytes / peaks["hbm_bytes_per_s"]
    return max(tf, tb), ("compute" if tf >= tb else "memory")
