"""Seeded weights for the benchmark, made on the device.

The program under test is handed a packed INT-b base and LoRA adapters.
Both are drawn here from ``--seed``, so the plain reference can draw the
very same numbers again, layer by layer, without taking anything the
program made.  Codes, zeros and scales are drawn directly (no rounding of
drawn float weights), so the two draws agree bit for bit whatever the
compiler fuses.

Every leaf of the program's layout (``quantized_param_shapes``) gets its own
key, ``fold_in(seed key, crc32(path))``; a leaf stacked over layers draws
layer ``i`` from ``fold_in(leaf key, i)``.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

EMBED_STD = 0.02
SCALE_SPREAD = (0.8, 1.2)


def seed_key(seed: int) -> jax.Array:
    return jax.random.PRNGKey(int(seed))


def leaf_key(key: jax.Array, path: str) -> jax.Array:
    return jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def draw_codes(key, shape, bits: int) -> jax.Array:
    """INT-b codes (..., m, n), uint8 in [0, 2^b)."""
    return jax.random.randint(key, shape, 0, 2 ** bits,
                              jnp.int32).astype(jnp.uint8)


def draw_zeros(key, shape, bits: int) -> jax.Array:
    """Zero points next to the middle level, 2^(b-1) - 1 or 2^(b-1)."""
    mid = 2 ** (bits - 1)
    return jax.random.randint(key, shape, mid - 1, mid + 1,
                              jnp.int32).astype(jnp.float32)


def draw_scales(key, shape, m: int, bits: int) -> jax.Array:
    """Scales that give ``scale * (code - zero)`` about the 1/sqrt(m)
    spread of an initialized layer: (code - zero) spreads by about
    sqrt((4^b - 1) / 12), 4.6 levels at 4 bits."""
    spread = np.sqrt((4.0 ** bits - 1) / 12)
    u = jax.random.uniform(key, shape, jnp.float32, *SCALE_SPREAD)
    return u * np.float32(1.0 / (spread * np.sqrt(m)))


def draw_lora(key, shape, dtype, std: float) -> jax.Array:
    """LoRA factor ``shape`` with entries N(0, std^2)."""
    w = jax.random.normal(key, shape, jnp.float32) * np.float32(std)
    return w.astype(dtype)


def adapter_layer(key, m: int, n: int, rank: int, dtype,
                  share: float | None = None) -> dict:
    """One layer's LoRA pair: ``lora_a`` (m, r) and ``lora_b`` (n, r).

    By default both factors are N(0, 0.09/k) with k their first size, so
    that ``x A B^T`` is a modest change of a site's output (the initial
    adapters of fine-tuning).  With ``share``, ``A`` is N(0, 1/m) and
    ``B`` N(0, share^2/r): for an input of unit spread ``x A B^T`` then
    spreads by ``share`` times the base's output, a tenant's trained
    adapter whose effect shows in the served tokens."""
    ka, kb = jax.random.split(key)
    if share is None:
        sa, sb = 0.3 / np.sqrt(m), 0.3 / np.sqrt(n)
    else:
        sa, sb = 1.0 / np.sqrt(m), share / np.sqrt(rank)
    return {"lora_a": draw_lora(ka, (m, rank), dtype, sa),
            "lora_b": draw_lora(kb, (n, rank), dtype, sb)}


def make_adapters(key, template: dict, rank: int, dtype,
                  share: float | None = None) -> dict:
    """A tenant's adapters ``{site: {"lora_a": (L, m, r), "lora_b":
    (L, n, r)}}`` for ``template = {site: (L, m, n)}``; layer ``i`` of a
    site is ``adapter_layer(fold_in(leaf_key(key, site), i), ...)``."""
    out = {}
    for site, (L, m, n) in sorted(template.items()):
        out[site] = _per_layer(
            lambda k, m=m, n=n: adapter_layer(k, m, n, rank, dtype, share),
            leaf_key(key, site), L)
    return out


def quant_site(key, m: int, n: int, group: int, bits: int):
    """One quantized linear's codes (m, n), scales and zeros (m/g, n)."""
    kc, ks, kz = jax.random.split(key, 3)
    return (draw_codes(kc, (m, n), bits),
            draw_scales(ks, (m // group, n), m, bits),
            draw_zeros(kz, (m // group, n), bits))


def dequant(codes, scales, zeros, group: int) -> jax.Array:
    """f32 weight (m, n) = scale * (code - zero), groups along m."""
    m, n = codes.shape
    c = codes.astype(jnp.float32).reshape(m // group, group, n)
    return ((c - zeros[:, None, :]) * scales[:, None, :]).reshape(m, n)


def _per_layer(fn, key, n_layers: int):
    """Stack ``fn(fold_in(key, i))`` over layers, one layer at a time."""
    return jax.lax.map(lambda i: fn(jax.random.fold_in(key, i)),
                       jnp.arange(n_layers))


def _site_leaves(key, m, n, group, bits, rank, lead, dtype, pack):
    """Leaves of one quantized site; ``lead`` is () or (E,) (experts)."""
    def one(k):
        codes, scales, zeros = quant_site(k, m, n, group, bits)
        out = {"qcodes": pack(codes), "scales": scales, "zeros": zeros}
        if rank:
            out.update(adapter_layer(jax.random.fold_in(k, 1), m, n, rank,
                                     dtype))
        return out
    if lead:
        return jax.vmap(one)(jax.random.split(key, lead[0]))
    return one(key)


def site_layer(key, path: str, layer, m, n, group, bits, rank, lead, dtype,
               pack=lambda c: c):
    """One layer of the site at ``path``: the draw that ``make_params``
    stacks, so the reference can make layer ``layer`` alone."""
    return _site_leaves(jax.random.fold_in(leaf_key(key, path), layer), m, n,
                        group, bits, rank, lead, dtype, pack)


def make_params(key, shapes: dict, group: int, bits: int, rank: int, dtype,
                vocab: int, lora_dtype=None):
    """A param tree with the structure of ``shapes`` (the program's
    quantized layout, a nested dict of ShapeDtypeStruct): quantized sites
    are drawn site by site, embeddings and heads as :func:`dense_leaf`
    says, norm scales are ones.  Call it under ``jax.jit`` with
    ``shapes`` and the rest static.  LoRA factors are drawn in
    ``lora_dtype`` (default ``dtype``)."""
    from repro.core.quantizer import pack_codes
    lora_dtype = lora_dtype or dtype

    def walk(node, path, n_layers):
        if "qcodes" in node:
            m = node["scales"].shape[-2] * group
            n = node["qcodes"].shape[-1]
            lead = node["qcodes"].shape[1:-2] if n_layers else ()
            r = rank if "lora_a" in node else 0
            pack = lambda c: pack_codes(c, bits)    # noqa: E731
            if n_layers:
                return _per_layer(
                    lambda k: _site_leaves(k, m, n, group, bits, r, lead,
                                           lora_dtype, pack),
                    leaf_key(key, path), n_layers)
            return _site_leaves(leaf_key(key, path), m, n, group, bits, r,
                                (), lora_dtype, pack)
        out = {}
        for name, sub in node.items():
            p = f"{path}.{name}" if path else name
            if isinstance(sub, dict):
                out[name] = walk(sub, p, n_layers)
            else:
                out[name] = leaf(sub, p, n_layers)
        return out

    def leaf(sds, path, n_layers):
        name = path.rsplit(".", 1)[-1]
        if name == "scale":
            return jnp.ones(sds.shape, sds.dtype)
        if name == "bias":
            return jnp.zeros(sds.shape, sds.dtype)
        k = leaf_key(key, path)
        if path.startswith("blocks.") and n_layers:
            shape = sds.shape[1:]
            return _per_layer(lambda kk: dense_leaf(kk, path, shape,
                                                    sds.dtype, vocab),
                              k, n_layers)
        return dense_leaf(k, path, sds.shape, sds.dtype, vocab)

    out = {}
    for name, sub in shapes.items():
        if name == "blocks":
            L = jax.tree.leaves(sub)[0].shape[0]
            out[name] = walk(sub, name, L)
        elif isinstance(sub, dict):
            out[name] = walk(sub, name, 0)
        else:
            out[name] = leaf(sub, name, 0)
    return out


def dense_leaf(key, path: str, shape, dtype, vocab: int):
    """A dense (unquantized) weight.  The embedding (V_pad, d) is
    N(0, EMBED_STD^2), an untied head (d, V_pad) and a router (d, E) are
    N(0, 1/d).  Rows (embedding) or columns (head) past ``vocab`` are the
    program's padding and are zero, so that only the published vocabulary
    carries weight."""
    std = EMBED_STD if path.startswith("embed") else 1.0 / np.sqrt(shape[-2])
    w = jax.random.normal(key, shape, jnp.float32) * np.float32(std)
    if path == "embed.w":
        w = w * (jnp.arange(shape[0]) < vocab)[:, None]
    elif path == "head.w":
        w = w * (jnp.arange(shape[1]) < vocab)[None, :]
    return w.astype(dtype)
