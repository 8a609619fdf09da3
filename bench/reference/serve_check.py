"""Served tokens against the plain reference.

For each sampled request the reference runs once over its prompt and its
served tokens (teacher-forced) and reads, at each position that produced
a served token, how far that token's logit lies below the reference's
best.  With ``control=True`` it also runs the same forward in float8 and
reads the gap of the token that float8 puts first: the control, which has
to fail the limit that sound runs pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights
from bench.reference import dense

HIGHEST = jax.lax.Precision.HIGHEST


def tenant_key(seed: int, tenant: int) -> jax.Array:
    """Tenant ``tenant``'s adapter key (the serve job draws with it)."""
    return jax.random.fold_in(
        jax.random.fold_in(weights.seed_key(seed), 0x7465), tenant)


def _adapters(dims, keys, ranks, layer, share):
    """Each tenant's layer-``layer`` adapters, padded to the largest rank
    with zeros: ``{site: (A (U, m, R), B (U, n, R))}`` in f32."""
    rmax = max(ranks)
    out = {}
    for site in dense.SITES:
        m, n = dims.site_shape(site)
        As, Bs = [], []
        for k, r in zip(keys, ranks):
            ad = weights.adapter_layer(
                jax.random.fold_in(weights.leaf_key(k, site), layer), m, n, r,
                jnp.bfloat16, share)
            pad = ((0, 0), (0, rmax - r))
            As.append(jnp.pad(ad["lora_a"].astype(jnp.float32), pad))
            Bs.append(jnp.pad(ad["lora_b"].astype(jnp.float32), pad))
        out[site] = (jnp.stack(As), jnp.stack(Bs))
    return out


@functools.partial(jax.jit,
                   static_argnames=("dims", "ranks", "share", "precs"))
def _layer(xs, layer, key, tkeys, rows, *, dims, ranks, share, precs):
    base = dense.base_layer(key, dims, layer)
    ad = _adapters(dims, list(tkeys), ranks, layer, share)
    lora = {s: (a[rows], b[rows]) for s, (a, b) in ad.items()}
    return tuple(dense.block(p, dims, x, base, lora)
                 for p, x in zip(precs, xs))


@functools.partial(jax.jit, static_argnames=("dims", "control"))
def _gaps(xs, targets, key, *, dims, control):
    head = dense.head(key, dims)

    def row(args):
        hs, tgt = args
        logits = jnp.einsum("td,dv->tv", dense.rmsnorm(hs[0]), head,
                            precision=HIGHEST)
        best = jnp.max(logits, axis=-1)
        served = jnp.take_along_axis(logits, jnp.maximum(tgt, 0)[:, None],
                                     axis=-1)[:, 0]
        out = [jnp.where(tgt >= 0, best - served, 0.0)]
        if control:
            low = dense.FP8.einsum("td,dv->tv", dense.rmsnorm(hs[1]), head)
            first = jnp.argmax(low, axis=-1)
            ctrl = jnp.take_along_axis(logits, first[:, None], axis=-1)[:, 0]
            out.append(jnp.where(tgt >= 0, best - ctrl, 0.0))
        return tuple(out)

    return jax.lax.map(row, (xs, targets))


def served_gaps(config: dict, seed: int, ranks: list[int], share: float,
                seqs: list, length: int, control: bool = False) -> dict:
    """``seqs`` is ``[(tenant, prompt, served tokens), ...]``; every
    sequence is padded to ``length`` positions; tenants' adapters are
    drawn as :func:`bench.weights.adapter_layer` draws them with
    ``share``.  Returns the gap of every
    served token, ``{"program": array}`` and with ``control`` also
    ``{"control": array}`` (the float8 forward's first choice)."""
    dims = dense.Dims.of(config)
    key = weights.seed_key(seed)
    N = len(seqs)
    tokens = np.zeros((N, length), np.int32)
    targets = np.full((N, length), -1, np.int32)
    rows = np.zeros((N,), np.int32)
    for i, (tenant, prompt, out) in enumerate(seqs):
        seq = list(prompt) + list(out[:-1])
        tokens[i, :len(seq)] = seq
        targets[i, len(prompt) - 1:len(prompt) - 1 + len(out)] = out
        rows[i] = tenant
    tkeys = jnp.stack([tenant_key(seed, t) for t in range(len(ranks))])
    emb = dense.embedding(key, dims)
    x = emb[jnp.asarray(tokens)]
    del emb
    precs = (dense.EXACT, dense.FP8) if control else (dense.EXACT,)
    xs = tuple(x for _ in precs)
    for layer in range(dims.n_layers):
        xs = _layer(xs, jnp.int32(layer), key, tkeys, jnp.asarray(rows),
                    dims=dims, ranks=tuple(ranks), share=share,
                    precs=precs)
    got = _gaps(jnp.stack(xs, axis=1), jnp.asarray(targets), key, dims=dims,
                control=control)
    mask = targets >= 0
    out = {"program": np.asarray(got[0])[mask]}
    if control:
        out["control"] = np.asarray(got[1])[mask]
    return out
