"""LoRA fine-tuning steps of the plain reference.

The reference draws the same packed base and initial adapters from the
seed as the program was given, and follows the program's first steps on
the same batches in float32 at ``highest``: the loss of each step, the
gradient of the first (clipped to a global norm as the optimizer clips
it), and AdamW's updates, written out here as the optimizer's published
rule.  Each sequence of a batch runs alone through a scan over layers that
recomputes each layer in the backward pass, and the loss runs over chunks
of positions, so that a 4096-token row fits.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights
from bench.reference import dense

LOSS_CHUNK = 1024


def initial_lora(key, dims: dense.Dims, rank: int, dtype: str) -> dict:
    """``{site: {"lora_a": (L, m, r), "lora_b": (L, n, r)}}`` in f32, as
    the program was given them (drawn in ``dtype``)."""
    out = {}
    for site in dense.SITES:
        m, n = dims.site_shape(site)

        def one(i, site=site, m=m, n=n):
            k = jax.random.fold_in(
                jax.random.fold_in(weights.leaf_key(key, f"blocks.{site}"), i),
                1)
            ad = weights.adapter_layer(k, m, n, rank, getattr(jnp, dtype))
            return jax.tree.map(lambda a: a.astype(jnp.float32), ad)
        out[site] = jax.lax.map(one, jnp.arange(dims.n_layers))
    return out


def _seq_loss(lora, key, tokens, labels, *, dims, prec):
    """Summed next-token loss of one row, and its token count."""
    x = dense.embedding(key, dims)[tokens][None]

    def body(x, inp):
        layer, lo = inp
        base = dense.base_layer(key, dims, layer)
        ad = {s: (lo[s]["lora_a"][None], lo[s]["lora_b"][None])
              for s in dense.SITES}
        return dense.block(prec, dims, x, base, ad), None

    body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, (jnp.arange(dims.n_layers), lora))
    h = dense.rmsnorm(x[0])
    head = dense.head(key, dims)

    def chunk(carry, inp):
        hc, lc = inp
        logits = prec.einsum("td,dv->tv", hc, head)
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, lc[:, None], axis=-1)[:, 0] - lse
        return carry - jnp.sum(ll), None

    S = h.shape[0]
    c = min(LOSS_CHUNK, S)
    total, _ = jax.lax.scan(jax.checkpoint(chunk), jnp.zeros((), jnp.float32),
                            (h.reshape(S // c, c, -1), labels.reshape(-1, c)))
    return total


@functools.partial(jax.jit, static_argnames=("dims", "prec"))
def _seq_grad(lora, key, tokens, labels, *, dims, prec):
    return jax.value_and_grad(_seq_loss)(lora, key, tokens, labels,
                                         dims=dims, prec=prec)


def loss_and_grad(lora, key, batch, dims, prec=dense.EXACT):
    """Mean token loss of ``batch`` and its gradient, row by row."""
    tokens, labels = np.asarray(batch["tokens"]), np.asarray(batch["labels"])
    total, grad = 0.0, None
    for row in range(tokens.shape[0]):
        s, g = _seq_grad(lora, key, jnp.asarray(tokens[row]),
                         jnp.asarray(labels[row]), dims=dims, prec=prec)
        total += float(s)
        grad = g if grad is None else jax.tree.map(jnp.add, grad, g)
    n = tokens.size
    return total / n, jax.tree.map(lambda g: g / n, grad)


def clip(grad, max_norm: float):
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grad)))
    return jax.tree.map(lambda g: g * jnp.minimum(1.0, max_norm / (norm + 1e-9)),
                        grad)


def adamw(params, grad, state, step: int, opt: dict):
    """Decoupled weight decay Adam (Loshchilov and Hutter), bias-corrected,
    after clipping the gradient to ``opt["clip_norm"]``."""
    b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
    lr, wd = opt["lr"], opt["weight_decay"]
    g = clip(grad, opt["clip_norm"])
    mu = jax.tree.map(lambda m, gi: b1 * m + (1 - b1) * gi, state["mu"], g)
    nu = jax.tree.map(lambda v, gi: b2 * v + (1 - b2) * gi * gi,
                      state["nu"], g)
    c1, c2 = 1 - b1 ** step, 1 - b2 ** step
    new = jax.tree.map(
        lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps)
                                  + wd * p), params, mu, nu)
    return new, {"mu": mu, "nu": nu}, g
