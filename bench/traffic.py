"""The one generator of the benchmark's traffic, read from a mix's data file.

Every seed gets the same set of sizes and gaps, in its own order: lengths,
gaps between arrivals and tenants are quantiles of the mix's stated
distributions, shuffled by the seed, and only token ids are drawn at
random.  So two seeds offer the same work, and what differs between them
is the order in which it comes.

Serving mixes (``"job": "serve"``) are open loops: each request has the
time at which it is due, whether or not the server has kept up.
"""
from __future__ import annotations

import dataclasses
import math
import statistics

import numpy as np


@dataclasses.dataclass(frozen=True)
class Request:
    due_s: float          # seconds after the window opens
    tenant: int
    prompt: tuple         # token ids
    max_new: int


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths: the quantiles of ``spec``'s distribution, clipped.

    ``{"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b}``
    or ``{"dist": "uniform", "min": a, "max": b}`` (integers, both ends
    included)."""
    q = _quantiles(n)
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(float(p)) for p in q])
        x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
        return np.clip(np.rint(x), lo, hi).astype(int)
    if spec["dist"] == "uniform":
        return np.minimum(lo + np.floor(q * (hi - lo + 1)), hi).astype(int)
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def zipf_counts(n: int, n_tenants: int, s: float) -> np.ndarray:
    """How many of ``n`` requests each tenant sends, tenant ``i`` in
    proportion to ``1 / (i + 1)^s`` (largest remainders rounded up)."""
    p = 1.0 / np.arange(1, n_tenants + 1) ** s
    want = n * p / p.sum()
    counts = np.floor(want).astype(int)
    short = n - counts.sum()
    counts[np.argsort(want - counts)[::-1][:short]] += 1
    return counts


def serve_requests(mix: dict, seed: int, horizon_s: float,
                   vocab: int) -> list[Request]:
    """Requests due in ``[0, horizon_s)``, in blocks of ``block_s``
    seconds, each block the same set of sizes in the seed's order.

    The mix gives ``rate_per_s``, ``block_s``, ``tenants`` and
    ``zipf_s``, and ``prompt`` and ``output`` length distributions."""
    rng = np.random.default_rng(seed)
    rate, block = float(mix["rate_per_s"]), float(mix["block_s"])
    n = max(1, int(round(rate * block)))
    gaps = -np.log1p(-_quantiles(n)) / rate
    gaps *= block / gaps.sum()          # a block lasts exactly block_s
    prompts = lengths(mix["prompt"], n)
    outputs = lengths(mix["output"], n)
    tenants = np.repeat(np.arange(mix["tenants"]),
                        zipf_counts(n, mix["tenants"], mix["zipf_s"]))
    out: list[Request] = []
    t0 = 0.0
    while t0 < horizon_s:
        g = rng.permutation(gaps)
        due = t0 + np.cumsum(g) - g          # the first at t0, all < t0+block
        for t, p, o, ten in zip(due, rng.permutation(prompts),
                                rng.permutation(outputs),
                                rng.permutation(tenants)):
            if t >= horizon_s:
                break
            ids = rng.integers(0, vocab, int(p))
            out.append(Request(float(t), int(ten), tuple(int(i) for i in ids),
                               int(o)))
        t0 += block
    return out


def tenant_ranks(mix: dict) -> list[int]:
    """Rank of each tenant: the mix's ``ranks`` taken in turn."""
    ranks = mix["ranks"]
    return [int(ranks[i % len(ranks)]) for i in range(mix["tenants"])]


def train_batch(seed: int, step: int, batch: int, seq_len: int,
                vocab: int) -> dict:
    """Step ``step``'s batch, drawn on the device: ``batch`` rows of
    ``seq_len + 1`` token ids, uniform over the vocabulary, every row of
    every step its own; ``tokens`` and ``labels`` are the row shifted by
    one."""
    import jax
    import jax.numpy as jnp
    key = jax.random.fold_in(jax.random.PRNGKey(int(seed)), 0x7261696E)
    rows = jax.random.randint(jax.random.fold_in(key, step),
                              (batch, seq_len + 1), 0, vocab, jnp.int32)
    return {"tokens": rows[:, :-1], "labels": rows[:, 1:]}
