"""Faults planted under the timed path.

Each one breaks the program underneath a run that is otherwise whole, so
that the correctness check can be seen to fail: ``bench/control.py
--faults`` reads them on the chip at a cell's own size, and the tests
under ``bench/tests`` at a smoke size.  A serving fault takes the set-up
job and wraps its engine's decode executable; a training fault maps the
feed the step is given.
"""
from __future__ import annotations


def _wrap_exec(job, fn):
    exec_ = job.engine._exec
    job.engine._exec = lambda *args: fn(exec_, *args)


def serve_wrong_slot(job) -> None:
    """Every row gathers the adapters of the next slot of its rank bucket:
    another tenant's adapter, of the same rank."""
    cap = job.registry.capacity

    def run(exec_, base, stacks, ad_slots, *rest):
        return exec_(base, stacks, (ad_slots + 1) % cap, *rest)
    _wrap_exec(job, run)


def serve_zero_adapters(job) -> None:
    """Rows of the largest rank bucket run with their adapters zeroed."""
    import jax
    import jax.numpy as jnp
    top = max(job.ranks)
    zero = jax.tree.map(jnp.zeros_like, job.registry.stacks(top))

    def run(exec_, base, stacks, *rest):
        rank = next(iter(stacks.values()))["lora_a"].shape[-1]
        return exec_(base, zero if rank == top else stacks, *rest)
    _wrap_exec(job, run)


def serve_altered_token(job) -> None:
    """The step returns the next id in the vocabulary instead of its
    choice: a token altered where it is produced."""
    vocab = job.cfg.vocab

    def run(exec_, *args):
        nxt, k, v = exec_(*args)
        return (nxt + 1) % vocab, k, v
    _wrap_exec(job, run)


def train_half_batch(feed):
    """The feed with the second half of every row's labels masked: half
    the batch left out, the mean taken over the rest."""
    import jax.numpy as jnp

    def broken(step):
        b = feed(step)
        S = b["labels"].shape[1]
        keep = jnp.arange(S) < S // 2
        return dict(b, labels=jnp.where(keep[None, :], b["labels"], -1))
    return broken


SERVE = {"wrong_slot": serve_wrong_slot, "zero_adapters": serve_zero_adapters,
         "altered_token": serve_altered_token}
TRAIN = {"half_batch": train_half_batch}
