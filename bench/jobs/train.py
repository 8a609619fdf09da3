"""LoRA fine-tuning on the packed base through ``make_train_step``.

Set-up draws the packed base and the initial adapters from the seed,
builds the state and the jitted step once, and drives that same step
through its first ``CHECKED_STEPS`` steps on the window's own feed (which
compiles it).  It keeps what the reference is compared with: each step's
loss, Adam's first moment after step 1 (the first clipped gradient times
``1 - b1``) and the adapters after the last checked step.  The window then
runs further steps of the same object, with ``AHEAD`` steps dispatched
beyond the one it waits for, until ``--seconds`` have passed; then it
sends nothing more and waits for every step sent.  Tokens per second are
the tokens of every step sent over the time to the end of the last one.
"""
from __future__ import annotations

import collections
import time

import numpy as np

from bench import traffic as gen
from bench.jobs import common

CHECKED_STEPS = 3
# steps in flight beyond the one the window waits for: about 6 s of work
# at the cell's size (1.9 s a step on one v5e)
AHEAD = 3
# traced slice: this many steps, after the first of the window
TRACE_STEPS = 2
# limits on what the reference is compared with (PERF.md gives the
# readings they were set from); each step's loss is read and printed but
# not compared: neither the float8 control nor a fault reads it far enough
# above sound runs to set a limit between them
LIMITS = {"grad_norm_rel": 4e-3, "update_norm_rel": 1.5e-3}


def _host(tree):
    import jax
    return jax.tree.map(lambda a: np.asarray(a, np.float32), tree)


def lora_leaves(tree) -> dict:
    """``{"attn.q.lora_a": array, ...}`` of a program's trainable tree
    (non-empty LoRA leaves under ``blocks``)."""
    from repro.utils import tree_paths
    return {p[len("blocks."):]: v for p, v in tree_paths(tree).items()
            if p.startswith("blocks.") and p.rsplit(".", 1)[-1]
            in ("lora_a", "lora_b") and v.size}


class Job:
    # a fault planted under the timed path by the controls and the tests:
    # ``feed -> feed`` applied to the batches the step is given
    fault = None

    def __init__(self, cell):
        self.cell = cell
        self.mix = cell.traffic
        self.batch = int(cell.config["train_batch"])
        self.seq = int(self.mix["seq_len"])

    def opt(self) -> dict:
        return dict(self.mix["optimizer"])

    def setup(self) -> None:
        import jax
        from repro.launch.steps import build_state, make_train_step
        from repro.models.parallel import LOCAL
        from repro.optim import OptConfig

        cell = self.cell
        rank = int(cell.config["lora_rank"])
        self.cfg = common.model_config(cell.config, rank=rank)
        import jax.numpy as jnp
        params, _ = common.packed_base(
            self.cfg, cell.config, cell.seed, rank,
            lora_dtype=getattr(jnp, cell.config["lora_dtype"]))
        o = self.opt()
        self.ocfg = OptConfig(lr=o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                              weight_decay=o["weight_decay"],
                              clip_norm=o["clip_norm"], schedule="const",
                              warmup_frac=0.0, trainable="lora",
                              total_steps=1 << 30)
        state = build_state(params, self.ocfg)
        del params
        self.step_fn = jax.jit(make_train_step(self.cfg, self.ocfg, LOCAL),
                               donate_argnums=0)
        seed, B, S, V = cell.seed, self.batch, self.seq, self.cfg.vocab
        self.feed = jax.jit(lambda step: gen.train_batch(seed, step, B, S, V))
        step_feed = self.fault(self.feed) if self.fault else self.feed
        self.losses = []
        for i in range(CHECKED_STEPS):
            state, metrics = self.step_fn(state, step_feed(i))
            self.losses.append(float(metrics["loss"]))
            if i == 0:
                self.first_mu = _host(lora_leaves(state["opt"]["mu"]))
        self.after = _host(lora_leaves(state["train"]))
        self.state = state
        self.next_step = CHECKED_STEPS
        print(f"train setup: batch={B}x{S} losses={self.losses}", flush=True)

    def window(self, tracer) -> None:
        import jax
        state, T = self.state, self.cell.seconds
        self.steps = 0
        pending: collections.deque = collections.deque()
        t0 = time.perf_counter()
        while True:
            if tracer.enabled and self.steps == 1:
                # the traced slice holds the TRACE_STEPS steps sent in it
                jax.block_until_ready(state)
                tracer.start()
            with tracer.span("bench.train_step", step=self.next_step):
                state, metrics = self.step_fn(state,
                                              self.feed(self.next_step))
            pending.append(metrics["loss"])
            self.next_step += 1
            self.steps += 1
            if tracer.active and self.steps == 1 + TRACE_STEPS:
                jax.block_until_ready(state)
                tracer.stop()
            # wait for the step AHEAD before the newest: the chip stays fed
            # while the host stands still
            while len(pending) > AHEAD:
                loss = float(pending.popleft())
            if time.perf_counter() - t0 >= T and not tracer.active:
                break
        # nothing more is sent; every step sent counts, over the time to
        # the end of the last one
        jax.block_until_ready(state)
        loss = float(pending[-1])
        elapsed = time.perf_counter() - t0
        self.window_s = elapsed
        self.state = state
        print(f"train window: steps={self.steps} seconds={elapsed:.3f} "
              f"last_loss={loss:.6f}", flush=True)

    def counts(self) -> tuple[int, int]:
        return self.steps, 0

    def end_to_end(self) -> dict:
        return {"train_tokens_per_s":
                self.steps * self.batch * self.seq / self.window_s}

    def reading(self, red, peaks) -> dict:
        return {"job": "train", "config": self.cell.config, "peaks": peaks,
                "trace": red, "tokens": TRACE_STEPS * self.batch * self.seq,
                "seq_len": self.seq}

    def release(self) -> None:
        import gc
        del self.state, self.step_fn
        gc.collect()

    # the reference's readings, once run (a job that shares its seed and
    # feed may be handed another's)
    reference = None

    def check(self, control: bool = False) -> list[dict]:
        """Losses, the first gradient and the change of the adapters over
        the checked steps, against the reference.  With ``control`` the
        reference in float8 takes the program's place."""
        from bench.reference import dense
        if self.reference is None:
            self.reference = reference_run(self, dense.EXACT)
        got = reference_run(self, dense.FP8) if control else program_run(self)
        self.readings = compare(got, self.reference)
        print(f"train check: {self.readings} "
              f"reference_losses={self.reference['losses']}", flush=True)
        return [{"name": k, "value": self.readings[k], "limit": LIMITS[k]}
                for k in LIMITS]


def leaf_gaps(got: dict, want: dict, leaves) -> dict:
    """``| |got| - |want| |`` of each of ``leaves``, against the larger of
    its own reference norm and the median leaf's."""
    norms = {k: float(np.linalg.norm(want[k])) for k in want}
    med = float(np.median(list(norms.values())))
    return {k: abs(float(np.linalg.norm(got[k])) - norms[k])
            / max(norms[k], med) for k in leaves}


def worst_leaf(got: dict, want: dict, leaves) -> float:
    """The largest of :func:`leaf_gaps`."""
    return max(leaf_gaps(got, want, leaves).values())


def reference_run(job, prec) -> dict:
    """The reference's checked steps on the job's batches, in ``prec``:
    its losses, first clipped gradient, initial adapters and adapters
    after the last step (flat ``{"attn.q.lora_a": array}``)."""
    import jax
    from bench import weights
    from bench.reference import dense, train_check

    dims = dense.Dims.of(job.cell.config)
    key = weights.seed_key(job.cell.seed)
    opt = job.opt()
    p0 = train_check.initial_lora(key, dims, int(job.cell.config["lora_rank"]),
                                  job.cell.config["lora_dtype"])
    flat = lambda t: {f"{s}.{k}": np.asarray(v[k], np.float32)  # noqa: E731
                      for s, v in t.items() for k in v}
    params = p0
    state = jax.tree.map(lambda a: a * 0.0, {"mu": p0, "nu": p0})
    losses, first = [], None
    for i in range(CHECKED_STEPS):
        loss, grad = train_check.loss_and_grad(params, key, job.feed(i), dims,
                                               prec)
        losses.append(loss)
        params, state, clipped = train_check.adamw(params, grad, state,
                                                   i + 1, opt)
        if i == 0:
            first = flat(clipped)
    return {"losses": losses, "grad": first, "p0": flat(p0),
            "after": flat(params)}


def program_run(job) -> dict:
    """The program's readings in the form of :func:`reference_run`."""
    b1 = job.opt()["b1"]
    return {"losses": job.losses,
            "grad": {k: v / (1.0 - b1) for k, v in job.first_mu.items()},
            "after": job.after}


def compare(got: dict, want: dict) -> dict:
    """The numbers compared: the worst step's loss, the worst leaf's
    first gradient and the worst leaf's change over the checked steps,
    each relative to the reference."""
    p0 = want["p0"]
    ref_delta = {k: want["after"][k] - p0[k] for k in p0}
    got_delta = {k: got["after"][k] - p0[k] for k in p0}
    # leaves whose reference gradient is nought to rounding (under a
    # thousandth of the median leaf's) move by round-off alone
    gn = {k: float(np.linalg.norm(v)) for k, v in want["grad"].items()}
    med = float(np.median(list(gn.values())))
    moving = [k for k in gn if gn[k] >= 1e-3 * med]
    grad = leaf_gaps(got["grad"], want["grad"], moving)
    update = leaf_gaps(got_delta, ref_delta, moving)
    return {
        "loss_rel": max(abs(a - b) / abs(b) for a, b in
                        zip(got["losses"], want["losses"])),
        "grad_norm_rel": max(grad.values()),
        "update_norm_rel": max(update.values()),
        # the median leaf's, beside the worst leaf's (printed, not compared)
        "grad_median_rel": float(np.median(list(grad.values()))),
        "update_median_rel": float(np.median(list(update.values()))),
        "leaves_left_out": sorted(set(gn) - set(moving)),
    }
