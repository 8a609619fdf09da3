"""Numerical health guards + degradation ladder for the quantization engines.

A single ill-conditioned Gram is enough to sink an entire quantization
pass: OPTQ's damped Cholesky (:func:`repro.core.optq.inv_cholesky_upper`)
turns non-PSD input into NaN, the NaN rides the error-compensation sweep
into every code of the layer, and ``W - Qd`` poisons the CLoQ solve — one
bad calibration site becomes a NaN leaf in the checkpoint.  Related
initializers hit the same cliffs (LoftQ's AltMin can diverge on
rank-deficient residuals), so the guards live here, in engine-neutral
form, not in per-method code.

Two pieces:

**Per-bucket check** (:func:`check_bucket`, :func:`check_single`).  After
each fused bucket the engine runs one cheap ``jit(vmap)`` pass over the
bucket's slices: finiteness of every produced leaf, plus a proxy-error
blowup test against a data-free RTN round-trip of the same weight at the
same bits — the unweighted ``||E||_F^2`` instance of the
:func:`repro.core.batched.eval_single` proxy (no Gram contraction on the
hot path, so a clean run pays O(m n) per slice against the sweep's
O(m^2 n)).  A slice fails when any leaf is non-finite or its residual
error exceeds ``blowup_factor x`` the RTN baseline.

**Degradation ladder** (:func:`heal_task`).  Failing slices are requeued
through the sequential single-layer oracle
(:func:`repro.core.batched.quantize_single_deq`) under an escalation
ladder, each rung accepted only if its output is finite and its
calibration-weighted proxy error (the :func:`~repro.core.batched.
eval_single` machinery) stays within the blowup bound of the RTN
baseline:

1. *re-damp* — retry with growing ``lambda_frac`` (both OPTQ's damping and
   CLoQ's Gram regularization ride :class:`~repro.core.batched.BucketSpec.
   lambda_frac`), rescuing mildly indefinite / rank-deficient Grams;
2. *identity Gram* — data-free fallback: the site's Gram is replaced by
   ``tr(H)/m * I`` (unit trace density), turning CLoQ into plain SVD of
   the residual and OPTQ into compensated RTN;
3. *RTN at the same bits* — drop the calibrated sweep entirely (structure-
   compatible with every method but NF4-coded ``qlora``);
4. *skip-to-dense* — the site keeps its dense weight (``None`` returned;
   the drivers leave ``w`` in place).

Every step — attempted rungs, acceptance errors, the diagnosis of the
original failure (weight/Gram/Cholesky-factor finiteness) — is recorded in
a per-site :class:`HealthReport`, serialized next to the manifest so a
production run documents exactly which sites degraded and how.

Doctest (the report is plain data — safe to build without a device):

>>> r = HealthReport()
>>> r.record("blocks.0.attn.q", None, "fallback_rtn",
...          ladder=({"rung": "redamp(0.05)", "accepted": False},
...                  {"rung": "rtn", "accepted": True}))
>>> sorted(r.fallbacks()) == ["blocks.0.attn.q"] and r.counts()["fallback_rtn"]
1
>>> HealthReport.site_key("blocks.1.moe.up", 3)
'blocks.1.moe.up[3]'
"""
from __future__ import annotations

import dataclasses
import json
import os
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.batched import (BucketSpec, eval_single, quantize_single_deq,
                                requeue_spec)
from repro.core.optq import cholesky_factor_finite
from repro.obs import metrics as obs_metrics
from repro.obs import names as obs_names
from repro.obs import trace as obs_trace
from repro.core.quantizer import (dequantize_int, dequantize_nf4,
                                  quantize_int, quantize_nf4, unpack_codes)

Array = jax.Array


class QuantPreempted(RuntimeError):
    """Raised by the engine at a bucket boundary when the driver's
    ``should_stop`` fires (SIGTERM during quantization).  Completed buckets
    are already committed to the journal; ``bucket`` is the last one."""

    def __init__(self, bucket: int):
        super().__init__(f"quantization preempted after bucket {bucket}")
        self.bucket = bucket


@dataclasses.dataclass(frozen=True)
class HealthPolicy:
    """Guard thresholds + ladder schedule.

    ``blowup_factor``: a slice fails when its residual error exceeds this
    multiple of the data-free RTN round-trip error of the same weight at
    the same bits — calibrated methods should *beat* RTN, so an order of
    magnitude above it means the calibrated solve went numerically wrong,
    not that the layer is merely hard.
    ``redamp_fracs``: the growing ``lambda_frac`` schedule of ladder rung 1
    (the engine default is 0.01)."""
    enabled: bool = True
    blowup_factor: float = 10.0
    abs_tol: float = 1e-8
    redamp_fracs: tuple[float, ...] = (0.05, 0.25)


class HealthReport:
    """Per-site record of every health decision of one quantization run.

    ``records`` maps a site key (``path`` or ``path[expert]``) to the
    outcome dict of its ladder walk; sites that pass the bucket check are
    only counted (``checked``), not recorded — a clean 70B run must not
    build a million-entry dict.  ``events`` collects run-level notes
    (skipped calibration batches, journal resumes, preemptions)."""

    def __init__(self) -> None:
        self.records: dict[str, dict] = {}
        self.events: list[str] = []
        self.checked: int = 0

    @staticmethod
    def site_key(path: str, expert: int | None = None) -> str:
        return path if expert is None else f"{path}[{expert}]"

    def event(self, msg: str) -> None:
        self.events.append(msg)

    def record(self, path: str, expert: int | None, status: str, *,
               ladder: tuple | list = (), diagnosis: dict | None = None,
               detail: str = "") -> None:
        site = self.site_key(path, expert)
        self.records[site] = {
            "status": status, "ladder": list(ladder),
            "diagnosis": diagnosis, "detail": detail}
        obs_metrics.counter(obs_names.HEALTH_PREFIX + status).inc()
        obs_trace.instant("health." + status, site=site)

    def fallbacks(self) -> dict[str, dict]:
        """Sites that did NOT come out of the primary fused path clean."""
        return dict(self.records)

    def counts(self) -> dict[str, int]:
        out: dict[str, int] = {}
        for r in self.records.values():
            out[r["status"]] = out.get(r["status"], 0) + 1
        return out

    def to_dict(self) -> dict:
        return {"checked": self.checked, "counts": self.counts(),
                "records": self.records, "events": self.events}

    def save(self, path: str) -> None:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.to_dict(), f, indent=1, sort_keys=True)

    def summary(self) -> str:
        c = self.counts()
        if not c and not self.events:
            return f"health: {self.checked} slices checked, all clean"
        parts = [f"{v}x {k}" for k, v in sorted(c.items())]
        return (f"health: {self.checked} slices checked, "
                + (", ".join(parts) if parts else "all clean")
                + (f"; {len(self.events)} event(s)" if self.events else ""))


# ---------------------------------------------------------------------------
# Fused per-bucket check.
# ---------------------------------------------------------------------------


def _leaves_dequant(leaves: dict, spec: BucketSpec) -> Array:
    """Dequantized base from stored leaves (one slice) — the same arrays
    the model's ``linear_apply`` would read, so the check also validates
    the pack/unpack round trip."""
    if spec.method == "qlora":
        codes = unpack_codes(leaves["qcodes"], 4, spec.m)
        return dequantize_nf4(codes, leaves["absmax"], spec.group_size)
    codes = unpack_codes(leaves["qcodes"], spec.bits, spec.m)
    return dequantize_int(codes, leaves["scales"], leaves["zeros"],
                          spec.group_size)


def _rtn_dequant(W: Array, spec: BucketSpec) -> Array:
    """Data-free RTN round trip of ``W`` at the slice's own format — the
    blowup baseline (always finite for finite ``W``: scales are floored)."""
    if spec.method == "qlora":
        codes, absmax = quantize_nf4(W, spec.group_size)
        return dequantize_nf4(codes, absmax, spec.group_size)
    codes, s, z = quantize_int(W, spec.bits, spec.group_size)
    return dequantize_int(codes, s, z, spec.group_size)


def _finite_leaves(leaves: dict) -> Array:
    ok = jnp.asarray(True)
    for k in sorted(leaves):
        v = leaves[k]
        if jnp.issubdtype(v.dtype, jnp.floating):
            ok = ok & jnp.all(jnp.isfinite(v))
    return ok


def _check_one(W: Array, leaves: dict, spec: BucketSpec):
    W = jnp.asarray(W, jnp.float32)
    finite = _finite_leaves(leaves)
    Qd = _leaves_dequant(leaves, spec)
    A = leaves["lora_a"].astype(jnp.float32)
    B = leaves["lora_b"].astype(jnp.float32)
    E = W - Qd - A @ B.T
    err = jnp.sum(E * E)
    R = W - _rtn_dequant(W, spec)
    return finite, err, jnp.sum(R * R)


_check_one_jit = jax.jit(_check_one, static_argnums=(2,))


@partial(jax.jit, static_argnames=("spec",))
def _check_bucket_jit(Ws: Array, leaves: dict, spec: BucketSpec):
    return jax.vmap(lambda W, lv: _check_one(W, lv, spec))(Ws, leaves)


def check_bucket(Ws: Array, leaves: dict, spec: BucketSpec,
                 policy: HealthPolicy) -> np.ndarray:
    """Health flags of one executed bucket: ``(L,)`` bool, True = slice is
    clean.  One compiled executable per bucket signature (same jit-cache
    discipline as :func:`repro.core.batched.run_bucket`); the
    blowup-factor comparison happens on the host so the policy is not
    baked into the executable.  A bucket that ran down the sequential
    path is checked slice by slice, as it ran (``Ws`` is then a list)."""
    if spec.exec_path == "sequential":
        return np.array([
            check_single(W, {k: v[j] for k, v in leaves.items()}, spec,
                         policy)
            for j, W in enumerate(Ws)], bool)
    finite, err, rerr = _check_bucket_jit(Ws, leaves, spec)
    finite = np.asarray(finite)
    err = np.asarray(err, np.float64)
    rerr = np.asarray(rerr, np.float64)
    ok = (finite & np.isfinite(err)
          & (err <= policy.blowup_factor * rerr + policy.abs_tol))
    return ok


def check_single(W: Array, leaves: dict, spec: BucketSpec,
                 policy: HealthPolicy) -> bool:
    """Single-slice instance of :func:`check_bucket` (the sequential
    engine's per-layer guard — identical criterion, identical math)."""
    finite, err, rerr = _check_one_jit(W, leaves, spec)
    err = float(err)
    return bool(finite) and np.isfinite(err) and \
        err <= policy.blowup_factor * float(rerr) + policy.abs_tol


# ---------------------------------------------------------------------------
# Diagnosis + the degradation ladder.
# ---------------------------------------------------------------------------


def diagnose(W, H, spec: BucketSpec) -> dict:
    """Host-side diagnosis of a failing slice: which ingredient is bad.
    ``cholesky_finite`` pinpoints the classic OPTQ failure — a finite but
    (effectively) non-PSD Gram whose damped Cholesky factor is NaN."""
    w_ok = bool(np.isfinite(np.asarray(W)).all())
    out: dict[str, Any] = {"w_finite": w_ok, "gram": None}
    if spec.has_gram and H is not None:
        g_ok = bool(np.isfinite(np.asarray(H)).all())
        out["gram"] = {"finite": g_ok,
                       "cholesky_finite":
                           cholesky_factor_finite(H, spec.lambda_frac)
                           if g_ok else False}
    return out


def identity_gram(H, m: int) -> np.ndarray:
    """The data-free stand-in Gram of ladder rung 2: ``tr(H)/m * I`` (unit
    input density at the original Gram's scale), falling back to plain
    ``I`` when the trace itself is unusable."""
    scale = 1.0
    if H is not None:
        tr = float(np.trace(np.asarray(H, np.float64)))
        if np.isfinite(tr) and tr > 0:
            scale = tr / m
    return np.eye(m, dtype=np.float32) * np.float32(scale)


@partial(jax.jit, static_argnames=("spec",))
def _attempt_jit(W: Array, H: Array | None, key: Array, spec: BucketSpec):
    """One ladder rung: quantize + finiteness + the calibration-weighted
    acceptance errors (``eval_single``'s ``tr(E^T H E)`` proxy for both
    the candidate and its RTN baseline — unweighted when the rung carries
    no Gram)."""
    leaves, Qd = quantize_single_deq(W, H, key, spec)
    finite = _finite_leaves(leaves)
    W32 = jnp.asarray(W, jnp.float32)
    E = W32 - Qd - leaves["lora_a"] @ leaves["lora_b"].T
    if spec.has_gram:
        err = jnp.einsum("ij,ik,kj->", E, jnp.asarray(H, jnp.float32), E)
    else:
        err = jnp.sum(E * E)
    rtn_spec = dataclasses.replace(spec, method="rtn", magr=False)
    rerr = eval_single(W, H, key, rtn_spec)
    return leaves, finite, err, rerr


def _try_rung(W, H, key, spec: BucketSpec, policy: HealthPolicy,
              name: str, steps: list):
    leaves, finite, err, rerr = _attempt_jit(W, H, key, spec)
    err_f, rerr_f = float(err), float(rerr)
    ok = bool(finite) and np.isfinite(err_f) and \
        err_f <= policy.blowup_factor * rerr_f + policy.abs_tol
    steps.append({"rung": name, "accepted": ok, "err": err_f,
                  "rtn_err": rerr_f})
    return leaves if ok else None


def heal_task(W, H, key, spec: BucketSpec, policy: HealthPolicy,
              report: HealthReport, path: str,
              expert: int | None = None) -> dict | None:
    """Walk the degradation ladder for one failing slice.

    Returns the accepted leaf dict, or ``None`` for skip-to-dense (the
    caller leaves the dense ``w`` in place).  Raises ``FloatingPointError``
    when the *weight itself* is non-finite — that is unrecoverable data
    corruption, not a numerical cliff, and must not be papered over.

    Both engines call this with the slice's own ``(W, H, key, spec)``
    (the batched engine after a failed bucket check, the sequential engine
    after its per-layer check), so a healed site is bit-identical across
    engines — the ladder runs through the same
    :func:`~repro.core.batched.quantize_single_deq` core unsharded, i.e.
    the sequential oracle."""
    with obs_trace.span("health.heal",
                        site=HealthReport.site_key(path, expert),
                        method=spec.method) as sp:
        out = _heal_ladder(W, H, key, spec, policy, report, path, expert)
        sp.set(healed=out is not None)
        return out


def _heal_ladder(W, H, key, spec: BucketSpec, policy: HealthPolicy,
                 report: HealthReport, path: str,
                 expert: int | None = None) -> dict | None:
    if not np.isfinite(np.asarray(W)).all():
        raise FloatingPointError(
            f"weight at {HealthReport.site_key(path, expert)} contains "
            "non-finite values — unrecoverable (corrupt input params)")
    diag = diagnose(W, H, spec)
    # heal single-slice, unsharded: requeue under the spec a fresh
    # meshless plan of this one slice would produce (the sequential
    # oracle) — batched.requeue_spec keeps n_shards/exec_path consistent
    # with the planner so the healed site's manifest/journal entry matches
    spec = requeue_spec(spec)
    steps: list[dict] = []
    gram_finite = bool(diag["gram"] and diag["gram"]["finite"])

    if spec.has_gram and gram_finite:
        for f in policy.redamp_fracs:
            out = _try_rung(W, H, key,
                            dataclasses.replace(spec, lambda_frac=f),
                            policy, f"redamp({f})", steps)
            if out is not None:
                report.record(path, expert, "recovered_redamp",
                              ladder=steps, diagnosis=diag,
                              detail=f"lambda_frac={f}")
                return out
    if spec.has_gram:
        H_id = identity_gram(H, spec.m)
        out = _try_rung(W, H_id, key, spec, policy, "identity_gram", steps)
        if out is not None:
            report.record(path, expert, "recovered_identity_gram",
                          ladder=steps, diagnosis=diag,
                          detail="calibration Gram replaced by tr(H)/m * I")
            return out
    if spec.method != "qlora":
        # same bits, same group, same leaf structure — NF4 (qlora) stores
        # absmax instead of scales/zeros, so it cannot take this rung
        rtn_spec = dataclasses.replace(spec, method="rtn", has_gram=False,
                                       magr=False)
        out = _try_rung(W, None, key, rtn_spec, policy, "rtn", steps)
        if out is not None:
            report.record(path, expert, "fallback_rtn", ladder=steps,
                          diagnosis=diag,
                          detail=f"data-free RTN at {spec.bits} bits")
            return out
    report.record(path, expert, "fallback_dense", ladder=steps,
                  diagnosis=diag, detail="site left dense")
    return None


def heal_site_lora(H_site, dW, rank: int, split: str,
                   policy: HealthPolicy, report: HealthReport,
                   path: str, site_path: str):
    """Ladder for one per-site adapter pair of a weight-shared block
    (``shared.site_lora``): the base is already quantized and healthy (or
    healed), only the closed-form per-site CLoQ solve failed.  Rungs:
    re-regularize the site Gram, identity-Gram (plain SVD of ``dW``), zero
    adapters (the site falls back to the shared base alone)."""
    from repro.core.cloq import cloq_init, regularize_gram

    dW = jnp.asarray(dW, jnp.float32)
    m, n = dW.shape
    steps: list[dict] = []

    def finite_pair(A, B):
        return bool(jnp.all(jnp.isfinite(A))) and \
            bool(jnp.all(jnp.isfinite(B)))

    if np.isfinite(np.asarray(H_site)).all():
        for f in policy.redamp_fracs:
            A, B = cloq_init(regularize_gram(jnp.asarray(H_site,
                                                         jnp.float32), f),
                             dW, rank, split)
            ok = finite_pair(A, B)
            steps.append({"rung": f"redamp({f})", "accepted": ok})
            if ok:
                report.record(path, None, "recovered_redamp", ladder=steps,
                              detail=f"site adapter {site_path}, "
                                     f"lambda_frac={f}")
                return A, B
    H_id = jnp.asarray(identity_gram(H_site, m))
    A, B = cloq_init(H_id, dW, rank, split)
    ok = finite_pair(A, B)
    steps.append({"rung": "identity_gram", "accepted": ok})
    if ok:
        report.record(path, None, "recovered_identity_gram", ladder=steps,
                      detail=f"site adapter {site_path}: plain SVD of dW")
        return A, B
    steps.append({"rung": "zero_adapters", "accepted": True})
    report.record(path, None, "fallback_zero_adapters", ladder=steps,
                  detail=f"site adapter {site_path} zeroed — site uses the "
                         "shared base alone")
    return (jnp.zeros((m, rank), jnp.float32),
            jnp.zeros((n, rank), jnp.float32))
