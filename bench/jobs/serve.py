"""Open-loop multi-tenant serving through ``ServeEngine``.

Set-up draws the packed base and every tenant's adapters on the device
from the seed, registers the tenants, builds the engine and warms up one
decode step per rank bucket.  The window submits each request when it is
due and steps the engine whenever anything is outstanding.  Time to first
token runs from when a request was due; the gaps between tokens are read
on the host clock after each engine step.  After the window closes,
arrivals go on, so the load stays, until every request due in the window
has its first token (at most ``DRAIN_S`` more seconds: one that has none
by then has failed).

The check runs the reference module that the configuration names
(``bench.jobs.common.reference``).  A fault (``FAULTS``) takes the set-up
job and breaks its engine underneath.
"""
from __future__ import annotations

import time

import numpy as np

from bench import faults
from bench import traffic as gen
from bench.jobs import common

DRAIN_S = 60.0
# traced slice: starts this far into the window, lasts TRACE_S
TRACE_AT, TRACE_S = 0.3, 3.0
# requests the reference replays after the window, the longest among them
CHECK_REQUESTS = 8
# served_logit_gap: the widest gap, in logits, by which a served token
# may lie below the reference's best (PERF.md gives the readings it was
# set from)
LIMITS = {"served_logit_gap": 0.25}
# faults planted under the engine at the end of set-up (``Job.fault``)
FAULTS = {"wrong_slot": faults.serve_wrong_slot,
          "zero_adapters": faults.serve_zero_adapters,
          "altered_token": faults.serve_altered_token}


def _percentile(xs, q: float) -> float:
    xs = np.sort(np.asarray(xs, np.float64))
    return float(xs[min(len(xs) - 1, int(np.ceil(q * len(xs))) - 1)])


class Job:
    # a fault planted under the timed path by the controls and the tests
    # (one of ``FAULTS``): ``job -> None``, applied once set-up is done
    fault = None

    def __init__(self, cell):
        self.cell = cell
        self.mix = cell.traffic
        self.ranks = gen.tenant_ranks(self.mix)
        self.share = float(self.mix["adapter_share"])

    # -- set-up ------------------------------------------------------------

    def setup(self) -> None:
        import jax
        from repro import obs
        from repro.serve import AdapterRegistry, ServeEngine
        from repro.serve.kv_cache import pages_needed

        from bench import weights
        from bench.reference.serve_check import tenant_key

        cell, mix = self.cell, self.mix
        self.cfg = common.model_config(cell.config)
        base, template = common.packed_base(self.cfg, cell.config, cell.seed,
                                            rank=0)
        eng_spec = mix["engine"]
        per_rank = {r: self.ranks.count(r) for r in set(self.ranks)}
        self.registry = AdapterRegistry(template,
                                        capacity=max(per_rank.values()),
                                        dtype=self.cfg.dtype)
        draw = {r: jax.jit(lambda k, r=r: weights.make_adapters(
            k, template, r, self.cfg.dtype, self.share)) for r in per_rank}
        self.tenants = [f"tenant-{i}" for i in range(len(self.ranks))]
        for i, (name, r) in enumerate(zip(self.tenants, self.ranks)):
            self.registry.register(name, draw[r](tenant_key(cell.seed, i)))
        cap, page = eng_spec["bucket_capacity"], eng_spec["page_size"]
        max_len = eng_spec["max_len"]
        # every slot of every rank bucket can hold a full-length request
        n_pages = len(per_rank) * cap * pages_needed(max_len, page) + 1
        self.engine = ServeEngine(base, self.cfg, self.registry,
                                  page_size=page, n_pages=n_pages,
                                  max_len=max_len, bucket_capacity=cap,
                                  use_kernel=True)
        del base
        self.max_len = self.engine.max_len
        # warm-up: one decode step of each rank bucket's executable
        for r in sorted(per_rank):
            self.engine.submit([1], self.tenants[self.ranks.index(r)], 1)
        self.engine.run()
        self.requests = gen.serve_requests(
            mix, cell.seed, cell.seconds + DRAIN_S, self.cfg.vocab)
        obs.metrics.reset()
        if cell.trace:
            obs.trace.get_tracer().clear()
            obs.trace.enable()
        if self.fault:
            self.fault(self)

    # -- window ------------------------------------------------------------

    def window(self, tracer) -> None:
        from repro import obs
        eng, reqs, T = self.engine, self.requests, self.cell.seconds
        n_window = sum(1 for r in reqs if r.due_s < T)
        live: dict[int, dict] = {}
        self.first: dict[int, float] = {}      # request index -> ttft
        self.gaps: list[float] = []
        self.finished: list[tuple] = []        # (index, tenant, prompt, out)
        self.late: list[float] = []
        self.rows_fed = 0                      # rows of decode in the window
        self.traced_steps: list[list] = []     # per step: [(rank, [len])]
        active_steps: dict[int, int] = {}
        self.waits: list[float] = []           # admission waits, due in window
        self.queue_len = []                    # waiting at T/2 and at T
        t0 = time.perf_counter()
        i = 0
        while True:
            now = time.perf_counter() - t0
            if len(self.queue_len) < int(2 * min(now, T) / T):
                # requests submitted and not yet admitted to a slot
                self.queue_len.append(len(eng.scheduler._queue))
            if now >= T and (len(self.first) >= n_window
                             or now >= T + DRAIN_S):
                break
            if tracer.enabled and tracer.t0 is None and now >= TRACE_AT * T:
                tracer.start()
            if tracer.active and now >= TRACE_AT * T + TRACE_S:
                tracer.stop()
            while i < len(reqs) and reqs[i].due_s <= now:
                r = reqs[i]
                with tracer.span("bench.submit"):
                    rid = eng.submit(r.prompt, self.tenants[r.tenant],
                                     r.max_new)
                live[rid] = {"i": i, "seen": 0, "last": None,
                             "admitted": False}
                self.late.append(now - r.due_s)
                i += 1
            if not eng.scheduler.outstanding():
                nxt = reqs[i].due_s if i < len(reqs) else now + 0.01
                time.sleep(max(0.0, min(nxt - now, 0.01)))
                continue
            with tracer.span("bench.engine_step"):
                eng.step()
            t = time.perf_counter() - t0
            step_rows = []
            for bucket, _slot, rid in eng.scheduler.trace[-1][2]:
                active_steps[rid] = active_steps.get(rid, 0) + 1
                step_rows.append((int(bucket), active_steps[rid]))
            if t <= T:
                self.rows_fed += len(step_rows)
            if tracer.active:
                by_rank: dict[int, list] = {}
                for rank, n in step_rows:
                    by_rank.setdefault(rank, []).append(n)
                self.traced_steps.append(sorted(by_rank.items()))
            for rid in list(live):
                st = live[rid]
                if not st["admitted"]:
                    req = eng._reqs[rid]
                    if req.t_admit:
                        st["admitted"] = True
                        if reqs[st["i"]].due_s < T:
                            self.waits.append(req.t_admit - req.t_submit)
                out = eng.result(rid)
                if len(out) == st["seen"]:
                    continue
                r = reqs[st["i"]]
                if st["seen"] == 0:
                    if r.due_s < T:
                        self.first[st["i"]] = t - r.due_s
                elif t <= T:
                    self.gaps.append(t - st["last"])
                st["seen"], st["last"] = len(out), t
                if len(out) >= r.max_new:
                    if r.due_s < T:
                        self.finished.append((st["i"], r.tenant, r.prompt,
                                              tuple(out)))
                    del live[rid]
        self.n_window = n_window
        self.decode_spans = [e["dur"] / 1e3 for e in
                             obs.trace.get_tracer().events()
                             if e["name"] == "serve.decode"]
        obs.trace.disable()
        late = np.asarray(self.late)
        print(f"serve window: due={n_window} first_tokens={len(self.first)} "
              f"finished={len(self.finished)} gaps={len(self.gaps)} "
              f"engine_steps={eng.steps} rows_fed={self.rows_fed} "
              f"generator_late_p50_ms={1e3 * np.median(late):.3f} "
              f"generator_late_max_ms={1e3 * late.max():.3f}", flush=True)

    # -- readings ----------------------------------------------------------

    def counts(self) -> tuple[int, int]:
        return self.n_window, self.n_window - len(self.first)

    def end_to_end(self) -> dict:
        ttft = [self.first.get(i, float("inf")) for i in range(self.n_window)]
        return {"ttft_p95_ms": 1e3 * _percentile(ttft, 0.95),
                "itl_p95_ms": 1e3 * _percentile(self.gaps, 0.95)}

    def reading(self, red, peaks) -> dict:
        return {"job": "serve", "config": self.cell.config, "peaks": peaks,
                "trace": red, "steps": self.traced_steps,
                "ranks": sorted(set(self.ranks)),
                "bucket_capacity": self.mix["engine"]["bucket_capacity"],
                "max_len": self.max_len,
                "decode_spans_ms": self.decode_spans,
                "queue_waits_ms": [1e3 * w for w in self.waits]}

    # -- correctness -------------------------------------------------------

    def release(self) -> None:
        import gc
        del self.engine, self.registry
        gc.collect()

    def sample(self) -> list:
        """The finished requests the reference replays: the longest, and
        the rest drawn from the seed."""
        done = sorted(self.finished)
        if not done:
            return []
        longest = max(range(len(done)),
                      key=lambda j: len(done[j][2]) + len(done[j][3]))
        rest = [j for j in range(len(done)) if j != longest]
        rng = np.random.default_rng(self.cell.seed)
        pick = rng.choice(rest, size=min(len(rest), CHECK_REQUESTS - 1),
                          replace=False) if rest else []
        return [done[j][1:] for j in [longest, *sorted(pick)]]

    def check(self, control: bool = False) -> list[dict]:
        """The served tokens against the reference.  With ``control`` the
        reference in float8 takes the program's place: the number compared
        is the gap of the tokens float8 puts first at the same positions,
        which has to fail the limit."""
        from bench.reference.serve_check import served_gaps
        seqs = self.sample()
        checks = [{"name": "requests_finished_missing",
                   "value": 0 if seqs else 1, "limit": 0}]
        if not seqs:
            return checks
        gaps = served_gaps(common.reference(self.cell.config),
                           self.cell.config, self.cell.seed, self.ranks,
                           self.share, seqs, self.max_len, control=control)
        self.gap_readings = {k: float(v.max()) for k, v in gaps.items()}
        print(f"serve check: requests={len(seqs)} "
              f"tokens={sum(len(s[2]) for s in seqs)} "
              f"gaps={self.gap_readings}", flush=True)
        checks.append({"name": "served_logit_gap",
                       "value": self.gap_readings[
                           "control" if control else "program"],
                       "limit": LIMITS["served_logit_gap"]})
        return checks

    @classmethod
    def control(cls, cell, fault_names: list) -> list[dict]:
        """For ``bench/control.py``: the cell served for its window at its
        own load, its served tokens read against the reference in float32
        (the program's reading) and in float8 (the control: the gap of the
        token float8 puts first); then served again with each of
        ``fault_names`` planted.  One reading each, with its verdict under
        ``LIMITS``."""
        from bench import harness
        out = []
        for fault in [None, *fault_names]:
            job = cls(cell)
            job.fault = FAULTS[fault] if fault else None
            job.setup()
            job.window(harness.Tracer(False))
            job.release()
            if fault:
                checks = job.check()
                out.append({"fault": fault, **_values(checks),
                            "correct": harness.passed(checks)})
                continue
            ctrl = job.check(control=True)
            prog = [dict(c, value=job.gap_readings["program"])
                    if c["name"] == "served_logit_gap" else c for c in ctrl]
            out.append({"program": _values(prog),
                        "program_correct": harness.passed(prog),
                        "control": _values(ctrl),
                        "control_correct": harness.passed(ctrl),
                        "finished": len(job.finished)})
        return out


def _values(checks: list) -> dict:
    return {c["name"]: c["value"] for c in checks}
