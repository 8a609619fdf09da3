"""Multi-tenant serving engine: continuous batching over one packed base.

One :class:`ServeEngine` owns

* the **packed base** param tree (quantized linears; the base's own LoRA
  leaves are stripped at every registry site — adapters come exclusively
  from the :class:`~repro.serve.registry.AdapterRegistry`),
* the paged KV pools (:mod:`repro.serve.kv_cache`),
* the continuous-batching :class:`~repro.serve.scheduler.Scheduler`, and
* ONE jitted decode step, specialized per rank bucket by jax's jit cache
  (stack shapes differ per rank — same executable-per-static-signature
  idiom as ``core.batched``).

Each :meth:`step`: the scheduler admits/retires requests, then every
active rank bucket runs one fused decode — adapters for the bucket's
requests are gathered from the stacked registry arrays *inside* jit
(``jnp.take`` over the tenant-slot axis) and applied as one batched
einsum per site, never a per-request loop.  KV pages are gathered to a
contiguous per-request view, the layer scan hands back the new token's
K/V rows, which are scattered into the donated pools in place, and
per-request lengths drive positions/masks, so heterogeneous requests
(different tenants, ranks, progress) share one device call.

Parity contract (the ``tests/test_serving.py`` oracle): every op in the
step is row-independent for ``dense`` models, and stale page content is
masked to an exact-zero softmax weight — so replaying one request alone
through the same executable reproduces its batched tokens **bit-
identically**.  MoE models serve fine but capacity-based routing mixes
rows, so the bitwise oracle applies to ``dense`` only.
"""
from __future__ import annotations

import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.parallel import LOCAL
from repro.obs import metrics as obs_metrics
from repro.obs import names as obs_names
from repro.obs import trace as obs_trace
from repro.models.transformer import decode_step_rows
from repro.serve.kv_cache import (PageAllocator, gather_pages, init_pools,
                                  pages_needed, scatter_token)
from repro.serve.registry import AdapterRegistry
from repro.serve.scheduler import Scheduler
from repro.utils import scope

Array = jax.Array


@dataclasses.dataclass
class _Request:
    rid: int
    tenant: str
    rank: int
    ad_slot: int
    prompt: list
    max_new: int
    eos: int | None
    pos: int = 0                       # tokens fed so far
    out: list = dataclasses.field(default_factory=list)
    t_submit: float = 0.0
    t_admit: float = 0.0               # first appearance in the active map
    t_first: float = 0.0               # first generated token
    t_finish: float = 0.0

    def next_token(self) -> int:
        # teacher-force the prompt, then feed back the last sample
        return (self.prompt[self.pos] if self.pos < len(self.prompt)
                else self.out[-1])


# the step's K and V pools: donated, so the token scatter writes in place
_POOL_ARGS = (3, 4)


def _with_adapters(base: dict, stacks: dict, ad_slots: Array,
                   sites) -> dict:
    """The base with each site's adapters gathered per request from the
    registry's stacks: ``(L, cap, m, r) -> (L, B, m, r)``."""
    params = dict(base)
    params["blocks"] = dict(base["blocks"])
    for site in sites:
        keys = site.split(".")
        node = _copy_to(params["blocks"], keys[:-1])
        leaf = dict(node[keys[-1]])
        st = stacks[site]
        with scope("lora_gather"):
            leaf["lora_a"] = jnp.take(st["lora_a"], ad_slots, axis=1)
            leaf["lora_b"] = jnp.take(st["lora_b"], ad_slots, axis=1)
        node[keys[-1]] = leaf
    return params


def _decode_step_fn(cfg, sites: tuple):
    """The raw (untraced) serving step for one (model config, site set) —
    jitted by :func:`_decode_exec` for the in-process path, or wrapped in a
    :class:`~repro.core.compile_cache.PersistedFunction` when the engine is
    given a compile cache (cold-start skips the retrace).  Both donate the
    pools (``_POOL_ARGS``): the layer scan hands back each row's new K/V,
    which is scattered straight into them."""

    def step_fn(base, stacks, ad_slots, k_pool, v_pool, page_tables,
                lengths, tokens):
        params = _with_adapters(base, stacks, ad_slots, sites)
        K = gather_pages(k_pool, page_tables)
        V = gather_pages(v_pool, page_tables)
        cache = {"k": K, "v": V, "idx": lengths}
        logits, _, (newk, newv) = decode_step_rows(params, cfg, cache,
                                                   tokens, pctx=LOCAL)
        k_pool = scatter_token(k_pool, newk, page_tables, lengths)
        v_pool = scatter_token(v_pool, newv, page_tables, lengths)
        nxt = jnp.argmax(logits[:, :cfg.vocab], axis=-1)
        return nxt.astype(jnp.int32), k_pool, v_pool

    return step_fn


@functools.lru_cache(maxsize=32)
def _decode_exec(cfg, sites: tuple):
    """One jitted serving step per (model config, site set) — cached at
    module level so every engine instance (and every benchmark rep)
    shares the same executable; jit's own cache then specializes it per
    rank-bucket shape signature."""
    return jax.jit(_decode_step_fn(cfg, sites), donate_argnums=_POOL_ARGS)


def _copy_to(node: dict, keys: list[str]) -> dict:
    """Copy nested dicts along a path so splicing never mutates the base."""
    for k in keys:
        node[k] = dict(node[k])
        node = node[k]
    return node


def _strip_adapters(params: dict, sites) -> dict:
    out = dict(params)
    out["blocks"] = dict(params["blocks"])
    for site in sites:
        keys = site.split(".")
        node = _copy_to(out["blocks"], keys[:-1])
        node[keys[-1]] = {k: v for k, v in node[keys[-1]].items()
                          if k not in ("lora_a", "lora_b")}
    return out


class ServeEngine:
    def __init__(self, params: dict, cfg, registry: AdapterRegistry, *,
                 page_size: int = 8, n_pages: int | None = None,
                 max_len: int = 64, bucket_capacity: int = 4,
                 use_kernel: bool = False, compile_cache=None):
        if cfg.family not in ("dense", "moe"):
            raise ValueError(
                f"ServeEngine serves attention-cache families (dense/moe); "
                f"{cfg.family!r} models use the static-slot loop in "
                "repro.launch.serve")
        if not cfg.scan_layers:
            raise ValueError("ServeEngine needs scan (stacked-layer) params")
        if cfg.quant is not None:
            cfg = dataclasses.replace(
                cfg, quant=dataclasses.replace(cfg.quant,
                                               use_kernel=use_kernel))
        self.cfg = cfg
        self.registry = registry
        self.bucket_capacity = bucket_capacity
        self._page = page_size
        self._maxp = pages_needed(max_len, page_size)
        self.max_len = self._maxp * page_size
        if n_pages is None:
            n_pages = 2 * bucket_capacity * self._maxp + 1
        self._base = _strip_adapters(params, registry.sites())
        hd = cfg.head_dim or (cfg.d_model // max(cfg.n_heads, 1))
        self._k_pool, self._v_pool = init_pools(
            cfg.n_layers, n_pages, page_size, cfg.n_kv_heads, hd, cfg.dtype)
        self.scheduler = Scheduler({}, PageAllocator(n_pages))
        self._reqs: dict[int, _Request] = {}
        self._next_rid = 0
        self.steps = 0
        sites = tuple(self.registry.sites())
        from repro.core.compile_cache import CompileCache, PersistedFunction
        self.compile_cache = CompileCache.coerce(compile_cache)
        if self.compile_cache is not None:
            # persisted AOT path: each rank-bucket shape signature resolves
            # through the disk cache, so a second process start deserializes
            # instead of retracing the decode step
            self._exec = PersistedFunction(
                self.compile_cache, "decode",
                {"cfg": repr(self.cfg), "sites": list(sites)},
                _decode_step_fn(self.cfg, sites), donate_argnums=_POOL_ARGS)
        else:
            self._exec = _decode_exec(self.cfg, sites)

    # -- request lifecycle -------------------------------------------------

    def submit(self, prompt, tenant: str, max_new: int = 16,
               eos: int | None = None) -> int:
        rank, ad_slot = self.registry.slot_of(tenant)
        self.scheduler.ensure_bucket(rank, self.bucket_capacity)
        prompt = [int(t) for t in prompt]
        if not prompt or max_new < 1:
            raise ValueError("need a non-empty prompt and max_new >= 1")
        n_tok = len(prompt) + max_new - 1
        if n_tok > self.max_len:
            raise ValueError(f"request needs {n_tok} cache positions, "
                             f"engine max_len is {self.max_len}")
        rid = self._next_rid
        self._next_rid += 1
        self._reqs[rid] = _Request(rid, tenant, rank, ad_slot, prompt,
                                   max_new, eos, t_submit=time.perf_counter())
        self.scheduler.submit(rid, rank, pages_needed(n_tok, self._page))
        obs_metrics.counter(obs_names.SERVE_SUBMITTED).inc()
        return rid

    def step(self) -> list[int]:
        """One engine iteration; returns rids finished this step.

        Its spans tile the host's work: ``serve.schedule`` (admission),
        then per rank bucket ``serve.prepare`` (the host arrays),
        ``serve.decode`` (``serve.upload``, ``serve.dispatch``,
        ``serve.sync``) and ``serve.commit`` (tokens, retirement), and a
        last ``serve.commit`` for the KV gauges.  Each request's phases
        are recorded as they end: ``serve.queue`` (submit to admit),
        ``serve.prefill`` (admit to first token), ``serve.generate``
        (first token to finish), each with its ``rid``."""
        with obs_trace.span("serve.step", step=self.steps) as step_sp:
            with obs_trace.span("serve.schedule"):
                active = self.scheduler.tick()
                self._admit(active)
            finished: list[int] = []
            for rank in sorted(b for b, ent in active.items() if ent):
                entries = active[rank]
                with obs_trace.span("serve.prepare", rank=rank):
                    stacks = self.registry.stacks(rank)
                    ad, toks, lens, pt = self._host_arrays(entries)
                with obs_trace.span("serve.decode", rank=rank,
                                    batch=len(entries)):
                    with obs_trace.span("serve.upload"):
                        ad, pt = jnp.asarray(ad), jnp.asarray(pt)
                        lens, toks = jnp.asarray(lens), jnp.asarray(toks)
                    with obs_trace.span("serve.dispatch"):
                        nxt, self._k_pool, self._v_pool = self._exec(
                            self._base, stacks, ad, self._k_pool,
                            self._v_pool, pt, lens, toks)
                    with obs_trace.span("serve.sync"):
                        nxt = np.asarray(nxt)    # host sync inside the span
                with obs_trace.span("serve.commit", rank=rank):
                    self._commit(entries, nxt, finished)
            with obs_trace.span("serve.commit"):
                self._kv_metrics()
                obs_metrics.counter(obs_names.SERVE_STEPS).inc()
            self.steps += 1
            step_sp.set(finished=len(finished))
        return finished

    def _admit(self, active: dict) -> None:
        """Stamp the requests that got a slot this step."""
        now = time.perf_counter()
        queue_hist = obs_metrics.histogram(obs_names.SERVE_QUEUE_WAIT)
        for entries in active.values():
            for _slot, rid in entries:
                r = self._reqs[rid]
                if r.t_admit == 0.0:
                    r.t_admit = now
                    queue_hist.observe(now - r.t_submit)
                    obs_trace.complete("serve.queue", r.t_submit, now,
                                       rid=rid, tenant=r.tenant)

    def _host_arrays(self, entries) -> tuple:
        """A bucket's adapter slots, next tokens, lengths and page
        tables, one row per slot."""
        B = self.bucket_capacity
        ad = np.zeros((B,), np.int32)
        toks = np.zeros((B, 1), np.int32)
        lens = np.zeros((B,), np.int32)
        pt = np.zeros((B, self._maxp), np.int32)
        for slot, rid in entries:
            r = self._reqs[rid]
            ad[slot] = r.ad_slot
            toks[slot, 0] = r.next_token()
            lens[slot] = r.pos
            pages = self.scheduler.pages_of(rid)
            pt[slot, :len(pages)] = pages
        return ad, toks, lens, pt

    def _commit(self, entries, nxt: np.ndarray, finished: list) -> None:
        """Advance each row of a bucket by the token it was given, keep
        the generated ones, and retire the requests that are done."""
        prompt_rows = 0
        for slot, rid in entries:
            r = self._reqs[rid]
            prompt_rows += r.pos < len(r.prompt)
            r.pos += 1
            if r.pos >= len(r.prompt):
                tok = int(nxt[slot])
                r.out.append(tok)
                obs_metrics.counter(obs_names.SERVE_TOKENS).inc()
                if len(r.out) == 1:
                    r.t_first = time.perf_counter()
                    obs_metrics.histogram(obs_names.SERVE_TTFT).observe(
                        r.t_first - r.t_submit)
                    obs_trace.complete("serve.prefill", r.t_admit,
                                       r.t_first, rid=rid,
                                       prompt=len(r.prompt))
                if len(r.out) >= r.max_new or tok == r.eos:
                    r.t_finish = time.perf_counter()
                    obs_trace.complete("serve.generate", r.t_first,
                                       r.t_finish, rid=rid,
                                       tokens=len(r.out))
                    self._retire_metrics(r)
                    self.scheduler.retire(rid)
                    finished.append(rid)
        if prompt_rows:
            obs_metrics.counter(obs_names.SERVE_PROMPT_ROWS).inc(prompt_rows)

    def _retire_metrics(self, r: _Request) -> None:
        obs_metrics.counter(obs_names.SERVE_FINISHED).inc()
        if len(r.out) > 1:
            obs_metrics.histogram(
                obs_names.SERVE_TOKEN_LATENCY).observe(
                (r.t_finish - r.t_first) / (len(r.out) - 1))

    def _kv_metrics(self) -> None:
        alloc = self.scheduler.allocator
        in_use = alloc.n_usable - alloc.n_free
        obs_metrics.gauge(obs_names.SERVE_KV_PAGES_IN_USE).set(in_use)
        obs_metrics.gauge(obs_names.SERVE_KV_PAGES_TOTAL).set(
            alloc.n_usable)
        obs_metrics.histogram(obs_names.SERVE_KV_OCCUPANCY).observe(
            in_use / alloc.n_usable)

    def run(self, max_steps: int | None = None) -> dict[int, list[int]]:
        """Drive until every submitted request retires."""
        if max_steps is None:
            max_steps = self.scheduler.outstanding() * (self.max_len + 2) + 4
        for _ in range(max_steps):
            if not self.scheduler.outstanding():
                break
            self.step()
        if self.scheduler.outstanding():
            raise RuntimeError("scheduler failed to drain the queue "
                               f"within {max_steps} steps")
        return {rid: list(r.out) for rid, r in self._reqs.items() if r.out}

    # -- views -------------------------------------------------------------

    def result(self, rid: int) -> list[int]:
        return list(self._reqs[rid].out)

    def latency(self, rid: int) -> float:
        r = self._reqs[rid]
        return r.t_finish - r.t_submit


def run_workload(engine: ServeEngine, requests, *,
                 sequential: bool = False) -> dict[int, list[int]]:
    """Serve ``[(tenant, prompt, max_new), ...]``; returns {i: tokens}.

    ``sequential=True`` is the parity reference: one request in flight at
    a time through the SAME engine/executables, so each batched row has a
    bit-identical single-request replay."""
    outs: dict[int, list[int]] = {}
    if sequential:
        for i, (tenant, prompt, max_new) in enumerate(requests):
            rid = engine.submit(prompt, tenant, max_new)
            engine.run()
            outs[i] = engine.result(rid)
    else:
        rids = [engine.submit(prompt, tenant, max_new)
                for tenant, prompt, max_new in requests]
        engine.run()
        outs = {i: engine.result(rid) for i, rid in enumerate(rids)}
    return outs
