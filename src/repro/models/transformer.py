"""Composable model stack: dense / MoE / SSM / hybrid / enc-dec / VLM.

One ``ModelConfig`` describes any assigned architecture; ``init_params``
builds the (optionally layer-stacked) param tree; ``forward``/``loss_fn``
are the training path (scan-over-layers + remat); ``init_decode_cache`` /
``decode_step`` are the serving path.

Quantized fine-tuning (the paper's deployment): block linears carry
OPTQ+CLoQ state ({qcodes, scales, zeros, lora_a, lora_b}); only LoRA params
train.  ``repro.core.pipeline`` converts a dense param tree into this form.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.models.attention import (AttnConfig, attn_apply, attn_decode,
                                    attn_init, cross_attn_apply)
from repro.models.mlp import swiglu_apply, swiglu_init
from repro.models.modules import (QSpec, embedding_apply, embedding_init,
                                  lm_head_apply, linear_init, rmsnorm_apply,
                                  rmsnorm_init)
from repro.models.moe import MoEConfig, moe_apply, moe_init
from repro.models.parallel import LOCAL, PContext
from repro.models.ssm import (SSMConfig, mamba_apply, mamba_decode,
                              mamba_init, mamba_init_cache)
from repro.utils import scope

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                   # dense | moe | ssm | hybrid | encdec
    n_layers: int
    d_model: int
    vocab: int
    n_heads: int = 0
    n_kv_heads: int = 0
    d_ff: int = 0
    head_dim: int | None = None
    qk_norm: bool = False
    qk_norm_span: str = "head"    # head: per head (qwen3) | proj: whole q/k
    attn_bias: bool = False
    rope_theta: float = 1e6
    rms_norm_eps: float = 1e-6
    tie_embeddings: bool = False
    # MoE
    n_experts: int = 0
    top_k: int = 0
    d_ff_expert: int = 0
    # None: dropless, grouped matmuls over every routed row, no drops
    capacity_factor: float | None = 1.25
    norm_topk: bool = True        # renormalize the top-k router weights
    router_aux_weight: float = 0.01   # load-balance term's weight in loss
    # SSM
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_groups: int = 1
    ssm_chunk: int = 256
    # hybrid (zamba2-style): shared attn+mlp block applied every k SSM layers
    hybrid_attn_every: int = 6
    hybrid_window: int | None = 4096   # sliding window at long context
    # enc-dec
    n_enc_layers: int = 0
    frontend: str | None = None   # "audio" | "vision" (stub embeddings input)
    n_prefix: int = 0             # vlm: number of patch positions
    vocab_pad_multiple: int = 1   # pad embedding/head rows for TP divisibility
    # training/runtime
    quant: QSpec | None = None
    lora_rank: int = 0            # LoRA on dense weights (fp16-LoRA baseline)
    scan_layers: bool = True
    remat: str = "full"           # full | dots | tp_out | none
    dtype: Any = jnp.bfloat16
    max_seq: int = 4096
    # §Perf levers (EXPERIMENTS.md §Perf; defaults = paper-faithful baseline)
    loss_chunk: int = 0           # >0: CE loss computed over seq chunks
    attn_chunk: int = 0           # >0: blockwise (flash-style) attention
    seq_shard: bool = False       # sequence-parallel residual stream (GSPMD)

    # ---- derived ----
    def attn_cfg(self, causal=True, window=None) -> AttnConfig:
        return AttnConfig(self.d_model, self.n_heads, self.n_kv_heads,
                          self.head_dim, self.qk_norm, self.rope_theta,
                          window, causal, self.attn_bias, self.qk_norm_span,
                          self.rms_norm_eps)

    def moe_cfg(self) -> MoEConfig:
        return MoEConfig(self.n_experts, self.top_k, self.d_model,
                         self.d_ff_expert, self.capacity_factor,
                         self.norm_topk)

    def ssm_cfg(self) -> SSMConfig:
        return SSMConfig(self.d_model, self.ssm_state, self.ssm_head_dim,
                         2, self.ssm_groups, 4, self.ssm_chunk)

    @property
    def trainable_rank(self) -> int:
        return self.quant.rank if self.quant else self.lora_rank

    @property
    def vocab_padded(self) -> int:
        m = self.vocab_pad_multiple
        return -(-self.vocab // m) * m

    @property
    def n_hybrid_sites(self) -> int:
        return self.n_layers // self.hybrid_attn_every if self.family == "hybrid" else 0


# ---------------------------------------------------------------------------
# Block init/apply per family.
# ---------------------------------------------------------------------------


def _block_init(key, cfg: ModelConfig) -> dict:
    ks = jax.random.split(key, 4)
    r = cfg.lora_rank
    if cfg.family in ("dense", "encdec"):
        return {"ln1": rmsnorm_init(cfg.d_model, cfg.dtype),
                "attn": attn_init(ks[0], cfg.attn_cfg(), dtype=cfg.dtype, lora_rank=r),
                "ln2": rmsnorm_init(cfg.d_model, cfg.dtype),
                "mlp": swiglu_init(ks[1], cfg.d_model, cfg.d_ff, dtype=cfg.dtype, lora_rank=r)}
    if cfg.family == "moe":
        return {"ln1": rmsnorm_init(cfg.d_model, cfg.dtype),
                "attn": attn_init(ks[0], cfg.attn_cfg(), dtype=cfg.dtype, lora_rank=r),
                "ln2": rmsnorm_init(cfg.d_model, cfg.dtype),
                "moe": moe_init(ks[1], cfg.moe_cfg(), dtype=cfg.dtype, lora_rank=r)}
    if cfg.family in ("ssm", "hybrid"):
        return {"norm": rmsnorm_init(cfg.d_model, cfg.dtype),
                "mamba": mamba_init(ks[0], cfg.ssm_cfg(), dtype=cfg.dtype, lora_rank=r)}
    raise ValueError(cfg.family)


def aux_zero(cfg: ModelConfig) -> dict:
    """What the layer stack gathers from its layers: the load-balance loss
    term (summed over layers) and, in an MoE model, the busiest expert's
    routed rows over the mean (the largest over layers)."""
    aux = {"balance": jnp.zeros((), jnp.float32)}
    if cfg.family == "moe":
        aux["load_max"] = jnp.zeros((), jnp.float32)
    return aux


def _aux_add(a: dict, b: dict) -> dict:
    return {k: jnp.maximum(a[k], b[k]) if k == "load_max" else a[k] + b[k]
            for k in a}


def _block_apply(p, cfg: ModelConfig, x: Array, *, pctx: PContext,
                 window: int | None = None) -> tuple[Array, dict]:
    """Returns (y, aux) as :func:`aux_zero` lays it out."""
    aux = aux_zero(cfg)
    q = cfg.quant
    chunk = cfg.attn_chunk or None
    if cfg.family in ("dense", "encdec"):
        with scope("attn"):
            y = attn_apply(p["attn"], cfg.attn_cfg(window=window),
                           rmsnorm_apply(p["ln1"], x, cfg.rms_norm_eps), qspec=q,
                           q_chunk=chunk)
            x = _seq_shard(cfg, x + _tag_tp_out(cfg, y), pctx)
        with scope("mlp"):
            y = swiglu_apply(p["mlp"], rmsnorm_apply(p["ln2"], x, cfg.rms_norm_eps), q)
            x = _seq_shard(cfg, x + _tag_tp_out(cfg, y), pctx)
    elif cfg.family == "moe":
        with scope("attn"):
            y = attn_apply(p["attn"], cfg.attn_cfg(window=window),
                           rmsnorm_apply(p["ln1"], x, cfg.rms_norm_eps), qspec=q,
                           q_chunk=chunk)
            x = _seq_shard(cfg, x + _tag_tp_out(cfg, y), pctx)
        with scope("moe"):
            y, aux = moe_apply(p["moe"], cfg.moe_cfg(),
                               rmsnorm_apply(p["ln2"], x, cfg.rms_norm_eps), qspec=q, pctx=pctx)
            x = _seq_shard(cfg, x + _tag_tp_out(cfg, y), pctx)
    elif cfg.family in ("ssm", "hybrid"):
        with scope("mamba"):
            y = mamba_apply(p["mamba"], cfg.ssm_cfg(),
                            rmsnorm_apply(p["norm"], x, cfg.rms_norm_eps), qspec=q)
            x = _seq_shard(cfg, x + _tag_tp_out(cfg, y), pctx)
    return x, aux


def _shared_block_init(key, cfg: ModelConfig) -> dict:
    """Zamba2-style shared transformer block + per-site LoRA stacks."""
    ks = jax.random.split(key, 3)
    blk = {"ln1": rmsnorm_init(cfg.d_model, cfg.dtype),
           "attn": attn_init(ks[0], cfg.attn_cfg(), dtype=cfg.dtype),
           "ln2": rmsnorm_init(cfg.d_model, cfg.dtype),
           "mlp": swiglu_init(ks[1], cfg.d_model, cfg.d_ff, dtype=cfg.dtype)}
    # per-site LoRA on every linear of the shared block (zamba2's mechanism —
    # and the natural carrier for per-site CLoQ initialization).
    r = max(cfg.trainable_rank, 8)
    n_sites = cfg.n_hybrid_sites
    hd = cfg.head_dim or cfg.d_model // cfg.n_heads
    dims = {"attn.q": (cfg.d_model, cfg.n_heads * hd),
            "attn.k": (cfg.d_model, cfg.n_kv_heads * hd),
            "attn.v": (cfg.d_model, cfg.n_kv_heads * hd),
            "attn.o": (cfg.n_heads * hd, cfg.d_model),
            "mlp.gate": (cfg.d_model, cfg.d_ff),
            "mlp.up": (cfg.d_model, cfg.d_ff),
            "mlp.down": (cfg.d_ff, cfg.d_model)}
    lora = {}
    kk = jax.random.split(ks[2], len(dims))
    for i, (path, (m, n)) in enumerate(sorted(dims.items())):
        lora[path.replace(".", "_")] = {
            "lora_a": (jax.random.normal(kk[i], (n_sites, m, r), jnp.float32)
                       / jnp.sqrt(m)).astype(cfg.dtype),
            "lora_b": jnp.zeros((n_sites, n, r), cfg.dtype),
        }
    return {"block": blk, "site_lora": lora}


def _with_site_lora(shared: dict, site_lora: dict, site: Array) -> dict:
    """Materialize the shared block with site-``site`` LoRA spliced in."""
    blk = {"ln1": shared["ln1"], "ln2": shared["ln2"],
           "attn": dict(shared["attn"]), "mlp": dict(shared["mlp"])}
    for key, sub in site_lora.items():
        mod, lin = key.split("_", 1)
        tgt = dict(blk[mod][lin])
        tgt["lora_a"] = jax.lax.dynamic_index_in_dim(sub["lora_a"], site, 0, False)
        tgt["lora_b"] = jax.lax.dynamic_index_in_dim(sub["lora_b"], site, 0, False)
        blk[mod][lin] = tgt
    return blk


def _shared_block_apply(p, cfg: ModelConfig, x: Array, site: Array, *,
                        window: int | None) -> Array:
    blk = _with_site_lora(p["block"], p["site_lora"], site)
    with scope("shared.attn"):
        x = x + attn_apply(blk["attn"], cfg.attn_cfg(window=window),
                           rmsnorm_apply(blk["ln1"], x, cfg.rms_norm_eps), qspec=cfg.quant)
    with scope("shared.mlp"):
        x = x + swiglu_apply(blk["mlp"], rmsnorm_apply(blk["ln2"], x, cfg.rms_norm_eps), cfg.quant)
    return x


# ---------------------------------------------------------------------------
# Whole-model init.
# ---------------------------------------------------------------------------


def init_params(key, cfg: ModelConfig) -> dict:
    keys = jax.random.split(key, 8)
    vp = cfg.vocab_padded
    p: dict = {"embed": embedding_init(keys[0], vp, cfg.d_model, cfg.dtype),
               "final_norm": rmsnorm_init(cfg.d_model, cfg.dtype)}
    if not cfg.tie_embeddings:
        p["head"] = linear_init(keys[1], cfg.d_model, vp, dtype=cfg.dtype)

    def make_stack(key, n):
        if cfg.scan_layers:
            return jax.vmap(lambda k: _block_init(k, cfg))(jax.random.split(key, n))
        return {str(i): _block_init(k, cfg)
                for i, k in enumerate(jax.random.split(key, n))}

    if cfg.family == "encdec":
        p["enc_blocks"] = make_stack(keys[2], cfg.n_enc_layers)
        p["dec_blocks"] = make_stack(keys[3], cfg.n_layers)
        # decoder cross-attention stack
        def cross_init(k):
            return {"ln": rmsnorm_init(cfg.d_model, cfg.dtype),
                    "xattn": attn_init(k, cfg.attn_cfg(causal=False),
                                       dtype=cfg.dtype, lora_rank=cfg.lora_rank)}
        if cfg.scan_layers:
            p["cross"] = jax.vmap(cross_init)(jax.random.split(keys[4], cfg.n_layers))
        else:
            p["cross"] = {str(i): cross_init(k)
                          for i, k in enumerate(jax.random.split(keys[4], cfg.n_layers))}
        p["enc_norm"] = rmsnorm_init(cfg.d_model, cfg.dtype)
    else:
        p["blocks"] = make_stack(keys[2], cfg.n_layers)
    if cfg.family == "hybrid":
        p["shared"] = _shared_block_init(keys[5], cfg)
    return p


# ---------------------------------------------------------------------------
# Forward (training / prefill).
# ---------------------------------------------------------------------------


def _remat_policy(cfg: ModelConfig):
    if cfg.remat == "none":
        return None
    if cfg.remat == "dots":
        return jax.checkpoint_policies.checkpoint_dots
    if cfg.remat == "tp_out":
        # save exactly the TP-boundary activations (attn/mlp block outputs,
        # tagged below): the backward sweep then re-runs block internals but
        # NOT the all-reduces that follow the tagged dots — kills the remat
        # doubling of TP collective traffic (§Perf iteration 2)
        return jax.checkpoint_policies.save_only_these_names("tp_out")
    return jax.checkpoint_policies.nothing_saveable


def _tag_tp_out(cfg: ModelConfig, x: Array) -> Array:
    if cfg.remat == "tp_out":
        from jax.ad_checkpoint import checkpoint_name
        return checkpoint_name(x, "tp_out")
    return x


def _seq_shard(cfg: ModelConfig, x: Array, pctx: PContext) -> Array:
    """Sequence-parallel residual stream: between blocks the (B, S, D)
    activations live sharded S-over-model; GSPMD turns the per-block
    all-reduces into reduce-scatter + all-gather pairs and the saved remat
    tensors shrink by the TP degree (§Perf iteration 3)."""
    if not cfg.seq_shard or pctx.mesh is None:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P
    spec = P(pctx.data_axes, pctx.model_axis, None)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(pctx.mesh, spec))


def _segment_blocks(blocks, n_layers: int, every: int, n_sites: int):
    """Reshape stacked block params (L, ...) into a (n_sites, every, ...)
    prefix plus an unscanned remainder (L - n_sites*every, ...)."""
    n_seg = n_sites * every

    def seg(a):
        return a[:n_seg].reshape(n_sites, every, *a.shape[1:])

    seg_blocks = jax.tree.map(seg, blocks)
    rem_blocks = jax.tree.map(lambda a: a[n_seg:], blocks)
    return seg_blocks, rem_blocks, n_layers - n_seg


def _run_stack(blocks, cfg: ModelConfig, x: Array, *, pctx: PContext,
               window: int | None = None, shared: dict | None = None):
    """Scan (or loop) the block stack. Returns (x, aux) over all layers."""
    every = cfg.hybrid_attn_every
    zero = aux_zero(cfg)

    def body_fn(carry, bp):
        x, aux = carry
        y, a = _block_apply(bp, cfg, x, pctx=pctx, window=window)
        return (y, _aux_add(aux, a)), None

    pol = _remat_policy(cfg)

    def scan_stack(x, aux, stacked):
        body = body_fn
        if pol is not None:
            body = jax.checkpoint(body_fn, policy=pol, prevent_cse=False)
        (x, aux), _ = jax.lax.scan(body, (x, aux), stacked)
        return x, aux

    if cfg.scan_layers:
        if shared is None:
            return scan_stack(x, zero, blocks)
        # hybrid: scan over (site segments of ``every`` SSM layers + one
        # shared-attn application), then the unscanned remainder layers.
        seg_blocks, rem_blocks, n_rem = _segment_blocks(
            blocks, cfg.n_layers, every, cfg.n_hybrid_sites)

        def seg_body(carry, inp):
            x, aux = carry
            bseg, site = inp
            x, aux = scan_stack(x, aux, bseg)
            x = _shared_block_apply(shared, cfg, x, site, window=window)
            return (x, aux), None

        body = seg_body
        if pol is not None:
            body = jax.checkpoint(seg_body, policy=pol, prevent_cse=False)
        (x, aux), _ = jax.lax.scan(
            body, (x, zero), (seg_blocks, jnp.arange(cfg.n_hybrid_sites)))
        if n_rem:
            x, aux = scan_stack(x, aux, rem_blocks)
        return x, aux

    aux = zero
    # unrolled path: same remat policy as the scanned path so depth-probe
    # costs extrapolate to the scanned executable (benchmarks/roofline.py).
    # jax.checkpoint traces its body, which would silence the eager
    # calibration hooks — skip remat while capturing Grams.
    from repro.utils import is_capturing
    use_remat = pol is not None and not is_capturing()
    if use_remat:
        block_fn = jax.checkpoint(
            lambda bp, x: _block_apply(bp, cfg, x, pctx=pctx, window=window),
            policy=pol, prevent_cse=False)
    for i in sorted(blocks, key=int):
        with scope(f"blocks.{i}"):
            if use_remat:
                x, a = block_fn(blocks[i], x)
            else:
                x, a = _block_apply(blocks[i], cfg, x, pctx=pctx, window=window)
            aux = _aux_add(aux, a)
        if shared is not None and (int(i) + 1) % every == 0:
            site = (int(i) + 1) // every - 1
            if site < cfg.n_hybrid_sites:
                with scope(f"sites.{site}"):
                    x = _shared_block_apply(shared, cfg, x, jnp.int32(site),
                                            window=window)
    return x, aux


def forward(params: dict, cfg: ModelConfig, batch: dict, *,
            pctx: PContext = LOCAL, window: int | None = None,
            return_hidden: bool = False):
    """Training/prefill forward.  batch:
        tokens (B, S) int32                       [LM families]
        enc_embeds (B, Se, D) [encdec stub] + tokens (B, S) decoder side
        prefix_embeds (B, P, D) [vlm stub] — prepended to token embeddings
    Returns (logits (B, S, V), aux) — or (hidden (B, S, D), aux) with
    ``return_hidden`` (chunked-loss path)."""
    if cfg.family == "encdec":
        return _forward_encdec(params, cfg, batch, pctx=pctx,
                               return_hidden=return_hidden)
    x = embedding_apply(params["embed"], batch["tokens"]).astype(cfg.dtype)
    if cfg.frontend == "vision" and "prefix_embeds" in batch:
        x = jnp.concatenate([batch["prefix_embeds"].astype(cfg.dtype), x], axis=1)
    shared = params.get("shared")
    x, aux = _run_stack(params["blocks"], cfg, x, pctx=pctx, window=window,
                        shared=shared)
    x = rmsnorm_apply(params["final_norm"], x, cfg.rms_norm_eps)
    if cfg.frontend == "vision" and "prefix_embeds" in batch:
        x = x[:, batch["prefix_embeds"].shape[1]:, :]
    if return_hidden:
        return x, aux
    return lm_logits(params, cfg, x), aux


def _forward_encdec(params, cfg: ModelConfig, batch, *, pctx: PContext,
                    return_hidden: bool = False):
    enc_x = batch["enc_embeds"].astype(cfg.dtype)      # frontend stub output
    # encoder: bidirectional attention
    def enc_body(carry, bp):
        x, _ = carry
        with scope("attn"):
            x = x + attn_apply(bp["attn"], cfg.attn_cfg(causal=False),
                               rmsnorm_apply(bp["ln1"], x, cfg.rms_norm_eps), qspec=cfg.quant)
        with scope("mlp"):
            x = x + swiglu_apply(bp["mlp"], rmsnorm_apply(bp["ln2"], x, cfg.rms_norm_eps), cfg.quant)
        return (x, jnp.zeros((), jnp.float32)), None

    if cfg.scan_layers:
        body = jax.checkpoint(enc_body, policy=_remat_policy(cfg) or
                              jax.checkpoint_policies.nothing_saveable,
                              prevent_cse=False)
        (enc_x, _), _ = jax.lax.scan(body, (enc_x, jnp.zeros((), jnp.float32)),
                                     params["enc_blocks"])
    else:
        for i in sorted(params["enc_blocks"], key=int):
            with scope(f"enc_blocks.{i}"):
                (enc_x, _), _ = enc_body((enc_x, jnp.zeros((), jnp.float32)),
                                         params["enc_blocks"][i])
    enc_out = rmsnorm_apply(params["enc_norm"], enc_x, cfg.rms_norm_eps)

    x = embedding_apply(params["embed"], batch["tokens"]).astype(cfg.dtype)

    def dec_body(carry, bps):
        x, aux = carry
        bp, cp = bps
        y, a = _block_apply(bp, dataclasses.replace(cfg, family="dense"), x,
                            pctx=pctx)
        with scope("cross"):
            y = y + cross_attn_apply(cp["xattn"], cfg.attn_cfg(causal=False),
                                     rmsnorm_apply(cp["ln"], y, cfg.rms_norm_eps), enc_out,
                                     qspec=cfg.quant)
        return (y, _aux_add(aux, a)), None

    if cfg.scan_layers:
        body = jax.checkpoint(dec_body, policy=_remat_policy(cfg) or
                              jax.checkpoint_policies.nothing_saveable,
                              prevent_cse=False)
        (x, aux), _ = jax.lax.scan(body, (x, aux_zero(cfg)),
                                   (params["dec_blocks"], params["cross"]))
    else:
        aux = aux_zero(cfg)
        for i in sorted(params["dec_blocks"], key=int):
            with scope(f"dec_blocks.{i}"):
                (x, aux), _ = dec_body(
                    (x, aux), (params["dec_blocks"][i], params["cross"][i]))
    x = rmsnorm_apply(params["final_norm"], x, cfg.rms_norm_eps)
    if return_hidden:
        return x, aux
    return lm_logits(params, cfg, x), aux


def lm_logits(params: dict, cfg: ModelConfig, x: Array) -> Array:
    """Logits of the hidden states ``x``: through the embedding where the
    model ties it to the head, else through the head."""
    if cfg.tie_embeddings:
        return lm_head_apply(params["embed"], x, tied=True)
    return lm_head_apply(params["head"], x, tied=False)


def _ce(logits: Array, labels: Array):
    logits = logits.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    return jnp.sum(ll * mask), jnp.sum(mask)


def loss_fn(params: dict, cfg: ModelConfig, batch: dict, *,
            pctx: PContext = LOCAL, window: int | None = None):
    labels = batch["labels"]
    C = cfg.loss_chunk
    if C and labels.shape[1] % C == 0 and labels.shape[1] > C:
        # chunked CE: never materializes the full (B, S, V) f32 logits —
        # head matmul + log-softmax stream over sequence chunks (§Perf).
        # UNROLLED (not lax.map) so cost_analysis FLOPs stay exact.
        hidden, aux = forward(params, cfg, batch, pctx=pctx, window=window,
                              return_hidden=True)
        B, S, D = hidden.shape
        nb = S // C
        tot_s = jnp.zeros((), jnp.float32)
        tot_c = jnp.zeros((), jnp.float32)
        for i in range(nb):
            s, c = _ce(lm_logits(params, cfg, hidden[:, i * C:(i + 1) * C]),
                       labels[:, i * C:(i + 1) * C])
            tot_s += s
            tot_c += c
        loss = -tot_s / jnp.maximum(tot_c, 1.0)
    else:
        logits, aux = forward(params, cfg, batch, pctx=pctx, window=window)
        s, c = _ce(logits, labels)
        loss = -s / jnp.maximum(c, 1.0)
    return loss + cfg.router_aux_weight * aux["balance"], (loss, aux)


# ---------------------------------------------------------------------------
# Decode (serving).
# ---------------------------------------------------------------------------


def init_decode_cache(cfg: ModelConfig, batch: int, cache_len: int,
                      dtype=None) -> dict:
    """KV/state caches for one-token-at-a-time decode with context
    ``cache_len`` (the dry-run's ``decode_*`` shapes)."""
    dtype = dtype or cfg.dtype
    hd = cfg.head_dim or (cfg.d_model // max(cfg.n_heads, 1))

    def kv(n_layers, length):
        return {"k": jnp.zeros((n_layers, batch, length, cfg.n_kv_heads, hd), dtype),
                "v": jnp.zeros((n_layers, batch, length, cfg.n_kv_heads, hd), dtype),
                "idx": jnp.zeros((), jnp.int32)}

    if cfg.family in ("dense", "moe"):
        return kv(cfg.n_layers, cache_len)
    def ssm_caches(scfg):
        return {"conv_x": jnp.zeros((cfg.n_layers, batch, scfg.conv_kernel - 1,
                                     scfg.d_inner), jnp.float32),
                "conv_bc": jnp.zeros((cfg.n_layers, batch, scfg.conv_kernel - 1,
                                      scfg.d_bc), jnp.float32),
                "state": jnp.zeros((cfg.n_layers, batch, scfg.n_heads,
                                    scfg.head_dim, scfg.d_state), jnp.float32)}

    if cfg.family == "ssm":
        return {**ssm_caches(cfg.ssm_cfg()), "idx": jnp.zeros((), jnp.int32)}
    if cfg.family == "hybrid":
        win = min(cache_len, cfg.hybrid_window or cache_len)
        return {**ssm_caches(cfg.ssm_cfg()),
                "shared_kv": kv(cfg.n_hybrid_sites, win),
                "idx": jnp.zeros((), jnp.int32)}
    if cfg.family == "encdec":
        enc_len = cache_len
        return {"enc_out": jnp.zeros((batch, enc_len, cfg.d_model), dtype),
                **kv(cfg.n_layers, cache_len), "idx": jnp.zeros((), jnp.int32)}
    raise ValueError(cfg.family)


def decode_step(params: dict, cfg: ModelConfig, cache: dict, tokens: Array, *,
                pctx: PContext = LOCAL) -> tuple[Array, dict]:
    """One decode step. tokens (B, 1) int32. Returns (logits (B, V), cache)."""
    logits, new_cache, _ = decode_step_rows(params, cfg, cache, tokens,
                                            pctx=pctx)
    return logits, new_cache


def decode_step_rows(params: dict, cfg: ModelConfig, cache: dict,
                     tokens: Array, *, pctx: PContext = LOCAL
                     ) -> tuple[Array, dict, tuple[Array, Array] | None]:
    """:func:`decode_step` that also returns, for the attention-cache
    families (else None), each layer's new K/V row ``(L, B, Hkv, hd)`` in
    the cache dtype: what the step wrote at ``cache["idx"]``.  A caller
    that keeps the cache itself (the serving engine's page pools) reads
    the rows and not the cache, and jit then drops the layer scan's full
    ``(L, B, T, Hkv, hd)`` outputs."""
    x = embedding_apply(params["embed"], tokens).astype(cfg.dtype)
    q = cfg.quant
    idx = cache["idx"]
    rows = None

    if cfg.family in ("dense", "moe", "encdec"):
        acfg = cfg.attn_cfg()

        def body(carry, inp):
            x = carry
            bp, k_l, v_l, extras = inp
            with scope("attn"):
                h = rmsnorm_apply(bp["ln1"], x, cfg.rms_norm_eps)
                y, new_kv, (k_row, v_row) = attn_decode(
                    bp["attn"], acfg, h, {"k": k_l, "v": v_l, "idx": idx},
                    qspec=q)
                x = x + y
            if cfg.family == "encdec":
                cp = extras
                with scope("cross"):
                    x = x + cross_attn_apply(
                        cp["xattn"], cfg.attn_cfg(causal=False),
                        rmsnorm_apply(cp["ln"], x, cfg.rms_norm_eps), cache["enc_out"], qspec=q)
            if cfg.family == "moe":
                with scope("moe"):
                    y2, _ = moe_apply(bp["moe"], cfg.moe_cfg(),
                                      rmsnorm_apply(bp["ln2"], x, cfg.rms_norm_eps), qspec=q,
                                      pctx=pctx)
            else:
                with scope("mlp"):
                    y2 = swiglu_apply(bp["mlp"], rmsnorm_apply(bp["ln2"], x, cfg.rms_norm_eps),
                                      q)
            return x + y2, (new_kv["k"], new_kv["v"], k_row, v_row)

        blocks = params["blocks" if cfg.family != "encdec" else "dec_blocks"]
        extras = params.get("cross") if cfg.family == "encdec" else None
        if cfg.scan_layers:
            n = jax.tree.leaves(blocks)[0].shape[0]
            ex = extras if extras is not None else jnp.zeros((n,))
            x, (K, V, Kr, Vr) = jax.lax.scan(
                body, x, (blocks, cache["k"], cache["v"], ex))
        else:
            outs = []
            for i in sorted(blocks, key=int):
                ex = extras[i] if extras is not None else None
                x, out = body(x, (blocks[i], cache["k"][int(i)],
                                  cache["v"][int(i)], ex))
                outs.append(out)
            K, V, Kr, Vr = (jnp.stack(a) for a in zip(*outs))
        new_cache = dict(cache, k=K, v=V, idx=idx + 1)
        rows = (Kr, Vr)

    elif cfg.family in ("ssm", "hybrid"):
        scfg = cfg.ssm_cfg()
        shared = params.get("shared")
        every = cfg.hybrid_attn_every
        acfg = (cfg.attn_cfg(window=cfg.hybrid_window)
                if cfg.family == "hybrid" else None)

        def body(carry, inp):
            x = carry
            bp, cx_l, cb_l, st_l = inp
            with scope("mamba"):
                h = rmsnorm_apply(bp["norm"], x, cfg.rms_norm_eps)
                y, nc = mamba_decode(bp["mamba"], scfg, h,
                                     {"conv_x": cx_l, "conv_bc": cb_l,
                                      "state": st_l}, qspec=q)
            x = x + y
            return x, (nc["conv_x"], nc["conv_bc"], nc["state"])

        blocks = params["blocks"]
        n = cfg.n_layers
        if cfg.scan_layers:
            if cfg.family == "hybrid":
                n_sites = cfg.n_hybrid_sites
                seg = lambda t: _segment_blocks(t, n, every, n_sites)
                seg_b, rem_b, n_rem = seg(blocks)
                seg_cx, rem_cx, _ = seg(cache["conv_x"])
                seg_cb, rem_cb, _ = seg(cache["conv_bc"])
                seg_st, rem_st, _ = seg(cache["state"])
                skv = cache["shared_kv"]

                def site_body(x, inp):
                    bseg, cx_seg, cb_seg, st_seg, site, kv_k, kv_v = inp
                    x, (CX, CB, S2) = jax.lax.scan(
                        body, x, (bseg, cx_seg, cb_seg, st_seg))
                    blk = _with_site_lora(shared["block"], shared["site_lora"],
                                          site)
                    h2 = rmsnorm_apply(blk["ln1"], x, cfg.rms_norm_eps)
                    y2, nkv, _ = attn_decode(
                        blk["attn"], acfg, h2,
                        {"k": kv_k, "v": kv_v, "idx": idx}, qspec=q)
                    x = x + y2
                    x = x + swiglu_apply(blk["mlp"],
                                         rmsnorm_apply(blk["ln2"], x, cfg.rms_norm_eps), q)
                    return x, (CX, CB, S2, nkv["k"], nkv["v"])

                x, (CXs, CBs, Ss, NK, NV) = jax.lax.scan(
                    site_body, x,
                    (seg_b, seg_cx, seg_cb, seg_st, jnp.arange(n_sites),
                     skv["k"], skv["v"]))
                merge = lambda a: a.reshape(-1, *a.shape[2:])
                if n_rem:
                    x, (CXr, CBr, Sr) = jax.lax.scan(
                        body, x, (rem_b, rem_cx, rem_cb, rem_st))
                    CX = jnp.concatenate([merge(CXs), CXr])
                    CB = jnp.concatenate([merge(CBs), CBr])
                    S_ = jnp.concatenate([merge(Ss), Sr])
                else:
                    CX, CB, S_ = merge(CXs), merge(CBs), merge(Ss)
                new_skv = dict(skv, k=NK, v=NV, idx=idx + 1)
                new_cache = dict(cache, conv_x=CX, conv_bc=CB, state=S_,
                                 shared_kv=new_skv, idx=idx + 1)
            else:
                x, (CX, CB, S_) = jax.lax.scan(
                    body, x, (blocks, cache["conv_x"], cache["conv_bc"],
                              cache["state"]))
                new_cache = dict(cache, conv_x=CX, conv_bc=CB, state=S_,
                                 idx=idx + 1)
        else:
            CXs, CBs, Ss = [], [], []
            for i in sorted(blocks, key=int):
                x, (cx_l, cb_l, s_l) = body(
                    x, (blocks[i], cache["conv_x"][int(i)],
                        cache["conv_bc"][int(i)], cache["state"][int(i)]))
                CXs.append(cx_l); CBs.append(cb_l); Ss.append(s_l)
                if cfg.family == "hybrid" and (int(i) + 1) % every == 0:
                    site = (int(i) + 1) // every - 1
                    if site < cfg.n_hybrid_sites:
                        blk = _with_site_lora(shared["block"], shared["site_lora"],
                                              jnp.int32(site))
                        skv = cache["shared_kv"]
                        h2 = rmsnorm_apply(blk["ln1"], x, cfg.rms_norm_eps)
                        y2, nkv, _ = attn_decode(
                            blk["attn"], acfg, h2,
                            {"k": skv["k"][site], "v": skv["v"][site],
                             "idx": idx}, qspec=q)
                        x = x + y2
                        x = x + swiglu_apply(blk["mlp"],
                                             rmsnorm_apply(blk["ln2"], x, cfg.rms_norm_eps), q)
                        skv["k"] = skv["k"].at[site].set(nkv["k"])
                        skv["v"] = skv["v"].at[site].set(nkv["v"])
            new_cache = dict(cache, conv_x=jnp.stack(CXs), conv_bc=jnp.stack(CBs),
                             state=jnp.stack(Ss), idx=idx + 1)
    else:
        raise ValueError(cfg.family)

    x = rmsnorm_apply(params["final_norm"], x, cfg.rms_norm_eps)
    logits = lm_logits(params, cfg, x)[:, 0, :]
    return logits, new_cache, rows
