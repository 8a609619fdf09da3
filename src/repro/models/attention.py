"""GQA attention with RoPE, optional qk-norm, KV-cache decode, sliding
window, and cross-attention (enc-dec).  Shapes: x (B, S, D); heads laid out
as (B, S, H, hd).  Softmax in f32.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.models.modules import (QSpec, linear_apply, linear_init,
                                  rmsnorm_apply, rmsnorm_init)
from repro.utils import scope

Array = jax.Array
NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int | None = None
    qk_norm: bool = False
    rope_theta: float = 1e6
    sliding_window: int | None = None   # None = full attention
    causal: bool = True
    bias: bool = False                  # qwen1.5-style qkv bias
    # qk-norm over each head's ``hd`` ("head", qwen3) or over the whole
    # q and k projections before they are split into heads ("proj", OLMoE)
    qk_norm_span: str = "head"
    norm_eps: float = 1e-6

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads


def rope_freqs(hd: int, theta: float) -> Array:
    return 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))


def apply_rope(x: Array, positions: Array, theta: float) -> Array:
    """x (B, S, H, hd); positions (B, S) or (S,)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                          # (hd/2,)
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B,S,hd/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    cos = cos[:, :, None, :]
    sin = sin[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def attn_init(key, cfg: AttnConfig, *, dtype=jnp.bfloat16,
              lora_rank: int = 0) -> dict:
    hd = cfg.hd
    ks = jax.random.split(key, 6)
    p = {
        "q": linear_init(ks[0], cfg.d_model, cfg.n_heads * hd, dtype=dtype,
                         bias=cfg.bias, lora_rank=lora_rank),
        "k": linear_init(ks[1], cfg.d_model, cfg.n_kv_heads * hd, dtype=dtype,
                         bias=cfg.bias, lora_rank=lora_rank),
        "v": linear_init(ks[2], cfg.d_model, cfg.n_kv_heads * hd, dtype=dtype,
                         bias=cfg.bias, lora_rank=lora_rank),
        "o": linear_init(ks[3], cfg.n_heads * hd, cfg.d_model, dtype=dtype,
                         lora_rank=lora_rank),
    }
    if cfg.qk_norm:
        proj = cfg.qk_norm_span == "proj"
        p["q_norm"] = rmsnorm_init(cfg.n_heads * hd if proj else hd, dtype)
        p["k_norm"] = rmsnorm_init(cfg.n_kv_heads * hd if proj else hd, dtype)
    return p


def _qk(p, cfg: AttnConfig, x: Array, kv_src: Array, qspec: QSpec | None):
    """q (B, Sq, H, hd) from ``x`` and k, v (B, Sk, Hkv, hd) from
    ``kv_src``, qk-normed as ``cfg`` says."""
    B, hd = x.shape[0], cfg.hd
    whole = cfg.qk_norm and cfg.qk_norm_span == "proj"

    def project(name, src, heads):
        with scope(name):
            y = linear_apply(p[name], src, qspec)
            if name == "v" or not whole:
                return y.reshape(B, src.shape[1], heads, hd)
        # OLMoE: one norm over the whole projection, before the split
        y = rmsnorm_apply(p[f"{name}_norm"], y, cfg.norm_eps)
        return y.reshape(B, src.shape[1], heads, hd)

    q = project("q", x, cfg.n_heads)
    k = project("k", kv_src, cfg.n_kv_heads)
    v = project("v", kv_src, cfg.n_kv_heads)
    if cfg.qk_norm and not whole:
        q = rmsnorm_apply(p["q_norm"], q, cfg.norm_eps)
        k = rmsnorm_apply(p["k_norm"], k, cfg.norm_eps)
    return q, k, v


def _project_qkv(p, cfg: AttnConfig, x: Array, positions: Array,
                 qspec: QSpec | None, rope: bool = True):
    q, k, v = _qk(p, cfg, x, x, qspec)
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _sdpa(q: Array, k: Array, v: Array, mask: Array | None) -> Array:
    """q (B,Sq,Hq,hd), k/v (B,Sk,Hkv,hd); GQA via head grouping."""
    B, Sq, Hq, hd = q.shape
    Hkv = k.shape[2]
    rep = Hq // Hkv
    qg = q.reshape(B, Sq, Hkv, rep, hd)
    logits = jnp.einsum("bqhrd,bkhd->bhrqk", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) / jnp.sqrt(hd)
    if mask is not None:
        logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhrqk,bkhd->bqhrd", probs, v.astype(jnp.float32))
    return out.reshape(B, Sq, Hq, hd)


def causal_mask(Sq: int, Sk: int, window: int | None = None,
                offset: int = 0) -> Array:
    """(1,1,1,Sq,Sk) boolean mask; offset = absolute position of query 0."""
    qpos = jnp.arange(Sq)[:, None] + offset
    kpos = jnp.arange(Sk)[None, :]
    m = kpos <= qpos
    if window is not None:
        m &= kpos > qpos - window
    return m[None, None, None, :, :]


def attn_apply(p, cfg: AttnConfig, x: Array, *, qspec: QSpec | None = None,
               positions: Array | None = None,
               q_chunk: int | None = None) -> Array:
    """Full (training / prefill) self-attention.

    ``q_chunk``: blockwise (flash-style) query chunking — peak logits memory
    drops from O(S^2) to O(q_chunk * S) per head (§Perf lever; the Pallas
    flash_attention kernel is the on-TPU realization of the same schedule).
    """
    B, S, _ = x.shape
    positions = jnp.arange(S) if positions is None else positions
    q, k, v = _project_qkv(p, cfg, x, positions, qspec)
    if q_chunk and S > q_chunk and S % q_chunk == 0:
        # UNROLLED query blocks (not lax.map): identical math and O(qc * S)
        # peak logits, but every block appears in the HLO so cost_analysis
        # FLOPs stay exact (lax.map bodies are counted once — §Dry-run note)
        nb = S // q_chunk
        outs = []
        for i in range(nb):
            qi = q[:, i * q_chunk:(i + 1) * q_chunk]
            mask = (causal_mask(q_chunk, S, cfg.sliding_window,
                                offset=i * q_chunk) if cfg.causal else None)
            outs.append(_sdpa(qi, k, v, mask))
        out = jnp.concatenate(outs, axis=1)
    else:
        mask = causal_mask(S, S, cfg.sliding_window) if cfg.causal else None
        out = _sdpa(q, k, v, mask)
    with scope("o"):
        return linear_apply(p["o"], out.reshape(B, S, -1).astype(x.dtype), qspec)


def attn_decode(p, cfg: AttnConfig, x: Array, cache: dict, *,
                qspec: QSpec | None = None
                ) -> tuple[Array, dict, tuple[Array, Array]]:
    """Single-token decode. cache = {"k": (B,T,Hkv,hd), "v": ..., "idx": ()}.

    ``idx`` is normally a scalar (every row at the same position); the
    serving engine's paged-cache path passes a per-request vector (B,) —
    each row then writes, ropes, and masks at its own position, which is
    what lets one batch mix requests at different progress.

    With ``qspec.use_kernel`` (full attention only) the masked softmax
    runs through the Pallas flash kernel's per-sequence ``lengths``
    operand instead of the dense ``_sdpa`` mask — same math, the serving
    integration point for the paged KV cache.

    With sliding_window, the cache is a ring buffer of size window.

    Returns ``(y, new_cache, (k_row, v_row))``: the rows ``(B, Hkv, hd)``
    are what this step wrote into the cache, in its dtype, for a caller
    that keeps the cache elsewhere (the serving engine's page pools)."""
    B, S, _ = x.shape
    assert S == 1, "decode processes one token"
    idx = cache["idx"]
    vec = getattr(idx, "ndim", 0) == 1
    positions = idx[:, None] if vec else jnp.full((B, 1), idx)
    q, k, v = _project_qkv(p, cfg, x, positions, qspec)
    T = cache["k"].shape[1]
    slot = jnp.mod(idx, T) if cfg.sliding_window else idx
    k_row = k[:, 0].astype(cache["k"].dtype)
    v_row = v[:, 0].astype(cache["v"].dtype)
    if vec:
        rows = jnp.arange(B)
        K = cache["k"].at[rows, slot].set(k_row)
        V = cache["v"].at[rows, slot].set(v_row)
    else:
        K = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, slot, 0, 0))
        V = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, slot, 0, 0))
    if qspec is not None and qspec.use_kernel and not cfg.sliding_window:
        from repro.kernels import ops as kops
        counts = (idx + 1) if vec else jnp.full((B,), idx + 1)
        out = kops.flash_attention(
            q.transpose(0, 2, 1, 3), K.transpose(0, 2, 1, 3),
            V.transpose(0, 2, 1, 3), causal=False,
            lengths=counts.astype(jnp.int32)).transpose(0, 2, 1, 3)
    else:
        kpos = jnp.arange(T)
        pos = idx[:, None] if vec else idx
        if cfg.sliding_window:
            valid = (kpos <= jnp.minimum(pos, T - 1)) | (pos >= T)  # ring full
        else:
            valid = kpos <= pos
        mask = (valid[:, None, None, None, :] if valid.ndim == 2
                else valid[None, None, None, None, :])
        out = _sdpa(q, K, V, mask)
    with scope("o"):
        y = linear_apply(p["o"], out.reshape(B, 1, -1).astype(x.dtype), qspec)
    return y, {"k": K, "v": V, "idx": idx + 1}, (k_row, v_row)


def cross_attn_apply(p, cfg: AttnConfig, x: Array, kv_src: Array, *,
                     qspec: QSpec | None = None) -> Array:
    """Encoder-decoder cross attention (no RoPE on cross path)."""
    B, Sq, _ = x.shape
    q, k, v = _qk(p, cfg, x, kv_src, qspec)
    out = _sdpa(q, k, v, None)
    with scope("o"):
        return linear_apply(p["o"], out.reshape(B, Sq, -1).astype(x.dtype), qspec)
