"""Distributed OPTQ + CLoQ (DESIGN.md §3): quantize a layer with its output
channels sharded over the model axis, and compute the calibrated LoRA init
with the exact Gram-trick SVD — one m x m psum of communication.  Then the
same thing at bucket scale: a stack of same-shape layers quantized by ONE
fused shard_map(vmap) program (`repro.core.batched.run_bucket_sharded`)
instead of per-layer sharded dispatches.

Runs on 8 fake CPU devices:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/distributed_quantize.py
"""
import os

if "host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cloq import (cloq_init, cloq_init_sharded, lowrank_objective,
                             regularize_gram)
from repro.core.optq import optq_quantize, optq_quantize_sharded
from repro.core.quantizer import QuantConfig
from repro.launch.mesh import make_model_mesh

rng = np.random.default_rng(0)
m, n, rank = 128, 512, 32
W = jnp.asarray(rng.normal(size=(m, n)), jnp.float32)
X = jnp.asarray(rng.normal(size=(4096, m)), jnp.float32)
H = X.T @ X

mesh = make_model_mesh(8)
cfg = QuantConfig(bits=2, group_size=64)

print(f"quantizing W {W.shape} INT{cfg.bits} over {len(jax.devices())} devices")
Qd_sh, _, _, _ = optq_quantize_sharded(W, H, cfg, mesh)      # column-sharded
Qd_loc, _, _, _ = optq_quantize(W, H, cfg)                   # reference
print("sharded OPTQ == local:",
      bool(jnp.allclose(Qd_sh, Qd_loc, atol=2e-4)))

Hreg = regularize_gram(H)
A_sh, B_sh = cloq_init_sharded(Hreg, W - Qd_sh, rank, mesh)  # Gram-trick SVD
A_loc, B_loc = cloq_init(Hreg, W - Qd_loc, rank)
obj_sh = lowrank_objective(Hreg, W - Qd_sh, A_sh, B_sh)
obj_loc = lowrank_objective(Hreg, W - Qd_loc, A_loc, B_loc)
print(f"calibrated objective: sharded {obj_sh:.3f} vs local {obj_loc:.3f}")
print("communication: one m x m psum =", m * m * 4, "bytes/layer")

# ---- bucket scale: L same-shape layers in ONE fused sharded program -------
import time

from repro.core.batched import (LayerTask, per_layer_sharded_dispatch,
                                plan_buckets, quantize_layer_batch)
from repro.models.modules import QSpec

L = 8
qspec = QSpec(bits=cfg.bits, group_size=cfg.group_size, rank=rank)
Ws = [jnp.asarray(rng.normal(size=(m, n)), jnp.float32) for _ in range(L)]
Hs = []
for _ in range(L):
    Xi = rng.normal(size=(2048, m)).astype(np.float32)
    Hs.append(jnp.asarray(Xi.T @ Xi))
keys = jax.random.split(jax.random.PRNGKey(0), L)
tasks = [LayerTask(f"layer{i}", None, Wi, Hi, ki)
         for i, (Wi, Hi, ki) in enumerate(zip(Ws, Hs, keys))]

spec = next(iter(plan_buckets(tasks, qspec, "cloq", mesh=mesh)))
print(f"\nbucket of {L} layers {m}x{n}: planner chose "
      f"{spec.n_shards} column shards")


def per_layer_sharded():
    # the pre-bucket status quo: one sharded OPTQ + one sharded CLoQ
    # dispatch per layer (same gates/alpha as the engine — shared baseline)
    outs = per_layer_sharded_dispatch(tasks, qspec, mesh)
    jax.block_until_ready(outs[-1][0])


def fused_bucket():
    outs = quantize_layer_batch(tasks, qspec, "cloq", mesh=mesh)
    jax.block_until_ready(outs[-1]["lora_a"])


per_layer_sharded(); fused_bucket()           # compile both before timing
t0 = time.time(); per_layer_sharded(); t_layer = time.time() - t0
t0 = time.time(); fused_bucket(); t_fused = time.time() - t0
print(f"per-layer sharded dispatch: {t_layer:.2f}s; "
      f"fused sharded bucket: {t_fused:.2f}s ({t_layer / t_fused:.2f}x)")
