"""What the job drivers share: the program's model configuration as a
configuration file states it, and the packed base drawn from the seed."""
from __future__ import annotations

import dataclasses

# keys of a configuration file that are the program's ``ModelConfig``
# fields; the rest (source, reduced, assumed, quant, lora_rank, ...)
# describe the deployment or the job
MODEL_KEYS = ("n_layers", "d_model", "vocab", "vocab_pad_multiple",
              "n_heads", "n_kv_heads", "head_dim", "qk_norm", "rope_theta",
              "d_ff", "tie_embeddings", "n_experts", "top_k", "d_ff_expert",
              "capacity_factor", "max_seq", "remat")


def model_config(config: dict, rank: int = 0):
    """The program's ``ModelConfig`` for ``config``: its published entry
    (``arch``) with every model key of the file applied, INT-b quantized
    as ``config["quant"]`` says, with LoRA rank ``rank``."""
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.models.modules import QSpec

    fields = {k: config[k] for k in MODEL_KEYS if k in config}
    fields["dtype"] = getattr(jnp, config["dtype"])
    q = config["quant"]
    qspec = QSpec(bits=q["bits"], group_size=q["group_size"],
                  rank=max(rank, 1))
    cfg = get_config(config["arch"], **fields)
    return dataclasses.replace(cfg, quant=qspec)


def adapter_template(shapes: dict) -> dict:
    """``{site: (L, m, n)}`` of every LoRA site under ``blocks``."""
    from repro.utils import tree_paths
    out = {}
    for path, sds in tree_paths(shapes.get("blocks", {})).items():
        if path.endswith(".lora_a"):
            site = path[:-len(".lora_a")]
            b = tree_paths(shapes["blocks"])[f"{site}.lora_b"]
            out[site] = (sds.shape[0], sds.shape[-2], b.shape[-2])
    return out


def packed_base(cfg, config: dict, seed: int, rank: int, lora_dtype=None):
    """The packed base (and, with ``rank``, its LoRA adapters in
    ``lora_dtype``), drawn on the device in one jitted call; returns
    ``(params, template)``."""
    import jax
    from repro.core.pipeline import quantized_param_shapes

    from bench import weights

    shapes = quantized_param_shapes(cfg)
    template = adapter_template(shapes)
    params = jax.jit(lambda k: weights.make_params(
        k, shapes, config["quant"]["group_size"], config["quant"]["bits"],
        rank, cfg.dtype,
        cfg.vocab, lora_dtype))(weights.seed_key(seed))
    return params, template
