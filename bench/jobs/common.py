"""What the job drivers share: the program's model configuration as a
configuration file states it, the plain reference it names, and the
packed base drawn from the seed."""
from __future__ import annotations

import dataclasses
import importlib

# keys of a configuration file that name ``ModelConfig`` fields but that
# the program does not take from it: the quantization and the LoRA rank
# become ``QSpec`` and the dtype's name a JAX dtype, which the jobs read
# themselves; ``name`` is the benchmark's name of the configuration, and
# the program keeps its published entry's
JOB_KEYS = ("quant", "lora_rank", "dtype", "name")
DEFAULT_REFERENCE = "dense"


def reference(config: dict):
    """The plain reference module that ``config["reference"]`` names,
    ``bench/reference/<name>.py`` (``dense`` where the file names none).

    ``bench/reference`` is a namespace package like the rest of
    ``bench``, so a new family's reference is one new file there.
    ``bench.reference.train_check`` and ``bench.reference.serve_check``
    drive any module that provides:

    * ``Dims.of(config)``: the sizes the reference needs, from the
      configuration file, as a hashable value (it is a static argument of
      the reference's jitted programs); it has ``n_layers``.
    * ``sites(dims)``: one layer's LoRA sites with their leading axes,
      ``{site: (lead, m, n)}``, ``lead`` ``()`` or ``(E,)`` for a site
      stacked over ``E`` experts; site names are paths under ``blocks``
      of the program's layout (``"attn.q"``), whose adapters
      ``bench.weights.make_params`` draws.
    * ``EXACT`` and ``FP8``: precisions with ``einsum(spec, a, b)``; the
      reference runs in ``EXACT``, the control in ``FP8``.
    * ``embedding(key, dims)``: the ``(V_pad, d)`` embedding in float32.
    * ``base_layer(key, dims, layer)``: layer ``layer``'s dequantized
      base, drawn again from the seed key by ``bench.weights``; ``layer``
      may be a traced integer.
    * ``block(prec, dims, x, base, lora)``: that layer on ``x (N, T, d)``;
      ``lora[site]`` is ``(A, B)`` with a leading row axis ``N`` (one
      adapter per row) before ``lead``, ``A (N, *lead, m, r)`` and
      ``B (N, *lead, n, r)``, or missing.
    * ``final_norm(x)``: the norm before the head.
    * ``head(key, dims)``: the ``(d, V)`` output head over the published
      vocabulary, in float32.

    A reference imports nothing of the program and takes nothing the
    program made: every weight is drawn again from the seed."""
    name = config.get("reference", DEFAULT_REFERENCE)
    return importlib.import_module(f"bench.reference.{name}")


def model_config(config: dict, rank: int = 0):
    """The program's ``ModelConfig`` for ``config``: its published entry
    (``arch``) with every key of the file that names a ``ModelConfig``
    field applied but ``JOB_KEYS`` (the rest, such as source, reduced or
    assumed, describe the deployment or the job), INT-b quantized as
    ``config["quant"]`` says, with LoRA rank ``rank``."""
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.models.modules import QSpec
    from repro.models.transformer import ModelConfig

    names = {f.name for f in dataclasses.fields(ModelConfig)}
    fields = {k: v for k, v in config.items()
              if k in names and k not in JOB_KEYS}
    fields["dtype"] = getattr(jnp, config["dtype"])
    q = config["quant"]
    qspec = QSpec(bits=q["bits"], group_size=q["group_size"],
                  rank=max(rank, 1))
    return dataclasses.replace(get_config(config["arch"]), **fields,
                               quant=qspec)


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
