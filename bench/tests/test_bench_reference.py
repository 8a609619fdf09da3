"""Each configuration finds its plain reference and its program
configuration by name, and the reference draws the adapters the program
was given, expert axis included."""
import dataclasses

import bench_tiny
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, weights
from bench.jobs import common
from bench.reference import train_check

SPEC = harness.spec()
# what bench.jobs.common.reference says every reference module provides
INTERFACE = ("Dims", "sites", "EXACT", "FP8", "embedding", "base_layer",
             "block", "final_norm", "head")


@pytest.mark.parametrize("entry", SPEC["configs"], ids=lambda e: e["name"])
def test_config_names_a_reference_with_the_interface(entry):
    config = harness.config_file(entry["name"])
    ref = common.reference(config)
    assert ref.__name__ == "bench.reference." + config.get("reference",
                                                           "dense")
    missing = [a for a in INTERFACE if not hasattr(ref, a)]
    assert not missing, f"{ref.__file__} lacks {missing}"
    dims = ref.Dims.of(config)
    hash(dims)
    assert dims.n_layers == config["n_layers"]
    for lead, m, n in ref.sites(dims).values():
        assert lead in ((), (config.get("n_experts"),)) and m > 0 and n > 0


def test_model_config_of_qwen3_is_unchanged():
    """Every ModelConfig key of the file reaches the program, and the
    published file gives the same config as the model keys it held."""
    from repro.configs import get_config
    from repro.models.modules import QSpec
    c = harness.config_file("qwen3-1.7b")
    keys = ("n_layers", "d_model", "vocab", "vocab_pad_multiple", "n_heads",
            "n_kv_heads", "head_dim", "qk_norm", "rope_theta", "d_ff",
            "tie_embeddings")
    want = get_config("qwen3-1.7b", dtype=jnp.bfloat16,
                      **{k: c[k] for k in keys})
    for rank in (0, 64):
        got = common.model_config(c, rank=rank)
        assert got == dataclasses.replace(
            want, quant=QSpec(bits=4, group_size=64, rank=max(rank, 1)))
    # a key the old fixed tuple did not list reaches the program too
    assert common.model_config(dict(c, attn_bias=True)).attn_bias is True
    assert common.model_config(dict(c, lora_rank=8)).lora_rank == 0
    # the benchmark's name of a configuration does not reach the program
    assert common.model_config(dict(c, name="renamed")) == \
        common.model_config(c)


def _tiny_moe(lora_dtype):
    from repro.configs import get_smoke_config
    from repro.models.modules import QSpec
    return dataclasses.replace(
        get_smoke_config("olmoe-1b-7b"), dtype=jnp.bfloat16,
        quant=QSpec(bits=4, group_size=16, rank=4))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_initial_lora_draws_expert_sites_as_the_program(dtype):
    from repro.core.pipeline import quantized_param_shapes
    from repro.utils import tree_paths
    cfg = _tiny_moe(dtype)
    shapes = quantized_param_shapes(cfg)
    key = weights.seed_key(2**31 + 5)
    params = jax.jit(lambda k: weights.make_params(
        k, shapes, 16, 4, 4, cfg.dtype, cfg.vocab,
        getattr(jnp, dtype)))(key)
    leaves = tree_paths(params["blocks"])
    sites = {}
    for path, a in leaves.items():
        if path.endswith(".lora_a"):
            site = path[:-len(".lora_a")]
            b = leaves[f"{site}.lora_b"]
            sites[site] = (a.shape[1:-2], a.shape[-2], b.shape[-2])
    assert sites["moe.gate"][0] == (cfg.n_experts,)
    assert sites["attn.q"][0] == ()
    got = train_check.initial_lora(key, sites, cfg.n_layers, 4, dtype)
    assert set(got) == set(sites)
    for site, pair in got.items():
        for name in ("lora_a", "lora_b"):
            want = np.asarray(leaves[f"{site}.{name}"].astype(jnp.float32))
            np.testing.assert_array_equal(np.asarray(pair[name]), want,
                                          err_msg=f"{site}.{name}")


def test_initial_lora_of_the_dense_reference_matches_the_program():
    """The cell's own layout at the fine-tune smoke size: the sites that
    ``dense.sites`` names are the program's, drawn alike."""
    from repro.core.pipeline import quantized_param_shapes
    from repro.utils import tree_paths
    wl = "qwen3-1.7b.finetune.packed"
    config = bench_tiny.cell(wl, 0).config
    rank = int(config["lora_rank"])
    cfg = common.model_config(config, rank=rank)
    ref = common.reference(config)
    dims = ref.Dims.of(config)
    key = weights.seed_key(7)
    params, template = common.packed_base(cfg, config, 7, rank,
                                          lora_dtype=jnp.float32)
    assert set(ref.sites(dims)) == set(template)
    got = train_check.initial_lora(key, ref.sites(dims), dims.n_layers, rank,
                                   "float32")
    leaves = tree_paths(params["blocks"])
    for site, pair in got.items():
        for name in ("lora_a", "lora_b"):
            np.testing.assert_array_equal(
                np.asarray(pair[name]), np.asarray(leaves[f"{site}.{name}"]))
