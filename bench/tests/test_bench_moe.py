"""The OLMoE cell's reference, its grouped-matmul count and its readers:
the reference draws the adapters the program was given and computes the
program's forward, and the expert roofline counts every grouped matmul
the fine-tune step runs."""
import dataclasses

import bench_tiny
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import expert_flops, flops, harness, weights
from bench import traffic as gen
from bench.jobs import common
from bench.reference import train_check
from bench.trace_reduce import Reduction

WL = "olmoe-1b-7b.finetune.packed"


@pytest.fixture(scope="module")
def smoke():
    """The cell's smoke-size configuration, its program configuration
    and the packed base drawn from seed 3, both cast to float32 (bf16
    rounding would hide a wrong reference)."""
    config = bench_tiny.cell(WL, 3).config
    rank = int(config["lora_rank"])
    cfg = common.model_config(config, rank=rank)
    params, template = common.packed_base(cfg, config, 3, rank,
                                          lora_dtype=jnp.float32)
    params = jax.tree.map(
        lambda a: a.astype(jnp.float32)
        if jnp.issubdtype(a.dtype, jnp.floating) else a, params)
    cfg = dataclasses.replace(cfg, dtype=jnp.float32)
    return config, cfg, params, template


def test_moe_reference_draws_the_programs_adapters(smoke):
    from repro.utils import tree_paths
    config, cfg, params, template = smoke
    ref = common.reference(config)
    assert ref.__name__ == "bench.reference.moe"
    dims = ref.Dims.of(config)
    assert set(ref.sites(dims)) == set(template)
    assert ref.sites(dims)["moe.down"] == ((config["n_experts"],),
                                           config["d_ff_expert"],
                                           config["d_model"])
    got = train_check.initial_lora(weights.seed_key(3), ref.sites(dims),
                                   dims.n_layers, int(config["lora_rank"]),
                                   "float32")
    leaves = tree_paths(params["blocks"])
    for site, pair in got.items():
        for name in ("lora_a", "lora_b"):
            np.testing.assert_array_equal(
                np.asarray(pair[name]), np.asarray(leaves[f"{site}.{name}"]),
                err_msg=f"{site}.{name}")


def test_moe_reference_computes_the_programs_forward(smoke):
    """Whole-projection qk-norm, unrenormalized top-k, dropless experts
    with their adapters, untied head: the program in float32 and the
    reference agree on the logits of a row."""
    from repro.models.transformer import forward
    config, cfg, params, _ = smoke
    ref = common.reference(config)
    dims = ref.Dims.of(config)
    key = weights.seed_key(3)
    tokens = gen.train_batch(3, 0, 1, 64, cfg.vocab)["tokens"]
    with jax.default_matmul_precision("highest"):
        logits, _ = forward(params, cfg, {"tokens": tokens})
        lora = train_check.initial_lora(key, ref.sites(dims), dims.n_layers,
                                        int(config["lora_rank"]), "float32")
        x = ref.embedding(key, dims)[tokens[0]][None]
        for layer in range(dims.n_layers):
            lo = jax.tree.map(lambda a: a[layer], lora)
            ad = {s: (lo[s]["lora_a"][None], lo[s]["lora_b"][None])
                  for s in lo}
            x = ref.block(ref.EXACT, dims, x, ref.base_layer(key, dims,
                                                             layer), ad)
        want = ref.EXACT.einsum("td,dv->tv", ref.final_norm(x[0]),
                                ref.head(key, dims))
    got = np.asarray(logits[0, :, :dims.vocab])
    np.testing.assert_allclose(got, np.asarray(want), rtol=1e-3, atol=1e-3)


def test_expert_flops_count_33_grouped_matmuls_a_layer():
    c = harness.config_file("olmoe-1b-7b")
    calls = expert_flops.step_calls(c, 4096)
    assert len(calls) == 33 * c["n_layers"]
    N, d, f, r, E = 4096 * 8, 2048, 1024, 16, 64
    # per site: the base product in the forward, its recompute and the
    # input's gradient; the adapter's four products of rank r besides
    base = 3 * 2.0 * N * d * f
    lora = (2 * 2 + 4) * 2.0 * N * r * (d + f) / 2
    want = c["n_layers"] * 3 * (base + lora)
    assert sum(fl for fl, _ in calls) == pytest.approx(want, rel=1e-12)
    # the first call reads the rows and the whole expert stack, once
    assert calls[0] == (2.0 * N * d * f, 2 * (N * d + E * d * f + N * f))


def _run(op_seconds, op_counts):
    red = Reduction(busy_s=1.0, window_s=1.0, n_chips=1,
                    op_seconds=op_seconds, op_counts=op_counts, gaps={})
    config = harness.config_file("olmoe-1b-7b")
    return {"job": "train", "config": config, "trace": red,
            "peaks": harness.peaks("TPU v5 lite"),
            "tokens": 2 * config["train_batch"] * 4096, "seq_len": 4096}


def test_expert_roofline_reads_the_grouped_matmul_ops_alone():
    read = harness.layer_reader("expert_matmul_roofline")
    ops = {"%ragged-dot-none.7 = bf16[32768,1024] custom-call(...)": 0.8,
           "%ragged-dot-metadata.1 = (s32[65]) custom-call(...)": 0.1,
           "%fusion.3 = bf16[32768,2048] fusion(%ragged-dot-none.7)": 5.0}
    counts = dict.fromkeys(ops, 33)
    run = _run(ops, counts)
    # two traced steps of two 4096-token rows
    assert run["config"]["train_batch"] == 2
    least = sum(flops.roofline_seconds(fl, b, run["peaks"])[0]
                for fl, b in expert_flops.step_calls(run["config"], 8192))
    assert read(run) == pytest.approx(100.0 * 2 * least / 0.8)
    assert read(_run({"%fusion.1 = f32[2]": 1.0}, {"%fusion.1 = f32[2]": 1})
                ) is None


def test_moe_time_adds_the_unscoped_grouped_matmuls(monkeypatch):
    """The TPU compiler's ragged-dot kernel drops the op's name stack, so
    ``train.moe_ms`` adds those ops to the time under the ``moe`` scope,
    and a ragged-dot op that does carry the scope is counted once;
    ``train.moe_dispatch_ms`` reads the routing and data movement scopes
    alone."""
    from bench import program_trace, xplane
    tables = program_trace.ProgramTrace(
        {}, {}, {"moe": 0.9, "route": 0.01, "dispatch": 0.12,
                 "combine": 0.1, "gate": 0.2, "attn": 0.4})
    monkeypatch.setattr(program_trace, "current", lambda *a: tables)
    ops = {1: ("%ragged-dot-none.7 = bf16[32768,1024] custom-call(...)",
               "ragged-dot-none:", 0.6),
           2: ("%ragged-dot-metadata.1 = (s32[65]) custom-call(...)",
               "ragged-dot-metadata:", 0.02),
           # under the scope, so already in its 0.9 s
           3: ("%ragged-dot-none.9 = bf16[32768,1024] custom-call(...)",
               "jit(train_step)/while/body/moe/gate/ragged_dot", 0.3),
           4: ("%fusion.3 = bf16[32768,2048] fusion(%ragged-dot-none.7)",
               "jit(train_step)/while/body/attn/mul", 5.0)}
    plane = xplane.Plane(
        "/device:TPU:0",
        [xplane.Line("XLA Ops", [xplane.Event(mid, 0.0, s * 1e9)
                                 for mid, (_, _, s) in ops.items()])],
        {mid: name for mid, (name, _, _) in ops.items()},
        {mid: {xplane.TF_OP: tf_op} for mid, (_, tf_op, _) in ops.items()})
    op_seconds = {name: s for name, _, s in ops.values()}
    run = _run(op_seconds, dict.fromkeys(op_seconds, 33))
    read_moe = harness.layer_reader("train.moe_ms")
    monkeypatch.setitem(read_moe.__globals__, "_planes", lambda: [plane])
    assert read_moe(run) == pytest.approx(1e3 * (0.9 + 0.62) / 2)
    dispatch = harness.layer_reader("train.moe_dispatch_ms")(run)
    assert dispatch == pytest.approx(1e3 * 0.23 / 2)
    tables.scope_seconds.clear()
    assert read_moe(run) is None
