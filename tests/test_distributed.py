"""Multi-device semantics (8 fake CPU devices, subprocess-isolated):
pjit train step == single-device numerics; distributed OPTQ/CLoQ == local;
MoE shard_map == local; int8-EF compressed psum; checkpoint reshard
(elastic and bucket-manifest driven)."""
import pytest

from tests.util import run_with_devices

pytestmark = pytest.mark.multidevice


def test_pjit_train_step_matches_local():
    run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.models.transformer import ModelConfig, init_params
        from repro.launch.steps import build_state, make_train_step, state_pspecs, named, batch_pspecs
        from repro.launch.mesh import pcontext_for
        from repro.models.parallel import LOCAL
        from repro.optim import OptConfig
        from repro.data import DataConfig, TokenStream

        cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=64,
                          vocab=128, n_heads=4, n_kv_heads=2, d_ff=128,
                          dtype=jnp.float32)
        p = init_params(jax.random.PRNGKey(0), cfg)
        ocfg = OptConfig(lr=1e-3, trainable="all", total_steps=5)
        ds = TokenStream(DataConfig(vocab=128, seq_len=32, global_batch=8, seed=2))
        batches = [ds.next_batch() for _ in range(3)]

        # local reference
        st = build_state(p, ocfg)
        f = jax.jit(make_train_step(cfg, ocfg, LOCAL))
        for b in batches: st, m_ref = f(st, b)

        # 2x4 mesh pjit
        from repro.launch.mesh import make_local_mesh
        mesh = make_local_mesh(2, 4)
        pctx = pcontext_for(mesh)
        st2 = build_state(p, ocfg)
        specs = state_pspecs(st2, mesh)
        bspecs = {k: P("data", None) for k in ("tokens", "labels")}
        f2 = jax.jit(make_train_step(cfg, ocfg, pctx),
                     in_shardings=(named(specs, mesh), named(bspecs, mesh)),
                     out_shardings=(named(specs, mesh), None))
        st2 = jax.device_put(st2, named(specs, mesh))
        for b in batches: st2, m = f2(st2, b)
        np.testing.assert_allclose(float(m["loss"]), float(m_ref["loss"]),
                                   rtol=2e-4)
        print("pjit == local:", float(m["loss"]), float(m_ref["loss"]))
    """)


def test_moe_shard_map_matches_local():
    run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.models.moe import MoEConfig, moe_init, moe_apply
        from repro.launch.mesh import pcontext_for
        cfg = MoEConfig(n_experts=8, top_k=2, d_model=32, d_ff=64,
                        capacity_factor=8.0)   # no drops => exact equality
        p = moe_init(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32))
        y_ref, aux_ref = moe_apply(p, cfg, x)
        from repro.launch.mesh import make_local_mesh
        mesh = make_local_mesh(2, 4)
        y, aux = moe_apply(p, cfg, x, pctx=pcontext_for(mesh))
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   atol=2e-5)
        # aux is pmean of per-shard load-balance stats (mean-of-products),
        # not the global-batch statistic: close but not bit-equal
        np.testing.assert_allclose(float(aux["balance"]),
                                   float(aux_ref["balance"]), rtol=5e-2)
        print("moe EP == local")
    """)


def test_distributed_optq_and_cloq_match_local():
    run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.optq import optq_quantize, optq_quantize_sharded
        from repro.core.cloq import cloq_init, cloq_init_sharded, regularize_gram
        from repro.core.quantizer import QuantConfig
        rng = np.random.default_rng(0)
        m, n = 64, 128
        W = jnp.asarray(rng.normal(size=(m, n)), jnp.float32)
        X = jnp.asarray(rng.normal(size=(512, m)), jnp.float32)
        H = X.T @ X
        cfg = QuantConfig(bits=4, group_size=16)
        from repro.launch.mesh import make_model_mesh
        mesh = make_model_mesh(8)
        Q1, C1, s, z = optq_quantize(W, H, cfg)
        Q2, C2, _, _ = optq_quantize_sharded(W, H, cfg, mesh)
        np.testing.assert_allclose(np.asarray(Q1), np.asarray(Q2), atol=2e-4)
        assert (np.asarray(C1) == np.asarray(C2)).mean() > 0.999
        Hreg = regularize_gram(H)
        A1, B1 = cloq_init(Hreg, W - Q1, 8)
        A2, B2 = cloq_init_sharded(Hreg, W - Q1, 8, mesh)
        np.testing.assert_allclose(np.asarray(A1 @ B1.T),
                                   np.asarray(A2 @ B2.T), atol=5e-3)
        print("sharded OPTQ + CLoQ == local")
    """)


def test_int8_ef_psum():
    run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.optim import ef_psum_int8
        from repro.launch.mesh import make_local_mesh
        mesh = make_local_mesh(8, 1)
        g = jax.random.normal(jax.random.PRNGKey(0), (8, 64))

        def f(g_local, res):
            synced, new_res = ef_psum_int8({"g": g_local[0]}, {"g": res[0]},
                                           "data")
            return synced["g"], new_res["g"][None]

        fn = jax.shard_map(f, mesh=mesh, in_specs=(P("data", None), P("data", None)),
                           out_specs=(P(None), P("data", None)),
                           check_vma=False)
        res0 = jnp.zeros((8, 64))
        synced, res1 = fn(g, res0)
        true_mean = jnp.mean(g, axis=0)
        err0 = float(jnp.max(jnp.abs(synced - true_mean)))
        # error feedback: quantization residual is carried, bounded by 1 LSB
        lsb = float(jnp.max(jnp.abs(g))) / 127
        assert err0 <= 2 * lsb, (err0, lsb)
        assert float(jnp.max(jnp.abs(res1))) <= lsb + 1e-6
        print("int8 EF psum ok; err", err0, "lsb", lsb)
    """)


def test_checkpoint_reshard_across_meshes():
    run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np, tempfile
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint import save_tree, restore_tree
        from repro.launch.mesh import make_local_mesh
        mesh1 = make_local_mesh(2, 4)
        w = jnp.arange(64 * 32, dtype=jnp.float32).reshape(64, 32)
        sharded = jax.device_put(w, NamedSharding(mesh1, P(None, "model")))
        d = tempfile.mkdtemp()
        save_tree({"w": sharded}, d, 1)
        # restore onto a DIFFERENT mesh shape (elastic restart)
        mesh2 = make_local_mesh(4, 2)
        sh = {"w": NamedSharding(mesh2, P("model", None))}
        tree, meta = restore_tree(d, shardings=sh)
        assert tree["w"].sharding.is_equivalent_to(sh["w"], 2)
        np.testing.assert_array_equal(np.asarray(tree["w"]), np.asarray(w))
        print("elastic reshard ok")
    """)


def test_bucket_manifest_restore_skips_planner():
    """A quantized checkpoint saved with its bucket manifest on a 2-device
    mesh restores onto a 4-device mesh with per-bucket shardings rebuilt
    from the manifest alone: the planner is poisoned to prove it is never
    called, column leaves come back sharded on the new mesh, and the
    dequantized base matches the saved one exactly."""
    run_with_devices("""
        import tempfile
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.checkpoint import restore_tree, save_tree
        from repro.core.pipeline import quantization_manifest, quantize_model
        from repro.core.quantizer import dequantize_int, unpack_codes
        from repro.data import DataConfig, TokenStream
        from repro.models.modules import QSpec
        from repro.models.transformer import ModelConfig, init_params
        from repro.utils import tree_paths

        devs = np.array(jax.devices())
        mesh2 = Mesh(devs[:2], ("model",))
        mesh4 = Mesh(devs, ("model",))

        cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=32,
                          vocab=128, n_heads=4, n_kv_heads=2, d_ff=64,
                          dtype=jnp.float32)
        qspec = QSpec(bits=4, group_size=16, rank=8)
        params = init_params(jax.random.PRNGKey(0), cfg)
        ds = TokenStream(DataConfig(vocab=128, seq_len=32, global_batch=2,
                                    seed=3))
        qp, qcfg, _ = quantize_model(params, cfg, [ds.next_batch()],
                                     qspec=qspec, mesh=mesh2)
        man = quantization_manifest(qcfg, "cloq", qspec, mesh=mesh2)
        d = tempfile.mkdtemp()
        save_tree(qp, d, 1, manifest=man)

        # restoring from the manifest must never touch the planner
        import repro.core.batched as batched
        def poisoned(*a, **k):
            raise AssertionError("planner called during manifest restore")
        batched.plan_buckets = poisoned

        tree, meta = restore_tree(d, mesh=mesh4)
        ft, fq = tree_paths(tree), tree_paths(qp)
        assert set(ft) == set(fq)
        n_sharded = 0
        for p, leaf in ft.items():
            np.testing.assert_array_equal(np.asarray(leaf),
                                          np.asarray(fq[p]))
            if hasattr(leaf, "sharding") and \\
                    not leaf.sharding.is_fully_replicated:
                n_sharded += 1
        assert n_sharded > 0, "no leaf came back sharded on the new mesh"

        # dequantized base identical after the 2-dev -> 4-dev reshard
        qc = tree["blocks"]["attn"]["q"]
        ref = qp["blocks"]["attn"]["q"]
        for layer in range(2):
            got = dequantize_int(
                unpack_codes(qc["qcodes"][layer], 4, 32),
                qc["scales"][layer], qc["zeros"][layer], 16)
            want = dequantize_int(
                unpack_codes(jnp.asarray(np.asarray(ref["qcodes"]))[layer],
                             4, 32),
                jnp.asarray(np.asarray(ref["scales"]))[layer],
                jnp.asarray(np.asarray(ref["zeros"]))[layer], 16)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        print("manifest restore ok:", n_sharded, "sharded leaves")
    """, n_devices=4)


def test_mixed_recipe_sharded_parity_and_manifest_restore():
    """The acceptance scenario of the QuantRecipe redesign, end to end on
    fake devices: a heterogeneous recipe (2-bit/r8 CLoQ MLPs, 4-bit/r4
    GPTQ attn.q, 4-bit/r2 RTN rest, mlp.down skipped) quantized by the
    2-device-sharded engine matches the per-site sequential oracle; its
    manifest (recipe + heterogeneous bucket specs) is saved with the
    checkpoint and restored onto a 4-device mesh with per-bucket shardings
    rebuilt from the manifest alone — planner poisoned, leaves bit-equal,
    skipped site restored dense."""
    import textwrap
    from tests.test_parity_matrix import _MIXED_SRC
    from tests.util import parity_prelude

    code = (
        "import jax, jax.numpy as jnp, numpy as np\n"
        + parity_prelude() + textwrap.dedent(_MIXED_SRC) + """
import tempfile
from jax.sharding import Mesh
from repro.checkpoint import restore_tree, save_tree
from repro.core.pipeline import quantization_manifest, quantize_model
from repro.utils import tree_paths

devs = np.array(jax.devices())
mesh2 = Mesh(devs[:2], ("model",))
mesh4 = Mesh(devs, ("model",))

cfg, params, calib = mixed_model()
qp_seq, _, _ = quantize_model(params, cfg, calib, recipe=MIXED_RECIPE,
                              engine="sequential")
qp_sh, qcfg, _ = quantize_model(params, cfg, calib, recipe=MIXED_RECIPE,
                                mesh=mesh2)
flat_sh, flat_seq = tree_paths(qp_sh), tree_paths(qp_seq)
assert_mixed_trees_close(flat_sh, flat_seq, assert_leaves_close)
print("PARITY OK mixed sharded")

man = quantization_manifest(qcfg, recipe=MIXED_RECIPE, mesh=mesh2)
assert man["recipe"]["rules"], "manifest must carry the recipe"
sigs = {(b["spec"]["method"], b["spec"]["bits"], b["spec"]["rank"])
        for b in man["buckets"]}
assert len(sigs) >= 3, sigs
d = tempfile.mkdtemp()
save_tree(qp_sh, d, 1, manifest=man)

# restoring from the manifest must never touch the planner
import repro.core.batched as batched
def poisoned(*a, **k):
    raise AssertionError("planner called during manifest restore")
batched.plan_buckets = poisoned

tree, meta = restore_tree(d, mesh=mesh4)
ft = tree_paths(tree)
assert set(ft) == set(flat_sh)
n_sharded = 0
for p, leaf in ft.items():
    np.testing.assert_array_equal(np.asarray(leaf),
                                  np.asarray(flat_sh[p]), err_msg=p)
    if hasattr(leaf, "sharding") and not leaf.sharding.is_fully_replicated:
        n_sharded += 1
assert n_sharded > 0, "no leaf came back sharded on the 4-device mesh"
assert "blocks.mlp.down.w" in ft          # skipped site restored dense
print("MANIFEST RESTORE OK", n_sharded, "sharded leaves")
""")
    out = run_with_devices(code, n_devices=4, timeout=900).stdout
    assert "PARITY OK mixed sharded" in out
    assert "MANIFEST RESTORE OK" in out


def test_site_lora_manifest_restore():
    """The weight-shared block's per-site adapter stacks
    (shared.site_lora.<name>.lora_a/lora_b) are covered by the bucket
    manifest: restore_tree(mesh=) lays them out on the new mesh straight
    from the manifest — lora_b column-sharded (engine layout, extra
    unsharded site dim), lora_a replicated — without re-running
    launch.shardings.param_specs (ROADMAP PR-3 follow-up)."""
    run_with_devices("""
        import tempfile
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.checkpoint import restore_tree, save_tree
        from repro.core.pipeline import quantization_manifest, quantize_model
        from repro.core.recipe import QuantRecipe
        from repro.data import DataConfig, TokenStream
        from repro.models.modules import QSpec
        from repro.models.transformer import ModelConfig, init_params
        from repro.utils import tree_paths

        devs = np.array(jax.devices())
        mesh2 = Mesh(devs[:2], ("model",))
        mesh4 = Mesh(devs, ("model",))

        cfg = ModelConfig(name="t", family="hybrid", n_layers=4, d_model=32,
                          vocab=128, n_heads=4, n_kv_heads=4, head_dim=8,
                          d_ff=64, ssm_state=16, ssm_head_dim=16,
                          ssm_groups=2, ssm_chunk=8, hybrid_attn_every=2,
                          hybrid_window=16, dtype=jnp.float32)
        recipe = QuantRecipe.single(
            "cloq", QSpec(bits=2, group_size=16, rank=8))
        params = init_params(jax.random.PRNGKey(0), cfg)
        ds = TokenStream(DataConfig(vocab=128, seq_len=32, global_batch=2,
                                    seed=3))
        qp, qcfg, _ = quantize_model(params, cfg, [ds.next_batch()],
                                     recipe=recipe, mesh=mesh2)
        man = quantization_manifest(qcfg, recipe=recipe, mesh=mesh2)
        assert man["site_lora"], "manifest must record the shared sites"
        names = {e["name"] for e in man["site_lora"]}
        assert "attn_q" in names and "mlp_down" in names, names

        d = tempfile.mkdtemp()
        save_tree(qp, d, 1, manifest=man)
        tree, meta = restore_tree(d, mesh=mesh4)
        sl = tree["shared"]["site_lora"]
        assert set(sl) == names, (set(sl), names)
        for name, sub in sl.items():
            assert not sub["lora_b"].sharding.is_fully_replicated, name
            assert sub["lora_a"].sharding.is_fully_replicated, name
        flat, want = tree_paths(tree), tree_paths(qp)
        for p in flat:
            np.testing.assert_array_equal(np.asarray(flat[p]),
                                          np.asarray(want[p]), err_msg=p)
        print("SITE-LORA RESTORE OK", sorted(names))
    """, n_devices=4, timeout=900)


def test_dryrun_cell_entrypoint_small():
    """The dryrun module itself (512 fake devices) on the smallest cell."""
    run_with_devices("""
        import sys
        sys.argv = ["dryrun", "--arch", "olmoe-1b-7b", "--cell", "train_4k",
                    "--out", "/tmp/dryrun_test"]
        from repro.launch.dryrun import main
        assert main() == 0
    """, n_devices=512, timeout=900)
