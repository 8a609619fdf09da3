"""Fault-tolerance behaviours of the quantization runtime and train driver:
the fault-injection matrix (repro.core.faults x engines) ends every run in
a finite, manifest-consistent tree with each fallback recorded in the
HealthReport; the quantization journal survives SIGKILL between buckets and
resumes bit-identical; torn/corrupt checkpoint shards fail restore with
actionable errors; preemption (SIGTERM) triggers a clean synchronous
checkpoint; --resume continues from it; the sliding-window decode ring
buffer matches windowed full attention."""
import json
import os
import signal
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def test_sigterm_checkpoints_and_resume_completes(tmp_path):
    env = dict(os.environ, PYTHONPATH=SRC)
    ck = str(tmp_path / "ck")
    # step count high enough that the run cannot finish before the signal
    # (smoke steps are ~ms; 500k steps of data gen alone outlast the test)
    args = [sys.executable, "-m", "repro.launch.train", "--arch", "qwen3-1.7b",
            "--smoke", "--method", "rtn", "--bits", "4", "--group-size", "16",
            "--rank", "8", "--steps", "500000", "--seq-len", "32",
            "--batch", "2", "--calib-batches", "1", "--ckpt-dir", ck,
            "--ckpt-every", "5"]
    proc = subprocess.Popen(args, env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)
    # wait until training has demonstrably started (first checkpoint exists)
    deadline = time.time() + 600
    while time.time() < deadline:
        if os.path.isdir(ck) and any(p.startswith("step_")
                                     for p in os.listdir(ck)):
            break
        if proc.poll() is not None:
            raise AssertionError("driver exited early:\n" +
                                 proc.stdout.read())
        time.sleep(1)
    time.sleep(2)
    proc.send_signal(signal.SIGTERM)
    out, _ = proc.communicate(timeout=300)
    assert proc.returncode == 0, out
    assert "[preempt]" in out, out
    steps = sorted(p for p in os.listdir(ck) if p.startswith("step_"))
    assert steps, "no checkpoint written on preemption"
    preempt_step = int(steps[-1][len("step_"):])
    assert preempt_step >= 1

    # resume completes a shortened run from the checkpoint
    args2 = [a for a in args]
    args2[args2.index("--steps") + 1] = str(preempt_step + 5)
    args2.append("--resume")
    out2 = subprocess.run(args2, env=env, capture_output=True, text=True,
                          timeout=600)
    assert out2.returncode == 0, out2.stdout + out2.stderr
    assert f"[resume] step {preempt_step}" in out2.stdout, out2.stdout
    assert "[done]" in out2.stdout


def test_window_ring_buffer_decode_matches_windowed_attention():
    """attn_decode with a ring buffer of size=window must equal full-cache
    attention under the sliding-window mask, including after wraparound."""
    from repro.models.attention import (AttnConfig, attn_apply, attn_decode,
                                        attn_init)
    rng = np.random.default_rng(0)
    W = 4          # window
    S = 10         # decode well past wraparound
    acfg = AttnConfig(d_model=16, n_heads=2, n_kv_heads=2, sliding_window=W,
                      rope_theta=1e4)
    p = attn_init(jax.random.PRNGKey(0), acfg, dtype=jnp.float32)
    x = jnp.asarray(rng.normal(size=(1, S, 16)), jnp.float32)
    y_full = attn_apply(p, acfg, x)           # windowed mask, full sequence
    cache = {"k": jnp.zeros((1, W, 2, 8)), "v": jnp.zeros((1, W, 2, 8)),
             "idx": jnp.zeros((), jnp.int32)}
    outs = []
    for t in range(S):
        o, cache, _ = attn_decode(p, acfg, x[:, t:t + 1], cache)
        outs.append(o[:, 0])
    y_dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(y_dec), np.asarray(y_full),
                               atol=1e-4)


# ---------------------------------------------------------------------------
# Fault-injection matrix: repro.core.faults x quantization engines.
# ---------------------------------------------------------------------------


def _quant_setup(calib_kind="full"):
    """Tiny dense model + calibration + recipe for the fault matrix.

    ``calib_kind="deficient"`` yields a single 16-token batch — fewer
    samples than ``d_model=32``, so every Gram is rank-deficient.  That is
    the regime ``gram_jitter`` needs: a full-rank Gram shrugs off the mild
    spectrum shift, a deficient one goes indefinite past the default
    damping and must be rescued by the re-damp rung."""
    from repro.core.recipe import QuantRecipe
    from repro.data import DataConfig, TokenStream
    from repro.models.modules import QSpec
    from repro.models.transformer import ModelConfig, init_params

    cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=32,
                      vocab=128, n_heads=4, n_kv_heads=2, d_ff=64,
                      dtype=jnp.float32, scan_layers=False)
    params = init_params(jax.random.PRNGKey(0), cfg)
    stream = TokenStream(DataConfig(vocab=128, seq_len=32, global_batch=2))
    calib = [stream.next_batch() for _ in range(2)]
    if calib_kind == "deficient":
        calib = [{k: (v[:1, :16] if getattr(v, "ndim", 0) >= 2 else v)
                  for k, v in calib[0].items()}]
    recipe = QuantRecipe.single(
        "cloq", QSpec(bits=4, group_size=16, rank=4, method="cloq"))
    return params, cfg, calib, recipe


def _assert_all_finite(flat):
    for pth, leaf in flat.items():
        arr = np.asarray(leaf)
        if np.issubdtype(arr.dtype, np.floating):
            assert np.isfinite(arr).all(), f"non-finite leaf {pth}"


# clean-run cache: (engine, calib_kind) -> flat quantized params; the fault
# matrix compares unaffected sites bit-identically against these
_CLEAN_RUNS: dict = {}


def _clean_run(engine, calib_kind):
    key = (engine, calib_kind)
    if key not in _CLEAN_RUNS:
        from repro.core.pipeline import quantize_model
        from repro.utils import tree_paths
        params, cfg, calib, recipe = _quant_setup(calib_kind)
        qp, _, _ = quantize_model(params, cfg, calib, recipe=recipe,
                                  engine=engine)
        _CLEAN_RUNS[key] = tree_paths(qp)
    return _CLEAN_RUNS[key]


@pytest.mark.fault
@pytest.mark.parametrize("engine", ["sequential", "batched"])
@pytest.mark.parametrize("point,expected", [
    ("gram_nan", "recovered_identity_gram"),
    ("gram_non_psd", "recovered_identity_gram"),
    ("gram_jitter", "recovered_redamp"),
])
def test_gram_fault_matrix(engine, point, expected):
    """Each gram-level injection x each engine: the run completes, every
    leaf is finite, the HealthReport names the injected site with a
    non-empty accepted ladder, and *unaffected* sites are bit-identical to
    the same engine's clean run (the guard must not perturb healthy
    slices)."""
    from repro.core import faults
    from repro.core.health import HealthReport
    from repro.core.pipeline import quantize_model
    from repro.utils import tree_paths

    calib_kind = "deficient" if point == "gram_jitter" else "full"
    params, cfg, calib, recipe = _quant_setup(calib_kind)
    target = "blocks.0.attn.q"
    report = HealthReport()
    with faults.inject(point, match=target):
        qp, _, _ = quantize_model(params, cfg, calib, recipe=recipe,
                                  engine=engine, report=report)
    flat = tree_paths(qp)
    _assert_all_finite(flat)
    assert target in report.records, report.records
    rec = report.records[target]
    assert rec["status"] == expected, rec
    assert rec["ladder"] and rec["ladder"][-1]["accepted"], rec
    clean = _clean_run(engine, calib_kind)
    assert set(flat) == set(clean)
    for pth, leaf in flat.items():
        if pth.startswith(target + "."):
            continue
        np.testing.assert_array_equal(np.asarray(leaf),
                                      np.asarray(clean[pth]), err_msg=pth)


@pytest.mark.fault
def test_healed_site_bit_identical_across_engines():
    """A healed site is requeued through the same unsharded sequential
    oracle in every engine — unlike the ~ulp jitter of the clean fused
    paths, the healed leaves must be *bit-identical* across engines."""
    from repro.core import faults
    from repro.core.health import HealthReport
    from repro.core.pipeline import quantize_model
    from repro.utils import tree_paths

    target = "blocks.0.attn.q"
    flats, reports = {}, {}
    for engine in ("sequential", "batched"):
        params, cfg, calib, recipe = _quant_setup()
        report = HealthReport()
        with faults.inject("gram_nan", match=target):
            qp, _, _ = quantize_model(params, cfg, calib, recipe=recipe,
                                      engine=engine, report=report)
        flats[engine] = tree_paths(qp)
        reports[engine] = report
    assert reports["sequential"].counts() == reports["batched"].counts()
    for pth, leaf in flats["batched"].items():
        if pth.startswith(target + "."):
            np.testing.assert_array_equal(
                np.asarray(leaf), np.asarray(flats["sequential"][pth]),
                err_msg=pth)


@pytest.mark.fault
@pytest.mark.parametrize("point", ["calib_nan", "calib_drop"])
def test_calibration_fault_skips_batch_and_logs(point):
    """A NaN-poisoned or dropped calibration batch is skipped and logged
    (report event), and the run still completes finite off the remaining
    batch."""
    from repro.core import faults
    from repro.core.health import HealthReport
    from repro.core.pipeline import quantize_model
    from repro.utils import tree_paths

    params, cfg, calib, recipe = _quant_setup()
    report = HealthReport()
    with faults.inject(point, match="0"):
        qp, _, _ = quantize_model(params, cfg, calib, recipe=recipe,
                                  report=report)
    _assert_all_finite(tree_paths(qp))
    assert any("batch 0" in e for e in report.events), report.events


@pytest.mark.fault
def test_calibration_all_batches_bad_raises():
    """Every batch dropped -> loud error, not a zero-sample GramStore."""
    from repro.core import faults
    from repro.core.pipeline import quantize_model

    params, cfg, calib, recipe = _quant_setup()
    with faults.inject("calib_drop", match="*"):
        with pytest.raises(RuntimeError, match="zero-sample"):
            quantize_model(params, cfg, calib, recipe=recipe)


@pytest.mark.fault
@pytest.mark.multidevice
def test_sharded_engine_fault_heal_parity():
    """The fault matrix extends to the sharded engine: a gram fault under
    mesh execution heals through the same unsharded oracle, so the healed
    site is bit-equal to the unsharded batched run and everything stays
    finite."""
    from tests.util import run_with_devices
    run_with_devices("""
        import jax, jax.numpy as jnp
        import numpy as np
        from repro.core import faults
        from repro.core.health import HealthReport
        from repro.core.pipeline import quantize_model
        from repro.core.recipe import QuantRecipe
        from repro.data import DataConfig, TokenStream
        from repro.models.modules import QSpec
        from repro.models.transformer import ModelConfig, init_params
        from repro.utils import tree_paths

        from repro.launch.mesh import make_model_mesh
        mesh = make_model_mesh(2)
        cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=32,
                          vocab=128, n_heads=4, n_kv_heads=2, d_ff=64,
                          dtype=jnp.float32, scan_layers=False)
        params = init_params(jax.random.PRNGKey(0), cfg)
        stream = TokenStream(DataConfig(vocab=128, seq_len=32,
                                        global_batch=2))
        calib = [stream.next_batch() for _ in range(2)]
        recipe = QuantRecipe.single(
            "cloq", QSpec(bits=4, group_size=16, rank=4, method="cloq"))
        target = "blocks.0.attn.q"

        flats = {}
        for use_mesh in (True, False):
            report = HealthReport()
            with faults.inject("gram_non_psd", match=target):
                qp, _, _ = quantize_model(
                    params, cfg, calib, recipe=recipe,
                    mesh=mesh if use_mesh else None, report=report)
            rec = report.records[target]
            assert rec["status"] == "recovered_identity_gram", rec
            flat = tree_paths(qp)
            for pth, leaf in flat.items():
                arr = np.asarray(leaf)
                if np.issubdtype(arr.dtype, np.floating):
                    assert np.isfinite(arr).all(), pth
            flats[use_mesh] = flat
        for pth, leaf in flats[True].items():
            if pth.startswith(target + "."):
                assert np.array_equal(np.asarray(leaf),
                                      np.asarray(flats[False][pth])), pth
        print("sharded fault heal ok")
    """, n_devices=2)


# ---------------------------------------------------------------------------
# Journaled (resumable) quantization.
# ---------------------------------------------------------------------------


@pytest.mark.fault
def test_journal_preempt_resume_bit_identical(tmp_path):
    """should_stop at the first bucket boundary raises QuantPreempted with
    bucket 0 committed; the resumed run restores it from the journal and
    produces a tree bit-identical to an uninterrupted run (f32/uint8 leaves
    round-trip npz losslessly)."""
    from repro.checkpoint.manager import QuantJournal
    from repro.core.health import HealthReport, QuantPreempted
    from repro.core.pipeline import quantize_model
    from repro.utils import tree_paths

    params, cfg, calib, recipe = _quant_setup()
    jd = str(tmp_path / "journal")
    with pytest.raises(QuantPreempted) as ei:
        quantize_model(params, cfg, calib, recipe=recipe,
                       journal_dir=jd, should_stop=lambda: True)
    assert ei.value.bucket == 0
    assert QuantJournal(jd).buckets() == [0]

    report = HealthReport()
    qp_resumed, _, _ = quantize_model(params, cfg, calib, recipe=recipe,
                                      journal_dir=jd, report=report)
    assert any("restored from journal" in e for e in report.events), \
        report.events
    assert os.path.isfile(os.path.join(jd, "health.json"))

    qp_fresh, _, _ = quantize_model(params, cfg, calib, recipe=recipe)
    flat_r, flat_f = tree_paths(qp_resumed), tree_paths(qp_fresh)
    assert set(flat_r) == set(flat_f)
    for pth in flat_f:
        np.testing.assert_array_equal(np.asarray(flat_r[pth]),
                                      np.asarray(flat_f[pth]), err_msg=pth)


@pytest.mark.fault
def test_kill_between_buckets_then_resume(tmp_path):
    """Hard preemption: SIGKILL injected right after a journal commit kills
    the driver mid-quantization; the committed buckets survive, and a rerun
    with the same --resume-quant completes with the same final loss as an
    uninterrupted run in a fresh journal."""
    from repro.checkpoint.manager import QuantJournal

    jd = str(tmp_path / "journal")
    args = [sys.executable, "-m", "repro.launch.train", "--arch",
            "qwen3-1.7b", "--smoke", "--method", "cloq", "--bits", "4",
            "--group-size", "16", "--rank", "4", "--steps", "3",
            "--seq-len", "32", "--batch", "2", "--calib-batches", "1",
            "--resume-quant", jd]
    env = dict(os.environ, PYTHONPATH=SRC)

    killed = subprocess.run(
        args, env=dict(env, REPRO_FAULTS="kill_between_buckets=1"),
        capture_output=True, text=True, timeout=600)
    assert killed.returncode == -signal.SIGKILL, \
        (killed.returncode, killed.stdout, killed.stderr)
    committed = QuantJournal(jd).buckets()
    assert committed == [0, 1], committed

    resumed = subprocess.run(args, env=env, capture_output=True, text=True,
                             timeout=600)
    assert resumed.returncode == 0, resumed.stdout + resumed.stderr
    assert "[done]" in resumed.stdout, resumed.stdout

    fresh_args = list(args)
    fresh_args[fresh_args.index("--resume-quant") + 1] = \
        str(tmp_path / "fresh")
    fresh = subprocess.run(fresh_args, env=env, capture_output=True,
                           text=True, timeout=600)
    assert fresh.returncode == 0, fresh.stdout + fresh.stderr

    def final_loss(out):
        line = [ln for ln in out.splitlines() if ln.startswith("[done]")][-1]
        return json.loads(line[len("[done]"):].strip())["final_loss"]

    assert final_loss(resumed.stdout) == final_loss(fresh.stdout)


# ---------------------------------------------------------------------------
# Torn / corrupt checkpoint shards and retention pinning.
# ---------------------------------------------------------------------------


def _demo_tree():
    rng = np.random.default_rng(0)
    return {"a": rng.normal(size=(64, 64)).astype(np.float32),
            "b": {"c": np.ones((128,), np.float32)}}


@pytest.mark.fault
def test_truncated_shard_restore_raises(tmp_path):
    """A torn arrays.npz fails restore with an actionable error instead of
    loading garbage."""
    from repro.checkpoint.manager import restore_tree, save_tree
    from repro.core import faults

    save_tree(_demo_tree(), str(tmp_path), 1)
    faults.truncate_file(os.path.join(str(tmp_path), "step_00000001",
                                      "arrays.npz"))
    with pytest.raises(ValueError, match="truncated|corrupt"):
        restore_tree(str(tmp_path), 1)


@pytest.mark.fault
def test_shard_truncate_injection_point(tmp_path):
    """The shard_truncate fault point tears the shard through the runtime's
    own post-commit hook (save_tree), targeted by step."""
    from repro.checkpoint.manager import restore_tree, save_tree
    from repro.core import faults

    with faults.inject("shard_truncate", match="1"):
        save_tree(_demo_tree(), str(tmp_path), 1)
    with pytest.raises(ValueError, match="truncated|corrupt"):
        restore_tree(str(tmp_path), 1)


@pytest.mark.fault
def test_checksum_mismatch_names_leaf(tmp_path):
    """Bit rot that keeps the zip readable (stale checksums in meta.json
    stand in for it — flipping payload bytes trips the zip CRC first) is
    caught by the per-leaf crc32 verify, naming the corrupt leaf."""
    from repro.checkpoint.manager import restore_tree, save_tree

    save_tree(_demo_tree(), str(tmp_path), 1)
    meta_path = os.path.join(str(tmp_path), "step_00000001", "meta.json")
    with open(meta_path) as f:
        meta = json.load(f)
    meta["checksums"]["a"] ^= 1
    with open(meta_path, "w") as f:
        json.dump(meta, f)
    with pytest.raises(ValueError, match="checksum mismatch for leaf 'a'"):
        restore_tree(str(tmp_path), 1)


@pytest.mark.fault
def test_pinned_checkpoint_survives_gc(tmp_path):
    """A pinned step (the preemption checkpoint) outlives any number of
    routine saves under retention GC; unpinned steps rotate normally."""
    from repro.checkpoint import CheckpointManager

    ck = CheckpointManager(str(tmp_path), keep=2, every=1,
                           async_write=False)
    tree = _demo_tree()
    ck.maybe_save(1, tree, force=True, pin=True)
    for s in range(2, 7):
        ck.maybe_save(s, tree, force=True)
    ck.wait()
    steps = sorted(p for p in os.listdir(str(tmp_path))
                   if p.startswith("step_"))
    assert "step_00000001" in steps, steps          # pinned survived
    assert "step_00000005" in steps and "step_00000006" in steps, steps
    for gone in ("step_00000002", "step_00000003", "step_00000004"):
        assert gone not in steps, steps
