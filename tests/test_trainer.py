"""TrainLoop tests: the jitted-step engine, sharding, checkpoint/resume.

Covers the reference-parity semantics SURVEY.md §4 lists as test-worthy:
EMA math (trainer.py:360-370), LR anneal (:257-263), grad clip (:246-255),
microbatch accumulation equivalence (:230-235), checkpoint filename
convention and auto-resume (:319-355) — all on a real 8-device mesh.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_pipeline_tpu.config.train import TrainSettings
from distributed_pipeline_tpu.data import load_data_from_args
from distributed_pipeline_tpu.models import create_model_from_config
from distributed_pipeline_tpu.parallel import make_mesh
from distributed_pipeline_tpu.parallel.sharding import (
    batch_shardings,
    param_shardings,
    shard_batch,
)
from distributed_pipeline_tpu.utils import checkpoint as ckpt
from distributed_pipeline_tpu.utils import logger
from distributed_pipeline_tpu.utils.perf import (
    AOTStep,
    enable_persistent_compilation_cache,
)
from distributed_pipeline_tpu.utils.trainer import TrainLoop, update_ema


def tiny_workload(fam="gpt2", seq_len=16):
    return create_model_from_config(
        model_family=fam, vocab_size=64, seq_len=seq_len, hidden_size=32,
        num_layers=2, num_heads=2, diffusion_steps=50, dtype="float32")


def tiny_data(fam="gpt2", batch_size=8, seq_len=16, seed=0):
    name = "synthetic-lm" if fam == "gpt2" else "synthetic-seq2seq"
    return load_data_from_args("train", batch_size=batch_size, dataset=name,
                               seq_len=seq_len, vocab_size=64, seed=seed)


def make_loop(tmp_path, fam="gpt2", **kw):
    kw.setdefault("batch_size", 8)
    kw.setdefault("lr", 1e-3)
    kw.setdefault("learning_steps", 1000)
    kw.setdefault("log_interval", 1000)
    kw.setdefault("save_interval", 10 ** 9)
    kw.setdefault("mesh", make_mesh(dp=8))
    kw.setdefault("ema_rate", "0.9")
    kw.setdefault("seed", 5)
    data = kw.pop("data", None) or tiny_data(fam, kw["batch_size"])
    return TrainLoop(model=tiny_workload(fam), data=data,
                     checkpoint_dir=str(tmp_path), **kw)


# --------------------------------------------------------------- core engine

def test_loss_decreases_over_steps(tmp_path):
    loop = make_loop(tmp_path)
    first = float(loop.run_step(next(loop.data))["loss"])
    for _ in range(30):
        m = loop.run_step(next(loop.data))
    assert float(m["loss"]) < first
    assert loop.step == 31


def test_loss_decreases_with_prefetch_and_lagged_dispatch(tmp_path):
    """The non-eager TrainLoop shipped as the CONFIG default (PR 5:
    prefetch_depth=2, dispatch_lag=1) must train like the eager path —
    tier-1 exercises the real-run configuration, not just the wrapper's
    own unit tests (test_device_prefetch.py)."""
    loop = make_loop(tmp_path, prefetch_depth=2, dispatch_lag=1)
    first = float(loop.run_step(next(loop.data))["loss"])  # DeviceBatch path
    for _ in range(30):
        m = loop.run_step(next(loop.data))
    loop.flush_metrics()  # drain the lagged ring like run_loop's boundaries
    assert float(m["loss"]) < first
    assert loop.step == 31


def test_grad_accumulation_equivalence(tmp_path):
    """microbatch=B vs microbatch=B/4 must produce identical updates for an
    rng-independent loss (the reference's no_sync accumulation semantics)."""
    batches = [next(tiny_data("gpt2", 8, seed=1)) for _ in range(2)]
    results = []
    for mb in (8, 2):
        it = iter(batches)
        loop = make_loop(tmp_path / f"mb{mb}", microbatch=mb, data=it,
                         mesh=make_mesh(dp=2, fsdp=1, tensor=1, sequence=1,
                                        devices=jax.devices()[:2]))
        for b in batches:
            loop.run_step(b)
        results.append(jax.tree_util.tree_leaves(loop.state.params))
    for a, b in zip(*results):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-6)


def test_lr_anneal_linear(tmp_path):
    loop = make_loop(tmp_path, lr=1e-2, learning_steps=100)
    m = loop.run_step(next(loop.data))
    # step 0 metric: lr * (1 - 0/100)
    np.testing.assert_allclose(float(m["lr"]), 1e-2, rtol=1e-6)
    for _ in range(9):
        m = loop.run_step(next(loop.data))
    np.testing.assert_allclose(float(m["lr"]), 1e-2 * (1 - 9 / 100), rtol=1e-5)


def test_grad_clip_changes_update_and_logs_preclip_norm(tmp_path):
    """Clip rescales grads before Adam (reference grad_clip trainer.py:
    246-255); the logged norm is the pre-clip norm. (Adam is scale-invariant
    in the long run but a one-step update still differs under clipping.)"""
    batch = next(tiny_data("gpt2", 8, seed=4))
    outs = {}
    for clip in (-1.0, 1e-3):
        loop = make_loop(tmp_path / f"clip{clip}", gradient_clipping=clip,
                         data=iter([batch]))
        m = loop.run_step(batch)
        outs[clip] = (float(m["grad_norm"]),
                      jax.tree_util.tree_leaves(loop.state.params))
    # same pre-clip grad norm logged in both runs
    np.testing.assert_allclose(outs[-1.0][0], outs[1e-3][0], rtol=1e-5)
    assert outs[-1.0][0] > 1e-3  # clip threshold actually binds
    diffs = [np.abs(np.asarray(a) - np.asarray(b)).max()
             for a, b in zip(outs[-1.0][1], outs[1e-3][1])]
    assert max(diffs) > 1e-6  # clipping altered the first-step update


def test_ema_update_math():
    ema = {"w": jnp.ones((4,))}
    params = {"w": jnp.zeros((4,))}
    out = update_ema(ema, params, 0.9)
    np.testing.assert_allclose(np.asarray(out["w"]), 0.9)


def test_ema_tracks_params(tmp_path):
    loop = make_loop(tmp_path, ema_rate="0.5,0.99")
    for _ in range(5):
        loop.run_step(next(loop.data))
    p = jax.tree_util.tree_leaves(loop.state.params)
    fast = jax.tree_util.tree_leaves(loop.state.ema["0.5"])
    slow = jax.tree_util.tree_leaves(loop.state.ema["0.99"])
    dist_fast = sum(float(jnp.sum((a - b) ** 2)) for a, b in zip(p, fast))
    dist_slow = sum(float(jnp.sum((a - b) ** 2)) for a, b in zip(p, slow))
    assert 0 < dist_fast < dist_slow  # fast EMA hugs params closer


def test_microbatch_validation(tmp_path):
    with pytest.raises(ValueError):
        make_loop(tmp_path, batch_size=8, microbatch=3)


def test_eval_step_and_metrics(tmp_path):
    loop = make_loop(tmp_path)
    m = loop.forward_only(next(loop.data))
    assert "loss" in m and np.isfinite(float(m["loss"]))


# ----------------------------------------------------------------- sharding

def test_params_are_fsdp_sharded(tmp_path):
    mesh = make_mesh(dp=2, fsdp=4)
    loop = make_loop(tmp_path, mesh=mesh)
    flat = jax.tree_util.tree_leaves_with_path(loop.state.params)
    sharded = [
        (jax.tree_util.keystr(p), l.sharding.spec)
        for p, l in flat
        if any(ax == "fsdp" or (isinstance(ax, tuple) and "fsdp" in ax)
               for ax in (l.sharding.spec or ()))
    ]
    assert sharded, "no parameter was sharded over the fsdp axis"
    # optimizer mu/nu must shard like params (ZeRO memory contract)
    mu_leaves = jax.tree_util.tree_leaves(loop.state.opt_state[0].mu)
    p_leaves = jax.tree_util.tree_leaves(loop.state.params)
    for m, p in zip(mu_leaves, p_leaves):
        assert m.sharding == p.sharding


@pytest.mark.parametrize("axes", [dict(dp=2, fsdp=2, tensor=2),
                                  dict(dp=1, fsdp=1, tensor=8)])
def test_train_step_runs_on_mixed_mesh(tmp_path, axes):
    """DP x FSDP x TP and pure-TP meshes compile and run the same engine
    (strategy = sharding spec, no new code — SURVEY.md §2.2 payoff)."""
    mesh = make_mesh(**axes)
    loop = make_loop(tmp_path / "mixed", mesh=mesh, batch_size=8, microbatch=4)
    m1 = loop.run_step(next(loop.data))
    m2 = loop.run_step(next(loop.data))
    assert np.isfinite(float(m2["loss"]))


@pytest.mark.slow  # heaviest tier: compile-dominated / multi-loop composition (VERDICT r5 weak #3)
def test_dp_invariance_across_meshes(tmp_path):
    """The same data must give the same loss no matter how it is sharded."""
    batches = [next(tiny_data("gpt2", 8, seed=9)) for _ in range(1)]
    losses = []
    for axes in (dict(dp=8), dict(dp=2, fsdp=4), dict(dp=4, tensor=2)):
        loop = make_loop(tmp_path / str(axes), mesh=make_mesh(**axes),
                         data=iter(batches))
        losses.append(float(loop.run_step(batches[0])["loss"]))
    np.testing.assert_allclose(losses[0], losses[1], rtol=1e-5)
    np.testing.assert_allclose(losses[0], losses[2], rtol=1e-5)


def test_shard_batch_layout():
    mesh = make_mesh(dp=8)
    b = {"x": np.arange(64, dtype=np.int32).reshape(8, 8)}
    g = shard_batch(mesh, b)
    assert g["x"].shape == (8, 8)
    assert g["x"].sharding.spec == batch_shardings(mesh).spec


# ------------------------------------------------------------- checkpointing

def test_parse_step_from_name():
    assert ckpt.parse_step_from_name("model_012345") == 12345
    assert ckpt.parse_step_from_name("ema_0.99_000020") == 20
    assert ckpt.parse_step_from_name("model_") is None


def test_checkpoint_roundtrip_and_discovery(tmp_path):
    d = str(tmp_path)
    tree = {"a": jnp.arange(8.0), "b": {"c": jnp.ones((2, 2))}}
    ckpt.save_checkpoint(d, 7, tree)
    ckpt.save_checkpoint(d, 20, jax.tree_util.tree_map(lambda x: x * 2, tree))
    assert ckpt.latest_step(d) == 20
    assert ckpt.find_resume_checkpoint(d).endswith("model_000020")
    abstract = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
    restored = ckpt.restore_checkpoint(os.path.join(d, "model_000007"),
                                       abstract)
    np.testing.assert_allclose(np.asarray(restored["a"]),
                               np.arange(8.0))


def test_resume_continues_training(tmp_path):
    loop = make_loop(tmp_path, save_interval=10 ** 9)
    for _ in range(3):
        loop.run_step(next(loop.data))
    loop.save()
    # new loop in the same dir auto-discovers and resumes
    loop2 = make_loop(tmp_path)
    assert loop2.step == 3
    assert int(loop2.state.step) == 3
    for a, b in zip(jax.tree_util.tree_leaves(loop.state.params),
                    jax.tree_util.tree_leaves(loop2.state.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))
    # EMA survived too
    for a, b in zip(jax.tree_util.tree_leaves(loop.state.ema["0.9"]),
                    jax.tree_util.tree_leaves(loop2.state.ema["0.9"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))
    m = loop2.run_step(next(loop2.data))
    assert loop2.step == 4 and np.isfinite(float(m["loss"]))


def test_resume_across_mesh_change(tmp_path):
    """Checkpoints are topology-independent: save on dp=8, resume on
    dp=2 x fsdp=4 (elastic-recovery story, SURVEY.md §5.3)."""
    loop = make_loop(tmp_path, mesh=make_mesh(dp=8))
    loop.run_step(next(loop.data))
    loop.save()
    loop2 = make_loop(tmp_path, mesh=make_mesh(dp=2, fsdp=4))
    assert loop2.step == 1
    for a, b in zip(jax.tree_util.tree_leaves(loop.state.params),
                    jax.tree_util.tree_leaves(loop2.state.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))


def test_explicit_resume_path_invalid_raises(tmp_path):
    """ADVICE r1 (medium): a typo'd --resume_checkpoint must fail loudly,
    never silently restart from scratch."""
    from distributed_pipeline_tpu.utils import checkpoint as ckpt_lib

    with pytest.raises(FileNotFoundError):
        ckpt_lib.restore_resume_state(
            str(tmp_path), abstract_params={},
            explicit_model_path=str(tmp_path / "model_000123.pt"))


def test_checkpoint_discovery_through_epath(tmp_path):
    """Discovery/save/resume drive through etils.epath so remote URIs
    (gs://...) take the same code path as local dirs (SURVEY.md §5.4)."""
    from etils import epath

    from distributed_pipeline_tpu.utils import checkpoint as ckpt_lib

    d = epath.Path(str(tmp_path))  # epath-style handle over a local dir
    params = {"w": jnp.arange(4, dtype=jnp.float32)}
    ckpt_lib.save_checkpoint(os.fspath(d), 7, params)
    found = ckpt_lib.find_resume_checkpoint(os.fspath(d))
    assert found is not None and found.endswith("model_000007")
    abstract = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
    out = ckpt_lib.restore_resume_state(os.fspath(d), abstract_params=abstract)
    assert out is not None and out["step"] == 7
    np.testing.assert_array_equal(np.asarray(out["params"]["w"]),
                                  np.arange(4, dtype=np.float32))


# ------------------------------------------------- debug/profiling flag wiring

def _profile_files(d):
    return [os.path.join(r, f) for r, _, fs in os.walk(d) for f in fs]


@pytest.mark.slow  # heaviest tier: compile-dominated / multi-loop composition (VERDICT r5 weak #3)
def test_profile_dir_writes_trace(tmp_path):
    """VERDICT r2 weak #5: --profile_dir captures a jax.profiler trace window
    (steps 3..8 after loop entry) into the directory."""
    trace_dir = tmp_path / "trace"
    loop = make_loop(tmp_path, learning_steps=10,
                     profile_dir=str(trace_dir))
    loop.run_loop()
    assert loop.step == 10 and not loop._profiling
    assert _profile_files(trace_dir), "no trace files written"


def test_profile_run_shorter_than_window(tmp_path):
    """A run that ends INSIDE the profiler window must still stop the trace
    (the run_loop finally clause) and flush files."""
    trace_dir = tmp_path / "trace"
    loop = make_loop(tmp_path, learning_steps=5,
                     profile_dir=str(trace_dir))
    loop.run_loop()  # window is (3, 8): started at 3, run ends at 5
    assert loop.step == 5 and not loop._profiling
    assert _profile_files(trace_dir), "interrupted trace was not flushed"


def test_debug_nans_flag_fails_loudly(tmp_path):
    """VERDICT r2 weak #5: --debug_nans must turn a NaN into a loud
    FloatingPointError at the op that produced it (SURVEY.md §5.2), wired
    through the real run/train.py main()."""
    from distributed_pipeline_tpu.run import train as run_train

    argv = ["--debug_nans", "true", "--lr", "1e38",  # lr overflow -> NaN
            "--batch_size", "8", "--microbatch", "8",
            "--learning_steps", "4", "--log_interval", "1000000",
            "--eval_interval", "1000000", "--save_interval", "1000000",
            "--vocab_size", "64", "--seq_len", "16", "--hidden_size", "32",
            "--num_layers", "1", "--num_heads", "2",
            "--diffusion_steps", "50", "--dtype", "float32",
            "--checkpoint_path", str(tmp_path / "run")]
    ns = run_train.create_parser().parse_args(argv)
    try:
        with pytest.raises(FloatingPointError):
            run_train.main(ns)
    finally:
        jax.config.update("jax_debug_nans", False)


def test_lr_warmup_schedule(tmp_path):
    """--warmup_steps ramps LR linearly before the reference anneal;
    warmup_steps=0 reproduces the reference schedule exactly."""
    loop = make_loop(tmp_path, lr=1e-3, learning_steps=100)
    assert np.isclose(float(loop._lr_at(0)), 1e-3)
    assert np.isclose(float(loop._lr_at(50)), 5e-4)

    loop_w = make_loop(tmp_path / "w", lr=1e-3, learning_steps=100,
                       warmup_steps=10)
    assert np.isclose(float(loop_w._lr_at(0)), 1e-3 * (1 / 10))
    assert np.isclose(float(loop_w._lr_at(4)), 1e-3 * (5 / 10) * 0.96)
    # past warmup: anneal only
    assert np.isclose(float(loop_w._lr_at(50)), 5e-4)
    # and the jitted step consumes it without recompilation issues
    m = loop_w.run_step(next(loop_w.data))
    assert np.isclose(float(m["lr"]), 1e-3 * (1 / 10) * 1.0, rtol=1e-3)


def test_async_save_overlaps_training(tmp_path):
    """save(wait=False) — the run_loop path — schedules the write and
    returns; training steps proceed while it is in flight, and the bytes
    that land are the state AT SAVE TIME, not the mutated-by-later-steps
    state (Orbax's synchronous device-to-host fetch is what makes the
    jitted step's buffer donation safe)."""
    loop = make_loop(tmp_path, save_interval=10 ** 9)
    for _ in range(2):
        loop.run_step(next(loop.data))
    snapshot = jax.tree_util.tree_map(lambda x: np.asarray(x).copy(),
                                      loop.state.params)
    loop.save(wait=False)
    for _ in range(3):  # training proceeds; params diverge from snapshot
        m = loop.run_step(next(loop.data))
    assert np.isfinite(float(m["loss"]))
    loop.wait_for_saves()
    abstract = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), snapshot)
    restored = ckpt.restore_checkpoint(
        os.path.join(str(tmp_path), "model_000002"), abstract)
    for a, b in zip(jax.tree_util.tree_leaves(snapshot),
                    jax.tree_util.tree_leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and the post-save steps really moved the live params
    moved = any(
        not np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree_util.tree_leaves(snapshot),
                        jax.tree_util.tree_leaves(loop.state.params)))
    assert moved


def test_keep_checkpoints_prunes_old_steps(tmp_path):
    """--keep_checkpoints N retains only the newest N steps, pruning
    model+EMA+opt together; 0 keeps everything (reference behavior)."""
    loop = make_loop(tmp_path, keep_checkpoints=2, save_interval=10 ** 9)
    for _ in range(3):
        loop.run_step(next(loop.data))
        loop.save()
    names = sorted(p.name for p in tmp_path.iterdir())
    assert [n for n in names if n.startswith("model_")] == [
        "model_000002", "model_000003"]
    assert not any(n.endswith("000001") for n in names), names
    # companions of surviving steps intact
    assert any(n.startswith("ema_") and n.endswith("000003") for n in names)
    assert "opt_000003" in names

    # keep_checkpoints=0: nothing pruned
    loop0 = make_loop(tmp_path / "all", keep_checkpoints=0,
                      save_interval=10 ** 9)
    for _ in range(3):
        loop0.run_step(next(loop0.data))
        loop0.save()
    names0 = [p.name for p in (tmp_path / "all").iterdir()
              if p.name.startswith("model_")]
    assert len(names0) == 3


def test_constant_lr_optstate_resumes(tmp_path):
    """Constant-LR runs (learning_steps=0) must keep the plain-float optax
    schedule so their opt_state pytree structure stays restorable."""
    loop = make_loop(tmp_path, learning_steps=0, save_interval=10 ** 9)
    loop.run_step(next(loop.data))
    loop.save()
    loop2 = make_loop(tmp_path, learning_steps=0)
    assert loop2.step == 1
    m = loop2.run_step(next(loop2.data))
    assert np.isfinite(float(m["loss"]))
    assert np.isclose(float(m["lr"]), loop2.lr)


def test_unfinalized_orbax_tmp_ignored(tmp_path):
    """A crash mid-save leaves 'model_NNNNNN.orbax-checkpoint-tmp-<ts>';
    its trailing timestamp must NOT rank as a step — neither for resume
    discovery nor for retention pruning (which would otherwise delete real
    checkpoints and keep the corrupt tmp)."""
    d = str(tmp_path)
    tree = {"a": jnp.arange(4.0)}
    ckpt.save_checkpoint(d, 1, tree)
    ckpt.save_checkpoint(d, 2, tree)
    (tmp_path / "model_000003.orbax-checkpoint-tmp-1712345678901234").mkdir()

    assert ckpt.latest_step(d) == 2
    assert ckpt.find_resume_checkpoint(d).endswith("model_000002")

    pruned = ckpt.prune_checkpoints(d, keep=2)
    assert pruned == []  # two real steps, both kept; tmp didn't count
    ckpt.save_checkpoint(d, 4, tree)
    pruned = ckpt.prune_checkpoints(d, keep=2)
    assert pruned == [1]
    names = {p.name for p in tmp_path.iterdir()}
    assert "model_000002" in names and "model_000004" in names
    # the in-flight/corrupt tmp is left alone
    assert "model_000003.orbax-checkpoint-tmp-1712345678901234" in names


def test_resume_eval_stream_exact_with_changed_interval(tmp_path):
    """VERDICT r4 weak #7: the consumed-eval-batch count is persisted in
    each checkpoint's meta sidecar, so a resume fast-forwards the eval
    stream EXACTLY even when --eval_interval changed between runs (the
    old flag-derived division would replay/skip eval batches)."""
    import json
    import subprocess
    import sys

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    # the conftest's 8-fake-device XLA_FLAGS must not leak into the child:
    # this config's microbatch 4 assumes the default single-device CPU
    env.pop("XLA_FLAGS", None)

    def run(steps, eval_interval):
        cfg = {
            "model_family": "gpt2", "vocab_size": 64, "seq_len": 16,
            "hidden_size": 32, "num_layers": 2, "num_heads": 2,
            "dtype": "float32", "batch_size": 4, "microbatch": 4,
            "lr": 1e-3, "learning_steps": steps, "log_interval": 10 ** 6,
            "save_interval": 4, "eval_interval": eval_interval,
            "dataset": "synthetic-lm",
            "checkpoint_path": str(tmp_path / "run"),
        }
        cfg_path = tmp_path / "cfg.json"
        cfg_path.write_text(json.dumps(cfg))
        out = subprocess.run(
            [sys.executable, "-m", "distributed_pipeline_tpu.run.train",
             "--config_json", str(cfg_path)],
            capture_output=True, text=True, timeout=300, cwd=repo_root,
            env=env)
        assert out.returncode == 0, out.stderr[-2000:]
        return out

    run(4, 2)  # evals at steps 2, 4 -> 2 eval batches consumed
    meta = json.loads((tmp_path / "run" / "meta_000004.json").read_text())
    assert meta["eval_batches_consumed"] == 2
    assert meta["eval_interval"] == 2

    # resume with a DIFFERENT interval: the meta count (2), not
    # resume_step // new_interval (4), must drive the fast-forward
    out = run(8, 1)
    assert "fast-forwarding data stream past 4 consumed train batches / " \
           "2 eval batches" in (out.stdout + out.stderr)
    meta = json.loads((tmp_path / "run" / "meta_000008.json").read_text())
    # resumed at 2 consumed + evals at steps 5,6,7,8 with interval 1
    assert meta["eval_batches_consumed"] == 6


@pytest.mark.slow  # heaviest tier: compile-dominated / multi-loop composition (VERDICT r5 weak #3)
def test_zero_intervals_disable_periodic_actions(tmp_path):
    """Interval <= 0 disables the periodic action instead of dying on the
    modulo (the reference's loop would ZeroDivisionError); the final save
    still runs so the run leaves a restorable checkpoint."""
    import os

    loop = make_loop(tmp_path, learning_steps=3, log_interval=0,
                     save_interval=0)
    loop.run_loop()
    assert loop.step == 3
    saved = sorted(d for d in os.listdir(tmp_path) if d.startswith("model_"))
    assert saved == ["model_000003"]  # exit save only, no periodic saves


# ------------------------------- compilation cache + AOT compile metrics
# (ahead of the sanitizer tests: after them this process traces a step about
# twice as slowly, and the cache-hit test below compares two compile times)

def test_enable_persistent_cache_resolution(tmp_path, monkeypatch):
    """The one rule: the variable if set (and nothing else written or
    exported), else one fixed path in the checkout — the same whatever
    the run directory — and 'off'."""
    from distributed_pipeline_tpu.utils import perf

    fixed = str(tmp_path / "fixed")
    monkeypatch.setattr(perf, "DEFAULT_COMPILE_CACHE_DIR", fixed)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        assert enable_persistent_compilation_cache("off") == ""
        # unset: the fixed path, identical for two different run dirs
        # (run dirs no longer enter into it at all)
        a = TrainSettings.from_argv(["--checkpoint_path", "/tmp/run_a"])
        b = TrainSettings.from_argv(["--checkpoint_path", "/tmp/run_b"])
        got = [enable_persistent_compilation_cache(s.compilation_cache_dir)
               for s in (a, b)]
        assert got == [fixed, fixed] and os.path.isdir(fixed)
        assert jax.config.jax_compilation_cache_dir == fixed
        assert "JAX_COMPILATION_CACHE_DIR" not in os.environ  # no export
        assert perf.DEFAULT_COMPILE_CACHE_DIR == fixed
        # the real default sits in the checkout, under a fixed name
        assert os.path.basename(os.path.dirname(os.path.dirname(
            perf.__file__))) == "distributed_pipeline_tpu"
        # set: that directory, and no other made
        outside = str(tmp_path / "outside")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outside)
        os.rmdir(fixed)
        assert enable_persistent_compilation_cache("auto") == outside
        assert jax.config.jax_compilation_cache_dir == outside
        assert os.environ["JAX_COMPILATION_CACHE_DIR"] == outside
        assert sorted(os.listdir(tmp_path)) == ["outside"]
        # 'off' wins over the variable, and leaves it alone
        assert enable_persistent_compilation_cache("off") == ""
        assert jax.config.jax_compilation_cache_dir is None
        assert os.environ["JAX_COMPILATION_CACHE_DIR"] == outside
    finally:
        # "off" resets jax's once-only cache object too — leaving it
        # initialized would pin this tmp dir for the whole test process
        enable_persistent_compilation_cache("off")


def test_aot_compile_metrics_and_cache_hit_path(tmp_path, monkeypatch):
    """compile_time_s/time_to_first_step_s are populated by the first step,
    and a RESUMED TrainLoop under a warm persistent cache compiles
    measurably faster — the exact elastic-restart path the cache exists
    for. The resume leg doubles as a regression test for donating
    orbax-restored buffers into a cache-deserialized executable (jaxlib
    0.4.37 CPU heap corruption; trainer copies restored trees)."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    try:
        enable_persistent_compilation_cache()

        cold = make_loop(tmp_path / "run")
        assert cold.compile_time_s is None  # nothing compiled at build time
        cold.run_step(next(cold.data))
        assert cold.compile_time_s > 0
        assert cold.time_to_first_step_s >= cold.compile_time_s
        assert os.listdir(str(tmp_path / "cache")), \
            "persistent cache wrote nothing"
        cold.save()

        warm = make_loop(tmp_path / "run")  # same dir: auto-resumes
        assert warm.step == 1
        warm.run_step(next(warm.data))
        warm.run_step(next(warm.data))  # steady state past the restore
        # The XLA compile is the dominant share of the cold number; a cache
        # hit replaces it with a disk read. 0.7 leaves headroom for the
        # (uncached) trace+lower share while still failing if the cache
        # silently stopped hitting.
        assert warm.compile_time_s < cold.compile_time_s * 0.7, (
            warm.compile_time_s, cold.compile_time_s)
    finally:
        enable_persistent_compilation_cache("off")


def test_aot_step_recompiles_on_shape_change():
    calls = []
    step = AOTStep(jax.jit(lambda x: x * 2), "mul",
                   on_compile=lambda n, s: calls.append((n, s)))
    a = step(jnp.ones((4,)))
    b = step(jnp.ones((4,)))          # same shape: no recompile
    assert len(calls) == 1
    c = step(jnp.ones((8,)))          # shape change: falls back to recompile
    assert len(calls) == 2
    assert float(a.sum()) == 8 and float(b.sum()) == 8
    assert float(c.sum()) == 16
    assert step.compile_time_s == pytest.approx(sum(s for _, s in calls))


# ---------------------------------------------------------- sanitizer mode

def test_sanitize_mode_counts_compiles_and_guards_transfers(tmp_path):
    """--sanitize (the runtime half of graftlint): recompile_count freezes
    once the step functions are built — growth across steady-state steps
    is exactly the silent-retrace regression the gauge exists to catch —
    and the step dispatch runs under a transfer guard that rejects
    implicit host->device transfers while the loop's own explicit
    device_put path keeps working."""
    loop = make_loop(tmp_path, sanitize=True)
    try:
        loop.run_step(next(loop.data))
        after_first = loop.recompile_count
        assert after_first >= 1  # init + train_step compiles were observed
        for _ in range(3):
            loop.run_step(next(loop.data))
        assert loop.step == 4
        assert loop.recompile_count == after_first  # steady state: frozen
        with logger.scoped_configure(dir=str(tmp_path / "l"),
                                     format_strs=["json"]):
            loop.log_step()
            assert logger.dumpkvs()["recompile_count"] == after_first

        # the guard really is armed: an implicit np->device transfer
        # inside the guarded region must raise, not silently transfer
        f = jax.jit(lambda x: x * 2)
        with pytest.raises(Exception, match="[Dd]isallow"):
            with loop._sanitize_guard():
                f(np.ones(3)).block_until_ready()

        # the monitor is still live outside the guard: a deliberate fresh
        # compile (distinctive constants so no cache can satisfy it) must
        # be counted
        g = jax.jit(lambda x: x * 3.14159 + 2.71828)
        g(jnp.ones(3)).block_until_ready()
        assert loop.recompile_count > after_first
        live = loop.recompile_count
    finally:
        final = loop.stop_sanitizer()
    assert final == live  # stop returns the count at detach time
    # and counting really stops once detached
    h = jax.jit(lambda x: x * 1.41421 - 0.57721)
    h(jnp.ones(3)).block_until_ready()
    assert loop.recompile_count == final
    loop.stop_sanitizer()  # idempotent


def test_sanitize_off_by_default(tmp_path):
    loop = make_loop(tmp_path)
    loop.run_step(next(loop.data))
    assert not loop.sanitize and loop.recompile_count == 0
    # steady-state knobs are opt-in at the TrainLoop API level (the
    # config layer turns them on for real runs)
    assert loop.prefetch_depth == 0 and loop.dispatch_lag == 0


def test_sanitize_covers_callbacks_and_checkpoint_roundtrip(tmp_path):
    """ISSUE 5 satellite (ROADMAP open item): the --sanitize transfer
    guard extends beyond step dispatch to eval callbacks and checkpoint
    save/restore. A guard-legal callback (explicit device_get) runs
    fine, saves scheduled under the guard land, a sanitized resume
    restores them — and an IMPLICIT transfer inside a callback raises
    instead of silently serializing the loop."""
    seen = {"n": 0}

    def cb(tl):
        seen["n"] += 1
        assert int(jax.device_get(tl.state.step)) == tl.step  # explicit: ok
        if seen["n"] == 2:
            # implicit host->device transfer: the guard must catch it
            jax.jit(lambda x: x + 1)(np.ones(3))

    loop = make_loop(tmp_path, learning_steps=2, eval_interval=1,
                     save_interval=1, sanitize=True,
                     eval_data=tiny_data("gpt2", 8, seed=6),
                     eval_callbacks=[cb])
    try:
        with pytest.raises(Exception, match="[Dd]isallow"):
            loop.run_loop()
    finally:
        loop.stop_sanitizer()
    assert seen["n"] == 2  # first (legal) callback ran; second tripped

    # step 1's save was scheduled UNDER the guard and still landed —
    # Orbax's device->host fetch is explicit, so sanitized saves work
    assert (tmp_path / "model_000001").is_dir()

    # restore path under the guard: a sanitized loop resumes the
    # guarded-save checkpoint without tripping
    loop2 = make_loop(tmp_path, sanitize=True)
    try:
        assert loop2.step == 1
        m = loop2.run_step(next(loop2.data))
        assert np.isfinite(float(jax.device_get(m["loss"])))
    finally:
        loop2.stop_sanitizer()


def test_shipped_decode_callback_is_guard_clean(tmp_path):
    """Code-review regression: make_decode_callback used to build its
    PRNGKey eagerly in-call and dispatch off-mesh args, tripping the
    --sanitize transfer guard the moment eval callbacks ran under it.
    The shipped callback must run guard-clean — diffuseq specifically,
    because its sampler CONSUMES the rng (gpt2's jit prunes the unused
    key arg, hiding the off-mesh reshard)."""
    from distributed_pipeline_tpu.models.sampling import make_decode_callback

    data = load_data_from_args("valid", batch_size=8,
                               dataset="synthetic-seq2seq", seq_len=16,
                               vocab_size=64, seed=0, deterministic=True)
    cb = make_decode_callback(data, sample_steps=3)
    loop = make_loop(tmp_path, fam="diffuseq", learning_steps=2,
                     eval_interval=1, sanitize=True,
                     eval_data=tiny_data("diffuseq", 8, seed=6),
                     eval_callbacks=[cb])
    try:
        with logger.scoped_configure(dir=str(tmp_path / "logs"),
                                     format_strs=["json"]):
            loop.run_loop()  # would raise Disallowed...transfer pre-fix
            d = logger.dumpkvs()
        assert 0.0 <= d["decode_acc"] <= 1.0
    finally:
        loop.stop_sanitizer()
