"""Training entry point.

Parity with the reference entry (``/root/reference/run/train.py:5-126``):
config -> distributed setup -> run dir -> logger -> seeding -> data ->
model -> args snapshot -> optional wandb -> TrainLoop. Launchable three ways,
exactly like the reference CLI (``run/train.py:124-126`` + ``train.sh``):

    python -m distributed_pipeline_tpu.run.train --config_json train_config.json
    python -m distributed_pipeline_tpu.run.train --lr 1e-4 --model_family gpt2 ...
    python -m distributed_pipeline_tpu.run.train --distributed [--nprocs N] ...
"""

from __future__ import annotations

import argparse
import json
import os
import time

from ..config.train import TrainSettings


def create_parser() -> argparse.ArgumentParser:
    """(reference run/train.py:5-6)"""
    return TrainSettings.to_argparse(add_json=True)


def resolve_run_dir(args: TrainSettings) -> str:
    """Run dir: ``model_checkpoints/Run_{dataset}_lr{lr}_seed{seed}_{ts}``
    (reference train.py:32-40). DPT_RUN_TIMESTAMP is pinned by the launcher
    so every worker, every host, and every restart attempt resolves the SAME
    dir — checkpoint auto-resume depends on it (parallel/launcher.py)."""
    if args.checkpoint_path:
        return args.checkpoint_path
    ts = os.environ.get("DPT_RUN_TIMESTAMP") or time.strftime("%Y%m%d-%H%M%S")
    return os.path.join(
        "model_checkpoints",
        f"Run_{args.dataset}_lr{args.lr}_seed{args.seed}_{ts}")


def mesh_flags_default(args) -> bool:
    """Whether the user left every mesh-axis flag at its default — the
    gate for applying a tuner artifact's mesh recommendation. An explicit
    --dp/--fsdp/... is an instruction; the recommendation then only logs."""
    return (args.dp == -1 and args.fsdp == 1 and args.sequence == 1
            and args.tensor == 1 and args.expert == 1 and args.pipe == 1)


def apply_tuned_layout(args, artifact, n_devices: int, n_hosts: int = 1):
    """Fold a tuner artifact (``--partition_rules`` dict form or the
    inline --auto_tune screen) into the settings: the RULES always apply;
    the mesh recommendation applies only when the user left the mesh
    flags at defaults AND it fits the live run — device count and the
    run's own global microbatch divisibility (an artifact tuned for
    another box or batch size must not break this one); ZeRO-1 is
    device-count-independent and follows only the default-gate. Returns
    the (possibly copied) args."""
    from ..utils import logger

    if artifact is None:
        return args
    mesh_rec = artifact.get("mesh")
    updates = {}
    if mesh_rec:
        sizes = {a: int(mesh_rec.get(a, 1)) for a in
                 ("data", "fsdp", "sequence", "tensor", "expert", "pipe")}
        product = 1
        for v in sizes.values():
            product *= v
        # the TrainLoop constructor's own divisibility contract, checked
        # here so a refusal degrades to the default layout instead of
        # crashing the run after model build
        micro = args.microbatch if args.microbatch > 0 else args.batch_size
        dpf = sizes["data"] * sizes["fsdp"] * sizes["expert"]
        if not mesh_flags_default(args):
            logger.info(f"tuned mesh recommendation {mesh_rec} NOT applied "
                        f"(mesh flags set explicitly)")
        elif product != n_devices:
            logger.warn(f"tuned mesh recommendation {mesh_rec} NOT applied "
                        f"(product {product} != {n_devices} devices — "
                        f"artifact tuned for another device set)")
        elif (micro * max(n_hosts, 1)) % dpf:
            logger.warn(f"tuned mesh recommendation {mesh_rec} NOT applied "
                        f"(global microbatch {micro * max(n_hosts, 1)} not "
                        f"divisible by data x fsdp x expert = {dpf} — "
                        f"artifact tuned at a different batch shape)")
        else:
            updates.update(dp=sizes["data"], fsdp=sizes["fsdp"],
                           sequence=sizes["sequence"],
                           tensor=sizes["tensor"], expert=sizes["expert"],
                           pipe=sizes["pipe"])
            logger.info(f"applying tuned mesh recommendation: {mesh_rec}")
    zero = artifact.get("shard_optimizer")
    if zero is not None and not args.shard_optimizer and zero:
        updates["shard_optimizer"] = True
        logger.info("applying tuned ZeRO-1 recommendation "
                    "(--shard_optimizer true)")
    return args.model_copy(update=updates) if updates else args


def run_inline_auto_tune(args, ckpt_path: str, rank: int):
    """--auto_tune: rank 0 runs the tuner's SCREEN for this exact
    model/shape on the live device count and writes
    ``<run_dir>/tune_artifact.json``; every rank then loads the artifact
    (barrier in between, so workers never race the write). A restart
    attempt finds the artifact already present and skips the tune —
    re-measuring on every respawn would burn the restart budget on
    telemetry. Returns the loaded artifact dict or None (tune failed:
    the run proceeds on the hand-tuned defaults, loudly)."""
    import jax

    from ..obs import trace as trace_lib
    from ..parallel import dist
    from ..parallel.partition import load_partition_artifact
    from ..utils import logger

    path = os.path.join(ckpt_path, "tune_artifact.json")
    if rank == 0 and not os.path.exists(path):
        from .tune import screen_for_workload
        tracer = trace_lib.tracer_for(ckpt_path, "tune")
        try:
            summary = screen_for_workload(
                model_kwargs=dict(
                    model_family=args.model_family,
                    model_size=args.model_size, seq_len=args.seq_len,
                    vocab_size=args.vocab_size,
                    hidden_size=args.hidden_size,
                    num_layers=args.num_layers, num_heads=args.num_heads,
                    dtype=args.dtype),
                batch_size=args.batch_size, microbatch=args.microbatch,
                n_devices=jax.device_count(),
                journal_path=os.path.join(ckpt_path, "tune_trials.jsonl"),
                budget_s=args.auto_tune_budget_s,
                artifact_path=path, screen_only=True,
                seed=args.seed, tracer=tracer,
                echo=lambda s: logger.info(s))
            if not summary.get("winner"):
                logger.warn(f"auto-tune produced no measured candidate "
                            f"({summary.get('error')}); training on the "
                            f"hand-tuned defaults")
        except Exception as e:
            logger.warn(f"auto-tune failed ({type(e).__name__}: {e}); "
                        f"training on the hand-tuned defaults")
        finally:
            tracer.close()
    dist.barrier("auto_tune")
    if os.path.exists(path):
        return load_partition_artifact(path)
    return None


def build_mesh(args, *, elastic: bool):
    """Mesh from the configured axis sizes — with ELASTIC re-derivation
    (ISSUE 10): under the launcher, a restart may land on shrunk/grown
    capacity (spot preemption took hosts; the simulated
    DPT_FORCE_DEVICES_PER_PROC schedule changed the ring), and pinned
    axis sizes that no longer multiply to the surviving device count
    would fail every restart attempt forever. Re-derive instead: first
    retry with ``dp=-1`` (data parallelism absorbs the capacity change —
    its gradient psum is the only collective that tolerates any width),
    then, if a pinned non-data axis still cannot fit, fall back to
    pure-DP and warn loudly. Standalone runs (``elastic=False``) keep
    the hard error: a typo'd --dp should fail, not silently reshape."""
    from ..parallel import make_mesh
    from ..utils import logger

    try:
        return make_mesh(dp=args.dp, fsdp=args.fsdp, sequence=args.sequence,
                         tensor=args.tensor, expert=args.expert,
                         pipe=args.pipe)
    except ValueError as e:
        if not elastic:
            raise
        logger.warn(f"mesh axes do not fit surviving capacity ({e}); "
                    f"re-deriving data axis for elastic resume")
        try:
            return make_mesh(dp=-1, fsdp=args.fsdp, sequence=args.sequence,
                             tensor=args.tensor, expert=args.expert,
                             pipe=args.pipe)
        except ValueError as e2:
            logger.warn(f"non-data axes do not fit either ({e2}); "
                        f"falling back to pure data parallelism")
            return make_mesh(dp=-1)


def resume_sample_position(resume_step: int, meta, batch_size: int,
                           process_count: int):
    """(skip_batches, consumed_samples) for the train-stream fast-forward.

    The topology-invariant resume position is GLOBAL SAMPLES CONSUMED,
    not steps: the checkpoint's meta sidecar records the global batch
    (and cumulative sample count) at save time, so a resume on a
    different host/device count skips the right number of the NEW
    stream's batches (see data.skip_batches_for_samples). On an
    UNCHANGED topology the skip is ``resume_step`` by definition — one
    step ate one batch of this exact stream — so that path is taken
    literally, never re-derived from the samples gauge (a subclass whose
    ``get_batch_length`` counts something other than examples would
    otherwise desync the bit-identical same-shape resume). Pre-elastic
    checkpoints (no ``global_batch`` in meta — or no meta at all) are
    treated as same-topology, preserving the old behavior exactly."""
    from ..data import skip_batches_for_samples

    gb_now = batch_size * max(process_count, 1)
    saved_gb = (int(meta["global_batch"])
                if meta and meta.get("global_batch") else gb_now)
    # the samples gauge continues from the recorded count when present
    # (exact even for exotic get_batch_length overrides)
    consumed = (int(meta["samples"])
                if meta and meta.get("samples") is not None
                else resume_step * saved_gb)
    if saved_gb == gb_now:
        return resume_step, consumed
    return skip_batches_for_samples(resume_step * saved_gb, batch_size,
                                    process_count), consumed


def _mpmd_main(args: TrainSettings) -> dict:
    """MPMD pipeline training (ISSUE 16): THIS process is the jax-free
    host driver — it writes the shared ``mpmd_config.json``, spawns one
    supervised launcher ring PER STAGE (each with its own restart budget,
    snapshots, and beacon watchdog — stages are independently
    preemptible), and broadcasts the microbatch schedule over the
    StageLink command links. The per-stage workers
    (mpmd/stage_worker.py) own the jax math; activations and grads move
    over the file-relay StageLink transport instead of a collective."""
    from ..mpmd.driver import PipelineDriver

    if not args.scan_layers:
        raise SystemExit("--mpmd requires --scan_layers true (stages "
                         "slice the stacked layer dim)")
    if args.pp_schedule not in ("1f1b", "gpipe"):
        raise SystemExit(
            "--mpmd runs the host-driven 1f1b or gpipe schedule; "
            "interleaved virtual stages are a single-program schedule "
            "(models/schedule_1f1b.py) — drop --mpmd or switch schedules")
    if args.learning_steps <= 0:
        raise SystemExit("--mpmd needs --learning_steps > 0 (the host "
                         "driver runs a bounded schedule)")
    if args.pipe > 1:
        raise SystemExit("--pipe is the in-program GPipe mesh axis; "
                         "under --mpmd stages are separate processes — "
                         "set --mpmd_stages instead")
    ckpt_path = resolve_run_dir(args)
    os.makedirs(ckpt_path, exist_ok=True)
    with open(os.path.join(ckpt_path, "training_args.json"), "w") as f:
        f.write(args.to_json())
    if args.trace:
        # arm tracing pipeline-wide (the fleet parent's pattern): the env
        # rides the launcher's worker environment to every stage attempt,
        # so stage fwd/bwd spans carry the per-microbatch trace ids that
        # stitch the cross-process timeline
        from ..obs.trace import TRACE_ENV
        os.environ[TRACE_ENV] = "1"
    flat = json.loads(args.to_json())
    config = {
        "n_stages": args.mpmd_stages,
        "n_microbatches": args.pp_chunks,
        "schedule": args.pp_schedule,
        # create_model_from_config / load_data_from_args both swallow the
        # full flat settings dict (the single-program path passes it
        # verbatim too); the loader gets batch_size positionally
        "model": flat,
        "data": {k: v for k, v in flat.items() if k != "batch_size"},
        "batch_size": args.batch_size,
        "seed": args.seed,
        "lr": args.lr,
        "weight_decay": args.weight_decay,
        "link_capacity": args.mpmd_link_capacity,
    }
    from ..parallel.launcher import WorkersDoNotFitHost
    try:
        driver = PipelineDriver(
            ckpt_path, config,
            max_restarts=args.mpmd_max_restarts,
            hang_timeout_s=args.mpmd_hang_timeout_s,
            trace_armed=True if args.trace else None)
    except WorkersDoNotFitHost as e:  # stages are processes: ROADMAP R8
        raise SystemExit(str(e)) from None
    try:
        result = driver.run(args.learning_steps)
    finally:
        driver.stop()
    with open(driver.result_path(), "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps({
        "mode": "mpmd", "stages": args.mpmd_stages,
        "schedule": args.pp_schedule, "steps": result["steps"],
        "final_loss": (result["losses"][-1] if result["losses"]
                       else None),
        "rewinds": result["rewinds"],
        "attempts_per_stage": result["attempts_per_stage"],
        "accounted_frac": result["goodput"].get("accounted_frac"),
    }))
    return result


def main(namespace: argparse.Namespace) -> None:
    """(reference run/train.py:10-121; late imports keep ``--help`` fast,
    mirroring the reference's in-function imports at train.py:15-24)"""
    args = TrainSettings.from_argparse(namespace)

    if args.mpmd:
        # before ANY jax import: the MPMD parent is the host driver and
        # must never initialize a backend (the stage workers pay it)
        _mpmd_main(args)
        return

    import jax

    from .. import parallel
    from ..data import load_data_from_args
    from ..models import create_model_from_config, seed_all
    from ..parallel import dist
    from ..parallel.mesh import local_mesh_info
    from ..utils import logger
    from ..utils.trainer import TrainLoop

    dist.setup_dist()
    rank = dist.get_rank()

    if args.debug_nans:  # SURVEY.md §5.2: debug flag -> jax NaN checker
        jax.config.update("jax_debug_nans", True)

    ckpt_path = resolve_run_dir(args)  # created by process 0
    if rank == 0:
        os.makedirs(ckpt_path, exist_ok=True)
    dist.barrier("mkdir")

    # log+csv sinks everywhere, stdout on the writer rank (reference
    # train.py:43); metrics averaged across hosts at dump time (the
    # reference's comm-averaged dumpkvs, logger.py:358-370).
    logger.configure(dir=ckpt_path,
                     format_strs=["log", "csv"] + (["stdout"] if rank == 0
                                                   else []),
                     comm=logger.distributed_mean_comm())
    seed_all(args.seed)

    # Persistent compilation cache BEFORE anything compiles: a restarted,
    # resumed or simply repeated run then pays a cache lookup instead of
    # the full XLA compile — compile_time_s in the logs shows the
    # difference.
    from ..utils.perf import enable_persistent_compilation_cache
    cache_dir = enable_persistent_compilation_cache(
        args.compilation_cache_dir)
    if cache_dir:
        logger.info(f"persistent compilation cache: {cache_dir}")

    # Run-dir handshake with the launcher (restart supervision): stamp the
    # resolved run dir into the file the launcher named, EARLY — even an
    # attempt that dies during model build then gets its attempts.jsonl
    # record in the right place.
    run_dir_file = os.environ.get("DPT_RUN_DIR_FILE")
    if run_dir_file and rank == 0:
        try:
            with open(run_dir_file, "w") as f:
                f.write(ckpt_path if "://" in ckpt_path
                        else os.path.abspath(ckpt_path))
        except OSError:
            pass

    # Chaos harness (fault injection): a ChaosPlan from the config field or
    # the DPT_CHAOS_PLAN env override — the env rides the launcher's worker
    # environment, so it reaches --config_json rings like
    # DPT_PREFETCH_DEPTH does.
    from ..chaos import CHAOS_PLAN_ENV, ChaosInjector, ChaosPlan
    chaos = None
    chaos_src = os.environ.get(CHAOS_PLAN_ENV) or args.chaos_plan
    if chaos_src:
        chaos = ChaosInjector(ChaosPlan.parse(chaos_src), rank=rank,
                              run_dir=ckpt_path)
        logger.info(f"chaos plan armed: {chaos.plan.describe()}")

    if args.pipe > 1 and not args.scan_layers:
        raise SystemExit("--pipe > 1 requires --scan_layers true (stacked "
                         "layer weights are what shard into pipeline "
                         "stages); without it the pipe axis would only "
                         "replicate work")

    # Tuned layout (ISSUE 13): --partition_rules accepts the tuner's
    # artifact verbatim (rules + mesh + ZeRO recommendations), and
    # --auto_tune runs the tuner's screen inline — rank 0 measures, every
    # rank loads the resulting artifact. Recommendations fold into the
    # settings BEFORE the mesh is built; an explicit mesh flag always
    # wins over a recommendation.
    from ..parallel.partition import load_partition_artifact
    artifact = load_partition_artifact(args.partition_rules)
    if args.auto_tune and artifact is None:
        artifact = run_inline_auto_tune(args, ckpt_path, rank)
    args = apply_tuned_layout(args, artifact, jax.device_count(),
                              n_hosts=jax.process_count())

    workload = create_model_from_config(**args.dict())
    # Elastic mesh derivation: re-derive axis sizes only when capacity
    # can actually have CHANGED under this worker — a restart attempt
    # (> 0) or an active capacity-override schedule (which can shrink
    # attempt 0 too). Attempt 0 of an ordinary supervised run keeps the
    # hard error: there a non-fitting --dp is a typo, not a preemption.
    from ..parallel.launcher import FORCE_DEVICES_ENV, FORCE_NPROCS_ENV
    elastic = (int(os.environ.get("DPT_ATTEMPT") or -1) > 0
               or bool(os.environ.get(FORCE_NPROCS_ENV))
               or bool(os.environ.get(FORCE_DEVICES_ENV)))
    mesh = build_mesh(args, elastic=elastic)
    logger.info(local_mesh_info(mesh))
    from ..utils.perf import device_summary
    logger.info(f"devices: {json.dumps(device_summary())}")

    if rank == 0:  # args snapshot for reproducibility (train.py:82-87)
        with open(os.path.join(ckpt_path, "training_args.json"), "w") as f:
            f.write(args.to_json())
    if rank == 0 and os.environ.get("WANDB_MODE", "disabled") != "disabled":
        try:  # optional, rank-0 only (reference train.py:90-98)
            import wandb
            wandb.init(project=os.environ.get("WANDB_PROJECT", "dpt"),
                       mode=os.environ["WANDB_MODE"])
            wandb.config.update(json.loads(args.to_json()),
                                allow_val_change=True)
            # Every dumpkvs now reaches wandb (reference logger.py:373-377).
            logger.append_output_format("wandb")
        except Exception as e:
            logger.warn(f"wandb unavailable: {e}")

    eval_callbacks = []
    if args.eval_decode:
        # End-task quality during training: decode ONE held-out batch at
        # every eval interval. Every process joins the callback's jit (the
        # params are globally sharded — see TrainLoop.run_loop), so every
        # host must see the SAME batch: host_sharded=False. One cached
        # batch, no prefetch workers, capped size (decoding is many model
        # fwds per example; the training batch would be slow).
        from ..models.sampling import make_decode_callback
        decode_data = load_data_from_args(
            "valid", **{**args.dict(), "deterministic": True,
                        "batch_size": min(args.batch_size, 32),
                        "num_loader_proc": 0, "data_loader_workers": 0,
                        "host_sharded": False})
        eval_callbacks.append(make_decode_callback(
            decode_data, sample_steps=args.eval_decode_sample_steps))

    # Steady-state knobs accept a launcher-env override (DPT_PREFETCH_DEPTH
    # / DPT_DISPATCH_LAG): --config_json runs reject individual CLI flags,
    # so the env is the one channel that can A/B prefetch across a whole
    # worker ring (the launcher forwards both vars to every spawned
    # worker) without minting a new config file.
    # `or`: an empty-string env value (DPT_PREFETCH_DEPTH= python ...)
    # means unset, not int("")
    prefetch_depth = int(os.environ.get("DPT_PREFETCH_DEPTH")
                         or args.prefetch_depth)
    dispatch_lag = int(os.environ.get("DPT_DISPATCH_LAG")
                       or args.dispatch_lag)

    # Two-phase wiring: the loop RESTORES FIRST (discovery, orbax reads,
    # and — when the newest checkpoint is corrupt — the walk-back to an
    # older one all live inside restore_resume_state), then the data
    # streams are fast-forwarded to the step ACTUALLY restored. The old
    # order resolved the resume target before construction, which a
    # walk-back would silently desync from the data stream.
    from ..chaos.goodput import beacon_max_step
    from ..utils.checkpoint import load_meta
    loop = TrainLoop(
        model=workload,
        data=None,
        eval_data=None,
        eval_callbacks=eval_callbacks,
        batch_size=args.batch_size,
        microbatch=args.microbatch,
        lr=args.lr,
        ema_rate=args.ema_rate,
        log_interval=args.log_interval,
        eval_interval=args.eval_interval,
        save_interval=args.save_interval,
        resume_checkpoint=args.resume_checkpoint,
        gradient_clipping=args.gradient_clipping,
        weight_decay=args.weight_decay,
        learning_steps=args.learning_steps,
        mesh=mesh,
        checkpoint_dir=ckpt_path,
        seed=args.seed,
        profile_dir=args.profile_dir,
        warmup_steps=args.warmup_steps,
        keep_checkpoints=args.keep_checkpoints,
        sanitize=args.sanitize,
        prefetch_depth=prefetch_depth,
        dispatch_lag=dispatch_lag,
        chaos=chaos,
        # Steps an earlier attempt already reached (per the progress
        # beacons) book as recompute, not useful — goodput accounting for
        # the lost last-checkpoint..crash window.
        recompute_until_step=beacon_max_step(ckpt_path),
        # Auto-sharding engine knobs: ZeRO-1 weight-update sharding and
        # the per-run partition-rule override — from the parsed artifact
        # (tuner output or a hand-written table; parallel/partition.py).
        shard_optimizer=args.shard_optimizer,
        fused_update=args.fused_update,
        partition_rules=(artifact or {}).get("rules"),
        # Span tracing (obs/): --trace arms explicitly; the default
        # defers to the DPT_TRACE launcher env, so supervised rings
        # armed at the launcher trace every attempt.
        trace=True if args.trace else None,
        profile_steps=args.profile_steps,
        # Cost ledger (obs/ledger.py): roofline MFU-gap attribution per
        # compiled program, logged each window + perf_ledger.json.
        cost_ledger=args.cost_ledger,
    )

    # Exact-resume data order: fast-forward both streams so the continued
    # run consumes the batches the uninterrupted one would have — together
    # with the step-derived train RNG this makes a same-topology resume
    # bit-identical. The train-stream position is GLOBAL SAMPLES CONSUMED
    # (recorded in the meta sidecar), not steps: an ELASTIC resume on a
    # different host count has a different global batch, and skipping
    # "resume_step batches" of the new stream would desync the sample
    # sequence (ISSUE 10 — the loss-continuity contract of shrink/grow).
    # Eval eats one batch per eval_interval steps.
    resume_step = loop.step
    # meta travels WITH the checkpoint: read it from the directory the
    # restored model_ lives in (an explicit --resume_checkpoint may point
    # into another run's dir — the run dir could hold a stale sidecar for
    # the same step number)
    meta = (load_meta(os.path.dirname(loop.resumed_from.rstrip("/")),
                      resume_step)
            if resume_step and loop.resumed_from else None)
    train_skip, consumed = resume_sample_position(
        resume_step, meta, args.batch_size, jax.process_count())
    if train_skip != resume_step and rank == 0:
        logger.info(
            f"elastic resume: checkpoint was written at global batch "
            f"{meta.get('global_batch')} ({consumed} samples consumed); "
            f"fast-forwarding {train_skip} batches of the current "
            f"global-batch-{loop.global_batch} stream (loss-continuity, "
            f"not bit-identity, across the topology change)")
    if meta is not None and "eval_batches_consumed" in meta:
        # the checkpoint records exactly how many eval batches were drawn
        # — the fast-forward no longer assumes --eval_interval is
        # unchanged (r4 advisor: 'a warning is not a contract')
        eval_skip = int(meta["eval_batches_consumed"])
    else:
        eval_skip = resume_step // max(args.eval_interval, 1)
        if resume_step and rank == 0:
            # pre-meta checkpoint: the division assumes the flag matches
            logger.warn(
                f"checkpoint has no meta sidecar; eval-stream "
                f"fast-forward assumes --eval_interval "
                f"({args.eval_interval}) is unchanged from the original "
                f"run (train stream is exact either way)")
    if resume_step and rank == 0:
        logger.info(f"fast-forwarding data stream past {train_skip} "
                    f"consumed train batches / {eval_skip} eval batches "
                    f"(exact-order resume)")
    loop.set_data(
        load_data_from_args("train", skip_batches=train_skip,
                            **args.dict()),
        eval_data=load_data_from_args(
            "valid", skip_batches=eval_skip,
            **{**args.dict(), "deterministic": True}),
        eval_batches_consumed=eval_skip,
        # the samples gauge continues from the TRUE consumed count, not
        # step x (possibly different) new global batch
        samples_consumed=consumed if resume_step else None)
    n_m = loop.n_params / 1e6
    logger.info(f"the parameter count is {loop.n_params} ({n_m:.1f}M)")
    loop.run_loop()
    # the exit flush's metrics (the last dispatch_lag steps' losses) and
    # the goodput summary were logged after the loop's last dump
    logger.dumpkvs()


if __name__ == "__main__":
    from ..parallel.launcher import parse_and_autorun

    ns = parse_and_autorun(create_parser())
    if ns is not None:
        main(ns)
