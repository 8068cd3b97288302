"""TrainLoop: the training runtime as ONE jitted step.

Capability parity with the reference engine (``/root/reference/utils/
trainer.py``): microbatch gradient accumulation, AdamW, multi-rate EMA,
linear LR annealing, gradient clipping with grad-norm telemetry, interval-
driven log/eval/save, and filename-convention checkpoint/resume.

TPU-native redesign (SURVEY.md §3.4 hot-loop notes) — everything the
reference does eagerly folds into a single compiled step:

==============================================  ===========================
reference (eager torch, per step)               here (inside one jit)
==============================================  ===========================
python micro loop + DDP ``no_sync`` juggling    ``lax.scan`` over a
  (trainer.py:230-235, 216-220)                 [n_micro, ...] batch; XLA
                                                emits ONE gradient psum
``(p.grad**2).sum().item()`` per param — a      ``optax.global_norm`` as a
  device->host sync every step (:265-271)       device scalar, no sync
``_anneal_lr`` mutating opt groups (:257)       optax schedule traced into
                                                the step
EMA python loop per rate (:360-370)             vectorized pytree lerp
DDP bucketed all-reduce (:115-128)              sharding propagation: grads
                                                inherit the params' specs
==============================================  ===========================

The loop structure, hook names, and checkpoint layout stay recognizably the
reference's (``run_loop``/``run_step``/``forward_only``/``save``), so a user
of the reference scaffold finds the same control surface.
"""

from __future__ import annotations

import collections
import contextlib
import os
import sys
import time
from typing import Any, Callable, Dict, Iterator, Optional, Sequence, Tuple, \
    Union

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import linen as nn
from flax import struct
from jax.sharding import Mesh

from ..data.device_prefetch import DeviceBatch, prefetch_to_device
from ..models import Workload
from ..obs import ledger as ledger_lib
from ..obs import trace as trace_lib
from ..ops.fused_update import fused_adamw_ema, resolve_fused_update
from ..parallel import mesh as mesh_lib
from ..parallel import partition as partition_lib
from ..parallel.sharding import (
    batch_shardings,
    param_shardings,
    replicated,
    shard_batch,
)
from . import checkpoint as ckpt_lib
from . import logger
from .perf import AOTStep, GoodputTracker, RecompileMonitor, \
    SanitizeReport, StallBreakdown, \
    StepTimer, device_peak_flops, device_summary, mfu, peak_live_bytes, \
    tpu_kernel_census, tree_bytes, tree_bytes_per_replica, \
    transformer_train_flops_per_token

__all__ = ["TrainLoop", "TrainState", "update_ema"]


@struct.dataclass
class TrainState:
    """Everything the jitted step owns (donated and returned every step)."""

    step: jnp.ndarray            # int32 scalar
    params: Any
    opt_state: Any
    ema: Dict[str, Any]          # rate-string -> params-shaped tree


def update_ema(ema: Any, params: Any, rate: float) -> Any:
    """``trg = trg*rate + src*(1-rate)`` as a pytree lerp (reference
    ``update_ema``, trainer.py:360-370, in-place loop)."""
    return jax.tree_util.tree_map(
        lambda e, p: e * rate + p * (1.0 - rate), ema, params)


def _abstract_like(tree: Any) -> Any:
    """Live tree -> ShapeDtypeStructs carrying the live shardings (the
    restore target for checkpoint resume)."""
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding),
        tree)


class TrainLoop:
    """Reference-shaped constructor (``TrainLoop(...)`` then ``.run_loop()``
    or ``()``, trainer.py:45/175/357); ``model`` is a :class:`models.Workload`.
    """

    def __init__(
        self,
        *,
        model: Workload,
        data: Iterator[Dict[str, np.ndarray]],
        batch_size: int,
        microbatch: int = -1,
        lr: float = 1e-4,
        ema_rate: str = "0.9999",
        log_interval: int = 50,
        eval_interval: int = 1000,
        save_interval: int = 10000,
        resume_checkpoint: str = "",
        gradient_clipping: float = -1.0,
        weight_decay: float = 0.0,
        learning_steps: int = 0,
        eval_data: Optional[Iterator[Dict[str, np.ndarray]]] = None,
        eval_callbacks: Sequence[Callable[["TrainLoop"], None]] = (),
        mesh: Optional[Mesh] = None,
        checkpoint_dir: str = "",
        seed: int = 102,
        profile_dir: str = "",
        warmup_steps: int = 0,
        keep_checkpoints: int = 0,
        eval_batches_consumed: int = 0,
        sanitize: bool = False,
        prefetch_depth: int = 0,
        dispatch_lag: int = 0,
        chaos: Optional[Any] = None,
        progress_file: str = "",
        recompute_until_step: int = 0,
        shard_optimizer: bool = False,
        fused_update: Any = "auto",
        partition_rules: Optional[Sequence[Tuple[str, Any]]] = None,
        trace: Optional[bool] = None,
        profile_steps: str = "",
        cost_ledger: bool = False,
    ) -> None:
        # Time-to-signal accounting starts at construction: everything up
        # to the end of the first optimizer step (state init, restore,
        # tracing, XLA compile, dispatch) is setup the user waits through.
        self._construct_t0 = time.perf_counter()
        self.workload = model
        self.data = data
        self.eval_data = eval_data
        self.eval_callbacks = list(eval_callbacks)
        self.batch_size = batch_size
        # microbatch default = whole batch (reference trainer.py:70)
        self.microbatch = microbatch if microbatch > 0 else batch_size
        if batch_size % self.microbatch:
            raise ValueError(f"batch_size {batch_size} not divisible by "
                             f"microbatch {self.microbatch} (static shapes)")
        self.n_micro = batch_size // self.microbatch
        self.lr = lr
        self.ema_rates: Tuple[str, ...] = tuple(
            r.strip() for r in str(ema_rate).split(",") if r.strip())
        self.log_interval = log_interval
        self.eval_interval = eval_interval
        # cumulative eval batches drawn (incl. before a resume) — recorded
        # in each checkpoint's meta sidecar so resumes fast-forward the
        # eval stream exactly even if --eval_interval changed
        self.eval_batches_consumed = eval_batches_consumed
        self.save_interval = save_interval
        self.gradient_clipping = gradient_clipping
        self.weight_decay = weight_decay
        self.learning_steps = learning_steps
        self.warmup_steps = warmup_steps
        self.keep_checkpoints = keep_checkpoints
        self._saver = ckpt_lib.AsyncSaver()
        self.checkpoint_dir = checkpoint_dir or logger.get_dir() or ""
        # Run-dir handshake: the launcher cannot re-derive the run dir a
        # wrapped script resolved, so workers stamp it into the file the
        # launcher names — that is where attempts.jsonl and the progress
        # beacons live. Every rank writes (identical content, last wins):
        # the rank-0 worker may be the one the chaos plan just killed.
        run_dir_file = os.environ.get("DPT_RUN_DIR_FILE", "")
        if run_dir_file and self.checkpoint_dir:
            d = (self.checkpoint_dir if "://" in self.checkpoint_dir
                 else os.path.abspath(self.checkpoint_dir))
            try:
                with open(run_dir_file, "w") as f:
                    f.write(d)
            except OSError:
                pass  # supervision telemetry must never fail training
        # SURVEY.md §5.1 rebuild note: a first-class jax.profiler trace hook.
        # A short window a few steps in (past compilation) is captured into
        # profile_dir in TensorBoard format; 0-length dir disables.
        # --profile_steps "A:B" overrides the window (loop steps, [A, B)) —
        # the programmatic XLA-level view next to the obs/ span timeline.
        self.profile_dir = profile_dir
        self._profile_window = (3, 8)  # [start, stop) steps after loop entry
        if profile_steps:
            try:
                a, b = (int(x) for x in profile_steps.split(":"))
            except ValueError:
                raise ValueError(f"profile_steps must be 'A:B' loop-step "
                                 f"ints, got {profile_steps!r}") from None
            if not 0 <= a < b:
                raise ValueError(f"profile_steps window must satisfy "
                                 f"0 <= A < B, got {profile_steps!r}")
            self._profile_window = (a, b)
        self._profiling = False
        # tri-state: True arms, False forces OFF (an A/B's control arm
        # must stay untraced even under DPT_TRACE), None defers to the
        # env (how launcher-supervised rings arm without a CLI flag)
        self._trace = trace

        # Cost ledger (obs/ledger.py): per-compiled-program FLOPs/bytes/
        # collective extraction + the roofline MFU-gap attribution row,
        # logged each log window and snapshotted to
        # <run_dir>/perf_ledger.json. Off by default: extraction is a
        # one-time HLO walk but the padding meter touches every batch.
        self.cost_ledger = cost_ledger
        self.padding = ledger_lib.PaddingMeter() if cost_ledger else None
        # measured steady rate anchor, armed at first-step completion:
        # (steps since, seconds since, stall sums since) excludes the
        # compile-bearing first step, the same boundary
        # steady_recompile_count uses
        self._ledger_watch: Optional[trace_lib.Stopwatch] = None
        self._ledger_step0 = 0
        self._ledger_stall0: Dict[str, float] = {}
        # extraction cache: cost_analysis + the HLO walk are immutable
        # per compiled executable, and as_text() on a real model is a
        # multi-second serialization — paying it once per log window
        # would inflate the very mfu_gap_host the ledger reports.
        # Keyed by executable identity so an AOTStep shape-change
        # recompile invalidates naturally.
        self._ledger_cost_cache: Dict[str, Tuple[Any, Dict[str, Any]]] = {}

        # Steady-state throughput layer (ISSUE 5): keep the device queue
        # full. prefetch_depth > 0 wraps the data iterator so batches are
        # device_put onto the mesh (with the step's exact sharding) while
        # the previous step computes; dispatch_lag = k defers fetching a
        # step's metric scalars until k later steps have dispatched, so
        # the host never blocks on the step it just enqueued. Both default
        # OFF here (the config layer turns them on for real runs) so the
        # eager semantics tests rely on stay the default API behavior.
        self.prefetch_depth = prefetch_depth
        self.dispatch_lag = dispatch_lag
        # every step booked as one tick (next_batch + run_step, always
        # on), beside the four gauges; perf.tick_account("train") finds it
        self.stalls = StallBreakdown()
        self._newest_loss: Any = None  # the last dispatched step's, for
        # the account's dry-dispatch probe (is_ready(): no transfer)
        # (loop step idx, dispatch-return timestamp, device metrics tree)
        self._inflight: "collections.deque" = collections.deque()

        # Chaos harness + goodput accounting (ISSUE 8). ``chaos`` is a
        # ChaosInjector (or None): three hook points — top of run_step,
        # before each batch pull, right after a save is scheduled — let a
        # ChaosPlan kill/stall/corrupt this process at an exact step.
        # ``progress_file`` (set by run/train.py under the launcher) is a
        # per-step beacon: current step + in-attempt goodput snapshot,
        # atomically replaced each step — a SIGKILLed attempt's flight
        # recorder, and how the launcher measures step progress for its
        # crash-loop fail-fast. ``recompute_until_step`` marks steps an
        # earlier attempt already paid for (the last-checkpoint..crash
        # window): their wall time books as recompute, not useful.
        self.chaos = chaos
        self.progress_file = progress_file
        self.recompute_until_step = recompute_until_step

        # Auto-sharding engine (ISSUE 9): params shard by the workload's
        # declared partition-rule table (parallel/partition.py) —
        # ``partition_rules`` overrides it per run; workloads with neither
        # keep the flax logical-metadata compat path. ``shard_optimizer``
        # turns on ZeRO-1: Adam moments and EMA copies additionally
        # sharded across the data mesh axis with gather-on-use inside the
        # compiled step (per-replica weight-update memory / ~dp).
        self.shard_optimizer = shard_optimizer
        # --fused_update swaps the staged optax update (opt.update ->
        # apply_updates -> one EMA tree-map per rate) for the single-pass
        # Pallas kernel (ops/fused_update.py); losses stay bit-identical
        # and the opt_state pytree keeps optax's structure, so checkpoints
        # and ZeRO-1 shardings don't care which path wrote them. The flag
        # is tri-state ("auto" = fused on TPU only); resolve it once here.
        self.fused_update = resolve_fused_update(fused_update)
        self.partition_rules = (tuple(partition_rules)
                                if partition_rules else None)
        self.goodput = GoodputTracker(t0=self._construct_t0)
        spawn_t = os.environ.get("DPT_SPAWN_T", "")
        if spawn_t:
            # The launcher stamps each worker's spawn wall-clock: the
            # interpreter+jax+distributed-init span before this
            # constructor ran is real attempt time, booked as startup.
            startup = max(0.0, time.time() - float(spawn_t))
            self.goodput.base_s = startup
            self.goodput.add("startup_s", startup)
        self._recompiles_at_first_step: Optional[int] = None

        # Runtime sanitizer (the dynamic half of analysis/ graftlint):
        # count every XLA compile into the recompile_count gauge, and run
        # the train/eval step dispatch under a jax transfer guard so any
        # IMPLICIT host<->device transfer (a stray numpy array reaching a
        # compiled call, a tracer silently fetched) raises instead of
        # quietly serializing the step. Explicit device_put/device_get —
        # everything the loop does on purpose — stays legal.
        self.sanitize = sanitize
        self._recompiles = RecompileMonitor(capture_sites=sanitize)
        # Machine-readable evidence sidecar (ISSUE 19 runtime bridge):
        # every guard trip / steady recompile lands in
        # <checkpoint_dir>/sanitize_report.json for the static pass to
        # cross-reference (analysis --runtime-evidence, GL013).
        self.sanitize_report = SanitizeReport(
            default_dir=self.checkpoint_dir if sanitize else "")
        self._sanitizer_reported = False
        if sanitize:
            self._recompiles.install()
        try:
            self._finish_init(mesh, batch_size, seed, resume_checkpoint)
        except BaseException:
            # construction can die mid-build (param init / AOT compile is
            # where an HBM OOM fires) and callers that retry with a smaller
            # batch never get a handle to stop_sanitizer() —
            # detach the process-global hooks here so a failed attempt
            # doesn't leak the 'jax' logging handler or leave
            # jax_log_compiles stuck on.
            self._recompiles.uninstall()
            raise

    def _finish_init(self, mesh, batch_size: int, seed: int,
                     resume_checkpoint: str) -> None:
        self.mesh = mesh if mesh is not None else mesh_lib.make_mesh()
        # Under the launcher (DPT_ATTEMPT set) every TrainLoop emits the
        # per-step progress beacon by default: supervision — crash-loop
        # detection, step-progress records, post-mortem goodput — works
        # for ANY wrapped script, not just run/train.py.
        if (not self.progress_file and self.checkpoint_dir
                and "://" not in self.checkpoint_dir
                and os.environ.get("DPT_ATTEMPT") is not None):
            from ..chaos.goodput import beacon_path
            self.progress_file = beacon_path(self.checkpoint_dir,
                                             jax.process_index())
        # Span tracing (obs/): armed by the trace flag or DPT_TRACE (the
        # env rides the launcher's worker environment to every attempt of
        # every ring, like DPT_PREFETCH_DEPTH). Not armed -> the tracer
        # that follows the profiler (trace_lib.FOLLOW): one is_enabled()
        # per hook, no span objects, no writes, until a jax.profiler
        # session is on — then the spans below sit in the xplane beside
        # the device's ops and in the in-memory ring. After-the-fact
        # spans are booked from the SAME measured seconds handed to the
        # goodput tracker, so the trace and the ledger can never disagree.
        self.tracer = trace_lib.tracer_for(
            self.checkpoint_dir, jax.process_index(), armed=self._trace)
        self.stalls.tracer = self.tracer   # a stalled step is an instant
        # global batch = per-host batch x hosts (reference trainer.py:89)
        self.global_batch = batch_size * jax.process_count()
        dpf = (self.mesh.shape["data"] * self.mesh.shape["fsdp"]
               * self.mesh.shape["expert"])
        global_micro = self.microbatch * jax.process_count()
        if global_micro % dpf:
            raise ValueError(
                f"global microbatch {global_micro} (= microbatch "
                f"{self.microbatch} x {jax.process_count()} hosts) must be "
                f"divisible by data x fsdp x expert mesh axes = {dpf}")
        self._base_rng = jax.random.PRNGKey(seed)

        # AOT compile metrics (perf.AOTStep): total seconds spent in
        # lower()/compile() and construction->first-optimizer-step wall time.
        # None until the first step so a zero can't masquerade as "free".
        self.compile_time_s: Optional[float] = None
        self.time_to_first_step_s: Optional[float] = None

        self._build_state(resume_checkpoint)
        self._build_step_fns()

        # Device prefetch wraps the data stream AFTER the step fns exist
        # (it places batches with _prepare's sharding — the layout the AOT
        # step was compiled for). Wrapping only reorders WHEN transfers
        # happen, never WHICH indices the underlying iterator draws, so
        # skip_batches exact-resume is untouched.
        if self.prefetch_depth > 0 and self.data is not None:
            self.data = self._wrap_prefetch(self.data)

        # Cumulative sample count via the get_batch_length hook; seeded from
        # the resumed step so the gauge is continuous across restarts.
        self._samples = self.step * self.global_batch

        tokens_per_step = self.global_batch * self.workload.seq_len
        self._timer = StepTimer(tokens_per_step)
        self._flops_per_token = transformer_train_flops_per_token(
            self.n_params, self.workload.num_layers,
            self.workload.hidden_size, self.workload.seq_len)
        # Goodput step-slice anchors: wall time between consecutive
        # run_step completions is one step's slice; compile/data-stall
        # deltas within a slice are already booked to their own
        # categories, so recompute attribution subtracts them.
        # Construction minus the restore share is setup: state init and
        # trace-time work a restart pays even warm. Booking it keeps the
        # useful residual to actual step-loop time.
        self.goodput.add("setup_s",
                         (time.perf_counter() - self._construct_t0)
                         - self.goodput.get("restore_s"))
        self._g_prev_t = time.perf_counter()
        self._g_prev_stall = self._stall_sum()
        self._g_prev_compile = self.goodput.get("compile_s")

    def _wrap_prefetch(self, data: Iterator) -> Iterator[DeviceBatch]:
        return prefetch_to_device(
            data, put=self._prepare, depth=self.prefetch_depth,
            length_of=self.get_batch_length, stats=self.stalls,
            tracer=self.tracer)

    def set_data(self, data: Iterator, *, eval_data: Optional[Iterator] = None,
                 eval_batches_consumed: Optional[int] = None,
                 samples_consumed: Optional[int] = None) -> None:
        """Late data wiring: iterators created AFTER construction, so their
        resume fast-forward can use the step this loop ACTUALLY restored —
        which may be older than the newest checkpoint when the restore
        walked back past a corrupt one (run/train.py builds the loop
        first, reads ``loop.step``, then skips exactly that many batches).
        Applies the same prefetch wrapping the constructor would.

        ``samples_consumed`` re-seeds the cumulative ``samples`` gauge:
        on an ELASTIC resume (global batch changed with the topology) the
        constructor's ``step * global_batch`` estimate uses the NEW
        global batch and would mis-state history — the checkpoint's meta
        sidecar records the true count."""
        self.data = (self._wrap_prefetch(data)
                     if self.prefetch_depth > 0 and data is not None
                     else data)
        if eval_data is not None:
            self.eval_data = eval_data
        if eval_batches_consumed is not None:
            self.eval_batches_consumed = eval_batches_consumed
        if samples_consumed is not None:
            self._samples = int(samples_consumed)

    def _stall_sum(self) -> float:
        s = self.stalls.sums()
        return s["data_wait_s"] + s["h2d_wait_s"]

    # ------------------------------------------------------------ state setup

    def _make_optimizer(self) -> optax.GradientTransformation:
        """AdamW with the reference's linear anneal ``lr*(1-step/total)``
        (trainer.py:257-263) and decoupled weight decay (trainer.py:99)."""
        # Constant-LR runs keep the plain float (not a schedule callable):
        # a callable changes the opt_state pytree structure
        # (ScaleByScheduleState vs empty ScaleState), which would break
        # optimizer-state restore of checkpoints saved before a schedule
        # was in play.
        sched = (self._lr_at if self.learning_steps > 0
                 or self.warmup_steps > 0 else self.lr)
        return optax.adamw(sched, b1=0.9, b2=0.999, eps=1e-8,
                           weight_decay=self.weight_decay)

    def _lr_at(self, step):
        """Reference linear anneal ``lr*(1-step/total)`` (trainer.py:257-263),
        optionally preceded by a linear warmup from 0 over ``warmup_steps``
        (exceeds the reference; default 0 keeps its exact schedule). One
        method is BOTH the optax schedule and the logged lr gauge."""
        lr = jnp.asarray(self.lr, jnp.float32)
        if self.learning_steps > 0:
            lr = lr * jnp.maximum(0.0, 1.0 - step / self.learning_steps)
        if self.warmup_steps > 0:
            lr = lr * jnp.minimum(1.0, (step + 1) / self.warmup_steps)
        return lr

    def _plan_state(self) -> Tuple[Any, Any]:
        """Shapes and layouts of the train state, no device work: builds
        the optimizer and the param / weight-update / optimizer-state
        shardings, and returns the abstract (params, opt_state). Kept
        apart from the allocation so that the step can be lowered from
        shapes alone — for a chip that is described and not attached."""
        wl = self.workload
        init_rng = jax.random.fold_in(self._base_rng, 0)
        abstract = jax.eval_shape(wl.init_params, init_rng)
        abstract_unboxed = nn.meta.unbox(abstract)
        # Param shardings from the declared rule table (the partition
        # engine); --partition_rules overrides, and workloads without a
        # table (custom families) fall back to the flax logical-metadata
        # compat path. The tables are equivalence-tested against that
        # path, so flipping engines never changes a layout.
        rules = (self.partition_rules
                 if self.partition_rules is not None
                 else partition_lib.rules_for_workload(wl))
        if rules is not None:
            specs = partition_lib.match_partition_rules(rules,
                                                        abstract_unboxed)
            pshard = partition_lib.resolve_shardings(self.mesh, specs,
                                                     abstract_unboxed)
        else:
            pshard = param_shardings(self.mesh, abstract)
        self._pshard = pshard
        self.opt = self._make_optimizer()

        # ZeRO-1 (--shard_optimizer): weight-update state — Adam moments
        # AND the EMA copies — lives sharded across the data axis on top
        # of whatever fsdp/tensor sharding the params already have. The
        # step only touches that state elementwise, so GSPMD gathers on
        # use (all-gather of the per-step updates, not the stored state)
        # and per-replica weight-update bytes drop by ~dp. With dp == 1
        # (or the flag off) the ZeRO layout degenerates to the param
        # layout and nothing changes.
        zshard = (partition_lib.zero1_shardings(self.mesh, pshard,
                                                abstract_unboxed)
                  if self.shard_optimizer else pshard)
        self._zshard = zshard

        # Optimizer-state shardings: params-shaped leaves (mu/nu) take the
        # weight-update layout — the param shardings (FSDP/ZeRO-3 contract,
        # SURVEY.md §7 hard parts), plus the data axis under ZeRO-1 — and
        # scalars (count) replicate. jit does NOT propagate input shardings
        # to outputs, so this must be explicit.
        rep = replicated(self.mesh)
        abstract_opt = jax.eval_shape(self.opt.init, abstract_unboxed)
        oshard = optax.tree_map_params(
            self.opt, lambda _, s: s, abstract_opt, zshard,
            transform_non_params=lambda _: rep)
        self._oshard = oshard
        return abstract_unboxed, abstract_opt

    def _build_state(self, resume_checkpoint: str) -> None:
        wl = self.workload
        init_rng = jax.random.fold_in(self._base_rng, 0)
        self._plan_state()
        pshard, zshard, oshard = self._pshard, self._zshard, self._oshard

        with self.mesh:
            params = jax.jit(
                lambda r: nn.meta.unbox(wl.init_params(r)),
                out_shardings=pshard)(init_rng)
            opt_state = jax.jit(self.opt.init, out_shardings=oshard)(params)
            # Fresh EMA = copy of params (reference deepcopies,
            # trainer.py:110-113). Distinct buffers, NOT aliases: the jitted
            # step donates the whole state, and donating one buffer through
            # several tree slots is an error. Under ZeRO-1 the copies land
            # directly in the data-sharded layout (one compiled copy fn,
            # reused per rate).
            if self.shard_optimizer:
                copy_to_z = jax.jit(
                    lambda p: jax.tree_util.tree_map(jnp.copy, p),
                    out_shardings=zshard)
                ema = {r: copy_to_z(params) for r in self.ema_rates}
            else:
                ema = {r: jax.tree_util.tree_map(jnp.copy, params)
                       for r in self.ema_rates}

        self.n_params = wl.param_count(params)
        self.step = 0

        # Sanitize mode guards the restore too (the cold-path half of the
        # checkpoint net): Orbax restores into the requested shardings via
        # explicit placement, so an implicit transfer here means resume
        # code regressed into a host round-trip.
        t_restore0 = time.perf_counter()
        t_restore0_wall = time.time()
        with self._sanitize_guard():
            restored = ckpt_lib.restore_resume_state(
                self.checkpoint_dir,
                abstract_params=_abstract_like(params),
                ema_rates=self.ema_rates,
                abstract_opt=_abstract_like(opt_state),
                # EMA restore target: under ZeRO-1 the EMA layout differs
                # from the params layout (data-sharded), and a degraded
                # (missing/corrupt) companion must land in it too — the
                # AOT step's pinned shardings reject a params-layout EMA
                # at the second step.
                abstract_ema=(_abstract_like(next(iter(ema.values())))
                              if ema else None),
                explicit_model_path=resume_checkpoint,
            )
        self.resumed_from = ""
        if restored is not None:
            self.step = restored["step"]
            self.resumed_from = restored.get("path", "")
            # One-time defensive copy: the jitted train step DONATES the
            # whole TrainState, and donating orbax-restored buffers directly
            # is unsafe when the executable came from the persistent
            # compilation cache (jaxlib 0.4.37 CPU: reproducible heap
            # corruption — "malloc(): smallbin double linked list
            # corrupted" — in the resume-with-warm-cache path). Copying
            # hands the step exclusively-owned buffers; sharding is
            # preserved (restore targeted the live shardings). Peak memory
            # stays at the pre-copy ~2x state: the fresh-init tree is
            # dropped BEFORE each copy and the restored source is popped so
            # it frees as soon as its copy materializes.
            own = lambda t: jax.tree_util.tree_map(jnp.copy, t)
            del params
            params = own(restored.pop("params"))
            if restored["ema"]:
                del ema
                ema = own(restored.pop("ema"))
            if restored["opt_state"] is not None:
                del opt_state
                opt_state = own(restored.pop("opt_state"))
            logger.info(f"resumed from step {self.step} "
                        f"({self.resumed_from or self.checkpoint_dir})")
        # Restore cost (discovery + orbax reads + walk-back + ownership
        # copies) is goodput overhead — the number a warm resume should
        # shrink, and the per-attempt "resume overhead" attempts.jsonl
        # records. The trace span books the SAME seconds.
        restore_dt = time.perf_counter() - t_restore0
        self.goodput.add("restore_s", restore_dt)
        if self.tracer.enabled:
            self.tracer.complete("restore", "ckpt", t_restore0_wall,
                                 restore_dt,
                                 args={"step": self.step,
                                       "resumed": restored is not None})
        self._resume_step = self.step

        self.state = TrainState(
            step=jax.device_put(jnp.asarray(self.step, jnp.int32),
                                replicated(self.mesh)),
            params=params, opt_state=opt_state, ema=ema)

    # ------------------------------------------------------------- step fns

    def _build_step_fns(self) -> None:
        wl = self.workload
        clip = self.gradient_clipping
        opt = self.opt
        rates = self.ema_rates
        # rate strings -> floats OUTSIDE the traced step (graftlint GL002:
        # float() under trace is indistinguishable from a device sync)
        rate_of = {r: float(r) for r in rates}
        pshard = self._pshard
        base_rng = self._base_rng
        lr_at = self._lr_at
        # the layouts the fused kernel shard_maps over: the weight-update
        # state's, and the params' own (they differ under ZeRO-1)
        zspecs = jax.tree_util.tree_map(lambda s: s.spec, self._zshard)
        pspecs = jax.tree_util.tree_map(lambda s: s.spec, pshard)

        def micro_scan(params: Any, batch: Dict[str, jnp.ndarray],
                       rng: jax.Array, with_grad: bool):
            """lax.scan over the [n_micro, ...] leading axis, accumulating
            loss metrics (and grads) — the reference's inner microbatch loop
            + DDP no_sync trick (trainer.py:230-235) with the single psum
            emitted by XLA at the end.

            Deliberate deviation from the reference: microbatch grads are
            AVERAGED (scale 1/n_micro), where the reference sums unscaled
            ``loss.backward()`` calls — so the effective gradient here is
            independent of the accumulation factor and the baseline lr must
            NOT be rescaled when comparing loss curves with microbatching
            (codified by test_grad_accumulation_equivalence)."""
            def loss_fn(p, mb, r):
                d = wl.compute_losses(p, mb, r)
                return d["loss"], d

            def one(mb, i):
                with jax.named_scope("microbatch"):
                    r = jax.random.fold_in(rng, i)
                    if with_grad:
                        (_, d), g = jax.value_and_grad(
                            loss_fn, has_aux=True)(params, mb, r)
                        return g, d
                    _, d = loss_fn(params, mb, r)
                    return (), d

            def body(carry, xs):
                mb, i = xs
                g, d = one(mb, i)
                g_acc, m_acc = carry
                if with_grad:
                    g_acc = jax.tree_util.tree_map(jnp.add, g_acc, g)
                m_acc = jax.tree_util.tree_map(jnp.add, m_acc, d)
                return (g_acc, m_acc), None

            n_micro = jax.tree_util.tree_leaves(batch)[0].shape[0]
            # First microbatch runs outside the scan: its outputs give the
            # carry its structure (no abstract tracing tricks needed).
            g0, m0 = one(jax.tree_util.tree_map(lambda x: x[0], batch),
                         jnp.asarray(0, jnp.int32))
            if n_micro > 1:
                rest = jax.tree_util.tree_map(lambda x: x[1:], batch)
                (g, m), _ = jax.lax.scan(
                    body, (g0, m0), (rest, jnp.arange(1, n_micro)))
            else:
                g, m = g0, m0
            scale = 1.0 / n_micro
            m = jax.tree_util.tree_map(lambda x: x * scale, m)
            if with_grad:
                g = jax.tree_util.tree_map(lambda x: x * scale, g)
            return g, m

        def train_step(state: TrainState, batch: Dict[str, jnp.ndarray]):
            rng = jax.random.fold_in(base_rng, state.step)
            grads, metrics = micro_scan(state.params, batch, rng,
                                        with_grad=True)
            gnorm = optax.global_norm(grads)
            # named scopes are op metadata only (xprof's op profile can
            # split the step by them); the compiled program is the same
            with jax.named_scope("optimizer"):
                if clip > 0:  # reference grad_clip, trainer.py:246-255
                    scale = jnp.minimum(1.0, clip / (gnorm + 1e-6))
                    grads = jax.tree_util.tree_map(lambda g: g * scale,
                                                   grads)
                if self.fused_update:
                    # single-pass kernel (ops/fused_update.py): same
                    # opt_state structure, bit-identical losses — the optax
                    # chain below is the reference twin
                    lr_fn = (self._lr_at
                             if self.learning_steps > 0
                             or self.warmup_steps > 0
                             else lambda _c: jnp.asarray(self.lr,
                                                         jnp.float32))
                    with jax.named_scope("fused_adamw_ema"):
                        params, opt_state, ema = fused_adamw_ema(
                            state.params, grads, state.opt_state,
                            state.ema, lr_fn=lr_fn,
                            weight_decay=self.weight_decay, mesh=self.mesh,
                            specs=zspecs, param_specs=pspecs)
                    params = jax.lax.with_sharding_constraint(params,
                                                              pshard)
                else:
                    updates, opt_state = opt.update(grads, state.opt_state,
                                                    state.params)
                    params = optax.apply_updates(state.params, updates)
                    params = jax.lax.with_sharding_constraint(params,
                                                              pshard)
                    ema = {r: update_ema(state.ema[r], params, rate_of[r])
                           for r in rates}
            metrics = dict(metrics)
            metrics["grad_norm"] = gnorm          # device scalar — no sync
            metrics["lr"] = lr_at(state.step)
            new_state = TrainState(step=state.step + 1, params=params,
                                   opt_state=opt_state, ema=ema)
            return new_state, metrics

        def eval_step(params: Any, batch: Dict[str, jnp.ndarray],
                      rng: jax.Array):
            _, metrics = micro_scan(params, batch, rng, with_grad=False)
            return metrics

        # Explicit AOT lower()/compile() instead of dispatch-time jit: the
        # first run_step/forward_only triggers a TIMED compile, surfaced as
        # the compile_time_s / time_to_first_step_s metrics (perf.AOTStep).
        # With the persistent compilation cache enabled (run/train.py)
        # a warm restart's compile_time_s collapses to the cache
        # lookup, and the split makes that visible instead of folding it
        # into the first step's wall time.
        #
        # out_shardings pins the output state to the INPUT state's layout
        # (params/mu/nu/EMA shard alike — the FSDP contract from
        # _build_state). Without it GSPMD may emit outputs with drifted
        # specs (e.g. a small bias's mu replicated instead of fsdp-sharded),
        # and the AOT executable — unlike dispatch jit, which would silently
        # recompile a second variant for step 2's new input shardings —
        # rejects the mismatch. Pinning gives step-stable shardings AND
        # kills that hidden double compile. Metrics are scalars: replicated.
        rep = replicated(self.mesh)
        state_shard = TrainState(step=rep, params=pshard,
                                 opt_state=self._oshard,
                                 ema={r: self._zshard for r in rates})
        self._train_step = AOTStep(
            jax.jit(train_step, donate_argnums=(0,),
                    out_shardings=(state_shard, rep)), "train_step",
            on_compile=self._note_compile)
        self._eval_step = AOTStep(jax.jit(eval_step, out_shardings=rep),
                                  "eval_step",
                                  on_compile=self._note_compile)
        # Sequence-parallel meshes shard the batch's L axis too, so each chip
        # only ever holds its L/n activation slice (ring attention does the
        # cross-shard interaction).
        self._batch_sharding = batch_shardings(
            self.mesh, microbatched=True,
            seq_sharded=self.mesh.shape["sequence"] > 1)

    def _note_compile(self, name: str, seconds: float) -> None:
        """AOTStep callback: accumulate and log compile time (summed across
        step functions and recompiles within a log window)."""
        self.compile_time_s = (self.compile_time_s or 0.0) + seconds
        self.goodput.add("compile_s", seconds)
        if self.tracer.enabled:
            # the span re-books the exact seconds the ledger got; the
            # wall anchor back-dates it so the timeline shows WHEN
            self.tracer.complete("compile", "compile",
                                 time.time() - seconds, seconds,
                                 args={"fn": name})
        logger.logkv_sum("compile_time_s", round(seconds, 3))
        logger.info(f"compiled {name} in {seconds:.2f}s")

    @property
    def recompile_count(self) -> int:
        """XLA compiles observed since construction (sanitize mode only;
        0 when the monitor is off). Steady state should freeze this."""
        return self._recompiles.count

    def stop_sanitizer(self) -> int:
        """Detach the sanitizer's process-global hooks (the 'jax' logging
        handler and the jax_log_compiles flag) and return the final
        recompile count. Idempotent; a no-op when sanitize was off. Call
        when the loop is done in a process that keeps running (the
        tests) — nothing re-arms it. Also the moment the evidence sidecar
        is finalized: steady-state recompiles become violations, and the
        report (possibly empty — that's the 'ran clean' evidence) lands
        beside the checkpoints."""
        self._recompiles.uninstall()
        if self.sanitize and not self._sanitizer_reported:
            self._sanitizer_reported = True
            if self._recompiles_at_first_step is not None:
                self.sanitize_report.note_recompiles(
                    self._recompiles, self._recompiles_at_first_step)
            self.sanitize_report.write(self.checkpoint_dir)
            # where an untraced run shows what its steps were
            self.stalls.close()
            print(self.stalls.report_line(), file=sys.stderr, flush=True)
        return self._recompiles.count

    def _sanitize_guard(self):
        return (self.sanitize_report.guard() if self.sanitize
                else contextlib.nullcontext())

    # ------------------------------------------------------------- data prep

    def _prepare(self, batch: Dict[str, np.ndarray]) -> Dict[str, jax.Array]:
        """Host batch [B, ...] -> global sharded [n_micro, B_micro_global, ...]."""
        if self.padding is not None and "pad_mask" in batch:
            # active-token accounting off the mask the data path already
            # carries — the padding_waste_frac side of the cost ledger.
            # np.sum on the host batch; thread-safe (the prefetch wrapper
            # calls _prepare from its own thread).
            pm = batch["pad_mask"]
            self.padding.add(int(pm.sum()), int(pm.size))
        mb = self.microbatch
        reshaped = {k: v.reshape((self.n_micro, mb) + v.shape[1:])
                    for k, v in batch.items()}
        return shard_batch(self.mesh, reshaped,
                           sharding=self._batch_sharding, batch_axis=1)

    # ------------------------------------------------------------- the loop

    def get_batch_length(self, batch: Dict[str, np.ndarray]) -> int:
        """Number of examples in a host batch — the reference's user hook
        (trainer.py:33-43) for custom batch structures; the default reads
        the first leaf's leading dim. Feeds the cumulative ``samples``
        gauge, so subclasses with exotic batches (nested, ragged-marker,
        dict-of-dicts) override ONE method instead of the loop."""
        return int(len(jax.tree_util.tree_leaves(batch)[0]))

    def next_batch(self) -> Union[Dict[str, np.ndarray], DeviceBatch]:
        """Pull the next training batch, attributing host-iterator wait to
        the ``data_wait_s`` stall gauge. With device prefetch on, the
        wrapper attributes its own waits internally (this call returns a
        buffered :class:`DeviceBatch` without double counting). A tick of
        the loop's account begins here and ends with ``run_step``."""
        self._begin_tick()
        with self.tracer.span("train.next_batch", "train"):
            if self.chaos is not None:
                # An injected iterator stall is exactly the failure the
                # data_wait gauge measures — attribute it there so the
                # stall lands in the goodput breakdown as input-pipeline
                # time.
                stalled = self.chaos.on_data(self)
                if stalled:
                    self.stalls.add("data_wait_s", stalled)
            if self.prefetch_depth > 0:
                return next(self.data)
            t0 = time.perf_counter()
            with self.tracer.span("data.host_wait", "data"):
                batch = next(self.data)
            self.stalls.add("data_wait_s", time.perf_counter() - t0)
            return batch

    def _begin_tick(self) -> None:
        # (goes on with the open tick when next_batch began it already)
        self.stalls.begin(inflight=len(self._inflight),
                          recompiles=self._recompiles.count,
                          traced=self.tracer.enabled)

    def run_step(self, batch: Union[Dict[str, np.ndarray], DeviceBatch]
                 ) -> Dict[str, Any]:
        """One optimizer step (reference run_step, trainer.py:198-201).

        Accepts either a host batch (prepared + transferred here, the
        eager path) or a :class:`DeviceBatch` from the prefetch wrapper
        (already on the mesh — dispatch is all that's left). With
        ``dispatch_lag > 0`` the returned metrics are the CURRENT step's
        device scalars, but logging them is deferred: step N-k's metrics
        are fetched/logged while step N runs, so the host never blocks on
        the step it just enqueued (flush_metrics drains the tail)."""
        tr = self.tracer
        self._begin_tick()
        try:
            with tr.span("train.run_step", "train",
                         args={"step": self.step + 1} if tr.enabled
                         else None):
                return self._run_step(batch)
        finally:
            self.stalls.end()

    def _run_step(self, batch: Union[Dict[str, np.ndarray], DeviceBatch]
                  ) -> Dict[str, Any]:
        tr = self.tracer
        if self.chaos is not None:
            # Kill/corrupt faults scheduled for the step about to run —
            # self.step is the count of COMPLETED steps, so a fault at
            # step k fires after k steps finished, before step k+1.
            self.chaos.on_step(self)
        first = self.time_to_first_step_s is None
        if isinstance(batch, DeviceBatch):
            prepared = batch.arrays
            n_items = batch.n_items
        else:
            t0 = time.perf_counter()
            with tr.span("data.h2d", "data"):
                prepared = self._prepare(batch)
            self.stalls.add("h2d_wait_s", time.perf_counter() - t0)
            n_items = self.get_batch_length(batch)
        self.stalls.dispatched(0, self._newest_loss)
        t0 = time.perf_counter()
        try:
            with tr.span("train.dispatch", "train"), self.mesh, \
                    self._sanitize_guard():
                self.state, metrics = self._train_step(self.state, prepared)
        except Exception as e:
            # --debug_nans: dispatch jit turns a NaN into FloatingPointError
            # (after an op-by-op re-run that names the op), but an
            # AOT-compiled call lets jax's internal error type through —
            # not a FloatingPointError at all. The documented contract is
            # one, so raise one, with the step. (Matched by name: the
            # class lives in jax._src.)
            if type(e).__name__ != "InternalFloatingPointError":
                raise
            raise FloatingPointError(
                f"non-finite value in train step {self.step + 1} "
                f"(--debug_nans): {e}") from e
        dispatched = time.perf_counter()
        self.stalls.add("dispatch_s", dispatched - t0)
        self._newest_loss = metrics["loss"]
        if first:
            # Block once so "time to first step" means a COMPLETED step
            # (async dispatch would otherwise stop the clock at enqueue).
            jax.block_until_ready(metrics["loss"])
            self.time_to_first_step_s = (time.perf_counter()
                                         - self._construct_t0)
            logger.logkv("time_to_first_step_s",
                         round(self.time_to_first_step_s, 3))
            # Steady-state recompile baseline: compiles after this point
            # are silent retraces — the gauge that must stay frozen on a
            # warm-cache resume.
            self._recompiles_at_first_step = self._recompiles.count
            # Ledger rate anchor: tokens/s and per-step stall means
            # measured from here on cover only steady steps (the first
            # step's dispatch_s carries the whole AOT compile, which
            # would swamp a mean taken from step 0).
            self._ledger_watch = trace_lib.Stopwatch()
            self._ledger_step0 = self.step + 1
            self._ledger_stall0 = self.stalls.sums()
            self.stalls.mark_steady()   # the account's, at the same boundary
        self.step += 1
        self._samples += n_items * jax.process_count()
        self._timer.tick()
        # Goodput step-slice attribution: the wall span since the previous
        # run_step completed is this step's slice. For steps an earlier
        # attempt already reached (<= recompute_until_step), the slice —
        # minus whatever compile/data-stall time inside it was already
        # booked to its own category — is recompute: real work, but work
        # the run has paid for once before.
        now = time.perf_counter()
        if self.step <= self.recompute_until_step:
            booked = ((self.goodput.get("compile_s") - self._g_prev_compile)
                      + (self._stall_sum() - self._g_prev_stall))
            self.goodput.add(
                "recompute_s", max(0.0, (now - self._g_prev_t) - booked))
        if tr.enabled:
            # the step span IS the goodput step-slice (previous run_step
            # completion -> this one): same boundary, same seconds, so
            # summing trace step spans reproduces the ledger's step time
            tr.complete(
                "step", "train", trace_lib.wall_at(self._g_prev_t),
                now - self._g_prev_t,
                args={"step": self.step,
                      "recompute": self.step <= self.recompute_until_step})
        self._g_prev_t = now
        self._g_prev_stall = self._stall_sum()
        self._g_prev_compile = self.goodput.get("compile_s")
        if self.progress_file:
            t0 = time.perf_counter()
            with tr.span("train.log", "train"):
                self._write_beacon()
            self.stalls.phase("log", time.perf_counter() - t0)
        if self.dispatch_lag > 0:
            self._inflight.append((self.step, dispatched, metrics))
            while len(self._inflight) > self.dispatch_lag:
                self._emit_lagged()
        else:
            logger.logkvs_mean(metrics)
        t0 = time.perf_counter()
        with tr.span("train.log", "train"):
            self.log_step()
        self.stalls.phase("log", time.perf_counter() - t0)
        return metrics

    def _emit_lagged(self) -> None:
        """Fetch/log the OLDEST in-flight step's metrics. Blocking here —
        k steps after dispatch — is where ``device_step_s`` is observed:
        the span from that step's dispatch returning to its outputs
        materializing (device execution + queue wait, a trailing upper
        bound). The values logged are exactly the step's device scalars,
        just late."""
        step_idx, dispatched, metrics = self._inflight.popleft()
        tr = self.tracer
        t0 = time.perf_counter()
        with tr.span("train.metrics_wait", "train",
                     args={"step": step_idx} if tr.enabled else None):
            jax.block_until_ready(metrics["loss"])
        now = time.perf_counter()
        self.stalls.phase("metrics_wait", now - t0)
        self.stalls.add("device_step_s", now - dispatched)
        logger.logkvs_mean(metrics)

    def flush_metrics(self) -> None:
        """Drain every in-flight lagged metric (logged values become
        complete up to the current step). Called before eval, before each
        checkpoint save, and at loop exit, so anything that reads the
        logs at those boundaries sees exact, fully-caught-up values."""
        while self._inflight:
            self._emit_lagged()

    def forward_only(self, batch: Dict[str, np.ndarray]) -> Dict[str, Any]:
        """Eval pass without grads (reference forward_only trainer.py:223-228);
        metrics are logged under an ``eval_`` prefix."""
        # fold_in data must be uint32; offset eval streams away from the
        # train stream (which folds in the raw step). Replicate the key
        # onto the mesh explicitly: a single-device key gets resharded
        # implicitly at dispatch, which the sanitize guard (rightly) trips
        # on when the eval step actually consumes it (diffuseq).
        rng = jax.device_put(
            jax.random.fold_in(self._base_rng, 0x7FFF0000 + self.step),
            replicated(self.mesh))
        t_eval0_wall = time.time()
        watch = trace_lib.Stopwatch() if self.tracer.enabled else None
        prepared = self._prepare(batch)
        with self.mesh, self._sanitize_guard():
            metrics = self._eval_step(self.state.params, prepared, rng)
        if watch is not None:
            # dispatch span only (blocking on the eval output here would
            # add the per-eval sync async dispatch exists to avoid)
            self.tracer.complete("eval", "eval", t_eval0_wall,
                                 watch.lap_s(), args={"step": self.step})
        logger.logkvs_mean({f"eval_{k}": v for k, v in metrics.items()})
        return metrics

    def log_step(self) -> None:
        """step + cumulative samples (reference log_step trainer.py:273-275);
        samples accumulate through the get_batch_length hook (equals
        ``step * global_batch`` unless a subclass overrides it)."""
        logger.logkv("step", self.step)
        logger.logkv("samples", self._samples)
        if self.sanitize:
            logger.logkv("recompile_count", self.recompile_count)

    # ------------------------------------------------------ goodput/beacon

    @property
    def steady_recompile_count(self) -> int:
        """XLA compiles observed AFTER the first completed step (sanitize
        mode): the warm-path gauge — a resumed attempt under a warm
        persistent cache must hold this at 0 even though its construction
        legitimately compiled (restore copies are new programs on a first
        resume)."""
        if self._recompiles_at_first_step is None:
            return 0
        return self._recompiles.count - self._recompiles_at_first_step

    def goodput_summary(self) -> Dict[str, float]:
        """Point-in-time goodput decomposition for this attempt: wall
        (spawn→now when the launcher stamped DPT_SPAWN_T, else
        construction→now) split into useful / startup / restore / compile
        / save / data-stall / recompute."""
        return self.goodput.summary(extra={"data_stall_s": self._stall_sum()})

    def _write_beacon(self) -> None:
        """Atomically replace the per-step progress beacon: step, wall
        clock, and the goodput snapshot. A killed attempt's last beacon is
        its flight recorder (the launcher snapshots it into
        attempts.jsonl); the step field doubles as the launcher's
        crash-loop progress probe and the next attempt's
        recompute-boundary."""
        payload = {
            "step": self.step,
            # the step THIS attempt restored from: progress must be judged
            # against it, not the run's high-water mark — an attempt that
            # walked back past a corrupt checkpoint makes real progress
            # below the old maximum
            "start_step": self._resume_step,
            "t": time.time(),
            "attempt": int(os.environ.get("DPT_ATTEMPT") or 0),
            "rank": jax.process_index(),
            "recompile_count": self.recompile_count,
            "steady_recompile_count": self.steady_recompile_count,
            "goodput": {k: round(v, 6)
                        for k, v in self.goodput_summary().items()},
        }
        tmp = self.progress_file + ".tmp"
        try:
            import json as _json
            with open(tmp, "w") as f:
                f.write(_json.dumps(payload))
            os.replace(tmp, self.progress_file)
        except OSError as e:  # beacon is telemetry: never fail a step
            logger.warn(f"progress beacon write failed: {e}")

    def program_evidence(self) -> Dict[str, Any]:
        """What actually ran: the device, the attention and update arms
        this loop resolved, and — from the compiled train step's own text
        — how many Mosaic kernels of each arm the program holds. An arm
        that was selected but is absent from the program shows here as 0
        (chip_smoke.py fails on it). Needs a compiled step; the
        ``as_text()`` walk costs seconds on a real model, so it is only
        taken in sanitize mode, once, at loop exit."""
        from ..ops import flash_attention as fa, fused_update as fu
        from ..ops.attention import resolve_attention_impl

        out = {
            "device": device_summary(),
            "attention_impl": resolve_attention_impl(
                getattr(self.workload.model, "attention_impl", "auto"),
                self.workload.seq_len, self.mesh),
            "fused_update": bool(self.fused_update),
        }
        compiled = self._train_step.compiled
        if compiled is not None:
            out["tpu_custom_calls"] = tpu_kernel_census(
                compiled, (fa.FWD_KERNEL_NAME, fa.BWD_KERNEL_NAME,
                           fu.KERNEL_NAME))
        return out

    def _write_goodput_record(self) -> None:
        """Rank 0, at loop exit: the attempt's final goodput record
        (``goodput_attempt{A:03d}.json`` next to the checkpoints). The
        clean-exit counterpart of the beacon — aggregate_run prefers it."""
        if not self.checkpoint_dir or jax.process_index() != 0:
            return
        attempt = int(os.environ.get("DPT_ATTEMPT") or 0)
        payload = {
            "attempt": attempt,
            "steps": [self._resume_step, self.step],
            "recompile_count": self.recompile_count,
            "steady_recompile_count": self.steady_recompile_count,
            "compile_time_s": self.compile_time_s or 0.0,
            **{k: round(v, 6) for k, v in self.goodput_summary().items()},
        }
        if self.sanitize:
            payload["program"] = self.program_evidence()
        try:
            import json as _json
            path = os.path.join(self.checkpoint_dir,
                                f"goodput_attempt{attempt:03d}.json")
            with open(path, "w") as f:
                f.write(_json.dumps(payload))
        except OSError as e:
            logger.warn(f"goodput record write failed: {e}")

    def footprint(self) -> Dict[str, int]:
        """HBM/params footprint gauges (ISSUE 9): logical state bytes plus
        the per-replica (one device's addressable shard) bytes — the
        number ZeRO-1 exists to shrink — and the backend's peak live
        allocation (0 where the backend doesn't report memory stats, e.g.
        CPU). Logged every log window."""
        s = self.state
        return {
            "params_bytes": tree_bytes(s.params),
            "params_bytes_per_replica": tree_bytes_per_replica(s.params),
            "opt_state_bytes": tree_bytes(s.opt_state),
            "opt_state_bytes_per_replica":
                tree_bytes_per_replica(s.opt_state),
            "ema_bytes_per_replica": tree_bytes_per_replica(s.ema),
            "peak_live_bytes": peak_live_bytes(),
        }

    # ------------------------------------------------------- cost ledger

    def ledger_rows(self) -> Dict[str, Dict[str, Any]]:
        """Per-compiled-program cost-ledger rows (obs/ledger.py): XLA's
        own FLOPs/bytes accounting + the HLO collective tally off the
        AOT executables this loop already holds, folded with the
        analytic ``flops_per_token``, the measured steady tokens/s, and
        the stall gauges into the roofline MFU-gap attribution. The
        train row reuses the EXACT stall/goodput seconds the ledger
        elsewhere reports (``data_stall_s_total`` is the same expression
        ``goodput_summary`` folds), so the two can never disagree."""
        rows: Dict[str, Dict[str, Any]] = {}
        tokens_per_step = self.global_batch * self.workload.seq_len
        steps_per_s = 0.0
        n_steady = 0
        if self._ledger_watch is not None:
            n_steady = self.step - self._ledger_step0
            dt = self._ledger_watch.peek_s()
            if n_steady > 0 and dt > 0:
                steps_per_s = n_steady / dt
        # steady-window per-step stall means (sums since the first-step
        # anchor / steady steps): the cumulative means would fold the
        # first step's compile-bearing dispatch into every attribution
        sums = self.stalls.sums()
        steady = {g: (max(0.0, s - self._ledger_stall0.get(g, 0.0))
                      / n_steady if n_steady > 0 else 0.0)
                  for g, s in sums.items()}
        host_stall = (steady["data_wait_s"] + steady["h2d_wait_s"]
                      + steady["dispatch_s"])
        device_kind = getattr(jax.devices()[0], "device_kind", "cpu")
        for name, aot in (("train_step", self._train_step),
                          ("eval_step", self._eval_step)):
            if aot.compiled is None:
                continue
            cached = self._ledger_cost_cache.get(name)
            if cached is None or cached[0] is not aot.compiled:
                cached = (aot.compiled,
                          ledger_lib.extract_cost(aot.compiled))
                self._ledger_cost_cache[name] = cached
            row: Dict[str, Any] = {"program": name, **cached[1]}
            if name == "train_step":
                row.update({
                    "tokens_per_step": tokens_per_step,
                    "flops_per_token": self._flops_per_token,
                    "analytic_flops_per_step":
                        self._flops_per_token * tokens_per_step,
                    "steps_per_s": steps_per_s,
                    "tokens_per_s": steps_per_s * tokens_per_step,
                    "device_step_s": steady["device_step_s"],
                    "host_stall_s_per_step": host_stall,
                    # goodput-identity fields: the SAME cumulative sums
                    # the goodput summary folds as data_stall_s
                    "data_stall_s_total": self._stall_sum(),
                })
                row.update(ledger_lib.roofline_attribution(
                    tokens_per_s=row["tokens_per_s"],
                    flops_per_token=self._flops_per_token,
                    peak_flops=device_peak_flops(),
                    n_devices=jax.device_count(),
                    steps_per_s=steps_per_s,
                    collective_bytes_per_step=row.get(
                        "collective_bytes_per_step", 0.0),
                    bytes_accessed=row.get("bytes_accessed", 0.0),
                    host_stall_s_per_step=host_stall,
                    device_kind=device_kind,
                    padding_waste_frac=(self.padding.frac
                                        if self.padding is not None
                                        else 0.0)))
            rows[name] = row
        return rows

    def _write_ledger_snapshot(self,
                               rows: Dict[str, Dict[str, Any]]) -> None:
        if not rows or not self.checkpoint_dir \
                or "://" in self.checkpoint_dir:
            return
        ledger_lib.write_ledger(
            self.checkpoint_dir, rows, t=time.time(),
            extra={"step": self.step,
                   "n_devices": jax.device_count(),
                   "device_kind": getattr(jax.devices()[0],
                                          "device_kind", "cpu")})

    def _log_throughput(self) -> None:
        sps, tps = self._timer.lap()
        if tps > 0:
            logger.logkv("steps_per_sec", round(sps, 4))
            logger.logkv("tokens_per_sec", round(tps, 1))
            logger.logkv("tokens_per_sec_per_chip",
                         round(tps / jax.device_count(), 1))
            logger.logkv("mfu", round(mfu(tps, self._flops_per_token), 4))
        # Stall breakdown: mean seconds/step over the window for each of
        # data_wait/h2d_wait/dispatch/device_step — "is the input pipeline
        # the bottleneck" as a number in every sink.
        for gauge, mean_s in self.stalls.lap().items():
            logger.logkv(gauge, round(mean_s, 6))
        # Cumulative goodput ratio (useful-step share of the attempt's
        # wall so far) rides the same cadence: a run bleeding time to
        # restarts/stalls shows it here, window by window.
        logger.logkv("goodput", round(self.goodput_summary()["goodput"], 4))
        # Memory footprint: params/opt-state bytes (per-replica is the
        # ZeRO-1 acceptance gauge) + backend peak live bytes.
        for gauge, b in self.footprint().items():
            logger.logkv(gauge, b)
        # Cost ledger (--cost_ledger): the train step's roofline MFU-gap
        # decomposition rides the same cadence, and the run-dir
        # perf_ledger.json snapshot refreshes (atomic replace) so
        # status/export read a live attribution, not only a post-mortem.
        if self.cost_ledger:
            rows = self.ledger_rows()
            tr = rows.get("train_step")
            if tr:
                for gauge in ledger_lib.GAP_TERMS:
                    logger.logkv(gauge, round(tr[gauge], 4))
                logger.logkv("collective_bytes_per_step",
                             tr["collective_bytes_per_step"])
                logger.logkv("padding_waste_frac",
                             round(tr["padding_waste_frac"], 4))
            self._write_ledger_snapshot(rows)

    def _maybe_profile(self, loop_step: int) -> None:
        """Start/stop the jax.profiler trace window (steps counted from loop
        entry so resumed runs still capture a post-compilation window)."""
        start, stop = self._profile_window
        if loop_step == start and not self._profiling:
            jax.profiler.start_trace(self.profile_dir)
            self._profiling = True
            logger.info(f"profiler: tracing steps {start}..{stop} "
                        f"-> {self.profile_dir}")
        elif loop_step == stop and self._profiling:
            jax.block_until_ready(self.state.params)
            self._stop_profile()

    def _stop_profile(self) -> None:
        """Close the profiler window and leave the program's own spans of
        it (the ring of obs/trace.py: on the xplane's clock, nested, with
        their arguments) beside the trace as ``host_spans.jsonl``."""
        jax.profiler.stop_trace()
        self._profiling = False
        try:
            trace_lib.dump(os.path.join(self.profile_dir,
                                        "host_spans.jsonl"))
        except OSError as e:  # telemetry: never fail the run
            logger.warn(f"host_spans.jsonl write failed: {e}")

    def run_loop(self) -> None:
        """Interval-driven outer loop (reference run_loop trainer.py:175-196):
        log every ``log_interval``, eval every ``eval_interval``, save every
        ``save_interval``, final save on exit. An interval <= 0 disables
        that periodic action (the reference's modulo would die on 0); the
        final save still runs with periodic saves disabled, so every run
        leaves a restorable checkpoint."""
        loop_step = 0
        try:
            while self.learning_steps <= 0 or self.step < self.learning_steps:
                if self.profile_dir:
                    self._maybe_profile(loop_step)
                batch = self.next_batch()
                self.run_step(batch)
                loop_step += 1
                if self.log_interval > 0 and self.step % self.log_interval == 0:
                    self._log_throughput()
                    logger.dumpkvs()
                if (self.eval_data is not None and self.eval_interval > 0
                        and self.step % self.eval_interval == 0):
                    # Lagged metrics are flushed at eval boundaries so the
                    # eval-step dump lines up with fully-logged train steps.
                    self.flush_metrics()
                    self.forward_only(next(self.eval_data))
                    self.eval_batches_consumed += 1
                    # Reference runs callbacks on rank 0 only
                    # (trainer.py:189-191) because torch callbacks are
                    # host-local. Here they may jit over globally-sharded
                    # params (e.g. the decode callback), and in
                    # multi-controller JAX every process must join such a
                    # computation — so ALL processes run the callbacks and
                    # output stays rank-gated in the logger sinks.
                    # Sanitize mode extends the transfer guard over the
                    # callbacks: with async dispatch on, an implicit
                    # transfer inside a callback is exactly the kind of
                    # accidental per-eval sync the guard exists to catch.
                    with self._sanitize_guard():
                        for cb in self.eval_callbacks:
                            cb(self)
                if (self.save_interval > 0
                        and self.step % self.save_interval == 0):
                    self.save(wait=False)  # write overlaps training
        finally:
            if self._profiling:  # run ended (or raised) inside the window:
                self._stop_profile()  # flush the trace either way
            try:
                # final flush: the last dispatch_lag steps' metrics are
                # still in flight — without this they would never reach
                # the sinks
                self.flush_metrics()
            finally:
                # exception path too — including a flush that re-raises
                # the poisoned in-flight step it blocks on: drain the
                # in-flight save before unwinding — a process exiting
                # mid-commit can hang the other hosts in orbax's
                # finalization barrier
                self.wait_for_saves()
        if self.save_interval <= 0 or self.step % self.save_interval != 0:
            self.save(wait=False)
        self.wait_for_saves()  # exit barrier: the last write must be durable
        self._prune()  # final retention pass over the finalized set
        # The attempt's goodput decomposition, durable next to the
        # checkpoints (and in the logs): the clean-exit record
        # aggregate_run folds with the launcher's attempts.jsonl.
        summary = self.goodput_summary()
        logger.logkvs({f"goodput_{k}" if k != "goodput" else k:
                       round(v, 4) for k, v in summary.items()})
        self._write_goodput_record()
        if self.cost_ledger:
            # final ledger snapshot: the attribution the run ends on
            self._write_ledger_snapshot(self.ledger_rows())
        self.tracer.close()
        if self.sanitize:
            # clean exit finalizes the evidence sidecar (trips already
            # auto-wrote on the way down in the exception path)
            self.stop_sanitizer()

    __call__ = run_loop  # reference trainer.py:357

    # ------------------------------------------------------------ checkpoint

    def save(self, wait: bool = True) -> None:
        """model_/ema_{rate}_/opt_{step:06d} under the run dir (reference
        save(), trainer.py:277-302). ``wait=False`` (what the step loop
        passes) schedules the write ASYNC so it overlaps the next
        ``save_interval`` of training; the barrier then runs before the
        next save, before retention pruning, and at loop exit
        (checkpoint.AsyncSaver). Orbax fetches to host synchronously inside
        the call, so the jitted step's buffer donation stays safe. The
        default ``wait=True`` keeps direct calls durable-on-return."""
        if not self.checkpoint_dir:
            logger.warn("no checkpoint_dir configured; skipping save")
            return
        # Checkpoint boundaries are metric-exact points: drain the lagged
        # metric ring so the logs at a save reflect every step saved.
        self.flush_metrics()
        # Sanitize mode keeps the transfer guard up through the save
        # scheduling, except for the one transfer a save IS: Orbax fetches
        # with ``copy_to_host_async``, which the guard counts as implicit
        # and refuses on a real device ("Disallowed device-to-host
        # transfer" — on the CPU backend there is no such transfer, so no
        # CPU test could see it). Host->device and device->device stay
        # disallowed, so an accidental transfer sneaking into the save
        # path still trips. The checkpoint library's own one-off slicing
        # programs are not step retraces (RecompileMonitor.not_counting).
        t_save0 = time.perf_counter()
        t_save0_wall = time.time()
        with self._sanitize_guard(), \
                jax.transfer_guard_device_to_host("allow"), \
                self._recompiles.not_counting():
            self._saver.save(
                self.checkpoint_dir, self.step, self.state.params,
                ema={r: self.state.ema[r] for r in self.ema_rates},
                opt_state=self.state.opt_state, wait=wait)
        save_dt = time.perf_counter() - t_save0
        self.goodput.add("save_s", save_dt)
        if self.tracer.enabled:
            self.tracer.complete("save", "ckpt", t_save0_wall, save_dt,
                                 args={"step": self.step,
                                       "async": not wait})
        if self.chaos is not None:
            # crash_in_save faults fire HERE: the async array write is in
            # flight (or, with wait=True, just finalized), so a SIGKILL
            # lands between write and finalize — the torn-checkpoint case
            # the resume path must survive.
            self.chaos.on_save(self)
        ckpt_lib.save_meta(self.checkpoint_dir, self.step, {
            "eval_batches_consumed": self.eval_batches_consumed,
            "eval_interval": self.eval_interval,
            # Elastic-resume topology facts (ISSUE 10): the GLOBAL batch
            # and cumulative sample count at save time. A resume on a
            # DIFFERENT topology (more/fewer hosts) must fast-forward the
            # data stream by global samples consumed — step count alone is
            # meaningless across a global-batch change. mesh shape rides
            # along for debugging/attribution.
            "global_batch": self.global_batch,
            "samples": self._samples,
            "mesh": {a: int(s) for a, s in self.mesh.shape.items()},
        })
        mode = ("saved checkpoint" if wait
                else "scheduled async checkpoint save")
        logger.info(f"{mode} at step {self.step} -> {self.checkpoint_dir}")
        # Retention ranks only FINALIZED checkpoints (unfinalized orbax tmp
        # dirs are excluded by prune_checkpoints), so pruning here never
        # needs to barrier on the save just scheduled: with wait=False it
        # simply lags by the one in-flight save (bounded at keep+1 dirs on
        # disk; run_loop runs a final pass at exit).
        self._prune()

    def _prune(self) -> None:
        if self.keep_checkpoints <= 0:
            return
        pruned = ckpt_lib.prune_checkpoints(self.checkpoint_dir,
                                            self.keep_checkpoints)
        if pruned:
            logger.info(f"pruned checkpoints at steps {pruned} "
                        f"(keep_checkpoints={self.keep_checkpoints})")

    def wait_for_saves(self) -> None:
        """Barrier on the in-flight async checkpoint saves, if any."""
        t0 = time.perf_counter()
        self._saver.wait()
        self.goodput.add("save_s", time.perf_counter() - t0)
