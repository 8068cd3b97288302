"""Performance accounting: step timing, tokens/sec, and MFU.

The reference's only profiling is the logger's wall-time context manager
(``/root/reference/basic_utils/logger.py:296-320``) plus a grad-norm metric
that forces a device->host sync every step (``utils/trainer.py:265-271``).
Here the north-star metric (BASELINE.md: tokens/sec/chip + MFU) gets
first-class gauges, and nothing in the hot path blocks on the device.
"""

from __future__ import annotations

import collections
import contextlib
import gc
import json
import logging
import math
import os
import time
import traceback
from typing import Any, Callable, Deque, Dict, List, Optional, Tuple

import jax

__all__ = ["device_peak_flops", "transformer_train_flops_per_token",
           "transformer_decode_flops_per_token",
           "StepTimer", "mfu", "enable_persistent_compilation_cache",
           "AOTStep", "RecompileMonitor",
           "device_summary", "tpu_kernel_census",
           "SanitizeReport", "SANITIZE_REPORT_NAME",
           "StallBreakdown", "tick_account", "EventStats", "GoodputTracker",
           "tree_bytes", "tree_bytes_per_replica", "peak_live_bytes"]

# Peak dense bf16 FLOP/s per chip (public spec sheets), matched IN ORDER
# against jax's device_kind strings — real hardware reports e.g.
# "TPU v5 lite" (v5e) and "TPU v5p", so specific patterns come first.
# CPU entry keeps the gauge meaningful in tests.
_PEAK_FLOPS = (
    ("v6 lite", 918e12), ("v6e", 918e12),
    ("v5 lite", 197e12), ("v5e", 197e12),
    ("v5p", 459e12), ("v5", 459e12),
    ("v4", 275e12), ("v3", 123e12), ("v2", 45e12),
    ("cpu", 1e11),
)


def device_peak_flops(device: Optional[jax.Device] = None) -> float:
    d = device or jax.devices()[0]
    kind = getattr(d, "device_kind", "cpu").lower()
    for key, flops in _PEAK_FLOPS:
        if key in kind:
            return flops
    # a device that is not in the table is an error, not a default: an
    # MFU against another chip's peak is a wrong number that looks right
    raise ValueError(f"no peak FLOP/s known for device kind {kind!r}: "
                     f"add it to utils/perf.py::_PEAK_FLOPS with its source")


def transformer_train_flops_per_token(n_params: int, n_layers: int,
                                      hidden: int, seq_len: int) -> float:
    """fwd+bwd FLOPs per trained token: the 6N weight-matmul term plus the
    12*l*h*s attention term (score + value matmuls, forward 4lhs, x3 with
    backward) — the standard accounting (e.g. PaLM appendix B)."""
    return 6.0 * n_params + 12.0 * n_layers * hidden * seq_len


def transformer_decode_flops_per_token(n_params: int) -> float:
    """Forward-only FLOPs per DECODED token: the 2N weight-matmul term
    (each param participates in one multiply-add). The per-token
    attention share during cached decode is position-dependent and small
    next to the weight streaming that actually bounds decode — the 2N
    figure is the standard serving roofline numerator."""
    return 2.0 * n_params


def mfu(tokens_per_sec: float, flops_per_token: float,
        n_devices: Optional[int] = None) -> float:
    n = n_devices if n_devices is not None else jax.device_count()
    return tokens_per_sec * flops_per_token / (device_peak_flops() * n)


def tree_bytes(tree: Any) -> int:
    """Logical (global, unsharded) bytes of a pytree of arrays/abstract
    values — the model-size side of the HBM footprint gauges."""
    import numpy as np

    return sum(int(np.prod(l.shape)) * np.dtype(l.dtype).itemsize
               for l in jax.tree_util.tree_leaves(tree)
               if hasattr(l, "shape") and hasattr(l, "dtype"))


def tree_bytes_per_replica(tree: Any) -> int:
    """Bytes of ONE device's shard of each leaf — what a replica actually
    holds. For ZeRO-1-sharded optimizer/EMA state this is the number that
    drops by ~dp vs :func:`tree_bytes`; unsharded leaves count in full."""
    import numpy as np

    total = 0
    for l in jax.tree_util.tree_leaves(tree):
        if not (hasattr(l, "shape") and hasattr(l, "dtype")):
            continue
        sharding = getattr(l, "sharding", None)
        shape = (sharding.shard_shape(l.shape) if sharding is not None
                 else l.shape)
        total += int(np.prod(shape)) * np.dtype(l.dtype).itemsize
    return total


def peak_live_bytes() -> int:
    """Peak live device allocation summed over local devices, from the
    backend's memory stats (``peak_bytes_in_use``); 0 where the backend
    reports none (CPU) — the gauge is then "unavailable", not "empty"."""
    total = 0
    for d in jax.local_devices():
        try:
            stats = d.memory_stats()
        except Exception:
            return 0
        if not stats:
            return 0
        total += int(stats.get("peak_bytes_in_use", 0))
    return total


# The one place a compile cache lives when nobody says otherwise: a fixed,
# git-ignored directory in the checkout. A directory that moves with the
# run (a run dir, a temp name, a pid, the clock) holds nothing the next run
# can find.
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".compile_cache")


def enable_persistent_compilation_cache(flag: str = "auto") -> str:
    """Turn on JAX's on-disk compilation cache and return the directory
    (\"\" = disabled). The ONE owner of where the cache lives — train,
    serve, bench and the launcher's workers all resolve it here:

    * ``"off"`` — disabled in this process;
    * ``"auto"`` — ``JAX_COMPILATION_CACHE_DIR`` if it is set (a cache
      placed from outside is used and no other is set), else
      :data:`DEFAULT_COMPILE_CACHE_DIR`, the same path for every run,
      server start and worker of this checkout.

    There is no third value: a directory of one's own is what the
    variable is for. The environment is never written: spawned workers
    inherit the variable if the caller set it, and resolve the same fixed
    path if not. The min-compile-time/entry-size gates are zeroed so the
    cache works for small CPU graphs too (tests, dev rings).

    JAX initializes its cache object at most once per process and then
    ignores config-dir changes, so both re-pointing at a new dir and
    ``"off"`` go through ``compilation_cache.reset_cache()``.
    """
    from jax.experimental.compilation_cache import compilation_cache as cc

    if flag == "off":
        cc.reset_cache()
        jax.config.update("jax_compilation_cache_dir", None)
        return ""
    if flag != "auto":
        raise ValueError(
            f"compilation cache is 'auto' or 'off', got {flag!r}: to place "
            f"it elsewhere set JAX_COMPILATION_CACHE_DIR")
    cache_dir = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
                 or DEFAULT_COMPILE_CACHE_DIR)
    os.makedirs(cache_dir, exist_ok=True)
    cc.reset_cache()
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


def device_summary() -> Dict[str, Any]:
    """The device a result ran on, as JAX reports it — every record that
    carries a device metric names this beside it."""
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def tpu_kernel_census(compiled: Any, names: Tuple[str, ...]) -> Dict[str, int]:
    """How many Mosaic kernels (``tpu_custom_call``) of each stable kernel
    name the compiled program holds — proof that a Pallas arm is IN the
    program, not merely selected. Interpret-mode kernels (CPU) lower to
    plain HLO and count 0."""
    counts = {n: 0 for n in names}
    for line in compiled.as_text().splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            for n in names:
                if n in line:
                    counts[n] += 1
    return counts


class AOTStep:
    """Lazily AOT-compiled wrapper around a jitted step function.

    First call (or any call whose arg shapes/dtypes changed) runs an
    explicit, timed ``lower()/compile()`` and reports the duration to
    ``on_compile(name, seconds)``; subsequent calls dispatch straight to
    the compiled executable. Dispatch-time compilation hides the (often
    dominant) compile cost inside the first call, where no one can measure
    it; lowering ahead of time puts a number on it — ``compile_time_s`` —
    and a persistent-cache hit shows up as that number collapsing. Shape
    changes fall back to a fresh compile rather than erroring, so callers
    keep jit's flexibility while gaining the timing split.

    ``pin_signature=True`` skips the per-call signature walk once compiled:
    for a large pytree argument (a params tree) the tree_map costs real
    host time on a hot sub-millisecond path (serving decode dispatches one
    step per generated token). Only for callers whose arg shapes are
    invariant by construction — a drifted shape then surfaces as the AOT
    executable's own mismatch error instead of a silent recompile."""

    def __init__(self, jitted: Any, name: str = "step",
                 on_compile: Optional[Callable[[str, float], None]] = None,
                 pin_signature: bool = False):
        self._jitted = jitted
        self.name = name
        self._on_compile = on_compile
        self._compiled: Any = None
        self._sig: Any = None
        self._pin = pin_signature
        self.compile_time_s = 0.0

    @property
    def compiled(self) -> Any:
        """The live compiled executable (``jax.stages.Compiled``), or
        None before the first call builds it — the handle the cost
        ledger (obs/ledger.py) extracts ``cost_analysis()``/
        ``memory_analysis()``/HLO text from."""
        return self._compiled

    @staticmethod
    def _signature(args: Any) -> Any:
        return jax.tree_util.tree_map(
            lambda a: (getattr(a, "shape", None), getattr(a, "dtype", None)),
            args)

    def __call__(self, *args: Any) -> Any:
        if self._pin and self._compiled is not None:
            return self._compiled(*args)
        sig = self._signature(args)
        if self._compiled is None or sig != self._sig:
            t0 = time.perf_counter()
            self._compiled = self._jitted.lower(*args).compile()
            dt = time.perf_counter() - t0
            self._sig = sig
            self.compile_time_s += dt
            if self._on_compile is not None:
                self._on_compile(self.name, dt)
        return self._compiled(*args)


class RecompileMonitor(logging.Handler):
    """Counts XLA compilations as they happen — the ``recompile_count``
    gauge behind sanitizer mode (``--sanitize``) and the bench leg rows.

    The static pass (analysis/, rule GL005) can only point at *patterns*
    that tend to recompile; this monitor observes the ground truth. It
    turns on ``jax_log_compiles`` and attaches itself as a logging
    handler on the ``jax`` logger: every backend compile emits exactly
    one ``"Compiling <name> ..."`` record (jax 0.9.0: dispatch AND the AOT
    lower()/compile() path; the record is written before the persistent
    cache is consulted, so a cache *hit* counts like a compile and the
    gauge does not depend on how warm the cache is).
    A steady-state training loop should stop counting after its step
    functions are built — growth after that is a silent retrace burning
    the accelerator.

    Use as a context manager or install()/uninstall(). ``count`` is the
    total since install; ``last`` keeps the most recent compile's name
    line for diagnostics. Compiles inside a :meth:`not_counting` block are
    left out: the gauge is for retraces of the program's
    own step functions, and a library the program calls between steps may
    build small programs of its own (orbax slices each sharded array with
    a jitted ``slice`` the first time it saves it — 22 of them in a
    3-step run on two devices once read as "steady recompiles")."""

    _MARKER = "Compiling "
    _MAX_SITES = 16

    def __init__(self, capture_sites: bool = False) -> None:
        super().__init__(level=logging.NOTSET)
        self.count = 0
        self._paused = 0
        self.last: str = ""
        self.sites: List[Dict[str, Any]] = []
        self._capture_sites = capture_sites
        self._prev_flag: Optional[bool] = None

    @contextlib.contextmanager
    def not_counting(self):
        """Leave compiles inside the block out of ``count`` (class
        docstring)."""
        self._paused += 1
        try:
            yield
        finally:
            self._paused -= 1

    def emit(self, record: logging.LogRecord) -> None:
        try:
            msg = record.getMessage()
        except Exception:  # pragma: no cover - malformed record
            return
        if msg.startswith(self._MARKER):
            if self._paused:
                return
            self.count += 1
            self.last = msg.split("\n", 1)[0][:200]
            if self._capture_sites and len(self.sites) < self._MAX_SITES:
                # the compile log fires synchronously under the user's
                # dispatch — the deepest non-library frame on the stack
                # right now IS the host-side call that triggered it
                site = _user_site(traceback.extract_stack())
                if site is not None:
                    site["detail"] = self.last
                    site["ordinal"] = self.count
                    self.sites.append(site)

    def install(self) -> "RecompileMonitor":
        self._prev_flag = bool(jax.config.jax_log_compiles)
        jax.config.update("jax_log_compiles", True)
        logging.getLogger("jax").addHandler(self)
        return self

    def uninstall(self) -> None:
        logging.getLogger("jax").removeHandler(self)
        if self._prev_flag is not None:
            jax.config.update("jax_log_compiles", self._prev_flag)
            self._prev_flag = None

    __enter__ = install

    def __exit__(self, *exc: Any) -> None:
        self.uninstall()


SANITIZE_REPORT_NAME = "sanitize_report.json"

_THIS_FILE = os.path.abspath(__file__)


def _user_site(frames: "traceback.StackSummary"
               ) -> Optional[Dict[str, Any]]:
    """Deepest frame that belongs to USER code — not jax/site-packages,
    not the stdlib, not this module. That frame is where the evidence
    points when the static pass is asked 'did you clear this site?'."""
    for fr in reversed(list(frames)):
        fn = fr.filename or ""
        if (not fn or fn.startswith("<")
                or "site-packages" in fn or "dist-packages" in fn
                or "importlib" in fn
                or os.path.abspath(fn) == _THIS_FILE
                or fn.startswith(_STDLIB_DIR)):
            continue
        return {"path": os.path.abspath(fn), "line": int(fr.lineno or 1),
                "func": fr.name or "?", "snippet": (fr.line or "")[:200]}
    return None


_STDLIB_DIR = os.path.dirname(os.path.abspath(contextlib.__file__))


class SanitizeReport:
    """Machine-readable evidence from the runtime sanitizer — the bridge
    between ``--sanitize`` and the static pass (analysis/, GL013).

    Violations accumulate as dicts ``{kind, path, line, func, detail,
    snippet}`` where ``kind`` is ``transfer_guard`` (an implicit
    host<->device transfer tripped ``jax.transfer_guard("disallow")``)
    or ``steady_recompile`` (XLA compiles kept happening after steady
    state). ``write(dir)`` drops a ``sanitize_report.json`` sidecar
    atomically and never raises — evidence collection must not take the
    run down with it. When ``default_dir`` is set, every ``record``
    re-writes the sidecar so the evidence survives the crash that the
    violation itself usually causes.

    ``analysis --runtime-evidence RUN_DIR`` consumes the sidecar: a
    violation at a site the static pass cleared is a GL013 coverage-gap
    finding — the linter and the sanitizer audit each other instead of
    silently disagreeing."""

    VERSION = 1

    def __init__(self, default_dir: str = "") -> None:
        self.violations: List[Dict[str, Any]] = []
        self.default_dir = default_dir

    # ------------------------------------------------------------- capture

    def record(self, kind: str, detail: str,
               site: Optional[Dict[str, Any]] = None) -> None:
        if site is None:  # {} means "explicitly no location"
            site = _user_site(traceback.extract_stack()) or {}
        self.violations.append({
            "kind": kind,
            "path": site.get("path", ""),
            "line": site.get("line", 0),
            "func": site.get("func", ""),
            "snippet": site.get("snippet", ""),
            "detail": detail[:500],
        })
        if self.default_dir:
            self.write(self.default_dir)

    @staticmethod
    def _is_trip(exc: BaseException) -> bool:
        return "isallow" in str(exc)  # [Dd]isallowed transfer guard trip

    @staticmethod
    def _site_from(exc: BaseException) -> Optional[Dict[str, Any]]:
        return _user_site(traceback.extract_tb(exc.__traceback__))

    @contextlib.contextmanager
    def guard(self):
        """``jax.transfer_guard("disallow")`` that records the trip —
        site taken from the deepest user frame of the raising traceback
        — before re-raising. The violation is never swallowed: sanitize
        mode still fails loudly, it just leaves evidence behind."""
        with jax.transfer_guard("disallow"):
            try:
                yield
            except Exception as e:
                if self._is_trip(e):
                    self.record("transfer_guard", detail=str(e)[:500],
                                site=self._site_from(e))
                raise

    @contextlib.contextmanager
    def watch(self):
        """Record-only variant for code that arms the transfer guard
        itself (DecodeServer's engine): captures a trip's evidence as it
        propagates, without arming a second guard."""
        try:
            yield
        except Exception as e:
            if self._is_trip(e):
                self.record("transfer_guard", detail=str(e)[:500],
                            site=self._site_from(e))
            raise

    def note_recompiles(self, monitor: RecompileMonitor,
                        steady_after: int) -> None:
        """Fold a monitor's captured compile sites into violations: every
        compile OBSERVED after the first ``steady_after`` is a steady-
        state recompile (the warmup budget is the caller's to define —
        compiles-at-first-step for the trainer, compiles-at-first-token
        for the decode server)."""
        for site in monitor.sites:
            if site.get("ordinal", 0) <= steady_after:
                continue
            self.record(
                "steady_recompile",
                detail=f"XLA compile after steady state "
                       f"({site.get('detail', '')})",
                site=site)
        if not monitor.sites and monitor.count > steady_after:
            # monitor ran without site capture: still leave evidence,
            # just without a source location to cross-reference
            self.record(
                "steady_recompile",
                detail=f"{monitor.count - steady_after} XLA compile(s) "
                       f"after steady state ({monitor.last})",
                site={})

    # ------------------------------------------------------------- sidecar

    def write(self, out_dir: str) -> str:
        """Atomic best-effort sidecar write; returns the path ("" on any
        failure — remote paths, read-only dirs, mid-teardown)."""
        if not out_dir or "://" in out_dir:
            return ""
        path = os.path.join(out_dir, SANITIZE_REPORT_NAME)
        try:
            os.makedirs(out_dir, exist_ok=True)
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump({"version": self.VERSION,
                           "violations": self.violations}, f, indent=1)
            os.replace(tmp, path)
            return path
        except OSError:  # pragma: no cover - defensive
            return ""


# ---- the step loops' own account of every tick (always on)

# A tick is STALLED when its whole period (tick part + between part) passes
# max(STALL_FLOOR_S, STALL_FACTOR x the running median of its kind).
STALL_FLOOR_S = 0.020
STALL_FACTOR = 4.0

# Log-spaced buckets of a tick's period: four an octave from 10 us up
# (the last holds everything past ~2.8 min), so p50 / p99 need no list of
# samples and read to within a bucket's width (19 %).
_BUCKET0_S = 1e-5
_N_BUCKETS = 96
_BUCKET_MID_S = tuple(_BUCKET0_S * 2.0 ** ((i + 0.5) / 4.0)
                      for i in range(_N_BUCKETS))

# Seconds the cyclic collector has run in this process, and the start of
# the collection that is running: a gc.callbacks hook, installed with the
# first account, that costs two clock readings a COLLECTION and nothing a
# tick.
_GC = [0.0, 0.0]
_ACCOUNTS: Dict[str, "StallBreakdown"] = {}


def _on_gc(phase: str, info: dict) -> None:
    if phase == "start":
        _GC[1] = time.perf_counter()
    else:
        _GC[0] += time.perf_counter() - _GC[1]


def tick_account(name: str) -> Optional["StallBreakdown"]:
    """The newest account a loop of this name (``serve``, ``train``) made
    in this process, held strongly: a reader finds it after the loop's
    owner is gone (plain Python, no device memory)."""
    return _ACCOUNTS.get(name)


class _KindStats:
    """What the account keeps of one kind of tick since the steady point."""

    __slots__ = ("count", "tick_s", "cpu_s", "between_s", "phase_s",
                 "buckets", "med", "below", "max_s")

    def __init__(self, n_phases: int) -> None:
        self.count = 0
        self.tick_s = self.cpu_s = self.between_s = self.max_s = 0.0
        self.phase_s = [0.0] * n_phases
        self.buckets = [0] * _N_BUCKETS
        self.med = 0      # bucket of the lower median, kept as ticks come
        self.below = 0    # ticks in the buckets under it

    def add(self, period: float, tick: float, cpu: float, between: float,
            phases: List[float]) -> None:
        b = (min(_N_BUCKETS - 1, int(4.0 * math.log2(period / _BUCKET0_S)))
             if period > _BUCKET0_S else 0)
        buckets = self.buckets
        buckets[b] += 1
        if not self.count:
            self.med = b
        elif b < self.med:
            self.below += 1
        self.count += 1
        # the lower median has rank (count - 1) // 2: move the bucket it
        # lies in by the one tick that came (amortised O(1), no sort)
        rank = (self.count - 1) // 2
        while self.below > rank:
            self.med -= 1
            self.below -= buckets[self.med]
        while self.below + buckets[self.med] <= rank:
            self.below += buckets[self.med]
            self.med += 1
        self.tick_s += tick
        self.cpu_s += cpu
        self.between_s += between
        if period > self.max_s:
            self.max_s = period
        mine = self.phase_s
        for i, s in enumerate(phases):
            mine[i] += s

    def median_s(self) -> float:
        return _BUCKET_MID_S[self.med] if self.count else 0.0

    def quantile_s(self, q: float) -> float:
        """Nearest-rank quantile of the period, to a bucket's middle."""
        # rank ceil(q x count) - 1, in whole thousandths (no float ceil)
        rank = max(0, -(-round(q * 1000) * self.count // 1000) - 1)
        seen = 0
        for b, n in enumerate(self.buckets):
            seen += n
            if seen > rank:
                return min(_BUCKET_MID_S[b], self.max_s)
        return self.max_s


class StallBreakdown:
    """A step loop's own account of every tick, always on: WHERE the host
    loop's wall time goes, whether or not anything traces.

    A TICK runs from one entry of the loop's step to the next entry
    (``DecodeServer.step``; ``TrainLoop.next_batch`` + ``run_step``). It
    has a tick part (``begin`` -> ``end``) and a between part (``end`` ->
    the next ``begin``: the caller's time); the two make its PERIOD. For
    every tick the account books

    * its ``kind``, by what it dispatched (``dispatched``: the kinds are
      indexed by the OR of the dispatches' bits);
    * wall seconds of both parts, and CPU seconds of the loop's own thread
      over the tick part (``time.thread_time``): wall far above CPU is a
      wait (the GIL, a blocked runtime call, a descheduled process), wall
      equal to CPU is the loop's own Python or a compile;
    * wall seconds by phase (``phase``), at the boundaries the loop's spans
      mark; what no phase covers is ``other``;
    * DRY dispatches: ``dispatched`` is told, just before a dispatch, the
      newest result still in flight; if that is ready already
      (``is_ready()``: no transfer, no wait) the device had nothing
      queued. The program's own reading of a starved device;
    * by kind: count, sums, log-spaced bucket counts of the period, and a
      running median from them;
    * a STALL RECORD for a tick whose period passes ``max(STALL_FLOOR_S,
      STALL_FACTOR x its kind's running median)`` (a kind's first tick has
      nothing to be compared with): the newest 64 are kept, their count
      and seconds over the median for ever; with a ``tracer`` that is
      enabled the record is also an instant ``<name>.stall``;
    * one small tuple a tick in a bounded ring (``ticks``): entry time
      (``perf_counter``), kind, tick seconds, CPU seconds, between
      seconds, dry dispatches.

    Everything is of the STEADY part: ``mark_steady`` (the first fetched
    token; the first completed step) drops what was booked up to and with
    the tick that called it. A tick is booked when the next begins;
    ``close`` books the last one, with no between part.

    A normal tick costs two ``thread_time`` readings, one ``perf_counter``
    reading a boundary and one tuple; it takes no lock and formats nothing.
    Everything else happens in the stall branch or in ``summary``.

    The train loop's four GAUGES stay what they were (``add`` / ``lap`` /
    ``sums``, attributed by the trainer / device-prefetch wrapper):

    * ``data_wait_s``   — blocked on the host iterator (batch assembly;
      the thread-prefetch queue was empty when the loop asked);
    * ``h2d_wait_s``    — blocked placing the batch on the mesh (the
      ``device_put``/``shard_batch`` call; near-zero when transfers
      overlap compute, the full copy cost on synchronous backends);
    * ``dispatch_s``    — enqueueing the compiled step (trace-cache
      lookup + argument handling; does NOT include device execution);
    * ``device_step_s`` — trailing: wall time from a step's dispatch
      returning to its outputs materializing, observed when the lagged
      metrics fetch blocks on a k-steps-old output (``dispatch_lag``;
      an upper bound on device execution — it includes queue wait).

    The first three ARE phases of the tick (``add`` books both from the
    same seconds); ``device_step_s`` spans ticks, and the part of it the
    loop stood waiting is the phase ``metrics_wait``. ``lap`` returns the
    window's per-step means and resets it (the ``log_interval`` cadence);
    ``sums`` is cumulative since construction. Gauges with no samples
    report 0.0 so every sink/bench row carries all four keys.
    """

    GAUGES = ("data_wait_s", "h2d_wait_s", "dispatch_s", "device_step_s")
    _GAUGE_PHASE = {"data_wait_s": "data_wait", "h2d_wait_s": "h2d",
                    "dispatch_s": "dispatch"}
    KEPT_STALLS = 64
    KEPT_TICKS = 65536

    def __init__(self, name: str = "train", *,
                 phases: Tuple[str, ...] = ("data_wait", "h2d", "dispatch",
                                            "metrics_wait", "log"),
                 waits: Tuple[str, ...] = ("data_wait", "metrics_wait"),
                 dispatches: Tuple[Tuple[str, int], ...] = (("step", 1),),
                 kinds: Tuple[str, ...] = ("idle", "step"),
                 tracer: Any = None) -> None:
        self._win = {g: [0.0, 0] for g in self.GAUGES}   # [sum, count]
        self._tot = {g: [0.0, 0] for g in self.GAUGES}
        self.name = name
        self.phases = tuple(phases)
        self._ix = {p: i for i, p in enumerate(self.phases)}
        self._wait_ix = tuple(self._ix[p] for p in waits)
        self._gauge_ix = {g: self._ix[p]
                          for g, p in self._GAUGE_PHASE.items()
                          if p in self._ix}
        self.dispatches = tuple(d for d, _ in dispatches)
        self._bit = tuple(b for _, b in dispatches)
        self.kinds = tuple(kinds)
        self.tracer = tracer
        # the open tick (begin .. end), then pending until the next begin
        self._open = self._pending = False
        self._cur = [0.0] * len(self.phases)
        self._t0 = self._c0 = self._t1 = self._tick_s = self._cpu_s = 0.0
        self._bits = self._dry = 0
        self._queued = self._active = self._inflight = 0
        self._recompiles = 0
        self._traced = False
        self._gc0 = 0.0
        self.n_ticks = 0              # since construction: a tick's number
        self._booked_until = 0.0      # where the last tick booked ended
        self.steady_t: Optional[float] = None
        self._steady_due = False
        self._reset()
        if _on_gc not in gc.callbacks:
            gc.callbacks.append(_on_gc)
        _ACCOUNTS[name] = self

    def _reset(self) -> None:
        self._kinds: Dict[str, _KindStats] = {}
        self.n_dispatched = [0] * len(self.dispatches)
        self.n_dry = [0] * len(self.dispatches)
        self.stall_count = 0
        self.stall_seconds = 0.0
        self.stalls: Deque[Dict[str, Any]] = collections.deque(
            maxlen=self.KEPT_STALLS)
        self.ticks: Deque[tuple] = collections.deque(maxlen=self.KEPT_TICKS)

    # ------------------------------------------------------- the four gauges

    def add(self, gauge: str, seconds: float) -> None:
        for acc in (self._win[gauge], self._tot[gauge]):
            acc[0] += seconds
            acc[1] += 1
        i = self._gauge_ix.get(gauge)
        if i is not None and self._open:
            self._cur[i] += seconds

    @staticmethod
    def _means(accs) -> dict:
        return {g: (s / n if n else 0.0) for g, (s, n) in accs.items()}

    def lap(self) -> dict:
        """Per-step means since the last lap; resets the window."""
        out = self._means(self._win)
        self._win = {g: [0.0, 0] for g in self.GAUGES}
        return out

    def sums(self) -> dict:
        """Cumulative SECONDS per gauge since construction (not means) —
        the goodput decomposition needs absolute time, not rates."""
        return {g: s for g, (s, _) in self._tot.items()}

    # ------------------------------------------------------------- one tick

    def begin(self, queued: int = 0, active: int = 0, inflight: int = 0,
              recompiles: int = 0, traced: bool = False) -> None:
        """A tick's entry: books the tick before it (its between part ends
        here). The arguments are the loop's state at entry, kept for a
        stall record: requests queued, slots active, results in flight,
        the recompile count, whether a profiler session is on. A tick that
        is open already (a step that raised, a batch pulled twice) goes
        on."""
        if self._open:
            return
        now = time.perf_counter()
        if self._pending:
            self._book(now - self._t1, traced, recompiles)
        if self._steady_due:
            self._steady_due = False
            self.steady_t = now
            self._reset()
        self._open = True
        self._queued, self._active, self._inflight = queued, active, inflight
        self._recompiles, self._traced = recompiles, traced
        self._gc0 = _GC[0]
        self._t0 = now
        self._c0 = time.thread_time()

    def phase(self, name: str, seconds: float) -> None:
        """Seconds of the open tick spent in one of the loop's phases (a
        drain or a flush outside any tick books nothing)."""
        if self._open:
            self._cur[self._ix[name]] += seconds

    def dispatched(self, what: int, newest: Any = None) -> None:
        """Called just BEFORE dispatch number ``what`` (an index into
        ``dispatches``), with the newest result still in flight: dry if
        there is none or it is ready already."""
        if not self._open:
            return
        self._bits |= self._bit[what]
        self.n_dispatched[what] += 1
        if newest is None or newest.is_ready():
            self.n_dry[what] += 1
            self._dry += 1

    def end(self) -> None:
        """The tick part ends (the step returns)."""
        if not self._open:
            return
        self._cpu_s = time.thread_time() - self._c0
        self._t1 = time.perf_counter()
        self._tick_s = self._t1 - self._t0
        self._open = False
        self._pending = True

    def mark_steady(self) -> None:
        """The loop is warm (both programs compiled, the first token
        fetched; the first step complete): the account starts again at the
        next tick's entry. Only the first call counts."""
        if self.steady_t is None:
            self._steady_due = True

    def close(self) -> None:
        """The loop is over: book the last tick, which has no between
        part."""
        if self._pending:
            self._book(0.0, self._traced, self._recompiles)

    def _book(self, between: float, traced: bool, recompiles: int) -> None:
        """One whole tick into its kind's sums, ``traced`` and
        ``recompiles`` being the loop's state as the period ended."""
        self._pending = False
        self.n_ticks += 1
        tick, cpu, cur = self._tick_s, self._cpu_s, self._cur
        period = tick + between
        kind = self.kinds[self._bits]
        stats = self._kinds.get(kind)
        if stats is None:
            stats = self._kinds[kind] = _KindStats(len(cur))
        elif period > STALL_FLOOR_S:
            median = stats.median_s()
            if period > STALL_FACTOR * median:
                self._stalled(kind, period, median, between, traced,
                              recompiles)
        stats.add(period, tick, cpu, between, cur)
        self.ticks.append((self._t0, kind, tick, cpu, between, self._dry))
        self._booked_until = self._t1 + between
        for i in range(len(cur)):
            cur[i] = 0.0
        self._bits = self._dry = 0

    def _stalled(self, kind: str, period: float, median: float,
                 between: float, traced: bool, recompiles: int) -> None:
        """The rare branch: keep the whole of a tick that stalled."""
        tick = self._tick_s
        phases = {p: round(s, 6) for p, s in zip(self.phases, self._cur)}
        phases["other"] = round(tick - sum(self._cur), 6)
        record = {
            "t": round(self._t0 + (time.time() - time.perf_counter()), 6),
            "tick": self.n_ticks, "kind": kind,
            "wall_s": round(tick, 6), "cpu_s": round(self._cpu_s, 6),
            "between_s": round(between, 6), "median_s": round(median, 6),
            "excess_s": round(period - median, 6), "phases": phases,
            "queued": self._queued, "active": self._active,
            "inflight": self._inflight,
            "gc_s": round(_GC[0] - self._gc0, 6),
            "recompiles": recompiles - self._recompiles,
            # a profiler session started or stopped inside it: the
            # caller's own doing, which a reader may set aside
            "session_edge": bool(traced != self._traced),
        }
        self.stall_count += 1
        self.stall_seconds += period - median
        self.stalls.append(record)
        tr = self.tracer
        if tr is not None and tr.enabled:
            tr.instant(f"{self.name}.stall", self.name, t=record["t"],
                       args=record)

    # ------------------------------------------------------------ read it

    def summary(self, records: bool = True) -> Dict[str, Any]:
        """The steady part so far (the ticks booked: an open or pending
        one is not in yet). By kind: count, seconds, the period's p50 /
        p99 / max, mean seconds of the tick part, the between part, the
        CPU and each phase, and the CPU's share of the tick part less its
        waits. Then the between part's share of all, dispatches and dry
        dispatches, and the stalls (``records=False``: without the
        records). ``span_s`` is the steady point to the end of the last
        tick booked, on two clock readings: ``seconds`` sums to it."""
        kinds: Dict[str, Any] = {}
        n = 0
        seconds = between_s = 0.0
        for kind, st in self._kinds.items():
            c = st.count
            waits = sum(st.phase_s[i] for i in self._wait_ix)
            phases = {p: round(s / c, 6)
                      for p, s in zip(self.phases, st.phase_s)}
            phases["other"] = round((st.tick_s - sum(st.phase_s)) / c, 6)
            kinds[kind] = {
                "count": c,
                "seconds": round(st.tick_s + st.between_s, 6),
                "p50_s": round(st.quantile_s(0.5), 6),
                "p99_s": round(st.quantile_s(0.99), 6),
                "max_s": round(st.max_s, 6),
                "tick_s": round(st.tick_s / c, 6),
                "between_s": round(st.between_s / c, 6),
                "cpu_s": round(st.cpu_s / c, 6),
                "cpu_share": round(st.cpu_s / (st.tick_s - waits), 4)
                if st.tick_s > waits else 0.0,
                "phases": phases}
            n += c
            seconds += st.tick_s + st.between_s
            between_s += st.between_s
        stalls: Dict[str, Any] = {"count": self.stall_count,
                                  "seconds": round(self.stall_seconds, 6)}
        if records:
            stalls["records"] = list(self.stalls)
        return {
            "name": self.name, "steady": self.steady_t is not None,
            "ticks": n, "seconds": round(seconds, 6),
            "span_s": round(self._booked_until - self.steady_t, 6)
            if n and self.steady_t is not None else None,
            "between_s": round(between_s, 6),
            "between_share": round(between_s / seconds, 6) if seconds else 0.0,
            "kinds": kinds,
            "dispatches": dict(zip(self.dispatches, self.n_dispatched)),
            "dry": dict(zip(self.dispatches, self.n_dry)),
            "stalls": stalls}

    def report_line(self) -> str:
        """``ticks <name> <json of summary()>``: what a loop run with
        ``sanitize`` leaves on standard error as it stops, so that an
        untraced run shows where its ticks went."""
        return (f"ticks {self.name} "
                + json.dumps(self.summary(), separators=(",", ":")))


class GoodputTracker:
    """Decomposes a training attempt's wall time into where it went, so
    "goodput" (useful-step time / wall time) is a number every run carries
    — the first-class metric large preemptible fleets are run by (ROADMAP
    item 5: preemption is the steady state, not the exception).

    Categories are EXCLUSIVE overheads, attributed by the trainer:

    * ``startup_s``   — process spawn -> TrainLoop construction (interpreter
      + jax import + distributed init; known only under the launcher, which
      stamps the spawn wall-clock into ``DPT_SPAWN_T``);
    * ``setup_s``     — TrainLoop construction minus restore (mesh/state
      init, trace-time work) — the share a restart pays even with a warm
      cache and nothing to restore;
    * ``restore_s``   — checkpoint discovery + restore (incl. the
      walk-back over corrupt checkpoints and the donation-safety copies);
    * ``compile_s``   — AOT lower()/compile() (collapses to the cache
      lookup on warm restarts);
    * ``save_s``      — blocking checkpoint-save time (schedule + barriers);
    * ``data_stall_s``— blocked on the input pipeline (attributed at
      summary time from the StallBreakdown sums);
    * ``recompute_s`` — re-running steps a previous attempt had already
      passed (work between the last checkpoint and a crash is lost and
      paid again after resume).

    One category deliberately does NOT live here: ``hang_s`` — the window
    a silently wedged attempt burned before the launcher's watchdog
    killed it. A hung process cannot attribute its own waste, so the
    LAUNCHER measures it (beacon freeze -> kill) and books it into the
    attempt record; :func:`chaos.goodput.aggregate_run` folds it as its
    own run-level category next to these.

    ``useful_step_s`` is the RESIDUAL: wall − Σ overheads. That makes the
    decomposition account for every second by construction — the honest
    framing, since "useful" legitimately includes dispatch and host-loop
    time the step pipeline needs. ``base_s`` shifts the wall-clock origin
    earlier than construction (the startup share measured on a different
    clock), so per-attempt wall ≈ spawn→now.
    """

    CATEGORIES = ("startup_s", "setup_s", "restore_s", "compile_s",
                  "save_s", "data_stall_s", "recompute_s")

    def __init__(self, t0: Optional[float] = None) -> None:
        self._t0 = time.perf_counter() if t0 is None else t0
        self.base_s = 0.0
        self._acc = {c: 0.0 for c in self.CATEGORIES}

    def add(self, category: str, seconds: float) -> None:
        self._acc[category] += max(0.0, seconds)

    def get(self, category: str) -> float:
        return self._acc[category]

    def wall_s(self) -> float:
        return self.base_s + (time.perf_counter() - self._t0)

    def summary(self, extra: Optional[dict] = None) -> dict:
        """Point-in-time decomposition. ``extra`` merges categories whose
        running total lives elsewhere (the trainer passes the
        StallBreakdown's ``data_stall_s`` sum here rather than mirroring
        every add)."""
        acc = dict(self._acc)
        for k, v in (extra or {}).items():
            acc[k] = acc.get(k, 0.0) + max(0.0, v)
        wall = self.wall_s()
        overhead = sum(acc.values())
        useful = max(0.0, wall - overhead)
        return {
            "wall_s": wall,
            "useful_step_s": useful,
            "goodput": (useful / wall) if wall > 0 else 0.0,
            **acc,
        }


class EventStats:
    """Per-event latency accounting (e.g. serving time-to-first-token):
    throughput means hide tail latency, and serving SLOs live in the tail.

    ``add`` records one event's seconds; ``summary`` reports count, mean,
    p50, p95 (nearest-rank on the sorted sample), and max — all 0.0 when
    empty so downstream rows always carry every key."""

    def __init__(self) -> None:
        self._vals: list = []

    def add(self, seconds: float) -> None:
        self._vals.append(float(seconds))

    def __len__(self) -> int:
        return len(self._vals)

    def summary(self) -> dict:
        if not self._vals:
            return {"count": 0, "mean": 0.0, "p50": 0.0, "p95": 0.0,
                    "max": 0.0}
        v = sorted(self._vals)
        n = len(v)
        return {
            "count": n,
            "mean": sum(v) / n,
            "p50": v[(n - 1) // 2],
            "p95": v[min(n - 1, max(0, -(-95 * n // 100) - 1))],
            "max": v[-1],
        }


class StepTimer:
    """Wall-clock step timing with warmup skip (first steps compile).

    ``lap()`` returns (steps/sec, tokens/sec) over the window since the last
    call. Async-dispatch friendly: call it right after a ``block_until_ready``
    on the step output (or accept one-step skew).
    """

    def __init__(self, tokens_per_step: float, warmup: int = 2):
        self.tokens_per_step = tokens_per_step
        self.warmup = warmup
        self._steps = 0
        self._t0: Optional[float] = None
        self._window_steps = 0

    def tick(self) -> None:
        self._steps += 1
        if self._steps == self.warmup:
            self._t0 = time.perf_counter()
            self._window_steps = 0
        elif self._steps > self.warmup:
            self._window_steps += 1

    def lap(self):
        if self._t0 is None or self._window_steps == 0:
            return 0.0, 0.0
        dt = time.perf_counter() - self._t0
        sps = self._window_steps / max(dt, 1e-9)
        self._t0 = time.perf_counter()
        self._window_steps = 0
        return sps, sps * self.tokens_per_step
