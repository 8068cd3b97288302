"""Performance accounting: step timing, tokens/sec, and MFU.

The reference's only profiling is the logger's wall-time context manager
(``/root/reference/basic_utils/logger.py:296-320``) plus a grad-norm metric
that forces a device->host sync every step (``utils/trainer.py:265-271``).
Here the north-star metric (BASELINE.md: tokens/sec/chip + MFU) gets
first-class gauges, and nothing in the hot path blocks on the device.
"""

from __future__ import annotations

import contextlib
import json
import logging
import os
import time
import traceback
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax

__all__ = ["device_peak_flops", "transformer_train_flops_per_token",
           "transformer_decode_flops_per_token",
           "StepTimer", "mfu", "enable_persistent_compilation_cache",
           "AOTStep", "RecompileMonitor",
           "device_summary", "tpu_kernel_census",
           "SanitizeReport", "SANITIZE_REPORT_NAME",
           "StallBreakdown", "EventStats", "GoodputTracker",
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


class StallBreakdown:
    """Per-step stall accounting: WHERE the host loop's wall time goes,
    so "is the input pipeline the bottleneck" is a number, not a guess.

    Four gauges, attributed by the trainer / device-prefetch wrapper:

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

    ``add`` accumulates; ``lap`` returns the window's per-step means and
    resets it (the ``log_interval`` cadence); ``totals`` is cumulative.
    Gauges with no samples report 0.0 so every sink/bench row carries
    all four keys.
    """

    GAUGES = ("data_wait_s", "h2d_wait_s", "dispatch_s", "device_step_s")

    def __init__(self) -> None:
        self._win = {g: [0.0, 0] for g in self.GAUGES}   # [sum, count]
        self._tot = {g: [0.0, 0] for g in self.GAUGES}

    def add(self, gauge: str, seconds: float) -> None:
        for acc in (self._win[gauge], self._tot[gauge]):
            acc[0] += seconds
            acc[1] += 1

    @staticmethod
    def _means(accs) -> dict:
        return {g: (s / n if n else 0.0) for g, (s, n) in accs.items()}

    def lap(self) -> dict:
        """Per-step means since the last lap; resets the window."""
        out = self._means(self._win)
        self._win = {g: [0.0, 0] for g in self.GAUGES}
        return out

    def totals(self) -> dict:
        """Cumulative per-step means since construction."""
        return self._means(self._tot)

    def sums(self) -> dict:
        """Cumulative SECONDS per gauge since construction (not means) —
        the goodput decomposition needs absolute time, not rates."""
        return {g: s for g, (s, _) in self._tot.items()}


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
