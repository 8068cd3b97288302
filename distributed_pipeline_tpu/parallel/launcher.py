"""One-flag distributed launcher.

Capability parity with the reference's self-relaunching elastic launcher
(``/root/reference/basic_utils/dist_run.py``): any script gains a
``--distributed`` flag plus launcher knobs; launcher args are split from
script args (dist_run.py:217-255); the reconstructed command line is echoed
(dist_run.py:36-44); spawned children detect the relaunch through an env flag
(dist_run.py:312-318).

TPU-native redesign rather than translation: torchrun re-execs N processes per
node because torch wants one process per GPU. JAX is **one process per host**
(all local chips addressable), so on a real TPU slice there is nothing to
spawn — ``--distributed`` validates/derives the ``jax.distributed`` coordinator
settings and continues in-process, printing the per-host command line for the
other hosts. For development without a pod, ``--nprocs N`` spawns N local
worker processes that form a real ``jax.distributed`` ring over loopback
(each worker restricted to CPU devices) — the stand-in for torchrun's
``--standalone`` local rendezvous (dist_run.py:115-122).
"""

from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

from ..chaos import goodput as goodput_lib
from ..obs import trace as trace_lib
from .dist import AUTORUN_ENV_FLAG, find_free_port, is_available

__all__ = [
    "create_distributed_parser",
    "parse_distributed_args",
    "run_argv_as_distributed",
    "parse_and_autorun",
    "get_main_modname",
    "parse_capacity_schedule",
    "WorkersDoNotFitHost",
    "require_workers_fit_host",
    "FORCE_NPROCS_ENV",
    "FORCE_DEVICES_ENV",
]


def create_distributed_parser() -> argparse.ArgumentParser:
    """Launcher-only args (mirror of reference dist_run.py:57-214, reshaped
    for the one-process-per-host JAX model)."""
    # allow_abbrev=False: parse_known_args must not steal prefix-abbreviated
    # SCRIPT flags (e.g. a wrapped script's --proc would otherwise be consumed
    # as --process_id).
    p = argparse.ArgumentParser(add_help=False, allow_abbrev=False)
    p.add_argument("--distributed", action="store_true",
                   help="launch/join a multi-process run")
    p.add_argument("--coordinator_address", default=None,
                   help="host:port of process 0 (like torchrun --master_addr/port)")
    p.add_argument("--num_processes", type=int, default=None,
                   help="total number of host processes")
    p.add_argument("--process_id", type=int, default=None,
                   help="this host's process index (like --node_rank)")
    p.add_argument("--nprocs", type=int, default=0,
                   help="spawn N supervised local worker processes (torchrun "
                        "--standalone's stand-in); they inherit this "
                        "process's JAX_PLATFORMS, and N > 1 needs it to be "
                        "cpu (a chip belongs to one process)")
    p.add_argument("--devices_per_proc", type=int, default=2,
                   help="virtual CPU devices per spawned worker (only when "
                        "the platform is cpu)")
    p.add_argument("--max_restarts", type=int, default=0,
                   help="restart-rate budget: respawn the worker ring after "
                        "a failure, at most this many times per sliding "
                        "--restart_window_s window (not a lifetime counter "
                        "— a week-long spot-capacity run may restart "
                        "hundreds of times, just not in a tight loop); "
                        "checkpoint auto-resume continues the run "
                        "(reference dist_run.py:123-129)")
    p.add_argument("--restart_window_s", type=float, default=3600.0,
                   help="sliding window (seconds) the --max_restarts budget "
                        "applies to; restarts older than this no longer "
                        "count against the budget. <= 0 restores lifetime "
                        "counting")
    p.add_argument("--restart_backoff_s", type=float, default=1.0,
                   help="base seconds of exponential backoff between "
                        "restart attempts (doubles per consecutive "
                        "failure, capped by --restart_backoff_max_s; "
                        "0 disables). A crashing dependency gets breathing "
                        "room instead of a spawn storm")
    p.add_argument("--restart_backoff_max_s", type=float, default=30.0,
                   help="cap on the exponential restart backoff")
    p.add_argument("--monitor_interval", type=float, default=0.2,
                   help="seconds between worker liveness polls (reference "
                        "dist_run.py:130-136; default is snappier than "
                        "torchrun's 5s — these are local dev workers)")
    p.add_argument("--hang_timeout_s", type=float, default=0.0,
                   help="hang watchdog: kill the worker ring when NO rank's "
                        "progress beacon advances for this many seconds "
                        "(a wedged collective / network stall never exits, "
                        "so liveness polling alone would burn wall time "
                        "forever); the killed window books as 'hang' in the "
                        "goodput fold and the normal restart machinery "
                        "resumes from the last checkpoint. Arms after the "
                        "attempt's FIRST beacon (startup/compile time is "
                        "not a hang); must exceed the slowest legitimate "
                        "step+save interval. 0 disables")
    p.add_argument("--hang_startup_timeout_s", type=float, default=0.0,
                   help="optional pre-first-beacon watchdog: kill an "
                        "attempt that produced NO beacon at all within this "
                        "many seconds of spawn (a worker wedged during "
                        "init/restore). Size it above worst-case "
                        "interpreter+compile+restore startup. 0 disables")
    p.add_argument("--log_dir", default="",
                   help="capture each spawned worker's stdout+stderr to "
                        "{log_dir}/worker_{i}.log (torchrun --log_dir/-r "
                        "redirects, dist_run.py:163-189); restarts append")
    p.add_argument("--log_tee", action="store_true",
                   help="with --log_dir: ALSO stream each worker's output "
                        "to this console, '[worker N]'-prefixed (torchrun "
                        "-t tee, dist_run.py:180-189)")
    return p


def parse_distributed_args(
    parser: argparse.ArgumentParser,
    argv: Optional[Sequence[str]] = None,
) -> Tuple[argparse.Namespace, List[str]]:
    """Split argv into (launcher namespace, remaining script argv)
    (reference dist_run.py:217-255). The script parser's help is augmented so
    ``--help`` documents both arg sets."""
    argv = list(sys.argv[1:] if argv is None else argv)
    dist_parser = create_distributed_parser()
    dist_ns, rest = dist_parser.parse_known_args(argv)
    # Surface launcher options in the script parser's help, like the
    # reference's usage/epilog injection (dist_run.py:227-247).
    epilog = ("launcher options: --distributed "
              "[--coordinator_address H:P] [--num_processes N] "
              "[--process_id I] [--nprocs N] [--devices_per_proc K] "
              "[--max_restarts R] [--restart_window_s S] "
              "[--restart_backoff_s S] [--restart_backoff_max_s S] "
              "[--monitor_interval S] [--hang_timeout_s S] "
              "[--hang_startup_timeout_s S] [--log_dir DIR] [--log_tee]")
    if epilog not in (parser.epilog or ""):
        parser.epilog = ((parser.epilog or "") + "\n\n" + epilog)
    return dist_ns, rest


def get_main_modname() -> Optional[str]:
    """Module name of the running ``__main__`` so children can be relaunched
    with ``-m`` (reference walks the frame stack, dist_run.py:258-282; the
    module spec carries the same information)."""
    main = sys.modules.get("__main__")
    spec = getattr(main, "__spec__", None)
    if spec is not None and spec.name:
        name = spec.name
        return name[:-len(".__main__")] if name.endswith(".__main__") else name
    return None


def _tee_pump(proc, sink, prefix: str):
    """Daemon thread streaming one worker's piped output to BOTH its log
    file and this console (torchrun -t tee semantics, dist_run.py:180-189).
    Returns the thread (joined before the log file closes)."""
    import threading

    def pump():
        echo = True
        for line in iter(proc.stdout.readline, b""):
            # the log file ALWAYS gets the line; a broken console (closed
            # stream, reader exited under a pipe) only disables the echo —
            # stopping the pump would deadlock the worker on a full pipe
            sink.write(line)
            sink.flush()
            if echo:
                try:
                    sys.stdout.write(
                        f"{prefix} {line.decode(errors='replace')}")
                    sys.stdout.flush()
                except (ValueError, OSError):
                    echo = False

    t = threading.Thread(target=pump, daemon=True)
    t.start()
    return t


def inherited_platform() -> str:
    """The platform spawned workers get: the parent's own ``JAX_PLATFORMS``
    if set ("cpu" under the tests and dev rings), else no pin at all — on
    a TPU host the worker then finds the chip the same way a bare
    ``python -m ...run.train`` would. One rule for every spawner
    (launcher rings, serving fleet, MPMD stages)."""
    return os.environ.get("JAX_PLATFORMS", "")


class WorkersDoNotFitHost(ValueError):
    """More accelerator worker processes than one host can hold
    (:func:`require_workers_fit_host`). The CLIs turn it into their exit
    message; library callers get an ordinary exception."""


def require_workers_fit_host(n_workers: int, platform: Optional[str],
                             what: str) -> None:
    """Refuse to put more than one accelerator worker on this host. A
    chip belongs to one process at a time, one process drives every chip
    of its host, and nothing here splits a host's chips between
    processes (ROADMAP R8) — so a second worker would fail on the device
    lock or, as this launcher once arranged, train on the CPU and say
    nothing. Only an explicit ``cpu`` platform (virtual devices) may hold
    several. Every place that adds a worker process asks here: a ring at
    launch (for every worker count its elastic schedule names), the
    serving fleet at construction and at each scale-up, the MPMD driver."""
    if platform is None:
        platform = inherited_platform()
    if n_workers > 1 and platform != "cpu":
        raise WorkersDoNotFitHost(
            f"{what}: {n_workers} worker processes on one host need an "
            f"explicit JAX_PLATFORMS=cpu (virtual devices); on platform "
            f"{platform or '<unpinned>'!r} a chip belongs to one process "
            f"and this launcher does not split a host's chips — use one "
            f"worker (it drives every chip of the host)")


def _worker_env(i: int, nprocs: int, coord: str, devices_per_proc: int,
                run_timestamp: Optional[str] = None,
                extra_env: Optional[dict] = None,
                platform: Optional[str] = None) -> dict:
    """Environment for spawned worker ``i`` — the caller's environment
    plus the ring coordinates. The persistent compile cache needs no
    hand-down: ``JAX_COMPILATION_CACHE_DIR`` is inherited when the caller
    set it, and otherwise every worker resolves the same fixed directory
    (utils/perf.py), so siblings and respawned attempts hit one cache.

    ``platform`` pins the worker's jax backend; None means
    :func:`inherited_platform` and "" means no pin. The virtual-device
    forcing is tied to an explicit "cpu": it exists to give dev rings and
    tests a multi-device mesh, never to hide real hardware."""
    env = dict(os.environ)
    if run_timestamp:
        env["DPT_RUN_TIMESTAMP"] = run_timestamp
    # Steady-state-throughput knobs ride the env too (run/train.py checks
    # DPT_PREFETCH_DEPTH / DPT_DISPATCH_LAG before its flags): inherited
    # from this process's environ above, so a launcher-level override
    # reaches every worker of every restart attempt — the one channel a
    # --config_json ring (which rejects individual CLI flags) can be
    # A/B'd through without minting a new config file.
    env.update({
        AUTORUN_ENV_FLAG: "1",
        "JAX_COORDINATOR_ADDRESS": coord,
        "JAX_NUM_PROCESSES": str(nprocs),
        "JAX_PROCESS_INDEX": str(i),
    })
    if platform is None:
        platform = inherited_platform()
    if platform:
        env["JAX_PLATFORMS"] = platform
    if platform == "cpu":
        env["XLA_FLAGS"] = (
            (env_flags := env.get("XLA_FLAGS", ""))
            + (" " if env_flags else "")
            + f"--xla_force_host_platform_device_count={devices_per_proc}")
    # Supervision channel (restart accounting): DPT_ATTEMPT / DPT_SPAWN_T /
    # DPT_RUN_DIR_FILE ride here — launcher-owned keys win over anything
    # inherited from the caller's environ.
    env.update(extra_env or {})
    return env


# Per-attempt capacity override schedules (elastic-topology simulation):
# comma-separated ints indexed by attempt, clamped to the last entry —
# "2,1" means attempt 0 gets 2, every later attempt gets 1. On a real
# fleet, surviving capacity comes from the scheduler/instance metadata;
# on this box's single-host dev rings the env IS the capacity probe, so
# shrink/grow restarts are reproducible in tests.
FORCE_NPROCS_ENV = "DPT_FORCE_NPROCS"
FORCE_DEVICES_ENV = "DPT_FORCE_DEVICES_PER_PROC"


def parse_capacity_schedule(text: str) -> Optional[List[int]]:
    """``"2,1"`` -> [2, 1]; empty/unset -> None. Raises on malformed or
    non-positive entries — a silently-ignored capacity override would run
    the wrong topology without anyone noticing."""
    if not text:
        return None
    out = []
    for tok in text.split(","):
        tok = tok.strip()
        if not tok.isdigit() or int(tok) < 1:
            raise ValueError(
                f"capacity schedule entries must be positive ints, got "
                f"{tok!r} in {text!r}")
        out.append(int(tok))
    return out


def _capacity_at(schedule: Optional[List[int]], attempt: int,
                 default: int) -> int:
    if not schedule:
        return default
    return schedule[min(attempt, len(schedule) - 1)]


def _beacon_mtimes(run_dir_file: str) -> Optional[Dict[str, float]]:
    """mtime per progress beacon in the run dir named by the handshake
    file, or None when the dir (or any beacon) isn't known yet. mtime is
    the liveness signal: the trainer atomically replaces each rank's
    beacon every optimizer step, so a frozen newest-mtime means NO rank
    is advancing — the hang signature (a straggler still advances, just
    slowly)."""
    try:
        with open(run_dir_file) as f:
            run_dir = f.read().strip()
    except OSError:
        return None
    if not run_dir or not os.path.isdir(run_dir):
        return None
    # the beacon naming (and the stat walk) is owned by chaos.goodput —
    # one source of truth for what counts as a progress beacon
    return goodput_lib.beacon_mtimes(run_dir) or None


def _run_worker_ring(cmd_base: List[str], nprocs: int, devices_per_proc: int,
                     monitor_interval: float,
                     run_timestamp: Optional[str] = None,
                     log_dir: str = "", log_tee: bool = False,
                     attempt: int = 0,
                     extra_env: Optional[dict] = None,
                     hang_timeout_s: float = 0.0,
                     hang_startup_timeout_s: float = 0.0,
                     run_dir_file: str = "",
                     status: Optional[dict] = None,
                     tag: str = "", platform: Optional[str] = None) -> int:
    """One attempt: spawn the ring, poll liveness, fail fast on any death.

    A worker that dies (e.g. on an import error before joining the ring)
    would leave its siblings blocked in jax.distributed.initialize forever —
    terminate them instead (torchrun's elastic agent behavior). Returns the
    max worker exit code.

    HANG WATCHDOG (``hang_timeout_s > 0``): liveness polling only catches
    workers that EXIT; the nastiest production failures are workers that
    wedge (a stuck collective, a network stall) and burn wall time without
    ever dying. The per-step progress beacons double as the liveness
    signal: once this attempt writes its first beacon the watchdog arms,
    and if no rank's beacon advances for ``hang_timeout_s`` the whole ring
    is SIGKILLed (every worker — the TrainLoop has no child processes, so
    killing each pid takes the whole ring down) and supervision treats it
    like any other dead attempt: restart, resume from the last checkpoint.
    ``status`` (a caller-provided dict) receives ``hung``/``hang_s``/
    ``hang_kind`` so the attempt record can book the wasted window to the
    ``hang`` goodput category. ``hang_startup_timeout_s`` optionally also
    bounds the pre-first-beacon window (a worker wedged in init/restore).
    """
    port = find_free_port()
    coord = f"127.0.0.1:{port}"
    label = f"[launcher{' ' + tag if tag else ''}]"
    print(f"{label} attempt {attempt}: spawning {nprocs} local workers, "
          f"coordinator {coord}")
    print(f"{label} worker cmd: {' '.join(cmd_base)}")  # cmdline echo,
    # like reference dist_run.py:36-44
    logs = []
    tee_threads = []
    if log_dir:
        os.makedirs(log_dir, exist_ok=True)
        mode = "tee'd to console and" if log_tee else "->"
        print(f"{label} per-worker output {mode} "
              f"{log_dir}/worker_N.log")
    procs = []
    # The spawn loop sits INSIDE the try: if opening worker k's log or its
    # Popen raises (OSError mid-loop), the finally still closes every
    # already-opened log and the except path below terminates every
    # already-spawned worker instead of leaking them (r4 advisor).
    codes: List[Optional[int]] = []
    try:
        for i in range(nprocs):
            env = _worker_env(i, nprocs, coord, devices_per_proc,
                              run_timestamp, extra_env=extra_env,
                              platform=platform)
            if log_dir:
                # append: a restarted ring continues the same files (the
                # attempt boundary is visible from the launcher's own log)
                f = open(os.path.join(log_dir, f"worker_{i}.log"), "ab")
                # Attempt header: respawned rings append to the same file,
                # so without a boundary line the interleaved output of N
                # attempts is unattributable when debugging a crash loop.
                f.write(f"[launcher] attempt {attempt}\n".encode())
                f.flush()
                logs.append(f)
                if log_tee:
                    # pipe through a pump thread: file AND console get
                    # every line (reference -t tee, dist_run.py:180-189)
                    proc = subprocess.Popen(cmd_base, env=env,
                                            stdout=subprocess.PIPE,
                                            stderr=subprocess.STDOUT)
                    tee_threads.append(_tee_pump(proc, f, f"[worker {i}]"))
                    procs.append(proc)
                else:
                    procs.append(subprocess.Popen(
                        cmd_base, env=env, stdout=f,
                        stderr=subprocess.STDOUT))
            else:
                procs.append(subprocess.Popen(cmd_base, env=env))
        codes = [None] * len(procs)
        # Hang-watchdog state: armed by this attempt's first beacon write
        # (beacon mtime >= spawn wall-clock — earlier attempts' stale
        # beacons never arm it), re-anchored by every later advance.
        t_spawn_wall = time.time()
        t_start = time.monotonic()
        hang_armed = False
        last_advance = t_start
        last_max_mtime = 0.0
        next_hang_poll = 0.0
        watch = hang_timeout_s > 0 or hang_startup_timeout_s > 0
        while any(c is None for c in codes):
            for i, p in enumerate(procs):
                if codes[i] is None:
                    codes[i] = p.poll()
            failed = [i for i, c in enumerate(codes) if c not in (None, 0)]
            if failed:
                print(f"{label} worker(s) {failed} exited with "
                      f"{[codes[i] for i in failed]}; terminating remaining workers")
                for i, p in enumerate(procs):
                    if codes[i] is None:
                        p.terminate()
                for i, p in enumerate(procs):
                    if codes[i] is None:
                        try:
                            codes[i] = p.wait(timeout=10)
                        except subprocess.TimeoutExpired:
                            p.kill()
                            codes[i] = p.wait()
                break
            now = time.monotonic()
            if watch and run_dir_file and now >= next_hang_poll:
                # beacon stat()s are cheap but not free: throttle the
                # probe independently of the (snappier) liveness poll
                next_hang_poll = now + max(monitor_interval, 0.1)
                mtimes = _beacon_mtimes(run_dir_file)
                mx = max(mtimes.values()) if mtimes else 0.0
                if mx > last_max_mtime:
                    last_max_mtime = mx
                    if mx >= t_spawn_wall - 1e-3:  # THIS attempt's write
                        hang_armed = True
                        last_advance = now
                hung_kind = ""
                if hang_armed and hang_timeout_s > 0 \
                        and now - last_advance > hang_timeout_s:
                    hung_kind = "stall"
                elif not hang_armed and hang_startup_timeout_s > 0 \
                        and now - t_start > hang_startup_timeout_s:
                    hung_kind = "startup"
                if hung_kind:
                    hang_s = now - (last_advance if hang_armed else t_start)
                    print(f"{label} hang watchdog: no rank advanced for "
                          f"{hang_s:.1f}s "
                          f"({'no first beacon' if hung_kind == 'startup' else 'beacons frozen'}); "
                          f"SIGKILLing the worker ring")
                    if status is not None:
                        status.update({"hung": True,
                                       "hang_s": round(hang_s, 3),
                                       "hang_kind": hung_kind})
                    for i, p in enumerate(procs):
                        if codes[i] is None:
                            try:
                                p.send_signal(signal.SIGKILL)
                            except OSError:
                                pass  # died between poll and kill
                    for i, p in enumerate(procs):
                        if codes[i] is None:
                            codes[i] = p.wait()
                    break
            time.sleep(max(monitor_interval, 0.02))
    except BaseException:
        # KeyboardInterrupt or a spawn-phase failure: nothing supervises
        # the ring anymore — tear it down rather than leak workers.
        for p in procs:
            if p.poll() is None:
                p.terminate()
        raise
    finally:
        for t in tee_threads:
            t.join(timeout=5)  # drain piped output before closing files
        for f in logs:
            f.close()
    # Any nonzero code fails the attempt — max() would mask a signal-killed
    # worker (negative returncode) behind a sibling's clean 0.
    return next((c for c in codes if c not in (None, 0)), 0)


class _RestartBudget:
    """R restarts per sliding window, not a lifetime counter: a week-long
    spot-capacity run legitimately restarts hundreds of times — what must
    be stopped is a tight crash loop. ``window_s <= 0`` restores lifetime
    counting (every restart counts forever)."""

    def __init__(self, max_restarts: int, window_s: float,
                 now=time.monotonic) -> None:
        self.max_restarts = max_restarts
        self.window_s = window_s
        self._now = now
        self._stamps: List[float] = []

    def spent(self) -> int:
        if self.window_s > 0:
            cutoff = self._now() - self.window_s
            self._stamps = [t for t in self._stamps if t >= cutoff]
        return len(self._stamps)

    def allows_restart(self) -> bool:
        return self.spent() < self.max_restarts

    def charge(self) -> None:
        self._stamps.append(self._now())


def _crash_looping(records: List[dict]) -> bool:
    """Two consecutive FAILED attempts with zero step progress: the run is
    not recovering, it is burning restarts — stop now rather than when the
    budget runs out. Attempts whose progress is unknown (no beacons: the
    wrapped script is not a TrainLoop run) never trigger this."""
    if len(records) < 2:
        return False
    for rec in records[-2:]:
        if rec.get("rc", 1) == 0 or rec.get("steps") is None \
                or rec.get("steps", 0) > 0:
            return False
    return True


def _harvest_attempt(run_dir_file: str, attempt: int, rc: int,
                     t_spawn: float, t_exit: float, prev_t_exit: float,
                     prev_max_step: Optional[int],
                     ring_status: Optional[dict] = None,
                     nprocs: Optional[int] = None,
                     devices_per_proc: Optional[int] = None
                     ) -> Tuple[dict, Optional[str]]:
    """Build the structured per-attempt record and locate the run dir.

    The run dir is learned through a handshake file the workers write
    (run/train.py / TrainLoop stamp their resolved checkpoint dir into
    ``DPT_RUN_DIR_FILE``) — the launcher cannot re-derive it without
    duplicating the script's dir logic. Step progress and the post-mortem
    goodput snapshot come from the per-rank beacons in that dir."""
    run_dir: Optional[str] = None
    try:
        with open(run_dir_file) as f:
            run_dir = f.read().strip() or None
    except OSError:
        run_dir = None
    end_step: Optional[int] = None
    start_step = prev_max_step
    beacon_goodput = None
    serving_snap = None
    stage = None
    resume_overhead = None
    recompiles = steady_recompiles = None
    if run_dir and os.path.isdir(run_dir):
        beacons = goodput_lib.read_beacons(run_dir)
        ours = {r: b for r, b in beacons.items()
                if int(b.get("attempt", 0)) == attempt}
        if beacons and not ours:
            # The run IS beacon-capable (earlier attempts reported), but
            # this attempt died before its first step — that is zero
            # progress, not unknown progress: the crash-loop fail-fast
            # must see it (an attempt that cannot even restore would
            # otherwise burn the whole restart budget).
            end_step = prev_max_step or 0
        if ours:
            end_step = max(int(b.get("step", 0)) for b in ours.values())
            # Progress is measured against the step THIS attempt restored
            # from (the beacon's start_step), not the run's high-water
            # mark: after a walk-back past a corrupt checkpoint an attempt
            # legitimately advances below the old maximum, and calling
            # that zero progress would let the crash-loop fail-fast kill
            # a recovering run.
            starts = [int(b["start_step"]) for b in ours.values()
                      if b.get("start_step") is not None]
            if starts:
                start_step = min(starts)
            # rank 0's beacon carries the attempt's goodput snapshot (the
            # flight recorder aggregate_run falls back to when the attempt
            # died before writing its clean-exit sidecar)
            b0 = ours.get(0) or next(iter(ours.values()))
            beacon_goodput = b0.get("goodput")
            # serving replicas beacon a `serving` snapshot instead of a
            # training goodput one — harvest it the same way, so a killed
            # replica attempt keeps its flight recorder (aggregate_serving
            # falls back to it when no clean-exit sidecar exists)
            snap = b0.get("serving")
            serving_snap = snap if isinstance(snap, dict) else None
            # MPMD stage workers stamp their stage id into every beacon:
            # carried into the attempt record so per-stage rings'
            # attempts.jsonl rows are attributable after the run
            stage = b0.get("stage")
            recompiles = b0.get("recompile_count")
            steady_recompiles = b0.get("steady_recompile_count")
            if isinstance(beacon_goodput, dict):
                resume_overhead = (beacon_goodput.get("startup_s", 0.0)
                                   + beacon_goodput.get("restore_s", 0.0)
                                   + beacon_goodput.get("compile_s", 0.0))
    steps = (None if end_step is None
             else max(0, end_step - (start_step or 0)))
    record = {
        "attempt": attempt,
        "rc": rc,
        "t_spawn": round(t_spawn, 3),
        "t_exit": round(t_exit, 3),
        "duration_s": round(t_exit - t_spawn, 3),
        "downtime_s": round(max(0.0, t_spawn - prev_t_exit), 3)
        if prev_t_exit else 0.0,
        "start_step": start_step,
        "end_step": end_step,
        "steps": steps,
        "resume_overhead_s": (round(resume_overhead, 3)
                              if resume_overhead is not None else None),
        "recompile_count": recompiles,
        "steady_recompile_count": steady_recompiles,
        "goodput": beacon_goodput,
    }
    if serving_snap is not None:
        record["serving"] = serving_snap
    if stage is not None:
        record["stage"] = stage
    if nprocs is not None:
        # The attempt's actual topology (elastic runs shrink/grow between
        # attempts): what aggregate/debug tooling needs to attribute a
        # resume to the capacity it ran at.
        record["nprocs"] = nprocs
        record["devices_per_proc"] = devices_per_proc
    if ring_status and ring_status.get("hung"):
        # Watchdog kill: the frozen window is measured, bounded waste —
        # its own goodput category (hang), not anonymous lost time.
        record["hung"] = True
        record["hang_s"] = ring_status.get("hang_s", 0.0)
        record["hang_kind"] = ring_status.get("hang_kind", "stall")
    return record, run_dir


def run_argv_as_distributed(modname: str, script_argv: Sequence[str],
                            nprocs: int, devices_per_proc: int = 2,
                            max_restarts: int = 0,
                            monitor_interval: float = 0.2,
                            log_dir: str = "", log_tee: bool = False,
                            restart_window_s: float = 3600.0,
                            restart_backoff_s: float = 1.0,
                            restart_backoff_max_s: float = 30.0,
                            hang_timeout_s: float = 0.0,
                            hang_startup_timeout_s: float = 0.0,
                            extra_env: Optional[dict] = None,
                            tag: str = "",
                            worker_platform: Optional[str] = None) -> int:
    """Spawn ``nprocs`` local worker processes forming a jax.distributed ring
    over loopback (one supervised worker on the host's accelerator, or
    a dev-mode multi-process ring of CPU workers).

    Restart supervision (reference torch.elastic via ``--max_restarts``,
    dist_run.py:123-136 + SURVEY.md §5.3 recovery story), hardened for
    chaos (ISSUE 8): when the ring dies, the whole ring is respawned on a
    fresh coordinator port and workers resume from the newest restorable
    checkpoint in their run dir. Between attempts the launcher

    * sleeps an EXPONENTIAL BACKOFF (``restart_backoff_s`` doubling per
      consecutive failure up to ``restart_backoff_max_s``),
    * charges a RESTART-RATE BUDGET (``max_restarts`` per sliding
      ``restart_window_s`` window — not a lifetime counter),
    * FAILS FAST on a crash loop (two consecutive attempts with zero step
      progress stop the run: restarts are not fixing anything),
    * RE-DERIVES CAPACITY (elastic topology, ISSUE 10): each attempt's
      worker count / fake-device count comes from the surviving capacity
      — on this box simulated by the ``DPT_FORCE_NPROCS`` /
      ``DPT_FORCE_DEVICES_PER_PROC`` per-attempt schedules ("2,1" =
      attempt 0 at 2, later attempts at 1) — so a run killed at dp=N
      resumes at dp=M and the elastic checkpoint/data machinery reshapes
      it (run/train.py re-derives mesh dims and fast-forwards the data
      stream by global samples consumed), and
    * appends a structured record to ``attempts.jsonl`` in the run dir
      (attempt, rc, duration, step progress, downtime, resume overhead,
      topology, hang window, post-mortem goodput snapshot) so every
      second of the run stays attributable (chaos.goodput.aggregate_run).

    ``hang_timeout_s`` arms the per-attempt HANG WATCHDOG (see
    :func:`_run_worker_ring`): silently wedged attempts are killed and
    restarted instead of burning the budgeted wall time forever.

    ``extra_env`` reaches every worker of every attempt (launcher-owned
    keys — DPT_ATTEMPT, ring coordinates, DPT_RUN_DIR_FILE — always win);
    ``worker_platform`` pins the workers' jax backend (None = the
    parent's JAX_PLATFORMS if set, else no pin — see
    :func:`inherited_platform`; "cpu" also forces ``devices_per_proc``
    virtual devices);
    ``tag`` prefixes this supervisor's log lines, so N rings supervised
    concurrently from one process (the serving fleet runs one per
    replica, in threads) stay attributable. This function is
    thread-safe: all state is local, and the per-ring run-dir handshake
    file is a fresh tempfile per call.

    Reference equivalent: in-process ``torch.distributed.run.run``
    (dist_run.py:13-54). Returns the final attempt's max worker exit code.
    """
    cmd_base = [sys.executable, "-m", modname, *script_argv]
    # Pin the run timestamp ONCE for all attempts: run/train.py derives its
    # auto-generated run dir from DPT_RUN_TIMESTAMP when set, so a respawned
    # ring lands in the SAME directory and checkpoint auto-resume actually
    # resumes (without this, each attempt would mint a fresh timestamped dir
    # and silently restart from step 0). Also removes the latent race where
    # workers spawned across a second boundary disagree on the dir name.
    # Passed to the WORKERS' env only — mutating this process's environ
    # would leak the timestamp into a second launch from the same process,
    # silently resuming run 2 from run 1's checkpoints.
    run_timestamp = os.environ.get("DPT_RUN_TIMESTAMP") or time.strftime(
        "%Y%m%d-%H%M%S")
    budget = _RestartBudget(max_restarts, restart_window_s)
    # Elastic capacity schedules (shrink/grow simulation): per-attempt
    # worker/device counts override the flags; parsed ONCE so a malformed
    # override fails the launch, not attempt 3.
    nprocs_sched = parse_capacity_schedule(
        os.environ.get(FORCE_NPROCS_ENV, ""))
    devices_sched = parse_capacity_schedule(
        os.environ.get(FORCE_DEVICES_ENV, ""))
    # every worker count any attempt may run at, checked before the first
    # spawn (same reason): attempt 3 must not be the one to find out
    most = max(nprocs_sched or [nprocs])
    require_workers_fit_host(most, worker_platform, f"--nprocs {most}")
    fd, run_dir_file = tempfile.mkstemp(prefix="dpt_run_dir_")
    os.close(fd)
    label = f"[launcher{' ' + tag if tag else ''}]"
    records: List[dict] = []
    attempt = 0
    consecutive_failures = 0
    prev_t_exit = 0.0
    prev_max_step: Optional[int] = None
    # Supervision trace (obs/, armed by DPT_TRACE): attempt spans, backoff
    # windows, and watchdog kills land in trace_launcher*.jsonl in the run
    # dir — created lazily once the worker handshake reveals the dir.
    tracer: Any = trace_lib.NULL
    try:
        while True:
            t_spawn = time.time()
            nprocs_a = _capacity_at(nprocs_sched, attempt, nprocs)
            devices_a = _capacity_at(devices_sched, attempt,
                                     devices_per_proc)
            if nprocs_a != nprocs or devices_a != devices_per_proc:
                print(f"{label} attempt {attempt}: capacity override "
                      f"-> {nprocs_a} worker(s) x {devices_a} device(s) "
                      f"(was {nprocs} x {devices_per_proc})")
            ring_status: dict = {}
            code = _run_worker_ring(
                cmd_base, nprocs_a, devices_a, monitor_interval,
                run_timestamp, log_dir=log_dir, log_tee=log_tee,
                attempt=attempt,
                extra_env={**(extra_env or {}),
                           "DPT_ATTEMPT": str(attempt),
                           "DPT_SPAWN_T": repr(t_spawn),
                           "DPT_RUN_DIR_FILE": run_dir_file},
                hang_timeout_s=hang_timeout_s,
                hang_startup_timeout_s=hang_startup_timeout_s,
                run_dir_file=run_dir_file,
                status=ring_status, tag=tag, platform=worker_platform)
            t_exit = time.time()
            record, run_dir = _harvest_attempt(
                run_dir_file, attempt, code, t_spawn, t_exit, prev_t_exit,
                prev_max_step, ring_status=ring_status,
                nprocs=nprocs_a, devices_per_proc=devices_a)
            records.append(record)
            if run_dir and os.path.isdir(run_dir):
                try:
                    goodput_lib.append_attempt(run_dir, record)
                except OSError as e:
                    print(f"{label} attempts.jsonl write failed: {e}")
                if tracer is trace_lib.NULL:
                    tracer = trace_lib.tracer_for(
                        run_dir, f"launcher_{tag}" if tag else "launcher")
            if tracer.enabled:
                tracer.complete(
                    f"attempt {attempt}", "supervise", t_spawn,
                    t_exit - t_spawn,
                    args={"rc": code, "steps": record["steps"],
                          "nprocs": nprocs_a,
                          "devices_per_proc": devices_a})
                if ring_status.get("hung"):
                    tracer.instant(
                        "watchdog_kill", "supervise", t=t_exit,
                        args={"hang_s": ring_status.get("hang_s"),
                              "kind": ring_status.get("hang_kind")})
            prev_t_exit = t_exit
            if record["end_step"] is not None:
                prev_max_step = max(prev_max_step or 0, record["end_step"])
            if code == 0:
                return 0
            # "Consecutive" failures reset when an attempt made real step
            # progress: a preemption after hours of healthy training is
            # not a tightening crash loop, and must not inherit the
            # accumulated backoff of unrelated failures days earlier.
            if (record["steps"] or 0) > 0:
                consecutive_failures = 1
            else:
                consecutive_failures += 1
            if _crash_looping(records):
                print(f"{label} crash loop: last 2 attempts made zero "
                      f"step progress (rc={code}); failing fast instead of "
                      f"burning {max_restarts - budget.spent()} more "
                      f"restart(s)")
                return code
            if not budget.allows_restart():
                window = (f"in the last {restart_window_s:.0f}s"
                          if restart_window_s > 0 else "total")
                print(f"{label} ring failed (rc={code}); restart budget "
                      f"exhausted ({budget.spent()}/{max_restarts} "
                      f"{window})")
                return code
            budget.charge()
            backoff = 0.0
            if restart_backoff_s > 0:
                backoff = min(restart_backoff_max_s,
                              restart_backoff_s
                              * (2.0 ** (consecutive_failures - 1)))
            attempt += 1
            print(f"{label} ring failed (rc={code}); restart "
                  f"{budget.spent()}/{max_restarts} (window "
                  f"{restart_window_s:.0f}s), backoff {backoff:.1f}s")
            if backoff > 0:
                if tracer.enabled:
                    # booked up front: the sleep below IS the window
                    tracer.complete("backoff", "supervise", time.time(),
                                    backoff,
                                    args={"consecutive_failures":
                                          consecutive_failures})
                time.sleep(backoff)
    finally:
        tracer.close()
        try:
            os.unlink(run_dir_file)
        except OSError:
            pass


def parse_and_autorun(
    parser: argparse.ArgumentParser,
    argv: Optional[Sequence[str]] = None,
) -> Optional[argparse.Namespace]:
    """Main launcher API (reference dist_run.py:285-327).

    * ``--distributed --nprocs N``: spawn N supervised local workers
      running this same module (platform inherited from this process:
      N > 1 needs JAX_PLATFORMS=cpu), wait, and exit with their code
      (parent exits, dist_run.py:314).
    * ``--distributed`` on a pod: set jax.distributed env from launcher args
      and fall through to run in-process (one process per host).
    * plain run / spawned child: parse script args and return the namespace;
      children (env flag set) force ``is_available`` true
      (dist_run.py:316-318) and set a descriptive proctitle when available.
    """
    dist_ns, script_argv = parse_distributed_args(parser, argv)

    # --nprocs 1 is a real (supervised) ring too: one spawned worker under
    # the launcher's restart/backoff/crash-loop machinery — the elastic
    # recovery story without cross-process collectives (which this image's
    # jax cannot run on CPU; see CHANGES r6).
    if dist_ns.distributed and dist_ns.nprocs >= 1:
        modname = get_main_modname()
        if modname is None:
            raise RuntimeError(
                "--nprocs relaunch requires running as a module (python -m ...)")
        try:
            code = run_argv_as_distributed(
                modname, script_argv, dist_ns.nprocs,
                dist_ns.devices_per_proc,
                max_restarts=dist_ns.max_restarts,
                monitor_interval=dist_ns.monitor_interval,
                log_dir=dist_ns.log_dir,
                log_tee=dist_ns.log_tee,
                restart_window_s=dist_ns.restart_window_s,
                restart_backoff_s=dist_ns.restart_backoff_s,
                restart_backoff_max_s=dist_ns.restart_backoff_max_s,
                hang_timeout_s=dist_ns.hang_timeout_s,
                hang_startup_timeout_s=dist_ns.hang_startup_timeout_s)
        except WorkersDoNotFitHost as e:
            raise SystemExit(str(e)) from None
        sys.exit(code)

    if dist_ns.distributed:
        # Multi-host in-process path: export coordinator settings for
        # dist.setup_dist, echo the command for the other hosts.
        if dist_ns.coordinator_address:
            os.environ["JAX_COORDINATOR_ADDRESS"] = dist_ns.coordinator_address
        elif (dist_ns.num_processes and dist_ns.num_processes > 1
              and "JAX_COORDINATOR_ADDRESS" not in os.environ):
            # No address given: default to this host (assumed process 0) on a
            # fixed port, so the echoed per-host command is actually runnable
            # (torchrun's master_addr/port defaults, dist_run.py:198-213).
            import socket
            os.environ["JAX_COORDINATOR_ADDRESS"] = f"{socket.gethostname()}:12321"
        if dist_ns.num_processes:
            os.environ["JAX_NUM_PROCESSES"] = str(dist_ns.num_processes)
        if dist_ns.process_id is not None:
            os.environ["JAX_PROCESS_INDEX"] = str(dist_ns.process_id)
        os.environ[AUTORUN_ENV_FLAG] = "1"
        is_available.cache = True  # type: ignore[attr-defined]
        if dist_ns.num_processes and dist_ns.num_processes > 1:
            # All hosts must agree on the auto-generated run dir; pin the
            # timestamp here and ship it in the echoed per-host command so
            # host clocks (and re-executions after a failure) can't diverge.
            # The COORDINATOR (process 0 / unset) mints a FRESH timestamp
            # every launch — inheriting a stale one from a previous run in
            # this environment would silently resume that run's checkpoints.
            # Workers (process_id > 0) inherit the value the coordinator's
            # echoed command gave them.
            if dist_ns.process_id in (None, 0):
                os.environ["DPT_RUN_TIMESTAMP"] = time.strftime(
                    "%Y%m%d-%H%M%S")
            else:
                os.environ.setdefault("DPT_RUN_TIMESTAMP",
                                      time.strftime("%Y%m%d-%H%M%S"))
            modname = get_main_modname() or "<module>"
            print(f"[launcher] per-host command (run with --process_id i): "
                  f"DPT_RUN_TIMESTAMP={os.environ['DPT_RUN_TIMESTAMP']} "
                  f"python -m {modname} --distributed "
                  f"--coordinator_address {os.environ['JAX_COORDINATOR_ADDRESS']} "
                  f"--num_processes {dist_ns.num_processes} "
                  f"{' '.join(script_argv)}")

    if os.environ.get(AUTORUN_ENV_FLAG):
        is_available.cache = True  # type: ignore[attr-defined]
        try:  # descriptive proctitle, like reference dist_run.py:319-323
            import setproctitle  # type: ignore[import-not-found]
            setproctitle.setproctitle(
                f"dpt-worker{os.environ.get('JAX_PROCESS_INDEX', '0')}: "
                + " ".join(sys.argv))
        except ImportError:
            pass

    ns = parser.parse_args(script_argv)
    # Record the exact argv this namespace came from so downstream checks
    # (TrainSettings' --config_json exclusivity) never have to guess from the
    # hosting process's sys.argv.
    ns._parsed_argv = list(script_argv)
    return ns
