"""ChaosInjector: executes a :class:`~.plan.ChaosPlan` inside a live run.

The trainer calls three tiny hooks (``on_step`` at the top of every
optimizer step, ``on_data`` before pulling a batch, ``on_save`` right after
a checkpoint save is scheduled); each hook fires whatever faults the plan
schedules for the current step on this rank. Every fault fires AT MOST ONCE
PER RUN: a marker file in the run dir (written BEFORE the fault executes)
makes the respawned attempt sail past the step that killed its predecessor
— the same marker idiom the launcher restart tests pioneered, now owned by
the injector so every fault kind gets it for free.

Import-light on purpose: the launcher may import this package before jax
exists in the process; the checkpoint-corruption helper touches only the
filesystem.
"""

from __future__ import annotations

import os
import signal
import sys
import time
from typing import Optional

from .plan import ChaosFault, ChaosPlan

__all__ = ["ChaosInjector", "corrupt_newest_checkpoint",
           "corrupt_checkpoint_payload"]

# Payload bytes for checkpoint corruption: long enough to guarantee any
# parser/checksum downstream sees garbage, loud enough to grep in a hexdump.
_GARBAGE = b"\xde\xad\xbe\xef CHAOS-CORRUPTED " * 8

# orbax's commit marker — corruption must leave it intact so the torn
# checkpoint still LOOKS finalized and exercises the restore walk-back
# (deleting it would exercise the cheaper discovery-skip path instead).
# Public under COMMIT_MARKERS: the serving fleet's jax-free checkpoint
# discovery needs the same notion of "finalized".
_COMMIT_MARKERS = COMMIT_MARKERS = ("_CHECKPOINT_METADATA",
                                    "commit_success.txt")


def corrupt_newest_checkpoint(directory: str) -> Optional[str]:
    """Garble the payload of the newest finalized ``model_*`` checkpoint
    under ``directory`` (every file except the commit marker gets its head
    overwritten). Returns the corrupted path, or None when there is no
    finalized checkpoint to corrupt. Local-filesystem only — chaos runs
    are dev rings."""
    best: Optional[str] = None
    best_step = -1
    try:
        names = os.listdir(directory)
    except OSError:
        return None
    for name in names:
        if not name.startswith("model_") or ".orbax-checkpoint-tmp" in name:
            continue
        digits = name[len("model_"):]
        if not digits.isdigit():
            continue
        path = os.path.join(directory, name)
        if not any(os.path.exists(os.path.join(path, m))
                   for m in _COMMIT_MARKERS):
            continue  # torn already — corrupt a checkpoint resume WOULD pick
        if int(digits) > best_step:
            best_step, best = int(digits), path
    if best is None:
        return None
    corrupt_checkpoint_payload(best)
    return best


def corrupt_checkpoint_payload(path: str) -> bool:
    """Garble the head of every payload file under ONE checkpoint dir,
    leaving the commit markers intact (the dir still looks finalized; any
    restore of it must fail). Returns whether anything was written —
    ``False`` means the dir had no payload to damage (missing/empty), so
    a caller injecting a swap fault can tell the fault went nowhere."""
    wrote = False
    for root, _, files in os.walk(path):
        for fname in files:
            if fname in _COMMIT_MARKERS:
                continue
            fpath = os.path.join(root, fname)
            try:
                with open(fpath, "r+b") as f:
                    f.write(_GARBAGE)
                wrote = True
            except OSError:
                pass  # a file we cannot open is already damage enough
    return wrote


class ChaosInjector:
    """Fires plan faults from the trainer's hook points.

    ``run_dir`` anchors the once-per-run markers; when the trainer passes
    no checkpoint dir, markers degrade to in-process memory
    — enough for single-attempt use, while multi-attempt kill/restart
    scenarios always have a run dir by construction (that is where the
    checkpoint being resumed lives)."""

    def __init__(self, plan: ChaosPlan, rank: int = 0,
                 run_dir: str = "") -> None:
        self.plan = plan
        self.rank = rank
        self.run_dir = run_dir
        self._fired_mem: set = set()

    # ------------------------------------------------------------- markers

    def _marker(self, idx: int) -> str:
        return os.path.join(self.run_dir, f".chaos_fired_{idx:02d}")

    def _already_fired(self, idx: int) -> bool:
        if idx in self._fired_mem:
            return True
        return bool(self.run_dir) and os.path.exists(self._marker(idx))

    def _mark_fired(self, idx: int, fault: ChaosFault) -> None:
        # Marker lands BEFORE the fault executes: a SIGKILL leaves no
        # chance to write afterwards, and a re-fired kill every attempt
        # would be an unrecoverable crash loop, not an injected fault.
        self._fired_mem.add(idx)
        if self.run_dir:
            with open(self._marker(idx), "w") as f:
                f.write(f"{fault.kind} step={fault.step} rank={fault.rank} "
                        f"t={time.time():.3f}\n")

    # --------------------------------------------------------------- hooks

    def _due(self, step: int, kinds) -> list:
        return [(i, f) for i, f in enumerate(self.plan.faults)
                if f.kind in kinds and f.rank == self.rank
                and f.step == step and not self._already_fired(i)]

    def _fire_kill(self, fault: ChaosFault) -> None:
        sig = getattr(signal, fault.sig, None)
        if not isinstance(sig, signal.Signals):
            raise ValueError(f"chaos kill: unknown signal {fault.sig!r}")
        print(f"[chaos] rank {self.rank}: {fault.sig} self at step "
              f"{fault.step}", file=sys.stderr, flush=True)
        os.kill(os.getpid(), sig)
        # SIGTERM may be handled/deferred by the host loop; SIGKILL never
        # returns here. Either way the fault's job is done.

    def on_step(self, loop) -> None:
        """Top of ``run_step``: corrupt/kill/stall_step faults scheduled
        for the step ABOUT to run (plan order — corrupt-then-kill at the
        same step is the classic 'newest checkpoint is garbage AND the
        worker died'), plus the slow_rank straggler delay."""
        for idx, fault in self._due(loop.step,
                                    ("corrupt_checkpoint", "kill",
                                     "stall_step")):
            self._mark_fired(idx, fault)
            if fault.kind == "corrupt_checkpoint":
                victim = corrupt_newest_checkpoint(
                    self.run_dir or loop.checkpoint_dir)
                print(f"[chaos] rank {self.rank}: corrupted checkpoint "
                      f"{victim}", file=sys.stderr, flush=True)
            elif fault.kind == "stall_step":
                # The wedge the hang watchdog exists for: the process
                # stays ALIVE but stops advancing — no beacon write, no
                # exit code, nothing the restart machinery can see. The
                # marker landed first, so the attempt the watchdog
                # eventually kills is resumed past the wedge step.
                print(f"[chaos] rank {self.rank}: wedging step loop "
                      f"{fault.seconds}s at step {fault.step}",
                      file=sys.stderr, flush=True)
                time.sleep(fault.seconds)
            else:
                self._fire_kill(fault)
        # slow_rank: a straggler, not a hang — sleeps before EVERY step in
        # its [step, until_step] range, with no once-per-run marker (it
        # never kills; a respawned attempt re-straggles only the steps it
        # actually replays). Beacons keep advancing, so the hang watchdog
        # must ride through it.
        for fault in self.plan.faults:
            if (fault.kind == "slow_rank" and fault.rank == self.rank
                    and fault.step <= loop.step <= fault.until_step):
                time.sleep(fault.seconds)

    def on_data(self, loop) -> float:
        """Before pulling the batch for the NEXT step: stall faults.
        Returns the injected stall seconds (the caller attributes them to
        the data-wait gauge, so goodput accounting sees the stall as the
        input-pipeline time it simulates)."""
        stalled = 0.0
        for idx, fault in self._due(loop.step, ("stall_data",)):
            self._mark_fired(idx, fault)
            print(f"[chaos] rank {self.rank}: stalling data "
                  f"{fault.seconds}s at step {fault.step}",
                  file=sys.stderr, flush=True)
            time.sleep(fault.seconds)
            stalled += fault.seconds
        return stalled

    # ------------------------------------------------------- serving hooks

    def on_serve_tick(self, admitted: int, in_flight: int) -> None:
        """Serving replica hook, called once per scheduler tick with the
        replica's cumulative ADMITTED request count and its current
        in-flight count. ``kill_replica`` / ``stall_replica`` faults for
        this replica (``rank`` = replica id) fire at the first tick where
        ``admitted >= step`` AND something is in flight — "mid-request"
        by construction, whatever the traffic process did to the
        schedule. Threshold (not equality) because admitted counts can
        jump by a whole prefill batch in one tick. Marker-once like every
        fault: a respawned replica sails past."""
        if in_flight <= 0:
            return
        due = [(i, f) for i, f in enumerate(self.plan.faults)
               if f.kind in ("kill_replica", "stall_replica")
               and f.rank == self.rank and admitted >= f.step
               and not self._already_fired(i)]
        for idx, fault in due:
            self._mark_fired(idx, fault)
            if fault.kind == "stall_replica":
                # the serving wedge: alive, beacons frozen — only the
                # per-replica hang watchdog can end this
                print(f"[chaos] replica {self.rank}: wedging serve loop "
                      f"{fault.seconds}s ({in_flight} in flight)",
                      file=sys.stderr, flush=True)
                time.sleep(fault.seconds)
            else:
                self._fire_kill(fault)

    def on_swap(self, checkpoint_path: str) -> bool:
        """Fleet-side hook at the start of a checkpoint hot-swap:
        ``corrupt_swap_checkpoint`` garbles the swap TARGET before any
        replica loads it (``step``/``rank`` ignored — the swap is a
        fleet-level event, and this injector's run_dir is the fleet dir).
        Returns whether a fault fired, so the swap report can say the
        abort was injected rather than organic."""
        due = [(i, f) for i, f in enumerate(self.plan.faults)
               if f.kind == "corrupt_swap_checkpoint"
               and not self._already_fired(i)]
        fired = False
        for idx, fault in due:
            self._mark_fired(idx, fault)
            wrote = corrupt_checkpoint_payload(checkpoint_path)
            print(f"[chaos] fleet: corrupted swap checkpoint "
                  f"{checkpoint_path} (payload garbled: {wrote})",
                  file=sys.stderr, flush=True)
            fired = True
        return fired

    def on_save(self, loop) -> None:
        """Right after a checkpoint save is SCHEDULED (async write in
        flight, finalize not reached): crash_in_save faults — the kill
        lands between the array write and finalize, leaving an
        unfinalized/torn checkpoint behind."""
        for idx, fault in self._due(loop.step, ("crash_in_save",)):
            self._mark_fired(idx, fault)
            print(f"[chaos] rank {self.rank}: SIGKILL mid-save at step "
                  f"{fault.step}", file=sys.stderr, flush=True)
            os.kill(os.getpid(), signal.SIGKILL)
