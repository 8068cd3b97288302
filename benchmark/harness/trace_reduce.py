"""From a profiler trace to three things, and nothing else:

1. per device: the union of the intervals in which an operation ran (busy;
   the envelopes of control flow, ``while``, ``conditional`` and ``call``,
   are left out of everything: their bodies' ops have events of their own),
   and the idle gaps between them, each labelled by the harness annotation
   (``next_batch``, ``run_step``, ``submit``, ``server.step``, ``harness``)
   that covers most of it on the host's clock;
2. summed device time by operation name and by program (module) name;
3. collective time during which no compute operation ran on that device.

``load_xplane`` reads the ``.xplane.pb`` the JAX profiler writes, with
``jax.profiler.ProfileData`` alone. What it found in a real v5e trace (PR 26):
a plane ``/device:TPU:<n>`` a chip with the lines ``XLA Modules`` (one event a
program execution, named ``jit_<fn>(<fingerprint>)``) and ``XLA Ops`` (one
event an HLO op or fusion; a Pallas kernel appears under its kernel name), and
host threads under ``/host:CPU`` carrying ``TraceAnnotation`` events by name.
Everything after the loading works on plain ``Event`` lists, so the tests
build traces by hand.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

ANNOTATIONS = ("next_batch", "run_step", "submit", "server.step", "harness")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"    # start -> done spans of async copies and
MODULES_LINE = "XLA Modules"    # collectives, beside the core's own ops
# control flow's envelopes: one event spanning the ops of its body, which have
# events of their own on the same line. Not work: counted as busy time it
# hides the idle time inside a loop, counted as compute it hides every
# collective inside it, and in the list of ops it counts its body twice
ENVELOPES = frozenset({"while", "conditional", "call"})
_COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast", re.I)


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float      # seconds on the trace's clock
    dur: float

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    """Events by device (ops and program executions) and the host's
    annotation spans; ``t0``/``t1`` bound the traced window."""

    ops: Dict[str, List[Event]]
    modules: Dict[str, List[Event]]
    host: List[Event]
    t0: float
    t1: float
    async_ops: Dict[str, List[Event]] = dataclasses.field(
        default_factory=dict)


def is_envelope(name: str) -> bool:
    return stable_name(name) in ENVELOPES


def is_collective(name: str) -> bool:
    """By the op's OWN name: the whole instruction text also names its
    operands, and a fusion that consumes an all-gather is compute."""
    return bool(_COLLECTIVE.search(stable_name(name)))


def stable_name(name: str) -> str:
    """An op's name without the numbering the compiler appends. The v5e
    trace names a device op by its whole HLO instruction (``%fusion.29 =
    bf16[...] fusion(...)``): the name is what stands before `` = ``, less
    ``%``, trailing ``.123`` numbering and the rematerialisation suffixes
    (``%flash_attention_fwd.4 = ...`` -> ``flash_attention_fwd``,
    ``%copy.495.remat`` -> ``copy``). A program keeps its jit name without
    the fingerprint (``jit_train_step(123456)`` -> ``jit_train_step``)."""
    name = name.split(" = ", 1)[0].strip().lstrip("%")
    name = re.sub(r"\(.*\)$", "", name)
    name = re.sub(r"(\.remat\d*|_(un)?compressed|\.clone)+$", "", name)
    name = re.sub(r"(\.\d+)+$", "", name)
    return re.sub(r"(\.remat\d*|_(un)?compressed|\.clone)+$", "", name)


# ---------------------------------------------------------------- intervals

def merge(intervals: Iterable[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(events: Sequence[Event], t0: float, t1: float
         ) -> List[Tuple[float, float]]:
    return [(max(e.start, t0), min(e.end, t1)) for e in events
            if e.end > t0 and e.start < t1]


def busy_and_gaps(events: Sequence[Event], t0: float, t1: float
                  ) -> Tuple[float, List[Tuple[float, float]]]:
    """Seconds in [t0, t1] covered by at least one event, and the gaps."""
    merged = merge(clip(events, t0, t1))
    busy = sum(e - s for s, e in merged)
    gaps: List[Tuple[float, float]] = []
    at = t0
    for s, e in merged:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if t1 > at:
        gaps.append((at, t1))
    return busy, gaps


def overlap(a: Sequence[Tuple[float, float]], b: Sequence[Tuple[float, float]]
            ) -> float:
    """Total length of the intersection of two merged interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            total += e - s
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total


def label_gaps(gaps: Sequence[Tuple[float, float]], host: Sequence[Event]
               ) -> Dict[str, float]:
    """Idle seconds by what the host was doing: each gap goes to the
    annotation that covers most of it, ``unlabelled`` where none does."""
    by_label: Dict[str, List[Tuple[float, float]]] = {}
    for ev in host:
        by_label.setdefault(ev.name, []).append((ev.start, ev.end))
    merged = {k: merge(v) for k, v in by_label.items()}
    out: Dict[str, float] = {}
    for gap in gaps:
        best, cover = "unlabelled", 0.0
        for label, spans in merged.items():
            c = overlap([gap], spans)
            if c > cover:
                best, cover = label, c
        out[best] = out.get(best, 0.0) + (gap[1] - gap[0])
    return out


def time_by_name(events: Sequence[Event], t0: float, t1: float,
                 key: Callable[[str], str] = stable_name
                 ) -> Dict[str, Tuple[float, int]]:
    """Summed duration and count of the events that START in [t0, t1)."""
    out: Dict[str, Tuple[float, int]] = {}
    for e in events:
        if t0 <= e.start < t1:
            k = key(e.name)
            d, n = out.get(k, (0.0, 0))
            out[k] = (d + e.dur, n + 1)
    return out


def collective_exposed(events: Sequence[Event], t0: float, t1: float,
                       async_events: Sequence[Event] = ()
                       ) -> Tuple[float, float]:
    """(collective seconds, of them with no compute op running) on one
    device. A collective is in flight from its start to its done, which the
    async line shows as one span; compute is every other op of the core."""
    coll = merge(clip([e for e in list(events) + list(async_events)
                       if is_collective(e.name)], t0, t1))
    comp = merge(clip([e for e in events if not is_collective(e.name)],
                      t0, t1))
    total = sum(e - s for s, e in coll)
    return total, total - overlap(coll, comp)


# ------------------------------------------------------------------ summary

@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float                       # mean over the devices
    busy_by_device: Dict[str, float]
    op_time: Dict[str, Tuple[float, int]]       # summed over devices
    module_time: Dict[str, Tuple[float, int]]   # summed over devices
    idle_by_label: Dict[str, float]             # device with the most idle
    collective_s: Dict[str, float]              # by device
    collective_exposed_s: Dict[str, float]      # by device
    n_devices: int

    def op_seconds(self, name: str) -> Tuple[float, int]:
        """Summed time and count of the ops with this stable name, a device
        (mean over the devices)."""
        d, n = self.op_time.get(name, (0.0, 0))
        return d / max(self.n_devices, 1), n // max(self.n_devices, 1)

    def module_seconds(self, name: str) -> Tuple[float, int]:
        d, n = self.module_time.get(name, (0.0, 0))
        return d / max(self.n_devices, 1), n // max(self.n_devices, 1)

    def breakdown(self, top: int = 10) -> Dict[str, List[List[object]]]:
        ops = sorted(self.op_time.items(), key=lambda kv: -kv[1][0])[:top]
        gaps = sorted(self.idle_by_label.items(), key=lambda kv: -kv[1])[:top]
        nd = max(self.n_devices, 1)
        return {"device_ops": [[k, v[0] / nd] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def drop_envelopes(events: Iterable[Event]) -> List[Event]:
    return [e for e in events if not is_envelope(e.name)]


def summarize(trace: Trace) -> TraceSummary:
    t0, t1 = trace.t0, trace.t1
    busy_by, op_time, mod_time = {}, {}, {}
    coll, exposed = {}, {}
    idle_worst: Dict[str, float] = {}
    worst_idle = -1.0
    for dev, events in trace.ops.items():
        events = drop_envelopes(events)
        busy, gaps = busy_and_gaps(events, t0, t1)
        busy_by[dev] = busy
        for k, (d, n) in time_by_name(events, t0, t1).items():
            a, b = op_time.get(k, (0.0, 0))
            op_time[k] = (a + d, b + n)
        coll[dev], exposed[dev] = collective_exposed(
            events, t0, t1, trace.async_ops.get(dev, ()))
        idle = (t1 - t0) - busy
        if idle > worst_idle:
            worst_idle, idle_worst = idle, label_gaps(gaps, trace.host)
    for dev, events in trace.modules.items():
        for k, (d, n) in time_by_name(events, t0, t1).items():
            a, b = mod_time.get(k, (0.0, 0))
            mod_time[k] = (a + d, b + n)
    n = len(trace.ops)
    return TraceSummary(
        window_s=t1 - t0,
        busy_s=sum(busy_by.values()) / max(n, 1),
        busy_by_device=busy_by, op_time=op_time, module_time=mod_time,
        idle_by_label=idle_worst, collective_s=coll,
        collective_exposed_s=exposed, n_devices=n)


# ------------------------------------------------------------------ loading

def load_xplane(path: str) -> Trace:
    """Read an ``.xplane.pb``. The traced window is the ``bench_window``
    annotation that run.py holds open from the profiler's start to its stop
    (host and device events share one clock); without it, the span of the
    device's own events."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: Dict[str, List[Event]] = {}
    async_ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    host: List[Event] = []
    window: Optional[Tuple[float, float]] = None
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[plane.name] = [
                        Event(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                        for e in line.events]
                elif line.name == ASYNC_LINE:
                    async_ops[plane.name] = [
                        Event(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                        for e in line.events
                        if is_collective(e.name)]
                elif line.name == MODULES_LINE:
                    modules[plane.name] = [
                        Event(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in ANNOTATIONS:
                        host.append(Event(e.name, e.start_ns * 1e-9,
                                          e.duration_ns * 1e-9))
                    elif e.name == "bench_window":
                        window = (e.start_ns * 1e-9,
                                  (e.start_ns + e.duration_ns) * 1e-9)
    if window is None:
        starts = [e.start for evs in ops.values() for e in evs]
        ends = [e.end for evs in ops.values() for e in evs]
        if not starts:
            raise RuntimeError("the trace holds no device operation")
        window = (min(starts), max(ends))
    return Trace(ops=ops, modules=modules, host=host,
                 t0=window[0], t1=window[1], async_ops=async_ops)
