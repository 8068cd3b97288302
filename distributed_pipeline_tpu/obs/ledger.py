"""Per-compiled-program cost ledger + roofline MFU-gap attribution.

Headline MFU is ONE number; this module decomposes it per compiled
program so "where do the missing FLOP-seconds go" has an answer a kernel
PR can be held to (ROADMAP item 3: every Pallas kernel must prove it
moves ``device_step_s``). Three layers:

* **extraction** — :func:`extract_cost` pulls XLA's own accounting off
  an already-AOT-compiled executable (``compiled.cost_analysis()`` /
  ``compiled.memory_analysis()``, duck-typed so this module never
  imports jax) and :func:`hlo_collective_tally` walks the executable's
  HLO text tallying collective ops (all-reduce / all-gather /
  reduce-scatter / collective-permute / all-to-all) with their shapes
  into bytes-moved per execution — the comms side of the roofline,
  measured off the real compiled program instead of estimated from the
  parallelism plan;
* **attribution** — :func:`roofline_attribution` folds the extracted
  FLOPs/bytes with the analytic ``flops_per_token``, the measured
  tokens/s, and the r8 stall gauges into one row per program::

      mfu + mfu_gap_host + mfu_gap_comms + mfu_gap_memory_bound
          + mfu_gap_residual == 1        (exactly, by construction)

  Each gap term is that component's estimated share of step wall time,
  capped so the cumulative sum can never exceed the gap; the residual
  absorbs what no modeled component explains (kernel inefficiency,
  padding inside the program, dispatch overlap) — the honest framing,
  since the components are roofline ESTIMATES while ``mfu`` itself is
  measured. Attribution order is trust order: host stalls (measured by
  the StallBreakdown) cap first, then comms (HLO-derived bytes over an
  interconnect roofline), then memory-boundedness (bytes-accessed over
  an HBM roofline, in excess of ideal compute time);
* **persistence** — :func:`write_ledger`/:func:`read_ledger` keep one
  ``perf_ledger.json`` per run dir (atomic replace, the beacon
  discipline) that ``run/perf_report.py``, ``run/status.py``, and
  ``obs/export.py`` (Perfetto counter tracks) all read.

The bandwidth/peak tables are public-spec roofline CONSTANTS (the same
posture as ``utils/perf._PEAK_FLOPS``): the attribution is a first-order
decomposition for steering optimization, not a simulator. This module
and ``utils/perf.py`` are the two sanctioned owners of FLOPs/MFU
arithmetic (graftlint GL010 flags figures computed from raw constants
anywhere else).

Import-light (stdlib only): the report/status/regress CLIs read ledgers
without paying a jax import.
"""

from __future__ import annotations

import json
import os
import re
import threading
from typing import Any, Dict, List, Optional

__all__ = [
    "LEDGER_FILENAME", "COLLECTIVE_OPS", "GAP_TERMS", "PaddingMeter",
    "attribution_columns", "device_bandwidths", "extract_cost",
    "gap_sum_identity", "hlo_collective_tally", "ledger_path",
    "read_ledger", "roofline_attribution", "write_ledger",
]

LEDGER_FILENAME = "perf_ledger.json"

# the attribution row's gap terms, in attribution (= trust) order
GAP_TERMS = ("mfu_gap_host", "mfu_gap_comms", "mfu_gap_memory_bound",
             "mfu_gap_residual")

# HLO collective ops tallied into bytes-moved (the async '-start' form
# counts; its '-done' twin moves nothing new and is skipped).
COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter",
                  "collective-permute", "all-to-all")

# element sizes for HLO shape strings (f32[256,128]{1,0})
_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

# One typed shape inside an HLO line: dtype[dims]{layout?}. dims empty =
# scalar. Tuple results wrap several of these in parentheses.
_SHAPE_RE = re.compile(r"([a-z]+[0-9a-z]*)\[([0-9,]*)\](?:\{[^}]*\})?")

# `%name = <result type(s)> <collective-op>(' — the -start async variant
# included, the -done completion excluded (it moves no new bytes).
_COLLECTIVE_RE = re.compile(
    r"=\s+(\([^)]*\)|[a-z]+[0-9a-z]*\[[0-9,]*\](?:\{[^}]*\})?)\s+"
    r"(" + "|".join(re.escape(op) for op in COLLECTIVE_OPS) + r")"
    r"(-start)?\(")


def _shape_byte_list(typed: str) -> List[int]:
    """Byte size of EACH shape in a type string, in order (token/opaque
    types count 0 — they move no tallyable payload)."""
    out: List[int] = []
    for m in _SHAPE_RE.finditer(typed):
        dtype, dims = m.group(1), m.group(2)
        size = _DTYPE_BYTES.get(dtype)
        if size is None:
            out.append(0)
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        out.append(n * size)
    return out


def _shape_bytes(typed: str) -> int:
    """Total bytes of one result-type string (single shape or tuple)."""
    return sum(_shape_byte_list(typed))


def hlo_collective_tally(hlo_text: str) -> Dict[str, Any]:
    """Tally the collective ops in one executable's HLO text.

    Returns ``{"counts": {op: n}, "bytes": {op: total}, "collective_bytes":
    sum}`` where bytes are the RESULT shapes' sizes per execution — the
    payload a step moves through the interconnect (all-gather results are
    the gathered size, reduce-scatter results the scattered shard; a
    first-order bytes-on-the-wire figure, not a ring-step simulation).

    Async ``-start`` forms return a TUPLE whose leading element(s) alias
    the input operand(s) (the XLA ``(operands..., results..., contexts
    ...)`` convention): only the result element(s) count, so the same
    collective tallies identical bytes whether XLA scheduled it sync or
    async — a scheduling flip must never read as a comms-bytes delta."""
    counts = {op: 0 for op in COLLECTIVE_OPS}
    bytes_ = {op: 0 for op in COLLECTIVE_OPS}
    for line in hlo_text.splitlines():
        m = _COLLECTIVE_RE.search(line)
        if not m:
            continue
        typed, op, started = m.group(1), m.group(2), m.group(3)
        counts[op] += 1
        elements = _shape_byte_list(typed)
        if started and typed.startswith("("):
            # operand shapes sit between the regex's trailing '(' and
            # the first ')' (shape layouts use {}, never parens)
            n_ops = len(_shape_byte_list(line[m.end():].split(")")[0]))
            if 0 < n_ops < len(elements):
                results = (elements[n_ops:2 * n_ops]
                           if len(elements) >= 2 * n_ops
                           else elements[n_ops:])
                elements = results
        bytes_[op] += sum(elements)
    return {
        "counts": {op: n for op, n in counts.items() if n},
        "bytes": {op: b for op, b in bytes_.items() if b},
        "collective_bytes": sum(bytes_.values()),
    }


def extract_cost(compiled: Any) -> Dict[str, Any]:
    """XLA's own per-execution accounting off a compiled executable
    (``jax.stages.Compiled`` duck-typed — any object with
    ``cost_analysis``/``memory_analysis``/``as_text`` works, so this
    module never imports jax). Every probe is guarded: a backend that
    reports nothing yields an absent/zero field, never an exception —
    extraction runs inside live trainers/servers."""
    out: Dict[str, Any] = {}
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        if isinstance(ca, dict):
            out["flops_per_execution"] = float(ca.get("flops", 0.0))
            out["bytes_accessed"] = float(ca.get("bytes accessed", 0.0))
    except Exception:
        pass
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            out["memory"] = {
                "argument_bytes": int(
                    getattr(ma, "argument_size_in_bytes", 0)),
                "output_bytes": int(getattr(ma, "output_size_in_bytes", 0)),
                "temp_bytes": int(getattr(ma, "temp_size_in_bytes", 0)),
                "alias_bytes": int(getattr(ma, "alias_size_in_bytes", 0)),
                "generated_code_bytes": int(
                    getattr(ma, "generated_code_size_in_bytes", 0)),
            }
    except Exception:
        pass
    try:
        tally = hlo_collective_tally(compiled.as_text())
        out["collectives"] = tally
        out["collective_bytes_per_step"] = tally["collective_bytes"]
    except Exception:
        pass
    return out


# ------------------------------------------------------- roofline constants

# (device-kind substring, HBM bytes/s, interconnect bytes/s per chip) —
# public-spec roofline numbers, matched in order like perf._PEAK_FLOPS.
# The CPU entry keeps CPU test attributions finite and small.
_BANDWIDTHS = (
    ("v6 lite", 1.6e12, 2.0e11), ("v6e", 1.6e12, 2.0e11),
    ("v5 lite", 8.1e11, 1.6e11), ("v5e", 8.1e11, 1.6e11),
    ("v5p", 2.77e12, 6.0e11), ("v5", 2.77e12, 6.0e11),
    ("v4", 1.2e12, 2.4e11), ("v3", 9.0e11, 1.4e11),
    ("v2", 7.0e11, 1.0e11),
    ("cpu", 2.0e10, 1.0e10),
)


def device_bandwidths(device_kind: str = "cpu") -> Dict[str, float]:
    """(rough, public-spec) per-chip HBM and interconnect bytes/s for a
    jax ``device_kind`` string — the roofline denominators."""
    kind = (device_kind or "cpu").lower()
    for key, hbm, ici in _BANDWIDTHS:
        if key in kind:
            return {"hbm_bytes_per_s": hbm, "ici_bytes_per_s": ici}
    # not in the table is an error, not a default (as utils/perf.py's peaks)
    raise ValueError(f"no HBM/ICI bandwidth known for device kind {kind!r}: "
                     f"add it to obs/ledger.py::_BANDWIDTHS with its source")


def roofline_attribution(*, tokens_per_s: float, flops_per_token: float,
                         peak_flops: float, n_devices: int,
                         steps_per_s: float = 0.0,
                         collective_bytes_per_step: float = 0.0,
                         bytes_accessed: float = 0.0,
                         host_stall_s_per_step: float = 0.0,
                         device_kind: str = "cpu",
                         padding_waste_frac: float = 0.0
                         ) -> Dict[str, float]:
    """The roofline MFU-gap decomposition for one program.

    ``mfu`` is MEASURED (achieved model FLOP/s over peak); each gap term
    is a component's estimated share of per-step wall time, capped in
    trust order (host -> comms -> memory) so the terms can never
    over-explain the gap; ``mfu_gap_residual`` is the exact remainder —
    ``mfu + sum(gaps) == 1`` to float precision, by construction. With
    no per-step wall clock (``steps_per_s`` 0) every modeled term is 0
    and the whole gap lands in the residual: an unattributed gap is
    reported as unattributed, never invented."""
    bw = device_bandwidths(device_kind)
    mfu = 0.0
    if peak_flops > 0 and n_devices > 0:
        mfu = tokens_per_s * flops_per_token / (peak_flops * n_devices)
    mfu = min(max(mfu, 0.0), 1.0)
    gap = 1.0 - mfu
    step_s = 1.0 / steps_per_s if steps_per_s > 0 else 0.0
    host_frac = comms_frac = mem_frac = 0.0
    if step_s > 0:
        # host: measured stall seconds per step (data/h2d/dispatch)
        host_frac = max(0.0, host_stall_s_per_step) / step_s
        # comms: HLO-tallied collective payload over the interconnect
        # roofline (per chip — the payload is per program execution)
        comms_frac = (max(0.0, collective_bytes_per_step)
                      / bw["ici_bytes_per_s"]) / step_s
        # memory-bound: HBM traffic time IN EXCESS of ideal compute time
        # (a compute-bound program's traffic hides under the MXU)
        ideal_s = 0.0
        if peak_flops > 0 and n_devices > 0 and tokens_per_s > 0:
            ideal_s = (tokens_per_s * flops_per_token * step_s
                       / (peak_flops * n_devices))
        mem_s = max(0.0, bytes_accessed / bw["hbm_bytes_per_s"] - ideal_s)
        mem_frac = mem_s / step_s
    gap_host = min(gap, host_frac)
    gap_comms = min(gap - gap_host, comms_frac)
    gap_mem = min(gap - gap_host - gap_comms, mem_frac)
    gap_residual = gap - gap_host - gap_comms - gap_mem
    return {
        "mfu": mfu,
        "mfu_gap_host": gap_host,
        "mfu_gap_comms": gap_comms,
        "mfu_gap_memory_bound": gap_mem,
        "mfu_gap_residual": gap_residual,
        "collective_bytes_per_step": float(
            max(0.0, collective_bytes_per_step)),
        "padding_waste_frac": min(max(float(padding_waste_frac), 0.0), 1.0),
    }


def attribution_columns(row: Dict[str, Any]) -> Dict[str, Any]:
    """The summary subset of a ledger program row: ``mfu`` (unrounded —
    the gap-sum identity must hold to 1e-6, which survives no 4-decimal
    rounding), the four gap terms, the collective payload, and the
    padding waste."""
    keys = ("mfu",) + GAP_TERMS + ("collective_bytes_per_step",
                                   "padding_waste_frac")
    return {k: row[k] for k in keys if k in row}


# ---------------------------------------------------------- padding meter

class PaddingMeter:
    """Active-vs-padded token accounting off the masks the data path
    already carries (``pad_mask``: 1 for real tokens). Thread-safe (the
    device-prefetch wrapper calls the trainer's ``_prepare`` from its
    own thread); ``frac`` is the cumulative padding-waste fraction —
    the share of step FLOPs spent on tokens that are pure padding."""

    def __init__(self) -> None:
        self._active = 0
        self._total = 0
        self._lock = threading.Lock()

    def add(self, active: int, total: int) -> None:
        with self._lock:
            self._active += int(active)
            self._total += int(total)

    @property
    def frac(self) -> float:
        with self._lock:
            if self._total <= 0:
                return 0.0
            return 1.0 - self._active / self._total


# ------------------------------------------------------------ persistence

def ledger_path(run_dir: str) -> str:
    return os.path.join(run_dir, LEDGER_FILENAME)


def write_ledger(run_dir: str, programs: Dict[str, Dict[str, Any]], *,
                 t: float, extra: Optional[Dict[str, Any]] = None) -> str:
    """Atomically replace the run dir's ``perf_ledger.json`` (the beacon
    discipline: a reader never sees a torn file). Telemetry: an OSError
    is swallowed — the ledger must never fail the run it describes."""
    path = ledger_path(run_dir)
    payload = {"t": t, "programs": programs, **(extra or {})}
    tmp = path + ".tmp"
    try:
        with open(tmp, "w") as f:
            json.dump(payload, f)
        os.replace(tmp, path)
    except OSError:
        pass
    return path


def read_ledger(run_dir: str) -> Optional[Dict[str, Any]]:
    """The run dir's ledger snapshot, or None (absent / torn / garbled
    — the readers are status CLIs that must not crash on a live dir)."""
    try:
        with open(ledger_path(run_dir)) as f:
            payload = json.load(f)
    except (OSError, ValueError):
        return None
    return payload if isinstance(payload, dict) else None


def gap_sum_identity(row: Dict[str, Any]) -> float:
    """``mfu + sum(gap terms)`` — the acceptance identity (== 1.0 within
    float precision for any row this module produced). One owner so the
    tests and the report CLI check the same expression."""
    return float(row.get("mfu", 0.0)) + sum(
        float(row.get(k, 0.0)) for k in GAP_TERMS)
