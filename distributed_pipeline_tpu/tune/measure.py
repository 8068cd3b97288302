"""Child-process layout measurement: ONE owner for spawn, env pinning,
the JSON result contract, and OOM/timeout error-row folding.

Every number the tuner ranks on comes from a CHILD process, for three
reasons:

* the mesh under test may need a DIFFERENT device count than the parent
  (``--xla_force_host_platform_device_count`` is consumed at backend
  init, so the parent's jax can never re-shape itself);
* a candidate that OOMs or wedges must fold to a pruned error row, never
  take the search down with it — a subprocess boundary is the only
  reliable blast wall around an XLA allocation failure;
* each candidate starts from a cold, identical runtime (no cross-
  candidate compile-cache-in-memory or allocator warmth skewing ranks;
  the on-disk persistent compile cache is shared deliberately, so
  resumed/repeated trials pay a lookup instead of a compile).

The child prints ONE machine-readable JSON row on stdout (the parent
parses the last non-empty line); everything else
goes to stderr. Two modes:

* single arm (``--spec``): the successive-halving screen — warmup then a
  timed window, reporting steps/s + the footprint gauges + steady
  recompiles;
* paired (``--spec --spec_b``): ABBA finals — both loops live, short
  timed windows interleaved with alternating order, delta from the
  position-balanced totals (the measure_prefetch_ab protocol; sequential
  legs on a drifting box flip the delta's sign run to run).

Fault injection for tests/acceptance (``DPT_TUNE_INJECT``): a comma list
of ``oom:<cid-glob>`` / ``timeout:<cid-glob>`` entries checked BEFORE the
jax import, so an injected candidate dies (or wedges) exactly like a real
OOM/hang but in milliseconds.
"""

from __future__ import annotations

import argparse
import fnmatch
import json
import os
import subprocess
import sys
import time
from typing import Any, Dict, List, Optional, Tuple

__all__ = [
    "INJECT_ENV", "check_injected", "child_env", "run_child",
    "build_loop", "warmup_loop", "timed_window", "arm_row",
    "measure_single", "measure_pair",
]

INJECT_ENV = "DPT_TUNE_INJECT"

_FORCE_FLAG = "--xla_force_host_platform_device_count"


def check_injected(cid: str) -> None:
    """Honor an injected fault for this candidate id. Called first thing
    in the child — before the jax import — so the injected OOM raises
    (and the injected hang sleeps) in milliseconds, not after a compile."""
    for tok in os.environ.get(INJECT_ENV, "").split(","):
        tok = tok.strip()
        if not tok or ":" not in tok:
            continue
        kind, pat = tok.split(":", 1)
        if not fnmatch.fnmatchcase(cid, pat):
            continue
        if kind == "oom":
            raise RuntimeError(
                f"RESOURCE_EXHAUSTED: injected tune OOM for {cid}")
        if kind == "timeout":
            print(f"# injected hang for {cid}", file=sys.stderr, flush=True)
            time.sleep(3600)


def child_env(force_devices: Optional[int] = None,
              base: Optional[dict] = None) -> dict:
    """Measurement-child environment. ``force_devices`` pins the child to
    CPU with that many forced host devices (the off-TPU path: the parent
    may hold only one real device, or a DIFFERENT forced count from the
    test harness — any inherited force flag is replaced, other XLA flags
    kept). ``None`` leaves the platform alone: on TPU the child sees the
    real chips."""
    env = dict(os.environ if base is None else base)
    if force_devices:
        env["JAX_PLATFORMS"] = "cpu"
        flags = [f for f in env.get("XLA_FLAGS", "").split()
                 if not f.startswith(_FORCE_FLAG)]
        flags.append(f"{_FORCE_FLAG}={int(force_devices)}")
        env["XLA_FLAGS"] = " ".join(flags)
    return env


def run_child(module: str, args: List[str], *, env: Optional[dict] = None,
              timeout_s: float = 150.0, cwd: Optional[str] = None,
              tag: str = "child") -> Dict[str, Any]:
    """Run ``python -m module args`` and return its last-stdout-line JSON
    row. EVERY failure mode folds to an ``{"error": ...}`` row — timeout
    (the wedged-candidate case), nonzero rc (OOM and friends), empty or
    unparseable output — so a caller iterating candidates can never be
    aborted by one of them."""
    cmd = [sys.executable, "-m", module, *args]
    try:
        proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                              timeout=timeout_s, cwd=cwd)
    except subprocess.TimeoutExpired:
        return {"error": f"{tag} exceeded its {timeout_s:.0f}s timeout"}
    lines = [ln for ln in (proc.stdout or "").splitlines() if ln.strip()]
    if proc.returncode != 0 or not lines:
        tail = (proc.stderr or proc.stdout or "")[-300:]
        return {"error": f"{tag} rc={proc.returncode}: {tail}"}
    try:
        row = json.loads(lines[-1])
    except ValueError:
        return {"error": f"{tag} wrote unparseable output: "
                         f"{lines[-1][:200]}"}
    if not isinstance(row, dict):
        return {"error": f"{tag} wrote a non-object row: {row!r}"[:300]}
    return row


# ------------------------------------------------------------- child side

def build_loop(spec: Dict[str, Any]):
    """TrainLoop for one candidate spec. The spec is plain JSON — model
    dims, mesh axis sizes, the rule table in the ``--partition_rules``
    wire format, the ZeRO-1 flag — so the parent never has to ship live
    objects across the process boundary."""
    from ..data import load_data_from_args
    from ..models import create_model_from_config
    from ..parallel import make_mesh
    from ..parallel.partition import rules_from_json
    from ..utils.trainer import TrainLoop

    wl = create_model_from_config(
        model_family=spec["family"], model_size=spec.get("size", "base"),
        seq_len=spec["seq_len"], vocab_size=spec["vocab"],
        hidden_size=spec.get("hidden", 0),
        num_layers=spec.get("layers", 0), num_heads=spec.get("heads", 0),
        dtype=spec.get("dtype", "float32"))
    dataset = ("synthetic-lm" if spec["family"] == "gpt2"
               else "synthetic-seq2seq")
    batch = int(spec["batch"])
    seed = int(spec.get("seed") or 0)
    data = load_data_from_args(
        "train", batch_size=batch, dataset=dataset,
        seq_len=spec["seq_len"], vocab_size=spec["vocab"], seed=seed,
        num_loader_proc=2)
    mesh_axes = spec.get("mesh") or {}
    if mesh_axes:
        kw = {("dp" if a == "data" else a): int(v)
              for a, v in mesh_axes.items()}
        mesh = make_mesh(**kw)
    else:
        mesh = make_mesh(dp=-1)
    rules = (rules_from_json(spec["rules"]) if spec.get("rules")
             else None)
    return TrainLoop(
        model=wl, data=data, batch_size=batch,
        microbatch=int(spec.get("microbatch") or 0) or batch, lr=1e-4,
        ema_rate="0.9999", learning_steps=0, log_interval=10 ** 9,
        save_interval=10 ** 9, mesh=mesh, checkpoint_dir="", seed=seed,
        sanitize=True, shard_optimizer=bool(spec.get("shard_optimizer")),
        partition_rules=rules)


def warmup_loop(loop, steps: int) -> None:
    import jax

    for _ in range(max(1, steps)):
        m = loop.run_step(loop.next_batch())
    float(jax.device_get(m["loss"]))


def timed_window(loop, steps: int) -> float:
    import jax

    if steps < 1:
        # fail the CHILD loudly up front: a 0-step window would hit an
        # unbound loop variable below and every candidate would fold to
        # a cryptic pruned row instead of one clear config error
        raise ValueError(f"timed window needs >= 1 step, got {steps}")
    t0 = time.perf_counter()
    for _ in range(steps):
        m = loop.run_step(loop.next_batch())
    float(jax.device_get(m["loss"]))
    return time.perf_counter() - t0


def arm_row(loop, n_steps: int, total_s: float) -> Dict[str, Any]:
    """One arm's result fields: rate + the footprint gauges the tuner
    ranks and reports on."""
    import jax

    fp = loop.footprint()
    return {
        "steps_per_s": round(n_steps / total_s, 4),
        "n_params": loop.n_params,
        "params_bytes": fp["params_bytes"],
        "opt_state_bytes": fp["opt_state_bytes"],
        "opt_state_bytes_per_replica": fp["opt_state_bytes_per_replica"],
        "ema_bytes_per_replica": fp["ema_bytes_per_replica"],
        "peak_live_bytes": fp["peak_live_bytes"],
        "dp": loop.mesh.shape["data"],
        "mesh": {a: int(s) for a, s in loop.mesh.shape.items() if s > 1},
        "n_devices": jax.device_count(),
        "compile_s": round(loop.compile_time_s or 0.0, 3),
    }


def measure_single(spec: Dict[str, Any], *, steps: int,
                   warmup: int = 2) -> Dict[str, Any]:
    """Screen measurement: one loop, warmup (first step pays the
    compile), one timed window."""
    loop = build_loop(spec)
    try:
        warmup_loop(loop, warmup)
        dt = timed_window(loop, steps)
        row = arm_row(loop, steps, dt)
        row["steady_recompile_count"] = loop.steady_recompile_count
        row["window_steps"] = steps
    finally:
        recompiles = loop.stop_sanitizer()
    row["recompile_count"] = recompiles
    return row


def measure_pair(spec_a: Dict[str, Any], spec_b: Dict[str, Any], *,
                 rounds: int, window_steps: int,
                 warmup: int = 3) -> Dict[str, Any]:
    """Paired interleaved ABBA between two candidate layouts in ONE
    process: both loops stay alive, short timed windows alternate order
    each round, and the delta comes from the position-balanced totals
    (even rounds cancel the measured second-window position cost — the
    measure_prefetch_ab rationale). Arm A is built and warmed FIRST so
    arm B's RecompileMonitor never sees A's construction compiles;
    monitors uninstall in reverse install order so their saved
    jax_log_compiles flags nest."""
    rounds += rounds % 2  # even: ABBA position balance
    loop_a = build_loop(spec_a)
    try:
        warmup_loop(loop_a, warmup)
        loop_b = build_loop(spec_b)
        try:
            warmup_loop(loop_b, warmup)
            a_dts: List[float] = []
            b_dts: List[float] = []
            for r in range(rounds):
                pair: Tuple = ((loop_a, a_dts), (loop_b, b_dts))
                for loop, dts in (pair[::-1] if r % 2 else pair):
                    dts.append(timed_window(loop, window_steps))
            n_steps = rounds * window_steps
            row_a = arm_row(loop_a, n_steps, sum(a_dts))
            row_b = arm_row(loop_b, n_steps, sum(b_dts))
            row_b["steady_recompile_count"] = loop_b.steady_recompile_count
        finally:
            recompiles_b = loop_b.stop_sanitizer()
    finally:
        loop_a.stop_sanitizer()
    row_b["recompile_count"] = recompiles_b
    return {
        "ab_method": "paired-interleaved",
        "ab_rounds": rounds, "ab_window_steps": window_steps,
        "a": row_a, "b": row_b,
        # identical step counts: the totals ratio IS the rate ratio
        # (positive = B faster than A)
        "ab_delta_pct": round(100.0 * (sum(a_dts) / sum(b_dts) - 1.0), 2),
    }


# --------------------------------------------------------------- child CLI

def create_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--spec", required=True,
                    help="candidate spec JSON (model dims + mesh + rules "
                         "+ shard_optimizer)")
    ap.add_argument("--spec_b", default="",
                    help="second candidate: run the paired ABBA protocol "
                         "between the two instead of a single screen")
    ap.add_argument("--steps", type=int, default=4,
                    help="timed window length (single-arm mode)")
    ap.add_argument("--warmup", type=int, default=2)
    ap.add_argument("--rounds", type=int, default=6,
                    help="ABBA rounds (paired mode; forced even)")
    ap.add_argument("--window_steps", type=int, default=4,
                    help="steps per ABBA window (paired mode)")
    return ap


def main(argv=None) -> None:
    args = create_parser().parse_args(argv)
    spec = json.loads(args.spec)
    spec_b = json.loads(args.spec_b) if args.spec_b else None
    # Injection check BEFORE the jax import: an injected candidate must
    # fail in milliseconds, exactly where a real pre-compile OOM would.
    check_injected(str(spec.get("cid", "")))
    if spec_b is not None:
        check_injected(str(spec_b.get("cid", "")))

    from ..utils import logger

    # stdout carries the ONE JSON row; silence the logger's default sink
    logger.configure(format_strs=[])
    if spec_b is not None:
        row = measure_pair(spec, spec_b, rounds=args.rounds,
                           window_steps=args.window_steps,
                           warmup=args.warmup)
        row["cid"], row["cid_b"] = spec.get("cid"), spec_b.get("cid")
    else:
        row = measure_single(spec, steps=args.steps, warmup=args.warmup)
        row["cid"] = spec.get("cid")
    print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
