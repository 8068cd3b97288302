#!/usr/bin/env python3
"""One run of one benchmark cell.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s>
        --trace <0|1>

Finds the cell in BENCHMARK.json, its configuration in
``benchmark/configs/<config>.json``, its traffic in
``benchmark/traffic/<traffic>.json`` and each per-layer metric's reader through
``benchmark/layer_metrics/<metric>.json``; the traffic's ``kind`` names the
driver (``harness/<kind>_driver.py``), the configuration's ``family`` and
``reference`` the adapter to the program and the plain reference. Warms the
cell's shapes (set-up), measures for ``--seconds``, checks what the timed path
produced against the plain reference, and prints one JSON object as the last
line of standard output. Without a TPU holding the chips
the cell asks for it exits non-zero and prints no result; ``--rehearse`` walks
the same code at a tiny size on whatever platform is there and reports no
metric at all.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse      # noqa: E402
import contextlib    # noqa: E402
import glob          # noqa: E402
import importlib.util  # noqa: E402
import json          # noqa: E402
import os            # noqa: E402
import shutil        # noqa: E402
import sys           # noqa: E402
import tempfile      # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_SECONDS = 3.0   # length of the traced sub-window of a --trace 1 run


def load_json(*parts: str) -> Dict[str, Any]:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def resolve_cell(workload: str) -> Dict[str, Any]:
    """The cell and everything that belongs to it, found by name."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json "
                         f"(have: {sorted(cells)})")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = load_json(ROOT, configs[cell["config"]]["file"])
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")

    def listed(metric: Dict[str, Any]) -> bool:
        return workload in metric.get("workloads", [workload])
    end_to_end = [m for m in bench["end_to_end"] if listed(m)]
    moved = {m["name"] for m in end_to_end}
    # a per-layer metric without a ``workloads`` key is reported in every
    # cell that reports the end-to-end metric it moves
    per_layer = [m for m in bench["per_layer"]
                 if listed(m) and ("workloads" in m or m["moves"] in moved)]
    return {"cell": cell, "config": cfg, "traffic": traffic,
            "end_to_end": end_to_end, "per_layer": per_layer}


def load_reader(metric_name: str):
    """``layer_metrics/<metric>.json`` names ``<module>.<function>`` under
    ``benchmark/readers/``; a later PR adds a metric as two new files."""
    spec = load_json(HERE, "layer_metrics", metric_name + ".json")
    module, func = spec["reader"].rsplit(".", 1)
    path = os.path.join(HERE, "readers", module + ".py")
    mspec = importlib.util.spec_from_file_location(
        f"benchmark_readers_{module}", path)
    mod = importlib.util.module_from_spec(mspec)
    mspec.loader.exec_module(mod)
    return getattr(mod, func), spec


class Profiler:
    """jax.profiler around the traced sub-window; ``stop`` reduces the trace
    and deletes it (little is left on disk)."""

    seconds = TRACE_SECONDS

    def __init__(self) -> None:
        self._dir: Optional[str] = None
        self._window = None

    def start(self) -> None:
        import jax

        self._dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self._dir, profiler_options=opts)
        self._window = jax.profiler.TraceAnnotation("bench_window")
        self._window.__enter__()

    def stop(self):
        import jax

        from harness import trace_reduce

        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        try:
            found = glob.glob(os.path.join(self._dir, "**", "*.xplane.pb"),
                              recursive=True)
            if not found:
                raise RuntimeError("the profiler wrote no .xplane.pb")
            return trace_reduce.summarize(trace_reduce.load_xplane(found[0]))
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)


def annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def devices_for(chips: int, rehearse: bool) -> List[Any]:
    import jax

    devs = jax.devices()
    if rehearse:
        if len(devs) < chips:
            raise SystemExit(
                f"rehearsal of a {chips}-chip cell needs XLA_FLAGS="
                f"--xla_force_host_platform_device_count={chips}")
        return devs[:chips]
    if devs[0].platform != "tpu":
        print(f"benchmark: no accelerator: jax.devices() is "
              f"{devs[0].platform!r}; a device metric comes only from the "
              f"chip (use --rehearse to walk the code on this platform)",
              file=sys.stderr)
        raise SystemExit(3)
    if len(devs) < chips:
        print(f"benchmark: the cell asks for {chips} chips, jax finds "
              f"{len(devs)}", file=sys.stderr)
        raise SystemExit(3)
    return devs[:chips]


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", default="0",
                    help="calibration: also read the lower-precision "
                         "controls and the planted faults (not a "
                         "benchmark run); 1 for all of them, or their "
                         "names with commas between")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny widths on any platform; prints no metric")
    ns = ap.parse_args(argv)

    sys.path.insert(0, HERE)
    sys.path.insert(0, ROOT)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    found = resolve_cell(ns.workload)
    cell, cfg, traffic = found["cell"], found["config"], found["traffic"]
    if ns.rehearse:
        from harness.rehearse import shrink
        cfg, traffic = shrink(cfg, traffic)

    import jax

    from distributed_pipeline_tpu.utils.perf import (
        enable_persistent_compilation_cache)
    from harness import driver_for

    devices = devices_for(cell["chips"], ns.rehearse)
    enable_persistent_compilation_cache()
    res = driver_for(traffic).run(
        cell, cfg, traffic, seed=ns.seed, seconds=ns.seconds,
        trace=bool(ns.trace), devices=devices, t_process=T_PROCESS,
        annotate=annotate, profiler=Profiler(),
        control=[] if ns.control == "0" else ns.control.split(","))

    checks = res["checks"]
    correct = all(row["ok"] for row in checks.values())
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": res["memory_peak_bytes"]}
    out: Dict[str, Any] = {
        "correct": bool(correct), "attempted": res["attempted"],
        "failed": res["failed"], "metrics": {}, "device": device}
    if ns.rehearse:
        out["rehearsal"] = True
    elif ns.trace:
        summary = res["traced"]["summary"]
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        out["breakdown"] = summary.breakdown()
        from harness.peaks import peaks_for
        ctx = {"trace": summary, "counters": res["counters"], "cell": cell,
               "config": cfg, "traffic": traffic,
               "peaks": peaks_for(devices[0].device_kind)}
        for m in found["per_layer"]:
            reader, _spec = load_reader(m["name"])
            value = reader(ctx)
            if value is not None:
                out["metrics"][m["name"]] = {"value": value,
                                             "unit": m["unit"]}
    else:
        for m in found["end_to_end"]:
            value = res["end_to_end"].get(m["name"])
            if value is not None:
                out["metrics"][m["name"]] = {"value": value,
                                             "unit": m["unit"]}
    out["counters"] = {k: v for k, v in res["counters"].items()
                       if isinstance(v, (int, float, str, list, dict))
                       or v is None}
    out["checks"] = checks
    for name, row in checks.items():
        print(f"check {name}: {row['value']} (limit {row['limit']}) "
              f"{'ok' if row['ok'] else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    with contextlib.suppress(BrokenPipeError):
        sys.exit(main())
