"""Fixture replica worker for serving-fleet tests (no jax import).

Plays the part of ``run/serve.py``'s ``_fleet_worker_main`` through the
REAL :class:`~distributed_pipeline_tpu.serving.fleet.WorkerProtocol` —
same inbox/outbox/ready/swap/beacon files, same chaos hooks, same
clean-inbox-at-startup contract — so the fleet supervisor, router,
watchdog, hot-swap, and goodput-ledger paths get full end-to-end coverage
in tier-1 without paying a jax import per replica process.

The "model" is a deterministic token function of (prompt, params salt):

    token[k] = (31 * sum(prompt) + 1000 * salt + k) % 50021

so replayed requests are token-identical across replicas at the same
params version (the greedy-decode contract) and a hot-swap visibly
changes outputs. "Checkpoints" are ``model_{step:06d}/params.json``
dirs carrying ``{"step": S, "salt": N}`` next to a commit-marker file;
loading json-parses the payload, so a chaos-garbled swap target fails
validation exactly like a corrupt orbax checkpoint does in the real
worker.

Argv: --fleet_worker_dir DIR --replica_id I --checkpoint_dir CKPTS
      [--step N] [--token_interval_s S] [--startup_s S]
      [--cost_ledger true|false] [--serve_transport file|socket]
      [--prefix_cache true|false]
"""

import argparse
import collections
import json
import os
import time

parser = argparse.ArgumentParser()
parser.add_argument("--fleet_worker_dir", required=True)
parser.add_argument("--replica_id", type=int, required=True)
parser.add_argument("--checkpoint_dir", required=True)
parser.add_argument("--step", type=int, default=1)
parser.add_argument("--token_interval_s", type=float, default=0.003)
parser.add_argument("--startup_s", type=float, default=0.0)
parser.add_argument("--cost_ledger", default="false")
parser.add_argument("--serve_transport", default="file")
parser.add_argument("--prefix_cache", default="false")
parser.add_argument("--page_size", type=int, default=4)
ns = parser.parse_args()

from distributed_pipeline_tpu.chaos import (  # noqa: E402
    CHAOS_PLAN_ENV,
    ChaosInjector,
    ChaosPlan,
)
from distributed_pipeline_tpu.serving.fleet import (  # noqa: E402
    ReplicaPaths,
    WorkerProtocol,
)
from distributed_pipeline_tpu.serving.transport import (  # noqa: E402
    prefix_block_hashes,
)

paths = ReplicaPaths.at(ns.fleet_worker_dir, ns.replica_id)
proto = WorkerProtocol(paths, ns.replica_id,
                       transport=ns.serve_transport)
pin = proto.startup()
if ns.startup_s > 0:
    time.sleep(ns.startup_s)


def load_params(step: int):
    """Raises on a garbled payload — the corrupt-swap validation path."""
    path = os.path.join(ns.checkpoint_dir, f"model_{step:06d}",
                        "params.json")
    with open(path) as f:
        payload = json.load(f)
    return int(payload["step"]), int(payload.get("salt", 0))


plan_src = os.environ.get(CHAOS_PLAN_ENV, "")
injector = (ChaosInjector(ChaosPlan.parse(plan_src), rank=ns.replica_id,
                          run_dir=paths.root) if plan_src else None)

cur_step, salt = load_params(int(pin["step"]) if pin else ns.step)
tick = 0
admitted = 0
completed = 0
tokens_out = 0
in_flight = {}  # id -> [payload, tokens]
t_serve0 = time.time()

# Simulated prefix cache (mirrors the real worker's advertisement):
# leading blocks already served here count as hits; every admitted
# block lands in a bounded LRU that rides the beacon/heartbeat.
prefix_on = ns.prefix_cache.strip().lower() in ("true", "1", "yes")
prefix_index: "collections.OrderedDict" = collections.OrderedDict()
prefix_hits = 0
prefix_misses = 0


def index_prefix(prompt) -> None:
    global prefix_hits, prefix_misses
    if not prefix_on:
        return
    hashes = prefix_block_hashes([int(t) for t in prompt], ns.page_size)
    leading = True
    for h in hashes:
        if leading and h in prefix_index:
            prefix_hits += 1
        else:
            leading = False
            prefix_misses += 1
        prefix_index.pop(h, None)
        prefix_index[h] = True
        while len(prefix_index) > 256:
            prefix_index.popitem(last=False)


def beacon_extra():
    if not prefix_on:
        return None
    return {"prefix_index": list(prefix_index),
            "prefix_hits": prefix_hits, "prefix_misses": prefix_misses}


def write_ledger():
    """Mirror of the real worker's --cost_ledger snapshot: the same
    perf_ledger.json file/row shape in the replica dir (mfu + gap terms
    summing to 1 by construction), so the status/export surfacing is
    provable over a real fleet ring without paying a jax import."""
    if ns.cost_ledger.strip().lower() not in ("true", "1", "yes"):
        return
    from distributed_pipeline_tpu.obs import ledger as ledger_lib
    wall = max(time.time() - t_serve0, 1e-6)
    mfu = 0.01 * (1 + ns.replica_id)
    row = {"program": "serve_decode", "mfu": mfu,
           "tokens_per_s": tokens_out / wall,
           "collective_bytes_per_step": 0.0,
           "padding_waste_frac": 0.25}
    gaps = dict.fromkeys(ledger_lib.GAP_TERMS, 0.0)
    gaps["mfu_gap_residual"] = 1.0 - mfu
    row.update(gaps)
    ledger_lib.write_ledger(ns.fleet_worker_dir, {"serve_decode": row},
                            t=time.time())


def token_fn(prompt, k: int) -> int:
    return (31 * sum(int(t) for t in prompt) + 1000 * salt + k) % 50021


def step_decode() -> bool:
    """One 'decode step': every in-flight request gains one token; the
    shared sleep stands in for device time (continuous batching: the
    step costs one interval regardless of occupancy). Traced like the
    real worker's scheduler (DPT_TRACE): one serve.step span per tick."""
    global completed, tokens_out
    if not in_flight:
        return False
    tr = proto.tracer
    with tr.span("serve.step", "serve", args={
            "queued": 0, "active": len(in_flight)} if tr.enabled else None):
        time.sleep(ns.token_interval_s)
    now = time.time()
    for rk in list(in_flight):
        payload, toks = in_flight[rk]
        toks.append(token_fn(payload["prompt"], len(toks)))
        if len(toks) == 1:
            payload["_ttft"] = now - float(payload.get("submit_t", now))
        if len(toks) >= int(payload["max_new_tokens"]):
            proto.write_result({
                "id": int(payload["id"]), "tokens": toks,
                "ttft_s": payload.get("_ttft"), "params_step": cur_step,
                "replays": int(payload.get("replays", 0))})
            completed += 1
            tokens_out += len(toks)
            del in_flight[rk]
    return True


proto.write_beacon(tick)
proto.announce_ready(cur_step)
write_ledger()

while not proto.stop_requested():
    cmd = proto.pending_swap()
    if cmd is not None:
        with proto.tracker.timed("drain_s"):
            while in_flight:
                step_decode()
                tick += 1
                proto.write_beacon(tick)
        with proto.tracker.timed("swap_s"):
            try:
                cur_step, salt = load_params(int(cmd["step"]))
                ok, err = True, ""
            except Exception as e:  # garbage payload: keep old params
                ok, err = False, f"{type(e).__name__}: {e}"
        if ok:
            proto.announce_ready(cur_step)
        proto.ack_swap(int(cmd["id"]), ok, cur_step, err)
    if injector is not None:
        injector.on_serve_tick(admitted, len(in_flight))
    moved = False
    for payload in proto.poll_inbox():
        in_flight[int(payload["id"])] = [payload, []]
        proto.consume(int(payload["id"]))
        index_prefix(payload["prompt"])
        admitted += 1
        moved = True
    moved = step_decode() or moved
    tick += 1
    proto.write_beacon(tick, extra=beacon_extra())
    if not moved:
        time.sleep(0.003)

with proto.tracker.timed("drain_s"):
    while in_flight:
        step_decode()
        tick += 1
        proto.write_beacon(tick)
write_ledger()
proto.write_sidecar({"ticks": tick, "admitted": admitted,
                     "completed": completed, "tokens": tokens_out,
                     "params_step": cur_step,
                     "prefix_hits": prefix_hits,
                     "prefix_misses": prefix_misses})
proto.tracer.close()
proto.close()
raise SystemExit(0)
