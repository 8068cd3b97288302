"""Serving entry point: continuous-batching decode, single replica or fleet.

``run/sample.py`` is a one-shot batch script — it decodes N fixed batches
and exits. This entry serves TRAFFIC, in three modes:

* SINGLE (default): requests (a JSONL prompt file or a synthetic
  workload) stream through one in-process :class:`serving.DecodeServer`
  — prefill/decode as separately AOT-compiled executables over the paged
  KV cache, free slots re-admitting queued requests every step. Arrivals
  come from the legacy step-cadence knob (``--traffic steps``) or a
  seeded wall-clock process (``--traffic poisson|bursty|diurnal``).
* FLEET (``--replicas N``, ISSUE 11): N replica WORKER processes — each
  its own supervised launcher ring with restart budget/backoff and the
  beacon-mtime hang watchdog — behind a health-gated, load-aware request
  router with a durable journal: in-flight requests on a killed/wedged
  replica replay on a sibling, and ``--swap_after_requests`` rolls a
  newer checkpoint through the fleet with zero downtime (>= N-1 replicas
  serving at every instant; a corrupt target aborts on the canary). The
  fleet parent process never imports jax.
* WORKER (internal, ``--fleet_worker_dir``): one replica — loads the
  checkpoint, serves its inbox, beacons every tick, executes hot-swap
  commands, and writes the serving goodput sidecar.

    python -m distributed_pipeline_tpu.run.serve --checkpoint_path RUNDIR \
        --decode_slots 64 --page_size 16 --max_new_tokens 128
    python -m distributed_pipeline_tpu.run.serve --checkpoint_path RUNDIR \
        --replicas 3 --traffic poisson --rate_rps 8 --synthetic_requests 64

stdout carries one machine-readable JSON summary (throughput, TTFT
percentiles, compile split, recompile count; fleet mode adds replay/swap/
goodput-ledger fields); progress goes to stderr.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

from ..config.serve import ServeSettings


def create_parser() -> argparse.ArgumentParser:
    return ServeSettings.to_argparse()


# worker argv: every serve setting EXCEPT the fleet-parent-only knobs
# (the fleet appends --fleet_worker_dir/--replica_id per replica). One
# owner, jax-free, so the argv plumbing is unit-testable: anything added
# to ServeSettings — e.g. cost_ledger — reaches the replica workers.
# The disagg knobs are parent-only too: the parent appends explicit
# --disagg_role/--disagg_links/--disagg_peers per worker tier.
_PARENT_ONLY = {"replicas", "fleet_dir", "fleet_worker_dir",
                "replica_id", "out", "prompt_file",
                "disagg", "disagg_role", "disagg_links", "disagg_peers"}


def _worker_argv(settings: ServeSettings) -> list:
    argv = []
    for name in type(settings).model_fields:
        if name in _PARENT_ONLY:
            continue
        argv += [f"--{name}", str(getattr(settings, name))]
    return argv


def _load_requests(settings: ServeSettings, max_prompt_len: int,
                   vocab_size: int):
    """(prompt int32 [L], max_new_tokens) pairs from the prompt file, or a
    synthetic workload of random prompts."""
    import numpy as np

    if settings.prompt_file:
        out = []
        with open(settings.prompt_file) as f:
            for line in f:
                if not line.strip():
                    continue
                row = json.loads(line)
                prompt = np.asarray(row["prompt_ids"], np.int32)
                if prompt.shape[0] > max_prompt_len:
                    # keep the TAIL — the context a continuation wants —
                    # and say so, rather than crashing the whole run on
                    # one long prompt
                    print(f"# serve: truncating a {prompt.shape[0]}-token "
                          f"prompt to the last {max_prompt_len}",
                          file=sys.stderr)
                    prompt = prompt[-max_prompt_len:]
                out.append((np.minimum(prompt, vocab_size - 1),
                            int(row.get("max_new_tokens",
                                        settings.max_new_tokens))))
        return out
    if settings.traffic != "steps" or settings.shared_prefix_len > 0:
        # traffic-process synthetic workload: prompts come from the same
        # seeded generator as the schedule (deterministic cross-process)
        from ..serving.traffic import TrafficGenerator

        gen = _generator(settings, default="poisson")
        plen = min(settings.synthetic_prompt_len or max_prompt_len,
                   max_prompt_len)
        reqs = gen.requests(settings.synthetic_requests,
                            vocab_size=vocab_size, prompt_len=plen,
                            max_new_tokens=settings.max_new_tokens,
                            shared_prefix_len=min(
                                settings.shared_prefix_len, plen))
        return [(r.prompt, r.max_new_tokens) for r in reqs]
    rng = np.random.default_rng(settings.seed)
    plen = min(settings.synthetic_prompt_len or max_prompt_len,
               max_prompt_len)
    return [(rng.integers(4, vocab_size, (plen,)).astype(np.int32), 0)
            for _ in range(settings.synthetic_requests)]


def _generator(settings: ServeSettings, default: str = "poisson"):
    """The settings' traffic process as a TrafficGenerator ('steps' maps
    to ``default`` — fleet mode has no scheduler-step clock to count)."""
    from ..serving.traffic import TrafficGenerator

    process = settings.traffic if settings.traffic != "steps" else default
    return TrafficGenerator(
        process, settings.rate_rps, settings.seed,
        burst_every_s=settings.burst_every_s,
        burst_size=settings.burst_size,
        diurnal_period_s=settings.diurnal_period_s,
        diurnal_floor=settings.diurnal_floor)


def _quantize_for_serving(settings: ServeSettings, params):
    """--serve_quant int8: round-trip the replica's weights through int8
    storage quantization (serving/quantize.py). Raises QuantizationError
    on a corrupt/pathological tree — at initial load that fails the
    worker before ready; inside a hot-swap restore it fails the swap ack,
    so the r13 canary keeps a bad quantization off the fleet."""
    if settings.serve_quant == "off":
        return params
    from ..serving.quantize import quantize_params
    return quantize_params(params)


def _resolve_chaos_plan(settings: ServeSettings):
    """--chaos_plan flag or the DPT_CHAOS_PLAN env (the launcher channel
    training uses); None when neither is set."""
    from ..chaos import CHAOS_PLAN_ENV, ChaosPlan

    src = settings.chaos_plan or os.environ.get(CHAOS_PLAN_ENV, "")
    return ChaosPlan.parse(src) if src else None


# =========================================================== single replica

def _serve_single(settings: ServeSettings) -> dict:
    import numpy as np

    from ..ops.flash_decode import resolve_decode_impl
    from ..parallel import make_mesh
    from ..serving import DecodeServer
    from ..utils import logger
    from ..utils.perf import device_summary
    from .sample import load_run

    if settings.trace:
        # tracing instruments the FLEET protocol layers (per-request
        # router trace ids, replica worker spans); the in-process
        # single-replica path has no run-dir artifacts to stitch — say
        # so instead of silently writing nothing (a user would otherwise
        # conclude tracing is broken)
        print("# serve: --trace instruments fleet mode (--replicas N); "
              "ignored on the single-replica path", file=sys.stderr,
              flush=True)

    mesh = make_mesh()
    wl, params, _targs, step, which = load_run(
        settings.checkpoint_path, settings.step, settings.ema, mesh=mesh)
    params = _quantize_for_serving(settings, params)

    max_len = settings.max_len or wl.seq_len
    max_prompt_len = settings.max_prompt_len or max(2, max_len // 2)
    server = DecodeServer(
        wl, params, decode_slots=settings.decode_slots,
        page_size=settings.page_size, max_pages=settings.max_pages,
        max_prompt_len=max_prompt_len, max_len=max_len,
        prefill_batch=settings.prefill_batch,
        decode_span=settings.decode_span,
        dispatch_lag=settings.dispatch_lag,
        temperature=settings.temperature, top_k=settings.top_k,
        top_p=settings.top_p, seed=settings.seed,
        eos_id=settings.eos_id if settings.eos_id >= 0 else None,
        mesh=mesh, sanitize=settings.sanitize,
        prefix_cache=settings.prefix_cache,
        decode_impl=settings.decode_impl,
        kv_quant=settings.kv_quant,
        spec_tokens=settings.spec_tokens,
        spec_draft=settings.spec_draft,
        draft_layers=settings.draft_layers)
    # the engine holds its serving form of the tree (the matrices in the
    # compute dtype); let the float32 masters go with this name
    del params

    pending = _load_requests(settings, max_prompt_len, wl.model.vocab_size)
    logger.info(f"serving {len(pending)} requests on {settings.decode_slots} "
                f"slots (page_size={settings.page_size}, "
                f"pool={server.mgr.num_pages} pages)")

    # wall-clock arrival schedule (the synthetic-arrival-knob replacement);
    # None keeps the legacy per-N-steps cadence
    offsets = (None if settings.traffic == "steps"
               else _generator(settings).schedule(len(pending)))

    t0 = time.perf_counter()
    submitted = []
    cadence = settings.arrival_every_steps
    steps = 0
    warm_compiles = None  # XLA compiles up to the first fetched token:
    # prefill+decode (and init fills) have all built by then, so any
    # growth past this snapshot is a steady-state recompile — the
    # regression the gauge exists to catch
    try:  # submits included: a bad request must still stop_sanitizer
        if offsets is None and cadence <= 0:
            # saturating workload: everything queued up front
            for prompt, n in pending:
                submitted.append(server.submit(
                    prompt, n or settings.max_new_tokens))
            pending = []
        while pending or server.busy:
            if offsets is not None:
                now = time.perf_counter() - t0
                while pending and offsets[len(submitted)] <= now:
                    prompt, n = pending.pop(0)
                    submitted.append(server.submit(
                        prompt, n or settings.max_new_tokens))
                if pending and not server.busy:
                    # idle gap before the next arrival: sleep it off
                    # instead of spinning no-op scheduler ticks
                    time.sleep(min(max(0.0, offsets[len(submitted)] - now),
                                   0.005))
            elif pending and cadence > 0 and steps % cadence == 0:
                prompt, n = pending.pop(0)
                submitted.append(server.submit(
                    prompt, n or settings.max_new_tokens))
            server.step()
            if warm_compiles is None and server.tokens_fetched > 0:
                warm_compiles = server.recompile_count
            steps += 1
        server.drain()
    finally:
        recompiles = server.stop_sanitizer()
        # evidence sidecar beside the served checkpoint (ISSUE 19
        # runtime bridge: analysis --runtime-evidence reads it)
        _sr_dir = settings.checkpoint_path
        if _sr_dir and not os.path.isdir(_sr_dir):
            _sr_dir = os.path.dirname(_sr_dir) or "."
        server.write_sanitize_report(_sr_dir)
    wall_s = time.perf_counter() - t0

    if settings.out:
        with open(settings.out, "w") as f:
            for req in submitted:
                f.write(json.dumps({
                    "id": req.id, "prompt": req.prompt.tolist(),
                    "tokens": req.tokens,
                    "ttft_s": round(req.ttft_s or 0.0, 4)}) + "\n")

    ttft = server.ttft.summary()
    result = {
        "step": step, "params": which,
        "requests": len(submitted),
        "decode_tokens": server.tokens_fetched,
        # replicated decode state: every chip runs the same step, so the
        # service rate IS the per-chip rate (dividing by device_count
        # would understate it)
        "decode_tokens_per_s_per_chip": round(
            server.tokens_fetched / max(wall_s, 1e-9), 1),
        "time_to_first_token_s": round(ttft["mean"], 4),
        "ttft_p50_s": round(ttft["p50"], 4),
        "ttft_p95_s": round(ttft["p95"], 4),
        "decode_steps": server.decode_steps,
        "prefill_steps": server.prefill_steps,
        "decode_slots": settings.decode_slots,
        "page_size": settings.page_size,
        "traffic": settings.traffic,
        "compile_time_s": round(server.compile_time_s, 3),
        "compile_s": {k: round(v, 3) for k, v in
                      server.engine.compile_times.items()},
        "wall_s": round(wall_s, 2),
        # what ran where: the device as jax reports it, and the decode
        # attention arm the engine's pool geometry resolved to
        "device": device_summary(),
        # (a model that brings its own decode step has the one arm)
        "decode_impl": "xla" if server.engine.chunked
        else resolve_decode_impl(
            settings.decode_impl,
            (server.mgr.num_pages, settings.page_size,
             wl.model.num_heads,
             wl.model.hidden_size // wl.model.num_heads),
            "int8" if settings.kv_quant == "int8" else wl.model.dtype),
    }
    if settings.spec_tokens > 0:
        # every fetched token is target-verified, so the accepted rate IS
        # the service rate; accept_rate is the draft's hit rate (the
        # dispatch-amortization lever)
        result["spec_tokens"] = settings.spec_tokens
        result["accept_rate"] = round(server.accept_rate, 4)
        result["accepted_tokens_per_s"] = result[
            "decode_tokens_per_s_per_chip"]
    result.update(server.prefix_stats())
    # the server's own account of its ticks since the first token: by
    # kind, the time between ticks, dry dispatches, stalls (no records)
    server.ticks.close()
    result["ticks"] = server.ticks.summary(records=False)
    if settings.cost_ledger:
        # roofline attribution off the live executables (obs/ledger.py);
        # n_devices=1: replicated decode, per-chip == service rate
        result["ledger"] = server.cost_ledger(wall_s=wall_s, n_devices=1)
    if settings.sanitize:
        # steady-state growth past the warm snapshot must be 0: the two
        # phase executables compile exactly once, during warmup
        result["recompile_count"] = (recompiles - warm_compiles
                                     if warm_compiles is not None
                                     else recompiles)
        result["xla_compiles_total"] = recompiles
    print(json.dumps(result))
    return result


# ============================================================ fleet worker

def _fleet_worker_main(settings: ServeSettings) -> dict:
    """One replica: serve the inbox until told to stop. Runs under a
    supervising launcher ring — beacons every tick (hang-watchdog
    liveness + kill flight recorder), clears stale inbox entries at
    startup (the router replays them), executes hot-swap commands with a
    local drain, and books drain/swap time so the fleet goodput ledger
    accounts every second."""
    import numpy as np

    from ..chaos import ChaosInjector, ChaosPlan
    from ..parallel import make_mesh
    from ..serving import DecodeServer
    from ..serving.fleet import ReplicaPaths, WorkerProtocol
    from ..utils import checkpoint as ckpt_lib
    from .sample import load_run

    rid = settings.replica_id
    paths = ReplicaPaths.at(settings.fleet_worker_dir, rid)
    proto = WorkerProtocol(paths, rid,
                           trace_armed=True if settings.trace else None,
                           transport=settings.serve_transport)
    pin = proto.startup()  # inbox cleared; params pin from a prior swap

    plan = _resolve_chaos_plan(settings)
    injector = (ChaosInjector(plan, rank=rid, run_dir=paths.root)
                if plan else None)

    step = int(pin["step"]) if pin else settings.step
    mesh = make_mesh()
    wl, params, _targs, step, _which = load_run(
        settings.checkpoint_path, step, settings.ema, mesh=mesh)
    params = _quantize_for_serving(settings, params)
    # abstract restore target for hot-swap restores: the SAME concrete-
    # sharding construction the initial load used (one owner —
    # run/sample.restore_target), so a swapped tree restores on any
    # replica topology AND meets the pinned AOT signature exactly
    from .sample import restore_target
    abstract = restore_target(wl, mesh)

    max_len = settings.max_len or wl.seq_len
    max_prompt_len = settings.max_prompt_len or max(2, max_len // 2)
    server = DecodeServer(
        wl, params, decode_slots=settings.decode_slots,
        page_size=settings.page_size, max_pages=settings.max_pages,
        max_prompt_len=max_prompt_len, max_len=max_len,
        prefill_batch=settings.prefill_batch,
        decode_span=settings.decode_span,
        dispatch_lag=settings.dispatch_lag,
        temperature=settings.temperature, top_k=settings.top_k,
        top_p=settings.top_p, seed=settings.seed,
        eos_id=settings.eos_id if settings.eos_id >= 0 else None,
        mesh=mesh, sanitize=settings.sanitize,
        prefix_cache=settings.prefix_cache,
        decode_impl=settings.decode_impl,
        kv_quant=settings.kv_quant,
        spec_tokens=settings.spec_tokens,
        spec_draft=settings.spec_draft,
        draft_layers=settings.draft_layers,
        # the scheduler's own serve.* spans and each request's life, in
        # this replica's shard under the router's trace id
        tracer=proto.tracer)
    del params  # the engine holds its serving form; let the masters go

    def _restore_params(target: str):
        # the abstract target's shardings place the tree during restore;
        # --serve_quant re-quantizes the SWAPPED tree too — a failing
        # guard raises here, the swap acks not-ok, and the canary aborts
        return _quantize_for_serving(
            settings, ckpt_lib.restore_checkpoint(target, abstract))

    # Warmup BEFORE announcing ready: the prefill/decode AOT compiles run
    # here, so the first routed request's TTFT is service time, not
    # compile time — and the watchdog (armed by the FIRST beacon) never
    # sees compilation as a hang. max_new_tokens=2: the FIRST token
    # comes out of prefill, so a 1-token warmup never dispatched (or
    # compiled) the decode executable — the first routed request then
    # paid the decode compile, and an idle replica's cost ledger had no
    # decode row.
    warm = server.submit(np.full((2,), 4, np.int32), max_new_tokens=2)
    server.drain()
    del warm
    server.reset_stats()

    # Per-replica cost ledger (r16 NOTE closed): --cost_ledger makes the
    # worker snapshot its roofline attribution into <replica>/perf_ledger
    # .json — the same file/shape a training run dir carries — so
    # run/status.py and obs/export.py surface per-replica MFU live.
    t_serve0 = time.perf_counter()
    last_ledger = [0.0]

    def _write_ledger(force: bool = False) -> None:
        if not settings.cost_ledger:
            return
        now = time.perf_counter()
        if not force and now - last_ledger[0] < 2.0:
            return  # snapshot cadence: the ledger is telemetry, not a
            # per-tick obligation on the decode hot path
        last_ledger[0] = now
        from ..obs import ledger as ledger_lib
        try:
            rows = server.cost_ledger(wall_s=now - t_serve0, n_devices=1)
            ledger_lib.write_ledger(paths.root, rows, t=time.time())
        except Exception as e:  # telemetry must never kill the replica
            print(f"[serve-worker {rid}] ledger write failed: {e}",
                  file=sys.stderr, flush=True)

    tick = 0
    admitted = 0
    in_flight = {}  # router req id -> (server Request, inbox payload)
    completed = 0
    tokens_out = 0
    current_step = [step]

    # Prefix-affinity advertisement: a bounded LRU of the page-aligned
    # prefix-block hashes this replica has served, riding every beacon
    # (file transport) and heartbeat (socket transport) so the router can
    # score warm placements. Only meaningful with the prefix cache on —
    # advertising warmth without a cache would just skew placement.
    import collections as _collections

    from ..serving.transport import prefix_block_hashes
    prefix_index: dict = _collections.OrderedDict()

    def _index_prefix(prompt) -> None:
        if not settings.prefix_cache:
            return
        for h in prefix_block_hashes(prompt, settings.page_size):
            prefix_index.pop(h, None)
            prefix_index[h] = None
        while len(prefix_index) > 256:
            prefix_index.popitem(last=False)

    def _beacon_extra() -> dict:
        extra = {}
        if settings.prefix_cache:
            stats = server.prefix_stats()
            extra.update({"prefix_index": list(prefix_index),
                          "prefix_hits": int(stats.get("prefix_hits", 0)),
                          "prefix_misses": int(
                              stats.get("prefix_misses", 0))})
        if settings.spec_tokens > 0:
            # live speculative gauges per replica (ISSUE 20 satellite:
            # run/status.py + Prometheus read these off the fleet dir)
            extra["accept_rate"] = round(server.accept_rate, 4)
            extra["accepted_tokens_per_s"] = round(
                server.tokens_fetched
                / max(time.perf_counter() - t_serve0, 1e-9), 1)
        return extra

    proto.tracker.ticks = server.ticks   # rides the beacon's snapshot
    proto.write_beacon(tick)
    proto.announce_ready(step)
    print(f"[serve-worker {rid}] ready at step {step} "
          f"(attempt {proto.attempt})", file=sys.stderr, flush=True)

    def _report_done() -> None:
        nonlocal completed, tokens_out
        for rk, (req, payload) in list(in_flight.items()):
            if not req.finished:
                continue
            # TTFT relative to the ROUTER's submit stamp: queue wait and
            # any replay delay are inside the number a user feels
            ttft = None
            if req.ttft_s is not None:
                lag = payload["_t_local"] - float(
                    payload.get("submit_t", payload["_t_local"]))
                ttft = max(0.0, lag) + req.ttft_s
            proto.write_result({
                "id": int(payload["id"]),
                "tokens": [int(t) for t in req.tokens],
                "ttft_s": ttft, "params_step": current_step[0],
                "replays": int(payload.get("replays", 0))})
            completed += 1
            tokens_out += len(req.tokens)
            del in_flight[rk]

    def _handle_swap(cmd: dict) -> None:
        # local drain first (belt over the router's braces: placement is
        # already off, but anything in flight finishes on the OLD params)
        nonlocal tick
        with proto.tracker.timed("drain_s"):
            while server.busy:
                server.step()
                tick += 1
                proto.write_beacon(tick)
        _report_done()
        with proto.tracker.timed("swap_s"):
            proto.write_beacon(tick)  # restore time is not a hang
            try:
                server.set_params(_restore_params(cmd["target"]))
                ok, err = True, ""
            except Exception as e:  # corrupt/missing payload: keep old
                ok, err = False, f"{type(e).__name__}: {e}"
            proto.write_beacon(tick)
        if ok:
            current_step[0] = int(cmd["step"])
            proto.announce_ready(current_step[0])
        proto.ack_swap(int(cmd["id"]), ok, current_step[0], err)
        print(f"[serve-worker {rid}] swap -> step {cmd['step']}: "
              f"{'ok' if ok else err}", file=sys.stderr, flush=True)

    try:
        while not proto.stop_requested():
            cmd = proto.pending_swap()
            if cmd is not None:
                _handle_swap(cmd)
            if injector is not None:
                injector.on_serve_tick(admitted, len(in_flight))
            moved = False
            for payload in proto.poll_inbox():
                try:
                    req = server.submit(
                        np.asarray(payload["prompt"], np.int32),
                        int(payload["max_new_tokens"]),
                        trace_id=payload.get("trace"))
                except ValueError as e:
                    proto.write_result({"id": int(payload["id"]),
                                        "tokens": [], "ttft_s": None,
                                        "error": str(e)})
                    proto.consume(int(payload["id"]))
                    continue
                payload["_t_local"] = time.time()
                in_flight[int(payload["id"])] = (req, payload)
                proto.consume(int(payload["id"]))
                _index_prefix(np.asarray(payload["prompt"], np.int32))
                admitted += 1
                moved = True
            if server.busy:
                server.step()
                moved = True
            _report_done()
            tick += 1
            proto.write_beacon(tick, extra=_beacon_extra())
            _write_ledger()
            if not moved:
                time.sleep(0.005)
    finally:
        server.stop_sanitizer()
        server.write_sanitize_report(paths.root)
    # graceful stop: drain whatever is still in flight before exiting 0
    with proto.tracker.timed("drain_s"):
        while server.busy:
            server.step()
            tick += 1
            proto.write_beacon(tick)
    _report_done()
    _write_ledger(force=True)  # final snapshot covers the whole attempt
    proto.tracer.close()
    summary = {"ticks": tick, "admitted": admitted, "completed": completed,
               "tokens": tokens_out, "params_step": current_step[0],
               **server.prefix_stats()}
    if settings.spec_tokens > 0:
        summary["accept_rate"] = round(server.accept_rate, 4)
    proto.write_sidecar(summary)
    proto.close()  # data-plane endpoint down AFTER the final results
    #                were drained by the router (it polls until all done)
    print(f"[serve-worker {rid}] stopping: {json.dumps(summary)}",
          file=sys.stderr, flush=True)
    return summary


# ============================================== disaggregated fleet workers

def _disagg_prefill_main(settings: ServeSettings) -> dict:
    """One PREFILL worker of a disaggregated fleet (ISSUE 16): router
    requests come through the normal replica inbox, but instead of
    decoding locally the worker runs ONLY the prompt forward and streams
    the request — paged-KV pages + first token — over its kv StageLink
    to the decode ring, then relays the decode ring's token replies back
    to the router outbox. TTFT is stamped HERE: the first token exists
    the moment prefill completes."""
    import numpy as np

    from ..mpmd.disagg import PrefillClient, pack_kv_frame
    from ..mpmd.link import FileStageLink
    from ..parallel import make_mesh
    from ..serving.fleet import ReplicaPaths, WorkerProtocol
    from .sample import load_run

    rid = settings.replica_id
    paths = ReplicaPaths.at(settings.fleet_worker_dir, rid)
    proto = WorkerProtocol(paths, rid,
                           trace_armed=True if settings.trace else None)
    proto.startup()

    mesh = make_mesh()
    wl, params, _targs, step, _which = load_run(
        settings.checkpoint_path, settings.step, settings.ema, mesh=mesh)
    params = _quantize_for_serving(settings, params)  # deterministic:
    # prefill and decode tiers quantize the same checkpoint identically
    max_len = settings.max_len or wl.seq_len
    max_prompt_len = settings.max_prompt_len or max(2, max_len // 2)
    pre = PrefillClient(
        wl, params, page_size=settings.page_size,
        max_prompt_len=max_prompt_len, max_len=max_len,
        temperature=settings.temperature, top_k=settings.top_k,
        top_p=settings.top_p, seed=settings.seed, mesh=mesh)
    del params  # the engine holds its serving form; let the masters go
    kv_link = FileStageLink(
        os.path.join(settings.disagg_links, f"kv_{rid}"),
        capacity=8, tracer=proto.tracer)
    tok_link = FileStageLink(
        os.path.join(settings.disagg_links, f"tok_{rid}"),
        capacity=64, tracer=proto.tracer)
    pre.warmup()  # compile before ready: first routed TTFT is service time

    tick = 0
    prefills = 0
    completed = 0
    outbound = None  # a packed frame the kv link refused (backpressure)
    in_flight = {}   # router req id -> inbox payload
    proto.write_beacon(tick)
    proto.announce_ready(step)
    print(f"[disagg-prefill {rid}] ready at step {step} "
          f"(attempt {proto.attempt})", file=sys.stderr, flush=True)

    while not proto.stop_requested():
        moved = False
        if outbound is not None:
            arrays, meta, payload = outbound
            if kv_link.send(arrays, meta, timeout_s=0.2,
                            interrupt=proto.stop_requested):
                in_flight[int(payload["id"])] = payload
                proto.consume(int(payload["id"]))
                outbound = None
                moved = True
        if outbound is None:
            for payload in proto.poll_inbox():
                if int(payload.get("id", -1)) in in_flight:
                    continue
                prompt = np.asarray(payload["prompt"], np.int32)
                try:
                    out = pre.prefill(prompt)
                except ValueError as e:
                    proto.write_result({"id": int(payload["id"]),
                                        "tokens": [], "ttft_s": None,
                                        "error": str(e)})
                    proto.consume(int(payload["id"]))
                    continue
                now = time.time()
                ttft = max(0.0, now - float(payload.get("submit_t", now)))
                arrays, meta = pack_kv_frame(
                    int(payload["id"]), prompt,
                    int(payload["max_new_tokens"]), out, src=rid,
                    submit_t=float(payload.get("submit_t", now)),
                    ttft_s=ttft, trace=payload.get("trace"))
                prefills += 1
                moved = True
                if kv_link.send(arrays, meta, timeout_s=0.2,
                                interrupt=proto.stop_requested):
                    in_flight[int(payload["id"])] = payload
                    proto.consume(int(payload["id"]))
                else:
                    outbound = (arrays, meta, payload)
                    break  # keep inbox order: ship this one first
        got = tok_link.recv(timeout_s=0.0)
        if got is not None:
            _, meta = got
            payload = in_flight.pop(int(meta["id"]), None)
            if payload is not None:
                proto.write_result({
                    "id": int(meta["id"]),
                    "tokens": [int(t) for t in meta.get("tokens", [])],
                    "ttft_s": meta.get("ttft_s"), "params_step": step,
                    "replays": int(payload.get("replays", 0))})
                completed += 1
            moved = True
        tick += 1
        proto.write_beacon(tick)
        if not moved:
            time.sleep(0.005)
    proto.tracer.close()
    summary = {"ticks": tick, "prefills": prefills, "completed": completed,
               "prompt_tokens": pre.prompt_tokens, "params_step": step,
               "link_wait_s": round(kv_link.take_wait_s(), 6)}
    proto.write_sidecar(summary)
    print(f"[disagg-prefill {rid}] stopping: {json.dumps(summary)}",
          file=sys.stderr, flush=True)
    return summary


def _disagg_decode_main(settings: ServeSettings) -> dict:
    """THE decode worker of a disaggregated fleet: polls every prefill
    worker's kv StageLink, admits transferred requests through
    ``DecodeServer.submit_prefilled`` (a ``None`` admission leaves the
    frame on the link — the link IS the backpressure), runs the decode
    loop, and answers each completed request on the owning prefill
    worker's tok link. Runs under its own supervised ring in
    ``<fleet_dir>/decode`` with the same beacon/sidecar discipline as a
    replica — a restart recovers the SERVICE; requests whose transferred
    KV died with the attempt are not replayed (the router only replays
    on prefill-replica death) and fall to the fleet deadline."""
    import numpy as np

    from ..mpmd.disagg import unpack_kv_frame
    from ..mpmd.link import FileStageLink
    from ..parallel import make_mesh
    from ..serving import DecodeServer
    from ..serving.fleet import ReplicaPaths, WorkerProtocol
    from .sample import load_run

    rid = settings.replica_id
    paths = ReplicaPaths.at(settings.fleet_worker_dir, rid)
    proto = WorkerProtocol(paths, rid,
                           trace_armed=True if settings.trace else None)
    proto.startup()

    mesh = make_mesh()
    wl, params, _targs, step, _which = load_run(
        settings.checkpoint_path, settings.step, settings.ema, mesh=mesh)
    params = _quantize_for_serving(settings, params)
    if settings.kv_quant != "fp" or settings.spec_tokens > 0:
        # the prefill->decode KV wire frames are fp and the spec draft's
        # prefill mirror rides the colocated _admit path — neither is
        # plumbed through the disagg transfer, so downgrade loudly
        # instead of serving a silently-mismatched pool
        print(f"[disagg-decode {settings.replica_id}] --kv_quant/"
              f"--spec_tokens are colocated-serving features; running "
              f"fp non-speculative", file=sys.stderr, flush=True)
    max_len = settings.max_len or wl.seq_len
    max_prompt_len = settings.max_prompt_len or max(2, max_len // 2)
    server = DecodeServer(
        wl, params, decode_slots=settings.decode_slots,
        page_size=settings.page_size, max_pages=settings.max_pages,
        max_prompt_len=max_prompt_len, max_len=max_len,
        prefill_batch=settings.prefill_batch,
        decode_span=settings.decode_span,
        dispatch_lag=settings.dispatch_lag,
        temperature=settings.temperature, top_k=settings.top_k,
        top_p=settings.top_p, seed=settings.seed,
        eos_id=settings.eos_id if settings.eos_id >= 0 else None,
        mesh=mesh, sanitize=settings.sanitize,
        decode_impl=settings.decode_impl)
    del params  # the engine holds its serving form; let the masters go
    n_peers = max(1, settings.disagg_peers)
    kv_links = [FileStageLink(
        os.path.join(settings.disagg_links, f"kv_{i}"),
        capacity=8, tracer=proto.tracer) for i in range(n_peers)]
    tok_links = [FileStageLink(
        os.path.join(settings.disagg_links, f"tok_{i}"),
        capacity=64, tracer=proto.tracer) for i in range(n_peers)]

    # decode-executable warmup before ready (the colocated worker's
    # rationale): budget 2 so the decode step compiles here, not on the
    # first transferred request
    warm = server.submit(np.full((2,), 4, np.int32), max_new_tokens=2)
    server.drain()
    del warm
    server.reset_stats()

    tick = 0
    admitted = 0
    completed = 0
    tokens_out = 0
    held = None       # (link index, unpacked frame) awaiting capacity
    in_flight = {}    # req key -> (server Request, frame meta)
    next_link = 0
    proto.write_beacon(tick)
    proto.announce_ready(step)
    print(f"[disagg-decode {rid}] ready at step {step} "
          f"(attempt {proto.attempt}, {n_peers} prefill peers)",
          file=sys.stderr, flush=True)

    def _reply_done() -> None:
        nonlocal completed, tokens_out
        for key, (req, meta) in list(in_flight.items()):
            if not req.finished:
                continue
            tok_links[int(meta["src"])].send({}, {
                "op": "tok", "id": int(meta["id"]),
                "tokens": [int(t) for t in req.tokens],
                "ttft_s": meta.get("ttft_s")},
                timeout_s=5.0, interrupt=proto.stop_requested)
            completed += 1
            tokens_out += len(req.tokens)
            del in_flight[key]

    try:
        while not proto.stop_requested():
            moved = False
            if held is None:
                for k in range(n_peers):
                    i = (next_link + k) % n_peers
                    got = kv_links[i].recv(timeout_s=0.0)
                    if got is not None:
                        held = unpack_kv_frame(*got)
                        next_link = (i + 1) % n_peers
                        moved = True
                        break
            if held is not None:
                try:
                    req = server.submit_prefilled(
                        held["prompt"], int(held["max_new_tokens"]),
                        first_token=int(held["first_token"]),
                        kv_pages=held["kv"])
                except ValueError as e:
                    tok_links[int(held["src"])].send({}, {
                        "op": "tok", "id": int(held["id"]), "tokens": [],
                        "ttft_s": None, "error": str(e)},
                        timeout_s=5.0, interrupt=proto.stop_requested)
                    held = None
                    moved = True
                else:
                    if req is not None:  # else: full — retry after a step
                        in_flight[(int(held["src"]), int(held["id"]))] = (
                            req, held)
                        admitted += 1
                        held = None
                        moved = True
            if server.busy:
                server.step()
                moved = True
            _reply_done()
            tick += 1
            proto.write_beacon(tick)
            if not moved:
                time.sleep(0.005)
    finally:
        server.stop_sanitizer()
        server.write_sanitize_report(paths.root)
    while server.busy:  # graceful stop: drain in-flight decodes
        server.step()
        tick += 1
        proto.write_beacon(tick)
    _reply_done()
    proto.tracer.close()
    summary = {"ticks": tick, "admitted": admitted, "completed": completed,
               "tokens": tokens_out, "params_step": step}
    proto.write_sidecar(summary)
    print(f"[disagg-decode {rid}] stopping: {json.dumps(summary)}",
          file=sys.stderr, flush=True)
    return summary


# ========================================================= fleet supervisor

def fleet_workload(settings: ServeSettings, vocab: int,
                   max_prompt_len: int):
    """THE fleet workload builder (r13 NOTE closed): jax-free, and the
    deterministic-order contract lives here, pinned by a cross-process
    test. Returns ``(gen, reqs)`` with ``reqs`` a list of
    ``(arrival_offset_s, prompt, max_new_tokens)`` in SUBMISSION order.

    With ``--prompt_file``, prompt i (file order) rides the i-th
    smallest arrival offset of the seeded generator — file order IS
    submission order, and for a fixed seed the whole (offset, prompt)
    pairing is identical in every process. Knobs fleet mode cannot honor
    fail LOUDLY instead of silently degrading: ``--arrival_every_steps``
    is a scheduler-step cadence, and the fleet parent has no scheduler
    steps to count (``--traffic steps`` itself degrades to poisson
    arrivals, which only reshapes TIMING, never order)."""
    if settings.arrival_every_steps > 0:
        raise SystemExit(
            "--arrival_every_steps is a single-server scheduler-step "
            "cadence; the fleet parent has no scheduler steps to count. "
            "Use --traffic poisson/bursty/diurnal with --rate_rps "
            "instead (prompt-file order is preserved either way)")
    gen = _generator(settings, default="poisson")
    if settings.prompt_file:
        pairs = _load_requests(settings, max_prompt_len, vocab)
        offsets = gen.schedule(len(pairs))
        reqs = [(float(offsets[i]), p, n or settings.max_new_tokens)
                for i, (p, n) in enumerate(pairs)]
    else:
        plen = min(settings.synthetic_prompt_len or max_prompt_len,
                   max_prompt_len)
        reqs = [(r.t, r.prompt, r.max_new_tokens)
                for r in gen.requests(
                    settings.synthetic_requests, vocab_size=vocab,
                    prompt_len=plen,
                    max_new_tokens=settings.max_new_tokens,
                    shared_prefix_len=min(settings.shared_prefix_len,
                                          plen))]
    return gen, reqs


def _fleet_main(settings: ServeSettings) -> dict:
    """N replicas behind the router, driven by a wall-clock traffic
    process; optional mid-run checkpoint hot-swap; serving goodput ledger
    at exit. This process stays jax-free — replicas pay the backend."""
    import numpy as np

    from ..chaos import CHAOS_PLAN_ENV, ChaosInjector, goodput
    from ..serving.fleet import ServingFleet
    from ..serving.router import Router

    targs_file = os.path.join(settings.checkpoint_path,
                              "training_args.json")
    with open(targs_file) as f:
        targs = json.load(f)
    vocab = int(targs["vocab_size"])
    seq_len = int(targs["seq_len"])
    max_len = settings.max_len or seq_len
    max_prompt_len = settings.max_prompt_len or max(2, max_len // 2)

    fleet_dir = settings.fleet_dir or os.path.join(
        settings.checkpoint_path, "fleet")
    os.makedirs(fleet_dir, exist_ok=True)

    if settings.trace:
        # arm tracing fleet-wide: the env rides the launcher's worker
        # environment to every replica attempt (worker spans) and arms
        # the supervisor threads' launcher shards in the replica dirs
        from ..obs.trace import TRACE_ENV
        os.environ[TRACE_ENV] = "1"

    plan = _resolve_chaos_plan(settings)
    if plan is not None:
        # serving faults ride the env to every replica worker of every
        # attempt (the same channel training chaos uses); the fleet-level
        # injector only executes corrupt_swap_checkpoint
        os.environ[CHAOS_PLAN_ENV] = plan.to_json()
    injector = (ChaosInjector(plan, rank=0, run_dir=fleet_dir)
                if plan else None)

    argv = _worker_argv(settings)

    # Disaggregation (ISSUE 16): the --replicas workers become PREFILL
    # tiers and a second 1-ring ServingFleet under <fleet_dir>/decode
    # runs the decode tier; both tiers get explicit role flags plus the
    # shared StageLink directory appended to the common worker argv.
    decode_fleet = None
    argv_prefill = argv
    if settings.disagg > 0:
        if settings.disagg != 1:
            raise SystemExit("--disagg supports exactly one decode ring "
                             f"(got {settings.disagg})")
        if settings.serve_transport != "file":
            raise SystemExit("--serve_transport socket is not supported "
                             "with --disagg (the disagg tiers speak "
                             "StageLinks between themselves)")
        if settings.autoscale:
            raise SystemExit("--autoscale cannot resize a disaggregated "
                             "fleet: the prefill peer count is pinned "
                             "into the decode ring's link topology")
        if settings.swap_after_requests > 0:
            # a hot-swap would drain the prefill tier while the decode
            # tier still holds transferred KV computed by OLD params —
            # token streams would silently mix checkpoints
            raise SystemExit("--disagg and --swap_after_requests are "
                             "mutually exclusive")
        links_dir = os.path.join(fleet_dir, "links")
        os.makedirs(links_dir, exist_ok=True)
        disagg_argv = ["--disagg_links", links_dir,
                       "--disagg_peers", str(settings.replicas)]
        argv_prefill = argv + ["--disagg_role", "prefill"] + disagg_argv

    # Replica backend: 'auto' = the parent's own platform selection
    # (JAX_PLATFORMS in this jax-free parent's env — "cpu" under every
    # test/dev ring, unset on a real TPU host so replicas get the
    # chips). The old launcher behavior pinned cpu UNCONDITIONALLY,
    # which made TPU fleet replicas impossible (r13 NOTE).
    platform = settings.replica_platform
    if platform == "auto":
        platform = os.environ.get("JAX_PLATFORMS", "")
    # Every worker process this fleet may come to hold — replicas up to
    # the autoscaler's ceiling, plus the disagg decode ring — must fit the
    # host; the library refuses with an exception, the CLI with a message.
    from ..parallel.launcher import WorkersDoNotFitHost, \
        require_workers_fit_host
    most = (max(settings.replicas,
                max(settings.autoscale_max, settings.autoscale_min)
                if settings.autoscale else 0)
            + (1 if settings.disagg > 0 else 0))
    try:
        require_workers_fit_host(most, platform,
                                 f"a fleet of up to {most} workers")
    except WorkersDoNotFitHost as e:
        raise SystemExit(str(e)) from None
    # build the workload BEFORE spawning anything: a knob fleet mode
    # cannot honor must abort with zero worker processes to clean up
    gen, reqs = fleet_workload(settings, vocab, max_prompt_len)
    fleet = ServingFleet(
        fleet_dir, settings.replicas,
        "distributed_pipeline_tpu.run.serve", argv_prefill,
        devices_per_proc=1,
        hang_timeout_s=settings.hang_timeout_s,
        max_restarts=settings.fleet_max_restarts,
        restart_backoff_s=settings.fleet_backoff_s,
        replica_platform=platform,
        transport=settings.serve_transport)
    fleet.start()
    if settings.disagg > 0:
        decode_fleet = ServingFleet(
            os.path.join(fleet_dir, "decode"), 1,
            "distributed_pipeline_tpu.run.serve",
            argv + ["--disagg_role", "decode"] + disagg_argv,
            devices_per_proc=1,
            hang_timeout_s=settings.hang_timeout_s,
            max_restarts=settings.fleet_max_restarts,
            restart_backoff_s=settings.fleet_backoff_s,
            replica_platform=platform)
        decode_fleet.start()
    router = Router(fleet.clients(), goodput.serving_journal_path(fleet_dir),
                    stale_beacon_s=settings.stale_beacon_s,
                    affinity=settings.route_affinity,
                    page_size=settings.page_size)

    scaler = None
    if settings.autoscale:
        from ..obs import trace as trace_lib
        from ..serving.autoscale import AutoScaler
        amax = settings.autoscale_max or settings.replicas
        scaler = AutoScaler(
            fleet, router,
            min_replicas=settings.autoscale_min,
            max_replicas=max(amax, settings.autoscale_min),
            slo_ttft_s=settings.autoscale_slo_ttft_s,
            up_backlog=settings.autoscale_up_backlog,
            down_frac=settings.autoscale_down_frac,
            cooldown_s=settings.autoscale_cooldown_s,
            window_s=settings.autoscale_window_s,
            drain_timeout_s=settings.drain_timeout_s,
            tracer=trace_lib.tracer_for(
                fleet_dir, "autoscaler",
                armed=True if settings.trace else None,
                proc="autoscaler"))

    print(f"# fleet: {settings.replicas} replicas, {len(reqs)} requests, "
          f"traffic {gen.describe()}", file=sys.stderr, flush=True)

    t0 = time.perf_counter()
    swap_report = None
    swap_armed = False
    next_idx = 0
    deadline_hit = False
    try:
        while True:
            elapsed = time.perf_counter() - t0
            while next_idx < len(reqs) and reqs[next_idx][0] <= elapsed:
                _, prompt, mnt = reqs[next_idx]
                router.submit(prompt, mnt, submit_t=time.time())
                next_idx += 1
            router.poll()
            if fleet.swap_active:
                rep = fleet.step_swap(router)
                if rep is not None:
                    swap_report = rep
                    print(f"# fleet: swap "
                          f"{'ok' if rep['ok'] else 'ABORTED'}: "
                          f"{rep.get('error') or rep['step']}",
                          file=sys.stderr, flush=True)
            elif (not swap_armed and settings.swap_after_requests > 0
                  and router.completed >= settings.swap_after_requests):
                swap_armed = True
                try:
                    arm = fleet.begin_hot_swap(
                        settings.checkpoint_path, settings.swap_step,
                        drain_timeout_s=settings.drain_timeout_s,
                        swap_timeout_s=settings.swap_timeout_s,
                        injector=injector)
                    print(f"# fleet: hot-swap armed -> {arm['target']}",
                          file=sys.stderr, flush=True)
                except (FileNotFoundError, RuntimeError) as e:
                    swap_report = {"ok": False,
                                   "error": f"arm failed: {e}"}
            if scaler is not None:
                scaler.step()
            if (next_idx >= len(reqs) and router.all_done()
                    and not fleet.swap_active):
                break
            if elapsed > settings.fleet_deadline_s:
                deadline_hit = True
                break
            time.sleep(0.01)
    finally:
        if scaler is not None:
            scaler.close()
            scaler.tracer.close()
        for c in router.clients.values():
            try:
                c.close()
            except OSError:
                pass
        rcs = fleet.stop()
        decode_rcs = decode_fleet.stop() if decode_fleet else None
    wall_s = time.perf_counter() - t0

    records = sorted(router.records.values(), key=lambda r: r.id)
    if settings.out:
        with open(settings.out, "w") as f:
            for rec in records:
                f.write(json.dumps({
                    "id": rec.id, "prompt": rec.prompt.tolist(),
                    "tokens": rec.tokens, "replica": rec.replica,
                    "replays": rec.replays,
                    "ttft_s": round(rec.ttft_s or 0.0, 4)}) + "\n")

    ttfts = router.ttfts()
    tokens = sum(len(r.tokens) for r in records if r.state == "done")
    agg = goodput.aggregate_serving(fleet_dir)
    dropped = router.submitted - router.completed

    # fleet-wide prefix-cache economics: sum the per-attempt sidecar
    # counters (each clean worker exit books its engine's totals)
    prefix_hits = prefix_misses = 0
    for rdir in goodput.list_replica_dirs(fleet_dir):
        for rec in goodput.read_serving_records(rdir).values():
            prefix_hits += int(rec.get("prefix_hits") or 0)
            prefix_misses += int(rec.get("prefix_misses") or 0)

    # fleet-wide decode roofline (ISSUE 18 satellite): average the
    # replicas' serve_decode attribution rows (each worker's --cost_ledger
    # snapshot in its replica dir) so the fleet summary carries
    # mfu_gap_memory_bound next to goodput
    decode_roofline = None
    if settings.cost_ledger:
        from ..obs import ledger as ledger_lib
        decs = []
        for rdir in goodput.list_replica_dirs(fleet_dir):
            led = ledger_lib.read_ledger(rdir)
            dec = (led or {}).get("programs", {}).get("serve_decode")
            if isinstance(dec, dict) and "mfu" in dec:
                decs.append(ledger_lib.attribution_columns(dec))
        if decs:
            keys = ("mfu",) + ledger_lib.GAP_TERMS
            decode_roofline = {
                k: round(sum(float(d.get(k) or 0.0) for d in decs)
                         / len(decs), 4) for k in keys}
            decode_roofline["replicas_reporting"] = len(decs)

    result = {
        "mode": "fleet",
        "replicas": settings.replicas,
        "traffic": gen.describe(),
        "requests": router.submitted,
        "completed": router.completed,
        "dropped": dropped,
        "replayed": router.replayed,
        "deadline_hit": deadline_hit,
        "decode_tokens": tokens,
        "decode_tokens_per_s": round(tokens / max(wall_s, 1e-9), 1),
        "ttft_p50_s": round(float(np.percentile(ttfts, 50)), 4)
        if ttfts else None,
        "ttft_p95_s": round(float(np.percentile(ttfts, 95)), 4)
        if ttfts else None,
        "swap": swap_report,
        "replica_rcs": rcs,
        "wall_s": round(wall_s, 2),
        "transport": settings.serve_transport,
        "affinity_placements": router.affinity_placements,
        "affinity_hits": router.affinity_hits,
        "prefix_hits": prefix_hits,
        "prefix_misses": prefix_misses,
        "prefix_hit_rate": round(
            prefix_hits / max(1, prefix_hits + prefix_misses), 4),
        "decode_roofline": decode_roofline,
        "autoscale": scaler.summary() if scaler is not None else None,
        "serving_goodput": {
            k: (round(v, 4) if isinstance(v, float) else v)
            for k, v in agg.items() if k != "per_replica"},
    }
    if decode_fleet is not None:
        dagg = goodput.aggregate_serving(os.path.join(fleet_dir, "decode"))
        result["disagg"] = settings.disagg
        result["decode_rcs"] = decode_rcs
        result["decode_goodput"] = {
            k: (round(v, 4) if isinstance(v, float) else v)
            for k, v in dagg.items() if k != "per_replica"}
    print(json.dumps(result))
    return result


def main(ns: argparse.Namespace) -> dict:
    settings = ServeSettings.from_argparse(ns)
    # orbax refuses relative checkpoint paths, and fleet worker argv must
    # survive whatever cwd the replica subprocess starts in — normalize
    # once here so every downstream consumer sees an absolute path
    settings.checkpoint_path = os.path.abspath(settings.checkpoint_path)
    if settings.replicas > 0 and not settings.fleet_worker_dir:
        return _fleet_main(settings)  # jax-free parent: compiles nothing
    # every main below compiles: one cache for all of them (and for
    # training), so a server start after the first does not compile cold
    from ..utils.perf import enable_persistent_compilation_cache
    cache_dir = enable_persistent_compilation_cache()
    print(f"# serve: persistent compilation cache: {cache_dir}",
          file=sys.stderr, flush=True)
    if settings.fleet_worker_dir:
        if settings.disagg_role == "prefill":
            return _disagg_prefill_main(settings)
        if settings.disagg_role == "decode":
            return _disagg_decode_main(settings)
        return _fleet_worker_main(settings)
    return _serve_single(settings)


if __name__ == "__main__":
    main(create_parser().parse_args())
