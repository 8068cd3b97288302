"""Driver for traffic of kind ``serve``: the program's ``DecodeServer``, built
as ``run.serve`` builds it, driven by ``submit`` / ``step`` under the load
the traffic file describes (``traffic_gen.Load``: a closed loop of clients,
or open-loop arrivals on a clock of their own).

Set-up makes the weights on the device from the seed, builds the server and
drives the load until prefill and decode have compiled, a few decode steps
have been fetched and the load is steady (closed loop: every slot filled
once; open loop: both programs compiled on one request, then the arrival
clock runs for ``warm_seconds``); the same server and the same loop then run
the measured window. Once the window has closed and
the last requests have drained, a sample of the requests it finished, drawn
from the seed with the longest in it, is compared with the plain reference:
one full forward over each prompt with its served tokens, and the widest gap
by which a served token's logit lies below the reference's best.
"""

from __future__ import annotations

import gc
import time
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from . import family_for, reference_for
from .stats import percentile, rate
from .traffic_gen import Load


class Tracked:
    """One request as the harness sees it."""

    __slots__ = ("req", "in_window", "due_t", "finished_t")

    def __init__(self, req, in_window: bool, due_t: float):
        self.req, self.in_window, self.due_t = req, in_window, due_t
        self.finished_t: Optional[float] = None

    @property
    def first_token_t(self) -> Optional[float]:
        return (None if self.req.ttft_s is None
                else self.req.submit_t + self.req.ttft_s)


def build_server(cfg: Dict[str, Any], traffic: Dict[str, Any], seed: int,
                 devices: List[Any]):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_pipeline_tpu.models import create_model_from_config
    from distributed_pipeline_tpu.parallel.mesh import make_mesh
    from distributed_pipeline_tpu.serving import DecodeServer

    ref, fam = reference_for(cfg), family_for(cfg)
    wl = create_model_from_config(seq_len=fam.dims(cfg)["positions"],
                                  **fam.program_flags(cfg))
    mesh = make_mesh(devices=devices)
    rep = NamedSharding(mesh, P())
    # the weights: one jitted call from the seed, in the type run.serve
    # serves them in (the float32 master copy, cast inside each program)
    weights = jax.jit(lambda s: ref.make_weights(cfg, s),
                      out_shardings=rep)(ref.seed_arg(seed))
    server = DecodeServer(
        wl, fam.to_program_tree(weights, cfg),
        decode_slots=traffic["decode_slots"], page_size=traffic["page_size"],
        max_prompt_len=traffic["max_prompt_len"], max_len=traffic["max_len"],
        prefill_batch=traffic.get("prefill_batch", 0),
        temperature=traffic.get("temperature", 0.0),
        seed=seed % (2 ** 31), mesh=mesh, sanitize=True,
        prefix_cache=bool(traffic.get("prefix_cache", False)))
    return server, weights


def tail_metrics(done: List[Tracked]) -> Dict[str, Optional[float]]:
    """TTFT (from the moment the request was due: in a closed loop the
    moment it was sent) and time per output token over ALL the requests
    given; one that never finished has no tpot and is counted by the caller
    as failed."""
    ttft = [(t.first_token_t - t.due_t) * 1e3 for t in done
            if t.first_token_t is not None]
    tpot = []
    for t in done:
        n = len(t.req.tokens)
        if t.finished_t is not None and t.first_token_t is not None and n > 1:
            tpot.append((t.finished_t - t.first_token_t) / (n - 1) * 1e3)
    return {"ttft_p95_ms": percentile(ttft, 95),
            "tpot_p95_ms": percentile(tpot, 95),
            "ttft_p50_ms": percentile(ttft, 50),
            "tpot_p50_ms": percentile(tpot, 50)}


def pick_sample(finished: List[Tracked], k: int, seed: int) -> List[Tracked]:
    """k finished requests drawn from the seed, the longest among them."""
    if not finished:
        return []
    longest = max(finished, key=lambda t: t.req.prompt_len + len(t.req.tokens))
    rest = [t for t in finished if t is not longest]
    rng = np.random.default_rng([int(seed), 0xC4EC])
    take = rng.permutation(len(rest))[:max(0, k - 1)]
    return [longest] + [rest[i] for i in take]


def check_served(weights, cfg: Dict[str, Any], sample: List[Tracked],
                 limit: float, control: Optional[str] = None, fwd=None
                 ) -> Dict[str, Any]:
    """Widest gap over the sample's served tokens (``control``: of the
    tokens that precision puts first, at the same positions)."""
    ref = reference_for(cfg)
    fwd = fwd or ref.make_logits_fn(cfg)
    widest, n_tokens, n_equal = 0.0, 0, 0
    for t in sample:
        ids = np.concatenate([t.req.prompt,
                              np.asarray(t.req.tokens, np.int32)])
        gaps = ref.served_gaps(weights, cfg, ids, t.req.prompt_len,
                               precision_pick=control, fwd=fwd)
        if not np.all(np.isfinite(gaps)):
            widest = float("inf")
        else:
            widest = max(widest, float(gaps.max()))
        n_tokens += len(gaps)
        n_equal += int((gaps == 0).sum())
    return {"value": widest, "limit": limit, "ok": bool(widest <= limit),
            "tokens": n_tokens, "tokens_equal_reference_pick": n_equal,
            "requests": len(sample)}


def run(cell: Dict[str, Any], cfg: Dict[str, Any], traffic: Dict[str, Any],
        *, seed: int, seconds: float, trace: bool, devices: List[Any],
        t_process: float, annotate, profiler,
        control: Sequence[str] = ()) -> Dict[str, Any]:
    ref, fam = reference_for(cfg), family_for(cfg)
    marks: List[List[Any]] = [["imports", time.perf_counter() - t_process]]
    server, weights = build_server(cfg, traffic, seed, devices)
    marks.append(["server_built", time.perf_counter() - t_process])
    slots = traffic["decode_slots"]
    load = Load(traffic, fam.dims(cfg)["vocab"], seed, slots)
    current: List[Optional[Tracked]] = [None] * load.n_clients
    everything: List[Tracked] = []
    unstamped: List[Tracked] = []
    state = {"in_window": False, "submitting": True}
    live_kv = {"token_steps": 0, "decode_steps_seen": 0}

    def poll(now: float) -> None:
        finished = []
        for c, t in enumerate(current):
            if t is not None and t.req.finished and t.finished_t is None:
                t.finished_t = now
            if t is None or t.finished_t is not None:
                finished.append(c)
        for t in unstamped:             # open loop: no client holds them
            if t.req.finished:
                t.finished_t = now
        unstamped[:] = [t for t in unstamped if t.finished_t is None]
        if not state["submitting"]:
            return
        for client, due_t, prompt, budget in load.due(now, finished):
            req = server.submit(prompt, budget)
            t = Tracked(req, state["in_window"],
                        req.submit_t if client is not None else due_t)
            if client is not None:
                current[client] = t
            else:
                unstamped.append(t)
            everything.append(t)

    def tick() -> None:
        with annotate("server.step"):
            advanced = server.step()
        if server.decode_steps != live_kv["decode_steps_seen"]:
            live_kv["decode_steps_seen"] = server.decode_steps
            live_kv["token_steps"] += sum(
                st.position for st in server.slots if st is not None)
        if not advanced:
            # idle (open loop only): wait for the next arrival, not spin
            nxt = load.next_due()
            wait = 0.001 if nxt is None else nxt - time.perf_counter()
            time.sleep(min(max(wait, 0.0), 0.001))
        now = time.perf_counter()
        with annotate("submit"):
            poll(now)

    def counters() -> Dict[str, float]:
        return {"tokens_fetched": server.tokens_fetched,
                "decode_steps": server.decode_steps,
                "prefill_steps": server.prefill_steps,
                "prompt_tokens": server.prompt_tokens_prefilled,
                "prefill_token_slots": server.prefill_token_slots,
                "slot_steps_active": server.slot_steps_active,
                "live_kv_token_steps": live_kv["token_steps"],
                "t": time.perf_counter()}

    # ---- set-up: compile both programs, fetch a few steps, reach the
    # load's steady state
    if load.loop == "open":
        warm = server.submit(np.full((load.longest_prompt,), 4, np.int32),
                             traffic["warm_decode_steps"] + 1)
        while not warm.finished:
            server.step()
        load.start(time.perf_counter())
        warm_until = time.perf_counter() + traffic["warm_seconds"]
        while time.perf_counter() < warm_until:
            tick()
    else:
        load.start(time.perf_counter())
        poll(time.perf_counter())
        first_fill = everything[:load.n_clients]
        while (server.decode_steps < traffic["warm_decode_steps"]
               or any(t.req.ttft_s is None for t in first_fill)):
            tick()
    recompiles0 = server.recompile_count

    # ---- the window
    state["in_window"] = True
    c0 = counters()
    t0 = c0["t"]
    setup_s = t0 - t_process
    traced: Dict[str, Any] = {}
    if trace:
        profiler.start()
        tr0 = counters()
    while True:
        now = time.perf_counter()
        if trace and not traced and (now - tr0["t"] >= profiler.seconds
                                     or now - t0 >= seconds):
            tr1 = counters()
            traced = {"summary": profiler.stop(),
                      **{k: tr1[k] - tr0[k] for k in tr0}}
            continue
        if now - t0 >= seconds:
            break
        tick()
    c1 = counters()
    window_s = c1["t"] - t0
    state["in_window"] = False
    state["submitting"] = False

    # ---- drain: every request sent in the window gets to finish
    deadline = time.perf_counter() + traffic["drain_seconds"]
    while server.busy and time.perf_counter() < deadline:
        tick()
    poll(time.perf_counter())
    recompiles = server.recompile_count - recompiles0
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    pool_bytes = int(server.engine.kv_pool_bytes())
    in_window = [t for t in everything if t.in_window]
    ok = [t for t in in_window
          if t.finished_t is not None and len(t.req.tokens) == t.req.g_max]
    failed = len(in_window) - len(ok)
    tails = tail_metrics(in_window)
    server.stop_sanitizer()
    del server
    gc.collect()

    # ---- the comparison, once the program's state is freed
    t_ref0 = time.perf_counter()
    sample = pick_sample(ok, traffic["check_requests"], seed)
    limit = traffic["limits"]["served_logit_gap"]
    fwd = ref.make_logits_fn(cfg)
    checks: Dict[str, Dict[str, Any]] = {
        "served_logit_gap": check_served(weights, cfg, sample, limit,
                                         fwd=fwd)}
    if not sample:
        checks["served_logit_gap"]["ok"] = False
    checks["requests_failed"] = {"value": failed, "limit": 0,
                                 "ok": failed == 0}
    checks["steady_recompiles"] = {"value": recompiles, "limit": 0,
                                   "ok": recompiles == 0}
    reference_s = time.perf_counter() - t_ref0
    # calibration only (--control): the reference in lower precisions in
    # the program's place, at the same prompts and positions
    names = ("fp8", "int8", "bfloat16") if "1" in control else control
    extra = {name: check_served(weights, cfg, sample, limit, control=name,
                                fwd=fwd) for name in names}
    n_params = ref.param_count(cfg)

    d = {k: c1[k] - c0[k] for k in c0}
    return {
        "attempted": len(in_window), "failed": failed, "checks": checks,
        "end_to_end": {
            "serve_tok_s": rate(d["tokens_fetched"], window_s),
            "ttft_p95_ms": tails["ttft_p95_ms"],
            "tpot_p95_ms": tails["tpot_p95_ms"],
            "setup_s": setup_s},
        "memory_peak_bytes": int(peak),
        "traced": traced,
        "counters": {
            "window_s": window_s, "window": d, "slots": slots,
            "traced": {k: v for k, v in traced.items() if k != "summary"},
            "n_params": n_params, "weight_bytes": 4 * n_params,
            "dims": fam.dims(cfg), "kv_pool_bytes": pool_bytes,
            "live_kv_tokens_mean": (
                d["live_kv_token_steps"] / max(d["decode_steps"], 1)),
            "ttft_p50_ms": tails["ttft_p50_ms"],
            "tpot_p50_ms": tails["tpot_p50_ms"],
            "requests_in_window": len(in_window),
            "requests_finished": len(ok), "reference_s": reference_s,
            "setup_marks": marks,
            "control": extra},
    }
