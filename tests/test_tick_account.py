"""The step loops' own account of every tick (utils/perf.py::StallBreakdown,
always on): planted stalls through a small DecodeServer come back as one
record each, naming the phase they were planted in with CPU and wall on the
right sides of each other; kinds are counted right; the buckets' quantiles
follow a sorted list; the handle outlives the server; the train loop's four
gauges are its phases' sums; a tick costs microseconds; a dispatch is dry
when the loop slept past its program and not otherwise."""

import gc
import json
import math
import random
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_pipeline_tpu.models import create_model_from_config
from distributed_pipeline_tpu.obs import trace as trace_lib
from distributed_pipeline_tpu.serving import DecodeServer
from distributed_pipeline_tpu.serving.scheduler import (
    TICK_DISPATCHES, TICK_KINDS, TICK_PHASES)
from distributed_pipeline_tpu.utils import perf
from distributed_pipeline_tpu.utils.perf import StallBreakdown

PLANTED_S = 0.15


@pytest.fixture(scope="module")
def wl_and_params():
    wl = create_model_from_config(
        model_family="gpt2", vocab_size=32, seq_len=64, hidden_size=32,
        num_layers=2, num_heads=2, dtype="float32")
    return wl, wl.init_params(jax.random.PRNGKey(3))


def warm_server(wl_and_params, **kw):
    """A two-slot server past its steady point, a dozen decode-only ticks
    booked (so that the kind has a median), one long request decoding."""
    wl, params = wl_and_params
    server = DecodeServer(wl, params, decode_slots=2, page_size=4,
                          max_prompt_len=8, max_len=64, **kw)
    req = server.submit(np.arange(1, 6, dtype=np.int32), 50)
    for _ in range(14):
        server.step()
    assert server.ticks.steady_t is not None and not req.finished
    assert server.ticks.stall_count == 0
    return server


def serve_account():
    return StallBreakdown("serve", phases=TICK_PHASES, waits=("fetch_wait",),
                          dispatches=TICK_DISPATCHES, kinds=TICK_KINDS)


class big_cycle:
    """Garbage the reference counts cannot free: a ring of lists."""

    def __init__(self, n=400_000):
        cells = [[] for _ in range(n)]
        for a, b in zip(cells, cells[1:] + cells[:1]):
            a.append(b)


def spin(seconds):
    until = time.perf_counter() + seconds
    while time.perf_counter() < until:
        pass


def collect_a_cycle():
    big_cycle()
    gc.collect()


# what is planted -> (inside engine.decode or None, between two steps or
# None, what the one record has to say)
PLANTED = {
    "sleep_in_decode": (
        lambda: time.sleep(PLANTED_S), None,
        lambda r: (r["phases"]["decode_dispatch"] >= PLANTED_S
                   and r["wall_s"] >= PLANTED_S and r["cpu_s"] < 0.020
                   and r["between_s"] < 0.020)),
    "busy_in_decode": (
        lambda: spin(PLANTED_S), None,
        lambda r: (r["phases"]["decode_dispatch"] >= PLANTED_S
                   and abs(r["cpu_s"] - r["wall_s"]) <= 0.3 * r["wall_s"])),
    "sleep_between": (
        None, lambda: time.sleep(PLANTED_S),
        lambda r: (r["between_s"] >= PLANTED_S and r["wall_s"] < 0.050
                   and r["phases"]["decode_dispatch"] < 0.050)),
    "collection_in_tick": (
        collect_a_cycle, None,
        lambda r: (r["gc_s"] > 0.0
                   and r["phases"]["decode_dispatch"] >= r["gc_s"])),
}


@pytest.mark.parametrize("what", sorted(PLANTED))
def test_planted_stall_is_one_record_naming_its_phase(wl_and_params, what,
                                                      tmp_path, monkeypatch):
    inside, between, says = PLANTED[what]
    tracer = trace_lib.tracer_for(str(tmp_path), 0, armed=True)
    server = warm_server(wl_and_params, tracer=tracer)
    decode = server.engine.decode
    if inside is not None:
        def planted_once():
            inside()
            monkeypatch.setattr(server.engine, "decode", decode)
            return decode()
        monkeypatch.setattr(server.engine, "decode", planted_once)
    n0 = server.ticks.n_ticks         # booked (the newest is pending yet)
    server.step()                     # the tick the stall is planted in
    if between is not None:
        between()
    server.step()                     # its period ends: it is booked
    server.step()
    acct = server.ticks
    assert acct.stall_count == 1 and len(acct.stalls) == 1
    (record,) = acct.stalls
    assert record["kind"] == "decode" and record["tick"] == n0 + 2
    assert says(record), record
    if inside is not None:            # the phase that held the time
        assert max(record["phases"], key=record["phases"].get) \
            == "decode_dispatch"
    assert record["excess_s"] == pytest.approx(
        record["wall_s"] + record["between_s"] - record["median_s"], abs=2e-6)
    assert acct.stall_seconds == pytest.approx(record["excess_s"], abs=2e-6)
    assert (record["queued"], record["active"], record["inflight"]) \
        == (0, 1, 1)
    assert record["recompiles"] == 0 and not record["session_edge"]
    assert abs(record["t"] - time.time()) < 60.0
    # the armed tracer has it as an instant, its fields as args
    tracer.close()
    (instant,) = [e for e in trace_lib.read_trace(
        trace_lib.trace_path(str(tmp_path), 0)) if e["name"] == "serve.stall"]
    assert instant["ph"] == "i" and instant["cat"] == "serve"
    assert instant["args"] == json.loads(json.dumps(record))
    summary = acct.summary()
    assert summary["stalls"]["records"] == [record]
    assert "records" not in acct.summary(records=False)["stalls"]


def test_tick_kinds_are_counted_by_what_each_tick_dispatched(wl_and_params,
                                                             capfd):
    wl, params = wl_and_params
    server = DecodeServer(wl, params, decode_slots=2, page_size=4,
                          max_prompt_len=8, max_len=64, sanitize=True)
    prompt = np.arange(1, 6, dtype=np.int32)
    seen = []

    def tick():
        p0, d0 = server.prefill_steps, server.decode_steps
        server.step()
        seen.append(TICK_KINDS[(server.prefill_steps > p0)
                               + 2 * (server.decode_steps > d0)])
    server.submit(prompt, 6)
    for _ in range(4):
        tick()
    server.submit(prompt[:3], 4)      # a late arrival: prefill beside decode
    while server.busy:
        tick()
    tick()                            # nothing left: an idle tick
    tick()
    server.stop_sanitizer()
    assert seen[0] == "prefill+decode" and seen[-1] == "idle"
    want = {k: seen[1:].count(k) for k in set(seen[1:])}   # the first tick
    summary = server.ticks.summary()      # fetched the first token: steady
    assert {k: v["count"] for k, v in summary["kinds"].items()} == want
    assert set(want) == {"decode", "prefill+decode", "idle"}
    assert summary["ticks"] == len(seen) - 1
    assert summary["dispatches"]["decode"] == server.decode_steps - 1
    assert summary["dispatches"]["prefill"] == 1
    # the sums are the loop's wall time since the steady point
    assert summary["seconds"] == pytest.approx(summary["span_s"], rel=1e-3)
    assert summary["between_s"] <= summary["seconds"]
    for row in summary["kinds"].values():
        assert row["p50_s"] <= row["p99_s"] <= row["max_s"] + 1e-9
        assert sum(row["phases"].values()) == pytest.approx(row["tick_s"],
                                                            abs=1e-5)
    # sanitize: ONE line on standard error as the server stops
    lines = [ln for ln in capfd.readouterr().err.splitlines()
             if ln.startswith("ticks serve ")]
    assert len(lines) == 1
    assert json.loads(lines[0][len("ticks serve "):])["ticks"] \
        == summary["ticks"]
    server.stop_sanitizer()           # idempotent: no second line
    assert "ticks serve" not in capfd.readouterr().err


def test_spec_rounds_are_a_kind_and_every_one_is_dry(wl_and_params):
    wl, params = wl_and_params
    server = DecodeServer(wl, params, decode_slots=2, page_size=4,
                          max_prompt_len=8, max_len=64, spec_tokens=2)
    server.submit(np.arange(1, 6, dtype=np.int32), 20)
    server.drain()
    server.ticks.close()
    summary = server.ticks.summary()
    assert summary["kinds"]["spec"]["count"] >= 2
    assert summary["dispatches"]["spec"] == summary["dry"]["spec"] > 0
    assert summary["kinds"]["spec"]["phases"]["fetch_wait"] > 0.0


def test_bucket_quantiles_follow_a_sorted_list():
    rng = random.Random(7)
    stats = perf._KindStats(0)
    periods = []
    width = 2.0 ** 0.25               # a bucket's width
    for i in range(5000):
        p = math.exp(rng.gauss(math.log(0.004), 0.8))
        if i % 500 == 0:
            p *= 40.0                 # a stall now and then
        periods.append(p)
        stats.add(p, p, 0.0, 0.0, [])
        if i in (0, 1, 2, 9, 100, 1234, 4999):
            exact = sorted(periods)[(len(periods) - 1) // 2]
            assert exact / width <= stats.median_s() <= exact * width, i
    ordered = sorted(periods)
    for q in (0.5, 0.9, 0.99):
        exact = ordered[max(0, math.ceil(q * len(ordered)) - 1)]
        assert exact / width <= stats.quantile_s(q) <= exact * width, q
    assert stats.median_s() == stats.quantile_s(0.5)
    assert stats.max_s == ordered[-1] and stats.count == 5000
    # a period outside the buckets' range lands in the end ones
    stats.add(1e-7, 1e-7, 0.0, 0.0, [])
    stats.add(1e4, 1e4, 0.0, 0.0, [])
    assert stats.buckets[0] >= 1 and stats.buckets[-1] == 1


def test_the_handle_outlives_the_server(wl_and_params):
    server = warm_server(wl_and_params)
    server.reset_stats()              # (does not clear the account)
    acct, n = server.ticks, server.ticks.summary()["ticks"]
    assert n >= 12
    del server
    gc.collect()
    assert perf.tick_account("serve") is acct
    assert perf.tick_account("serve").summary()["ticks"] == n
    assert perf.tick_account("no-such-loop") is None
    # one small tuple a tick, to cut a window out by time
    t0, kind, tick_s, cpu_s, between_s, dry = acct.ticks[-1]
    assert kind == "decode" and tick_s > 0.0 and dry in (0, 1)
    assert [t[0] for t in acct.ticks] == sorted(t[0] for t in acct.ticks)


@pytest.mark.parametrize("feed", ["eager", "prefetched"])
def test_train_gauges_are_the_phases_sums(tmp_path, feed, capfd):
    from distributed_pipeline_tpu.data import load_data_from_args
    from distributed_pipeline_tpu.parallel import make_mesh
    from distributed_pipeline_tpu.utils import logger
    from distributed_pipeline_tpu.utils.trainer import TrainLoop

    wl = create_model_from_config(
        model_family="gpt2", vocab_size=64, seq_len=16, hidden_size=32,
        num_layers=2, num_heads=2, dtype="float32")
    data = load_data_from_args("train", batch_size=8, dataset="synthetic-lm",
                               seq_len=16, vocab_size=64, seed=0)
    kw = dict(prefetch_depth=2, dispatch_lag=1) if feed == "prefetched" \
        else {}
    with logger.scoped_configure(format_strs=[]):
        loop = TrainLoop(model=wl, data=data, batch_size=8, lr=1e-3,
                         learning_steps=100, log_interval=10 ** 9,
                         save_interval=10 ** 9, mesh=make_mesh(dp=8),
                         checkpoint_dir=str(tmp_path), seed=5, sanitize=True,
                         **kw)
        for _ in range(4):            # the first compiles: three are steady
            loop.run_step(loop.next_batch())
        loop.flush_metrics()
        loop.stop_sanitizer()
    acct = perf.tick_account("train")
    assert acct is loop.stalls
    summary = acct.summary()
    assert summary["kinds"].keys() == {"step"} and summary["ticks"] == 3
    step = summary["kinds"]["step"]
    sums = loop.stalls.sums()
    since = {g: sums[g] - loop._ledger_stall0[g] for g in sums}
    for gauge, phase in (("data_wait_s", "data_wait"), ("h2d_wait_s", "h2d"),
                         ("dispatch_s", "dispatch")):
        assert 3 * step["phases"][phase] == pytest.approx(since[gauge],
                                                          abs=5e-6), gauge
    assert since["dispatch_s"] > 0.0
    assert step["phases"]["log"] > 0.0
    # device_step_s spans ticks; the loop's own wait in it is the phase
    assert 3 * step["phases"]["metrics_wait"] <= since["device_step_s"] + 5e-6
    assert summary["dispatches"] == {"step": 3}
    assert summary["seconds"] == pytest.approx(summary["span_s"], rel=1e-3)
    lines = [ln for ln in capfd.readouterr().err.splitlines()
             if ln.startswith("ticks train ")]
    assert len(lines) == 1
    assert json.loads(lines[0][len("ticks train "):])["kinds"]["step"][
        "count"] == 3
    # run_step alone (a caller with its own batches) is a tick too
    batch = next(data)
    before = acct.n_ticks
    loop.run_step(batch)
    loop.run_step(batch)
    assert acct.n_ticks == before + 1     # (the second is still pending)


def test_a_tick_costs_microseconds():
    """The account's own cost a tick, over 10,000 ticks of a stub loop
    with a decode tick's calls: under 10 us here, asserted at 25 us so
    that a loaded machine does not flap."""
    class Busy:
        @staticmethod
        def is_ready():
            return False
    acct, newest = serve_account(), Busy()
    acct.mark_steady()
    decode = [d for d, _ in TICK_DISPATCHES].index("decode")

    def loop(n, on):
        t_in = time.perf_counter()
        for _ in range(n):
            if on:
                acct.begin(0, 16, 1, 0, False)
                acct.dispatched(decode, newest)
            t0 = time.perf_counter()
            t1 = time.perf_counter()
            if on:
                acct.phase("decode_dispatch", t1 - t0)
            t2 = time.perf_counter()
            t3 = time.perf_counter()
            if on:
                acct.phase("fetch_wait", t3 - t2)
                acct.phase("fetch_host", time.perf_counter() - t3)
                acct.end()
        return time.perf_counter() - t_in
    loop(1000, True)
    cost = min((loop(10_000, True) - loop(10_000, False)) / 10_000
               for _ in range(3))
    assert cost < 25e-6, cost
    assert acct.summary()["kinds"]["decode"]["count"] >= 30_000
    assert acct.stall_count == 0 and len(acct.ticks) <= acct.KEPT_TICKS


def test_a_dispatch_is_dry_when_the_loop_slept_past_its_program():
    program = jax.jit(lambda x: jax.lax.fori_loop(
        0, 40, lambda i, a: jnp.tanh(a @ a), x))
    x = jnp.ones((384, 384), jnp.float32)
    program(x).block_until_ready()
    acct = StallBreakdown("stub")
    acct.mark_steady()
    newest = None
    for sleep_past in (False, False, True, False, True):
        if sleep_past:
            newest.block_until_ready()
        acct.begin()
        acct.dispatched(0, newest)
        newest = program(x)
        acct.end()
    newest.block_until_ready()
    acct.close()
    summary = acct.summary()
    # the first has nothing in flight before it, two slept past theirs
    assert summary["dispatches"] == {"step": 5}
    assert summary["dry"] == {"step": 3}
    assert [t[5] for t in acct.ticks] == [1, 0, 1, 0, 1]


def test_the_beacon_and_the_prometheus_snapshot_carry_the_summary(
        wl_and_params, tmp_path):
    """The operator's view: a replica's beacon holds the summary without
    the records in its ``serving`` snapshot, and ``prometheus_lines`` prints
    tick latency by kind, stalls and dry dispatches from it."""
    from distributed_pipeline_tpu.chaos import goodput
    from distributed_pipeline_tpu.obs import export as export_lib
    from distributed_pipeline_tpu.serving.fleet import (
        ReplicaPaths, WorkerProtocol)

    server = warm_server(wl_and_params)
    server.step()
    time.sleep(0.05)                  # one stalled tick, the caller's
    server.step()
    server.step()
    fleet = str(tmp_path / "fleet")
    replica = goodput.replica_dir(fleet, 0)
    proto = WorkerProtocol(ReplicaPaths.at(replica, 0), 0, attempt=0)
    proto.tracker.ticks = server.ticks
    proto.write_beacon(17)
    snap = goodput.read_beacons(replica)[0]["serving"]
    assert snap["ticks"] == json.loads(json.dumps(
        server.ticks.summary(records=False)))
    assert snap["ticks"]["stalls"] == {
        "count": 1, "seconds": snap["ticks"]["stalls"]["seconds"]}
    assert {"wall_s", "serving_s", "drain_s", "swap_s"} <= set(snap)
    text = "\n".join(export_lib.prometheus_lines(fleet, now=time.time()))
    decode = snap["ticks"]["kinds"]["decode"]
    assert (f'dpt_tick_seconds{{kind="decode",quantile="0.5",replica="0"}} '
            f'{decode["p50_s"]:g}') in text
    assert 'dpt_tick_seconds{kind="decode",quantile="0.99",replica="0"}' \
        in text
    assert 'dpt_stalls_total{replica="0"} 1' in text
    assert 'dpt_stall_seconds_total{replica="0"} ' in text
    assert (f'dpt_dry_dispatches_total{{dispatch="decode",replica="0"}} '
            f'{snap["ticks"]["dry"]["decode"]}') in text
    proto.close()
