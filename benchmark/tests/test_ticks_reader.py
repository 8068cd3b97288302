"""readers/ticks.py on a hand-built account and trace summary, on the
program's own account, and with no account at all."""

import time
import types

import pytest

import run as bench_run

SERVE = ("tick_stall_share.serve", "device_dry_dispatch_share.serve",
         "tick_between_share.serve", "decode_tick_device_share.serve")
TRAIN = ("step_stall_share.train", "device_dry_dispatch_share.train")


def record(kind, wall, between, median, edge=False, tick=0):
    return {"kind": kind, "wall_s": wall, "between_s": between, "tick": tick,
            "median_s": median, "excess_s": wall + between - median,
            "session_edge": edge}


def account_of(kinds, records, dispatches, dry, stall_s, ring=(),
               n_ticks=0):
    """What ``StallBreakdown.summary()`` gives, as far as a reader looks:
    ``kinds`` maps a kind to (count, summed tick part, summed between)."""
    rows = {k: {"count": n, "seconds": tick + between}
            for k, (n, tick, between) in kinds.items()}
    summary = {
        "ticks": sum(n for n, _, _ in kinds.values()),
        "seconds": sum(t + b for _, t, b in kinds.values()),
        "between_s": sum(b for _, _, b in kinds.values()),
        "kinds": rows, "dispatches": dispatches, "dry": dry,
        "stalls": {"count": len(records), "seconds": stall_s,
                   "records": records}}
    return types.SimpleNamespace(summary=lambda: summary, ticks=list(ring),
                                 stalls=records, n_ticks=n_ticks)


# 1000 decode ticks of 10 ms (1 ms of it between) and 100 with a prefill of
# 30 ms; one decode tick stalled 110 ms in itself, one waited 3 s for the
# harness to reduce its trace (a session's edge), one prefill tick 2 s more
SERVE_ACCOUNT = account_of(
    {"decode": (1000, 9.0 + 0.1, 1.0 + 3.0),
     "prefill+decode": (100, 2.9, 0.1 + 2.0)},
    [record("decode", 0.109, 0.001, 0.010),
     record("decode", 0.009, 3.001, 0.010, edge=True, tick=1050),
     record("prefill+decode", 0.029, 2.001, 0.030)],
    {"prefill": 100, "chunk": 0, "decode": 1100, "spec": 0},
    {"prefill": 3, "chunk": 0, "decode": 21, "spec": 0},
    stall_s=0.100 + 3.000 + 2.000,
    # the ring's tail: tick 1,050 closed the traced window (the trace was
    # reduced behind it); the 2.4 s before it held 200 decode ticks of
    # 10 ms and 10 with a prefill; the drain's ticks after it are shorter
    ring=[(100.0 + 0.1 * i, "decode", 0.009, 0.002, 0.001, 0)
          for i in range(40)]                       # before the window
    + [(110.0 + 0.011 * i, "prefill+decode" if i % 21 == 20 else "decode",
        0.009, 0.002, 0.001, 0) for i in range(210)]
    + [(112.31, "decode", 0.009, 0.002, 3.001, 0)]  # tick 1,050
    + [(116.0 + 0.006 * i, "decode", 0.005, 0.002, 0.001, 0)
       for i in range(50)],                         # the drain
    n_ticks=1100)
TRAIN_ACCOUNT = account_of(
    {"step": (80, 19.0, 1.0 + 4.0)},
    [record("step", 0.240, 4.010, 0.250, edge=True)],
    {"step": 80}, {"step": 3}, stall_s=4.0,
    # (entry, kind, tick s, CPU s, between s, dry): set-up's three followed
    # steps found the device drained, one step of the window's 77 did
    ring=[(float(i), "step", 0.24, 0.01, 0.01, int(i < 3 or i == 40))
          for i in range(80)])


def trace(seconds, count, window_s=2.4):
    return types.SimpleNamespace(
        window_s=window_s,
        module_seconds=lambda name: (seconds, count) if name
        == "jit_decode_fn" else (0.0, 0))


def reader(metric, accounts):
    fn, _spec = bench_run.load_reader(metric)
    fn.__globals__["account"] = accounts.get
    return fn


def test_the_six_on_a_hand_built_account():
    have = {"serve": SERVE_ACCOUNT, "train": TRAIN_ACCOUNT}
    ctx = {"trace": trace(2.4, 300)}          # 8 ms of device time a step
    # the edge tick (3.01 s, 3.000 over its median) is out of every sum
    all_s = 9.1 + 4.0 + 2.9 + 2.1 - 3.01
    assert reader(SERVE[0], have)(ctx) == pytest.approx(
        100.0 * (0.100 + 2.000) / all_s)
    assert reader(SERVE[1], have)(ctx) == pytest.approx(100.0 * 24 / 1200)
    assert reader(SERVE[2], have)(ctx) == pytest.approx(
        100.0 * (4.0 + 2.1 - 3.001) / all_s)
    # the traced 2.4 s held 200 decode-only ticks of 10 ms: 8 ms of program
    # in each (the drain's 6 ms ticks and the edge tick's 3 s are not in)
    assert reader(SERVE[3], have)(ctx) == pytest.approx(80.0)
    assert reader(TRAIN[0], have)({}) == pytest.approx(0.0, abs=1e-9)
    assert reader(TRAIN[1], have)({"counters": {"steps": 77}}) \
        == pytest.approx(100.0 * 1 / 77)
    assert reader(TRAIN[1], have)({}) is None     # no window to cut out


@pytest.mark.parametrize("metric", SERVE + TRAIN)
def test_nothing_without_an_account(metric):
    ctx = {"trace": trace(2.4, 300), "counters": {"steps": 77}}
    assert reader(metric, {})(ctx) is None
    empty = account_of({}, [], {}, {}, 0.0)
    assert reader(metric, {"serve": empty, "train": empty})(ctx) is None


def test_decode_share_is_nothing_without_a_decode_only_tick_or_a_trace():
    chunks_only = account_of(
        {"prefill+decode": (10, 1.0, 0.1)},
        [record("prefill+decode", 0.1, 30.0, 0.1, edge=True, tick=10)],
        {"chunk": 10, "decode": 10}, {}, 0.0,
        ring=[(float(i), "prefill+decode", 0.1, 0.0, 0.01, 0)
              for i in range(10)], n_ticks=10)
    fn = reader(SERVE[3], {"serve": chunks_only})
    assert fn({"trace": trace(2.4, 300)}) is None
    fn = reader(SERVE[3], {"serve": SERVE_ACCOUNT})
    assert fn({"trace": None}) is None
    assert fn({"trace": trace(0.0, 0)}) is None


def test_on_the_programs_own_account():
    """The names the readers ask for are the ones ``summary()`` gives: a
    real account, found under the loop's name as run.py's readers find it."""
    from distributed_pipeline_tpu.utils import perf

    acct = perf.StallBreakdown(
        "serve", phases=("decode_dispatch", "fetch_wait"),
        waits=("fetch_wait",), dispatches=(("decode", 2),),
        kinds=("idle", "idle", "decode", "decode"))
    acct.mark_steady()
    ready = types.SimpleNamespace(is_ready=lambda: True)
    for i in range(30):
        acct.begin(traced=10 <= i < 20)
        acct.dispatched(0, ready if i % 3 == 0 else None if i == 1 else
                        types.SimpleNamespace(is_ready=lambda: False))
        if i in (9, 19, 25):
            time.sleep(0.03)          # two at a session's edges, one not
        acct.end()
    acct.close()
    assert acct.stall_count == 3
    assert [r["session_edge"] for r in acct.stalls] == [True, True, False]
    ctx = {"trace": trace(1e-4, 100, window_s=10.0)}
    for metric in SERVE:
        fn, _ = bench_run.load_reader(metric)      # its own account()
        assert 0.0 <= fn(ctx) <= 100.0, metric
    fn, _ = bench_run.load_reader(SERVE[0])
    assert 25.0 < fn(ctx) <= 100.0    # one of three stalls is the loop's


def test_benchmark_json_lists_the_six_for_their_cells():
    import json
    import os

    with open(os.path.join(os.path.dirname(bench_run.HERE),
                           "BENCHMARK.json")) as f:
        bench = json.load(f)
    rows = {m["name"]: m for m in bench["per_layer"]}
    serve = [c["name"] for c in bench["workloads"] if ".serve." in c["name"]]
    train = [c["name"] for c in bench["workloads"] if ".train." in c["name"]]
    # under load every tick of the two latent cells' traced 3 s carries a
    # chunk (read on the chip in the dots3 cell): no decode-only tick there
    in_window = [c for c in serve if c.startswith(("gpt2-large", "keye"))]
    for name in SERVE + TRAIN:
        row = rows[name]
        assert row["source"] == "program_counter" and row["unit"] == "%"
        assert row["workloads"] == (
            in_window if name == "decode_tick_device_share.serve"
            else serve if name in SERVE else train), name
        assert row["moves"] == ("serve_tok_s" if name in SERVE
                                else "train_tok_s_chip")
