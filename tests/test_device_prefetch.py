"""Steady-state throughput layer tests (ISSUE 5): device-side prefetch
preserves batch order and exact-resume semantics, bounds its in-flight
buffers, composes with the sanitizer, and async lagged-metrics dispatch is
numerically identical to eager mode after flush."""

import numpy as np
import pytest

import jax

from distributed_pipeline_tpu.data import (
    DeviceBatch,
    batch_iterator,
    prefetch_to_device,
)
from distributed_pipeline_tpu.data.dataset import SyntheticLMDataset
from distributed_pipeline_tpu.utils import logger
from distributed_pipeline_tpu.utils.perf import StallBreakdown

from tests.test_trainer import make_loop, tiny_data


def host_batches(n):
    for i in range(n):
        yield {"x": np.full((4, 3), i, dtype=np.int32)}


# ------------------------------------------------------------ pure wrapper


def test_prefetch_preserves_order_and_bounds_inflight():
    puts = []

    def put(b):
        puts.append(int(b["x"][0, 0]))
        return b

    out = []
    for db in prefetch_to_device(host_batches(10), put=put, depth=3):
        assert isinstance(db, DeviceBatch)
        assert db.n_items == 4
        out.append(int(db.arrays["x"][0, 0]))
        # in flight = transferred but not yet delivered: bounded by depth
        assert len(puts) - len(out) <= 3
    assert out == list(range(10))
    assert puts == list(range(10))  # transfer order == draw order


def test_prefetch_depth_validated_eagerly():
    with pytest.raises(ValueError):
        prefetch_to_device(host_batches(3), put=lambda b: b, depth=0)


def test_prefetch_drains_finite_stream():
    got = list(prefetch_to_device(host_batches(5), put=lambda b: b, depth=3))
    assert [int(b.arrays["x"][0, 0]) for b in got] == list(range(5))
    assert list(prefetch_to_device(iter(()), put=lambda b: b, depth=2)) == []


def test_prefetch_composes_with_skip_batches_resume():
    """Exact-resume contract: prefetch only reorders WHEN transfers
    happen, never WHICH indices are drawn — a resumed (skip_batches)
    stream seen through the prefetch wrapper is bit-identical to the
    uninterrupted stream's tail."""
    ds = SyntheticLMDataset(seq_len=16, vocab_size=64, size=64, seed=3)
    full = batch_iterator(ds, 8, shuffle=True, seed=1, loop=True)
    expect = [next(full) for _ in range(8)][4:]
    resumed = batch_iterator(ds, 8, shuffle=True, seed=1, loop=True,
                             skip_batches=4)
    pre = prefetch_to_device(resumed, put=lambda b: b, depth=2)
    for want in expect:
        got = next(pre)
        np.testing.assert_array_equal(got.arrays["input_ids"],
                                      want["input_ids"])


def test_prefetch_attributes_stalls():
    stats = StallBreakdown()
    list(prefetch_to_device(host_batches(4), put=lambda b: b, depth=2,
                            stats=stats))
    totals = stats.sums()
    assert set(totals) == set(StallBreakdown.GAUGES)
    assert totals["data_wait_s"] >= 0.0 and totals["h2d_wait_s"] >= 0.0


# ------------------------------------------------- TrainLoop integration


def _logged_losses(loop, batches):
    """Run the loop over ``batches``, dumping after every step; returns
    (per-step losses from run_step's return, per-dump logged losses)."""
    ret, logged = [], []
    for _ in range(len(batches)):
        m = loop.run_step(loop.next_batch())
        ret.append(float(jax.device_get(m["loss"])))
        d = logger.dumpkvs()
        if "loss" in d:
            logged.append(d["loss"])
    loop.flush_metrics()
    d = logger.dumpkvs()
    if "loss" in d:
        logged.append(d["loss"])
    return ret, logged


def test_prefetch_and_lagged_metrics_match_eager(tmp_path):
    """The tentpole's numerical contract: prefetch_depth + dispatch_lag
    change WHEN work happens, never WHAT is computed — per-step losses
    and the logged loss sequence (after flush) are bit-identical to the
    eager loop's."""
    batches = [next(tiny_data("gpt2", 8, seed=11)) for _ in range(6)]

    eager = make_loop(tmp_path / "eager", data=iter(batches))
    with logger.scoped_configure(dir=str(tmp_path / "le"), format_strs=[]):
        eager_ret, eager_logged = _logged_losses(eager, batches)

    lagged = make_loop(tmp_path / "lagged", data=iter(batches),
                       prefetch_depth=2, dispatch_lag=1)
    assert lagged.prefetch_depth == 2 and lagged.dispatch_lag == 1
    with logger.scoped_configure(dir=str(tmp_path / "ll"), format_strs=[]):
        lag_ret, lag_logged = _logged_losses(lagged, batches)

    np.testing.assert_array_equal(eager_ret, lag_ret)
    # with lag=1 and a dump per step, the logged sequence is the SAME
    # values one dump late; the final flush delivers the tail
    np.testing.assert_array_equal(eager_logged, lag_logged)
    assert not lagged._inflight  # flush drained the ring


def test_sanitizer_and_stalls_clean_under_prefetch(tmp_path):
    """The sanitizer's counters stay clean under prefetch + lag: the
    wrapper's device placement is explicit (guard-legal) and steady state
    triggers no recompiles; the stall gauges all populate."""
    loop = make_loop(tmp_path, sanitize=True, prefetch_depth=2,
                     dispatch_lag=1)
    try:
        loop.run_step(loop.next_batch())
        base = loop.recompile_count
        assert base >= 1
        for _ in range(4):
            loop.run_step(loop.next_batch())
        loop.flush_metrics()
        assert loop.step == 5
        assert loop.recompile_count == base  # frozen: no silent retrace
        totals = loop.stalls.sums()
        assert set(totals) == set(StallBreakdown.GAUGES)
        assert totals["dispatch_s"] > 0.0
        assert totals["device_step_s"] > 0.0  # the lagged fetch observed it
    finally:
        loop.stop_sanitizer()


@pytest.mark.slow  # throughput-shaped: full run_loop composition (ISSUE 5)
def test_run_loop_prefetch_eval_save_and_flush(tmp_path):
    """End-to-end run_loop with prefetch + lag + sanitize: eval callbacks
    fire under the transfer guard, periodic + final saves land, and the
    lagged ring is drained at exit."""
    calls = []

    def cb(tl):
        calls.append(int(jax.device_get(tl.state.step)))

    loop = make_loop(tmp_path, learning_steps=6, eval_interval=3,
                     save_interval=3, eval_data=tiny_data("gpt2", 8, seed=2),
                     prefetch_depth=2, dispatch_lag=2, sanitize=True,
                     eval_callbacks=[cb])
    try:
        loop.run_loop()
    finally:
        loop.stop_sanitizer()
    assert loop.step == 6
    assert calls == [3, 6]
    assert not loop._inflight
    names = {p.name for p in tmp_path.iterdir()}
    assert "model_000003" in names and "model_000006" in names
