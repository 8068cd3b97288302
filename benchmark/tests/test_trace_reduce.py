"""trace_reduce.py on hand-built traces."""

import pytest

from harness.trace_reduce import (Event, Trace, busy_and_gaps,
                                  collective_exposed, label_gaps,
                                  stable_name, summarize)


def test_busy_union_counts_overlapping_ops_once():
    ops = [Event("fusion.1", 0.0, 1.0), Event("fusion.2", 0.5, 1.0),
           Event("copy.3", 3.0, 0.5)]
    busy, gaps = busy_and_gaps(ops, 0.0, 4.0)
    assert busy == pytest.approx(2.0)           # [0, 1.5] and [3, 3.5]
    assert gaps == [(1.5, 3.0), (3.5, 4.0)]


def test_busy_clips_to_the_window():
    busy, gaps = busy_and_gaps([Event("a", -1.0, 2.0), Event("b", 3.5, 2.0)],
                               0.0, 4.0)
    assert busy == pytest.approx(1.5)
    assert gaps == [(1.0, 3.5)]


def test_gap_is_labelled_by_the_annotation_that_covers_most_of_it():
    gaps = [(1.0, 2.0), (5.0, 5.5)]
    host = [Event("next_batch", 0.9, 0.3), Event("run_step", 1.2, 0.9),
            Event("next_batch", 4.9, 0.7)]
    got = label_gaps(gaps, host)
    assert got == {"run_step": pytest.approx(1.0),
                   "next_batch": pytest.approx(0.5)}
    assert label_gaps([(8.0, 9.0)], host) == {"unlabelled": 1.0}


def test_collective_half_under_compute():
    ops = [Event("all-gather.7", 0.0, 2.0), Event("fusion.1", 1.0, 3.0)]
    total, exposed = collective_exposed(ops, 0.0, 5.0)
    assert total == pytest.approx(2.0)
    assert exposed == pytest.approx(1.0)


def test_async_collective_span_counts_from_start_to_done():
    ops = [Event("%fusion.1 = f32[2] fusion(%all-gather-done.3)", 0.0, 1.0),
           Event("%all-gather-done.3 = f32[2] all-gather-done(x)", 1.0, 0.5)]
    spans = [Event("%all-gather-start.3 = f32[2] all-gather-start(y)",
                   0.5, 1.0)]
    total, exposed = collective_exposed(ops, 0.0, 2.0, spans)
    assert total == pytest.approx(1.0)          # 0.5 .. 1.5
    assert exposed == pytest.approx(0.5)        # 1.0 .. 1.5: nothing else


def test_a_while_envelope_is_not_work():
    """A microbatch loop: the ``while`` event spans its body's ops. A
    collective inside it with nothing beside it is exposed, the idle time
    inside the loop is idle, and the loop's time is not counted twice."""
    dev = [Event("%while.3 = (s32[], f32[8]) while(%tuple.1)", 0.0, 4.0),
           Event("fusion.1", 0.0, 1.0),
           Event("all-reduce.2", 1.0, 1.0),         # nothing beside it
           Event("fusion.4", 3.0, 1.0),             # 2.0 .. 3.0: idle
           Event("%conditional.7 = f32[] conditional(%p)", 4.0, 0.5),
           Event("copy.8", 4.0, 0.5)]
    tr = Trace(ops={"/device:TPU:0": dev}, modules={},
               host=[Event("run_step", 0.0, 5.0)], t0=0.0, t1=5.0)
    s = summarize(tr)
    assert s.busy_s == pytest.approx(3.5)
    assert "while" not in s.op_time and "conditional" not in s.op_time
    assert s.collective_s["/device:TPU:0"] == pytest.approx(1.0)
    assert s.collective_exposed_s["/device:TPU:0"] == pytest.approx(1.0)
    assert s.idle_by_label == {"run_step": pytest.approx(1.5)}
    assert all(name != "while" for name, _ in s.breakdown()["device_ops"])


def test_stable_names():
    assert stable_name("%fusion.123") == "fusion"
    assert stable_name("flash_attention_fwd.4") == "flash_attention_fwd"
    assert stable_name("jit_train_step(1234567)") == "jit_train_step"
    assert stable_name("all-reduce.1.2") == "all-reduce"
    assert stable_name(
        "%flash_attention_bwd.59 = (bf16[96,1024,128]{2,1,0}) custom-call("
        "bf16[96,1024,128] %flash_attention_fwd.3)") == "flash_attention_bwd"
    assert stable_name("%copy.495.remat = bf16[3073,16,20,64] copy(x)") == \
        "copy"
    assert stable_name("%fusion.89.remat_uncompressed = bf16[3] copy(x)") == \
        "fusion"


def test_summary_over_two_devices():
    dev0 = [Event("fusion.1", 0.0, 1.0), Event("all-reduce.2", 1.0, 1.0)]
    dev1 = [Event("fusion.1", 0.0, 0.5), Event("all-reduce.2", 0.5, 1.0),
            Event("fusion.9", 1.0, 0.5)]
    tr = Trace(ops={"/device:TPU:0": dev0, "/device:TPU:1": dev1},
               modules={"/device:TPU:0": [Event("jit_step(1)", 0.0, 2.0)],
                        "/device:TPU:1": [Event("jit_step(1)", 0.0, 1.5)]},
               host=[Event("run_step", 0.0, 4.0)], t0=0.0, t1=4.0)
    s = summarize(tr)
    assert s.window_s == 4.0 and s.n_devices == 2
    assert s.busy_s == pytest.approx((2.0 + 1.5) / 2)
    assert s.op_seconds("fusion") == (pytest.approx((1.0 + 0.5 + 0.5) / 2), 1)
    assert s.module_seconds("jit_step")[0] == pytest.approx(1.75)
    assert s.collective_exposed_s["/device:TPU:0"] == pytest.approx(1.0)
    assert s.collective_exposed_s["/device:TPU:1"] == pytest.approx(0.5)
    assert s.idle_by_label == {"run_step": pytest.approx(2.5)}
    b = s.breakdown()
    assert b["device_ops"][0][0] in ("fusion", "all-reduce")
    assert b["idle_gaps"] == [["run_step", pytest.approx(2.5)]]
