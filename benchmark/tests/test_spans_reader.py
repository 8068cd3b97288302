"""readers/spans.py on hand-built event lists (the ring's event model)."""

import types

import pytest

import run as bench_run


def ev(name, sid, dur, parent=None, **args):
    e = {"ph": "X", "name": name, "cat": name.split(".")[0], "t": 0.0,
         "dur": dur, "sid": sid}
    if parent:
        e["parent"] = parent
    if args:
        e["args"] = args
    return e


def reader(metric, events):
    """The metric's reader, found as run.py finds it, over ``events``."""
    fn, _spec = bench_run.load_reader(metric)
    fn.__globals__["ring"] = lambda: list(events)
    return fn


def ctx(window_s=2.0):
    return {"trace": types.SimpleNamespace(window_s=window_s)}


TRAIN = [
    ev("train.next_batch", "h:1", 0.010),
    ev("data.host_wait", "h:2", 0.004, "h:1"),
    ev("train.run_step", "h:3", 0.100, step=7),
    ev("train.dispatch", "h:4", 0.002, "h:3"),
    ev("train.metrics_wait", "h:5", 0.090, "h:3", step=6),
    ev("train.next_batch", "h:6", 0.030),
    ev("train.run_step", "h:7", 0.020, step=8),        # no child at all
    ev("train.metrics_wait", "h:8", 0.500, step=8),    # flush: no parent
]

SERVE = [
    ev("serve.step", "h:1", 0.100, queued=0, active=16),
    ev("serve.decode_dispatch", "h:2", 0.003, "h:1"),
    ev("serve.fetch", "h:3", 0.095, "h:1", n_tokens=16),
    ev("serve.fetch_wait", "h:4", 0.090, "h:3"),       # grandchild
    ev("serve.step", "h:5", 0.050, queued=1, active=15),
    ev("serve.spec_round", "h:6", 0.040, "h:5"),
    ev("serve.fetch_wait", "h:7", 0.010, "h:6"),
    ev("serve.fetch_wait", "h:8", 0.020, "h:6"),
    ev("serve.fetch_wait", "h:9", 7.000),              # a drain outside a tick
    ev("request.queue", "h:10", 0.002, "h:1", id=1, prompt_len=40),
    ev("request.first_token", "h:11", 0.198, "h:3", id=1),
    ev("request.queue", "h:12", 0.050, "h:5", id=2, prompt_len=9),
    ev("request.first_token", "h:13", 0.150, "h:3", id=2),
    ev("request.queue", "h:14", 9.000, "h:5", id=3, prompt_len=9),  # alone
    ev("request.first_token", "h:15", 9.000, "h:3", id=4),          # alone
    ev("request.decode", "h:16", 3.0, "h:3", id=1, n_tokens=30),
]


@pytest.mark.parametrize("metric, events, want", [
    # (0.010 + 0.030) / 2 s
    ("input_wait_share.train", TRAIN, 2.0),
    # (0.100 - 0.090) + 0.020, the parentless wait left alone, / 2 s
    ("host_step_share.train", TRAIN, 1.5),
    # (0.100 - 0.090) + (0.050 - 0.010 - 0.020) / 2 s
    ("sched_host_share.serve", SERVE, 1.5),
    # requests 1 and 2 alone: (0.002 + 0.050) / (0.200 + 0.200)
    ("ttft_queue_share", SERVE, 13.0),
])
def test_reader_on_hand_built_spans(metric, events, want):
    assert reader(metric, events)(ctx()) == pytest.approx(want)


@pytest.mark.parametrize("metric", [
    "input_wait_share.train", "host_step_share.train",
    "sched_host_share.serve", "ttft_queue_share"])
def test_reader_finds_nothing(metric):
    """An empty ring, spans of another kind only, or no traced window:
    nothing, and no error."""
    assert reader(metric, [])(ctx()) is None
    other = [ev("harness", "h:1", 1.0),
             ev("request.queue", "h:2", 1.0, id=1)]     # one of the two
    assert reader(metric, other)(ctx()) is None
    if metric != "ttft_queue_share":
        assert reader(metric, TRAIN + SERVE)({"trace": None}) is None


def test_parent_loop_in_the_ring_ends():
    """Two tracers of one label in one process could mint one id twice; a
    chain that loops must not hang the reader."""
    fn, _ = bench_run.load_reader("sched_host_share.serve")
    less = fn.__globals__["seconds_less_beneath"]
    loop = [ev("serve.step", "h:1", 1.0),
            ev("a", "h:2", 1.0, "h:3"), ev("b", "h:3", 1.0, "h:2"),
            ev("serve.fetch_wait", "h:4", 0.5, "h:2")]
    assert less(loop, "serve.step", "serve.fetch_wait") == (1.0, 0.0)


def test_ring_of_a_program_without_spans_is_empty(monkeypatch):
    """The reader runs over the parent's checkout too: a tracer module with
    no ``recorded`` reads as nothing."""
    from distributed_pipeline_tpu.obs import trace
    fn, _ = bench_run.load_reader("input_wait_share.train")
    monkeypatch.delattr(trace, "recorded")
    assert fn.__globals__["ring"]() == []
    assert fn(ctx()) is None
