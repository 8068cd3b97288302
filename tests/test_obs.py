"""Observability tests (ISSUE 12): tracer roundtrip + explicit IDs,
torn-tail tolerance (the chaos.goodput.read_journal one-owner reader
contract), the zero-cost tracing-off path, Chrome-trace export schema
validity, Prometheus/status snapshots folding the live beacon `serving`
snapshots, and the chaos-marked fleet e2e — kill_replica + hot-swap under
DPT_TRACE, exported as ONE timeline where the kill, the replay on the
sibling, and the drain/swap windows are all visible with one shared
trace id per request."""

import json
import os
import time

import numpy as np
import pytest

from distributed_pipeline_tpu.chaos import CHAOS_PLAN_ENV, goodput
from distributed_pipeline_tpu.obs import export as export_lib
from distributed_pipeline_tpu.obs import trace as trace_lib
from distributed_pipeline_tpu.run import status as status_lib
from distributed_pipeline_tpu.serving.fleet import ServingFleet
from distributed_pipeline_tpu.serving.router import Router


# ================================================================= tracer

def test_tracer_roundtrip_nested_spans_and_explicit_ids(tmp_path):
    tr = trace_lib.tracer_for(str(tmp_path), 0, armed=True)
    with tr.span("step", "train", args={"step": 1}):
        tr.complete("compile", "compile", time.time() - 0.25, 0.25,
                    args={"fn": "train_step"})
        tr.instant("mark", "train", trace_id="req00000001")
    tr.close()
    events = trace_lib.read_trace(trace_lib.trace_path(str(tmp_path), 0))
    assert len(events) == 3
    by = {e["name"]: e for e in events}
    # IDs are explicit {proc}:{counter} — never wall-clock-derived
    assert by["step"]["sid"] == "rank0:1"
    assert all(e["sid"].startswith("rank0:") for e in events)
    assert len({e["sid"] for e in events}) == 3
    # nesting: bookings inside the open span carry it as parent
    assert by["compile"]["parent"] == by["step"]["sid"]
    assert by["mark"]["parent"] == by["step"]["sid"]
    assert by["mark"]["trace"] == "req00000001"
    # completed spans re-book the exact measured seconds
    assert by["compile"]["dur"] == 0.25
    assert by["step"]["ph"] == "X" and by["mark"]["ph"] == "i"


def test_spans_of_two_threads_keep_their_own_parent_chains(tmp_path):
    """One parent stack a thread: two threads that open nested spans at
    once (a shortened switch interval interleaves them) each point their
    ``parent`` links into their own thread, and every event carries the
    thread's id."""
    import sys
    import threading

    tr = trace_lib.tracer_for(str(tmp_path), 0, armed=True)
    start = threading.Barrier(2)

    def work(label):
        start.wait(timeout=10)
        for i in range(200):
            with tr.span(f"{label}.outer", label, args={"i": i}):
                with tr.span(f"{label}.inner", label):
                    tr.complete(f"{label}.booked", label, time.time(), 0.0)
                tr.instant(f"{label}.mark", label)

    threads = [threading.Thread(target=work, args=(label,))
               for label in ("a", "b")]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    tr.close()
    events = trace_lib.read_trace(trace_lib.trace_path(str(tmp_path), 0))
    assert len(events) == 2 * 200 * 4
    by_sid = {e["sid"]: e for e in events}
    assert len(by_sid) == len(events)           # ids stay unique
    tids = {}
    for e in events:
        tids.setdefault(e["cat"], set()).add(e["tid"])
        kind = e["name"].split(".")[1]
        if kind == "outer":
            assert "parent" not in e
            continue
        parent = by_sid[e["parent"]]
        assert parent["cat"] == e["cat"] and parent["tid"] == e["tid"]
        assert parent["name"].split(".")[1] == {
            "inner": "outer", "booked": "inner", "mark": "outer"}[kind]
    assert len(tids["a"]) == len(tids["b"]) == 1 and tids["a"] != tids["b"]


def test_data_workers_book_data_assemble_on_their_own_threads(tmp_path,
                                                              monkeypatch):
    """``data.assemble`` lies round the making of one batch in a prefetch
    worker (through FOLLOW: one ``is_enabled()`` a batch outside a
    session), under the worker's thread id and no parent of the loop's."""
    import threading

    from distributed_pipeline_tpu.data import load_data_from_args

    tr = trace_lib.tracer_for(str(tmp_path), 0, armed=True)
    monkeypatch.setattr(trace_lib, "FOLLOW", tr)
    data = load_data_from_args("train", batch_size=4, dataset="synthetic-lm",
                               seq_len=16, vocab_size=64, seed=0,
                               data_loader_workers=2)
    with tr.span("train.next_batch", "train"):
        batches = [next(data) for _ in range(6)]
    data.close()
    tr.close()
    assert all(b["input_ids"].shape == (4, 16) for b in batches)
    events = trace_lib.read_trace(trace_lib.trace_path(str(tmp_path), 0))
    made = [e for e in events if e["name"] == "data.assemble"]
    assert len(made) >= 6 and all(e["cat"] == "data" for e in made)
    assert {e["args"]["worker"] for e in made} == {0, 1}
    assert all("parent" not in e for e in made)
    assert threading.get_ident() not in {e["tid"] for e in made}
    assert len({e["tid"] for e in made}) == 2


def test_second_session_appending_to_shard_keeps_ids_unique(tmp_path,
                                                            monkeypatch):
    """A manual (launcher-less) resume appends a SECOND session to the
    same shard with its counter restarting at 1 — the pid qualifier
    keeps the collision-free contract; under the launcher the attempt
    index plays that role instead."""
    monkeypatch.delenv("DPT_ATTEMPT", raising=False)
    t1 = trace_lib.tracer_for(str(tmp_path), 0, armed=True)
    t1.instant("a", "x")
    t1.close()
    t2 = trace_lib.tracer_for(str(tmp_path), 0, armed=True)  # appends
    t2.instant("b", "x")
    t2.close()
    monkeypatch.setenv("DPT_ATTEMPT", "3")
    t3 = trace_lib.tracer_for(str(tmp_path), 0, armed=True)
    t3.instant("c", "x")
    t3.close()
    events = trace_lib.read_trace(trace_lib.trace_path(str(tmp_path), 0))
    sids = [e["sid"] for e in events]
    assert len(sids) == 3 and len(set(sids)) == 3, sids
    assert sids[0] == "rank0:1"
    assert sids[1].startswith("rank0.p")      # pid-qualified append
    assert sids[2].startswith("rank0.a3:")    # attempt-qualified


def test_trace_reader_skips_torn_tail(tmp_path):
    """A SIGKILL mid-append leaves one partial line; the reader (the
    read_journal one-owner contract) yields the intact prefix."""
    tr = trace_lib.tracer_for(str(tmp_path), 3, armed=True)
    tr.instant("a", "x")
    tr.instant("b", "x")
    tr.close()
    path = trace_lib.trace_path(str(tmp_path), 3)
    with open(path, "a") as f:
        f.write('{"ph": "X", "name": "torn mid-wri')
    events = trace_lib.read_trace(path)
    assert [e["name"] for e in events] == ["a", "b"]
    # and the exporter rides the same reader: no raise, torn line absent
    ct = export_lib.chrome_trace(str(tmp_path))
    assert not any("torn" in e.get("name", "")
                   for e in ct["traceEvents"])


def _tiny_gpt2():
    from distributed_pipeline_tpu.models import create_model_from_config

    return create_model_from_config(
        model_family="gpt2", vocab_size=64, seq_len=16, hidden_size=32,
        num_layers=2, num_heads=2, dtype="float32")


def _tiny_loop(wl, ckpt_dir, workers=0, **kw):
    from distributed_pipeline_tpu.data import load_data_from_args
    from distributed_pipeline_tpu.parallel import make_mesh
    from distributed_pipeline_tpu.utils.trainer import TrainLoop

    data = load_data_from_args("train", batch_size=8,
                               dataset="synthetic-lm", seq_len=16,
                               vocab_size=64, seed=0,
                               data_loader_workers=workers)
    return TrainLoop(model=wl, data=data, batch_size=8, lr=1e-3,
                     learning_steps=100, log_interval=10 ** 9,
                     save_interval=10 ** 9, mesh=make_mesh(dp=8),
                     checkpoint_dir=ckpt_dir, seed=5, **kw)


def test_tracing_off_path_is_free(tmp_path, monkeypatch):
    """The off path allocates NO span objects and writes nothing: span()
    returns one shared singleton, and any _Span construction, shard
    write, TraceAnnotation or ring append during a TrainLoop step or a
    DecodeServer tick with no profiler session is a test failure. What
    the off path does cost is counted: one ``is_enabled()`` a boundary."""
    import jax

    assert trace_lib.NULL.span("a") is trace_lib.NULL.span("b")
    assert trace_lib.NULL.complete("x", "c", 0.0, 1.0) == ""
    assert not trace_lib.NULL.enabled

    def bomb(*a, **k):
        raise AssertionError("tracing-off path built a span / wrote")

    asked = []

    class Annotation:
        """jax.profiler.TraceAnnotation's place: asked, never built."""

        __init__ = bomb

        @staticmethod
        def is_enabled():
            asked.append(1)
            return False

    class Ring(list):
        append = bomb

    monkeypatch.delenv(trace_lib.TRACE_ENV, raising=False)
    monkeypatch.setattr(trace_lib._Span, "__init__", bomb)
    monkeypatch.setattr(trace_lib.Tracer, "_emit", bomb)
    monkeypatch.setattr(trace_lib, "_ANNOTATION", Annotation)
    monkeypatch.setattr(trace_lib, "_RING", Ring())
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)

    from distributed_pipeline_tpu.parallel import make_mesh
    from distributed_pipeline_tpu.serving import DecodeServer
    from distributed_pipeline_tpu.utils import logger

    wl = _tiny_gpt2()
    loop = _tiny_loop(wl, str(tmp_path), prefetch_depth=2, dispatch_lag=1)
    # not armed: the tracer that follows the profiler, off with no session
    assert loop.tracer is trace_lib.FOLLOW
    assert not loop.tracer.enabled
    assert loop.tracer.span("a") is trace_lib._NULL_SPAN
    server = DecodeServer(wl, wl.init_params(jax.random.PRNGKey(3)),
                          decode_slots=2, page_size=4, max_prompt_len=8,
                          max_len=16, mesh=make_mesh(dp=8))
    assert server.tracer is trace_lib.FOLLOW
    with logger.scoped_configure(format_strs=[]):
        loop.run_step(loop.next_batch())
        loop.run_step(loop.next_batch())
        del asked[:]
        loop.run_step(loop.next_batch())
        per_step = len(asked)
        loop.save()
        req = server.submit(np.arange(1, 6, dtype=np.int32), 4)
        server.step()
        del asked[:]
        server.step()
        per_tick = len(asked)
        server.drain()
    # the whole off cost: a boolean a boundary (train: next_batch, two
    # data spans, run_step and its args, dispatch, the step booking,
    # metrics_wait and its args, log; serve: step and its args, dispatch,
    # fetch, fetch_wait and their args checks)
    assert 1 <= per_step <= 16, per_step
    assert 1 <= per_tick <= 16, per_tick
    assert req.finished and req.admit_t is not None \
        and req.finish_t >= req.submit_t + req.ttft_s
    assert not os.path.exists(trace_lib.trace_path(str(tmp_path), 0))
    assert len(trace_lib.recorded()) == 0


# The spans of ISSUE 27's table. LIVE spans are opened where the work
# happens, so they are in the profiler's host plane and in the ring; the
# three request.* spans are booked after the fact from Request's own
# stamps (an annotation cannot start in the past): ring and shard only.
TRAIN_SPANS = ("train.next_batch", "data.host_wait", "data.h2d",
               "train.run_step", "train.dispatch", "train.metrics_wait",
               "train.log")
SERVE_SPANS = ("serve.step", "serve.sweep", "serve.admit",
               "serve.prefill_dispatch", "serve.decode_dispatch",
               "serve.fetch", "serve.fetch_wait", "serve.spec_round",
               "serve.weights")
REQUEST_SPANS = ("request.queue", "request.first_token", "request.decode")
PARENTS = {   # span -> the spans it may lie directly beneath
    "data.host_wait": {"train.next_batch"},
    "data.h2d": {"train.next_batch", "train.run_step"},
    "train.dispatch": {"train.run_step"},
    "train.metrics_wait": {"train.run_step", None},   # None: flush_metrics
    "train.log": {"train.run_step"},
    "serve.sweep": {"serve.step"}, "serve.admit": {"serve.step"},
    "serve.prefill_dispatch": {"serve.admit"},
    "serve.decode_dispatch": {"serve.step"},
    "serve.fetch": {"serve.step", None},              # None: drain's last
    "serve.fetch_wait": {"serve.fetch", "serve.spec_round"},
    "serve.spec_round": {"serve.step"},
    "serve.weights": {None},          # construction and the hot swap
}


@pytest.fixture(scope="module")
def profiled_session(tmp_path_factory):
    """One CPU ``jax.profiler`` session round a tiny TrainLoop (both feed
    arms) and a tiny DecodeServer (plain and speculative): the ring's
    events, the host plane's events, and the requests served."""
    import glob
    import warnings

    import jax

    from distributed_pipeline_tpu.parallel import make_mesh
    from distributed_pipeline_tpu.serving import DecodeServer
    from distributed_pipeline_tpu.utils import logger

    tmp = tmp_path_factory.mktemp("profiled")
    wl = _tiny_gpt2()
    params = wl.init_params(jax.random.PRNGKey(3))
    mesh = make_mesh(dp=8)
    kw = dict(decode_slots=2, page_size=4, max_prompt_len=8, max_len=16,
              mesh=mesh)
    prompt = np.arange(1, 6, dtype=np.int32)
    trace_lib.clear_recorded()
    with logger.scoped_configure(format_strs=[]):
        fed = _tiny_loop(wl, str(tmp / "a"), workers=2, prefetch_depth=2,
                         dispatch_lag=1)
        eager = _tiny_loop(wl, str(tmp / "b"))
        server = DecodeServer(wl, params, **kw)
        spec = DecodeServer(wl, params, spec_tokens=2, **kw)
        # warm every program outside the session; a later token of the
        # greedy answer becomes the EOS that makes the session sweep
        for loop in (fed, eager):
            loop.run_step(loop.next_batch())
        warm = [srv.submit(prompt, 6) for srv in (server, spec)]
        server.drain()
        spec.drain()
        assert warm[0].tokens == warm[1].tokens
        answer = warm[0].tokens
        eos_at = next(i for i, t in enumerate(answer) if t != answer[0])
        assert trace_lib.recorded() == []      # no session: ring empty

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp / "xplane"), profiler_options=opts)
        for loop in (fed, eager):
            for _ in range(3):
                loop.run_step(loop.next_batch())
            loop.flush_metrics()
        reqs = [server.submit(prompt, 6, eos_id=answer[eos_at]),
                server.submit(prompt[:3], 5),
                server.submit(prompt[:4], 4,
                              trace_id=trace_lib.request_trace_id(4242))]
        server.drain()
        reqs.append(spec.submit(      # its own counter: ids would collide
            prompt, 6, trace_id=trace_lib.request_trace_id(4243)))
        spec.drain()
        server.set_params(params)     # the hot swap: one serve.weights
        jax.profiler.stop_trace()
        for loop in (fed, eager):
            loop.run_step(loop.next_batch())   # after it: ring untouched
    ring = trace_lib.recorded()
    trace_lib.clear_recorded()
    from jax.profiler import ProfileData
    found = glob.glob(str(tmp / "xplane" / "**" / "*.xplane.pb"),
                      recursive=True)
    plane = {}
    for pl in ProfileData.from_file(found[0]).planes:
        if pl.name.startswith("/host:CPU"):
            for line in pl.lines:
                for e in line.events:
                    with warnings.catch_warnings():
                        # "builtin type event_stats has no __module__"
                        warnings.simplefilter("ignore", DeprecationWarning)
                        stats = dict(e.stats)
                    plane.setdefault(e.name, []).append(
                        (e.start_ns * 1e-9, e.duration_ns * 1e-9, stats))
    return {"ring": ring, "plane": plane, "reqs": reqs,
            "eos_tokens": eos_at + 1}


@pytest.mark.parametrize("name", TRAIN_SPANS + SERVE_SPANS)
def test_profiler_session_puts_live_span_in_host_plane_and_ring(
        profiled_session, name):
    """Two sinks, one clock: the span is a TraceAnnotation in /host:CPU
    and an event in the ring, as often in the one as in the other, each
    pair's durations within 1 ms and ``t - start_ns`` one constant (the
    session's start) for every span of the session."""
    ring = [e for e in profiled_session["ring"] if e["name"] == name]
    plane = sorted(profiled_session["plane"].get(name, []))
    assert ring and len(ring) == len(plane), (name, len(ring), len(plane))
    ring.sort(key=lambda e: e["t"])
    for e, (start, dur, _stats) in zip(ring, plane):
        assert abs(e["dur"] - dur) < 1e-3, (name, e["dur"], dur)
    anchor = min(e["t"] for e in profiled_session["ring"]
                 if e["name"] in TRAIN_SPANS + SERVE_SPANS)
    first = min(s for n in TRAIN_SPANS + SERVE_SPANS
                for s, _, _ in profiled_session["plane"][n])
    for e, (start, _dur, _stats) in zip(ring, plane):
        assert abs((e["t"] - start) - (anchor - first)) < 1e-3, name


def test_span_arguments_reach_both_sinks(profiled_session):
    """What a span knows when it opens is an annotation's stat and the
    ring event's args; what only its end knows (admit, fetch) is in the
    ring alone."""
    ring, plane = profiled_session["ring"], profiled_session["plane"]
    steps = [e["args"]["step"] for e in ring if e["name"] == "train.run_step"]
    assert sorted(steps) == [2, 2, 3, 3, 4, 4]
    assert sorted(int(st["step"]) for _, _, st in plane["train.run_step"]) \
        == sorted(steps)
    tick = next(e for e in ring if e["name"] == "serve.step")
    assert tick["args"] == {"queued": 3, "active": 0}
    assert {"queued", "active"} <= set(plane["serve.step"][0][2])
    admits = [e["args"] for e in ring if e["name"] == "serve.admit"]
    assert sum(a["n"] for a in admits) == 4
    assert sum(a["prompt_tokens"] for a in admits) == 5 + 3 + 4 + 5
    (held,) = [e["args"] for e in ring if e["name"] == "serve.weights"]
    # a float32 model's tree is right as it is: nothing cast, same bytes
    assert held["leaves_cast"] == 0 < held["bytes_in"] \
        == held["bytes_serving"]
    fetched = sum(e["args"]["n_tokens"] for e in ring
                  if e["name"] == "serve.fetch")
    # the plain server's tokens, and the one token the speculative server
    # fetches from its prefill (its rounds fetch inside serve.spec_round)
    assert fetched == 1 + sum(len(r.tokens)
                              for r in profiled_session["reqs"][:3])


def test_serve_weights_span_says_what_the_engine_holds(tmp_path):
    """``serve.weights`` (cat ``serve``) lies round the serving copy of
    the weights, at construction and at each ``set_params``, target and
    draft engine each: bytes given, bytes held, leaves cast — the engine's
    own ``weights`` and the cost ledger's ``weights`` row."""
    import jax

    from distributed_pipeline_tpu.models import create_model_from_config
    from distributed_pipeline_tpu.serving import DecodeServer

    wl = create_model_from_config(
        model_family="gpt2", vocab_size=64, seq_len=16, hidden_size=32,
        num_layers=2, num_heads=2, dtype="bfloat16")
    params = wl.init_params(jax.random.PRNGKey(3))
    tr = trace_lib.tracer_for(str(tmp_path), 0, armed=True, proc="r0.rank0")
    server = DecodeServer(wl, params, decode_slots=2, page_size=4,
                          max_prompt_len=8, max_len=16, spec_tokens=2,
                          spec_draft="model", draft_layers=1, tracer=tr)
    server.set_params(wl.init_params(jax.random.PRNGKey(4)))
    req = server.submit(np.arange(1, 6, dtype=np.int32), 3)
    server.drain()
    tr.close()
    spans = [e for e in trace_lib.read_trace(
        trace_lib.trace_path(str(tmp_path), 0))
        if e["name"] == "serve.weights"]
    assert [e["cat"] for e in spans] == ["serve"] * 4
    n_params = wl.param_count(params)
    cast = {"bytes_in": 4 * n_params, "leaves_cast": 2 * 4 + 1,
            "bytes_serving": server.engine.weights["bytes_serving"]}
    # target then draft, twice; the draft is views of what the target
    # holds (one block of two, nothing left to cast)
    assert spans[0]["args"] == spans[2]["args"] == cast
    assert spans[1]["args"] == spans[3]["args"] \
        == server._draft_engine.weights
    assert spans[1]["args"]["leaves_cast"] == 0
    assert 2 * n_params < cast["bytes_serving"] < 3 * n_params
    assert server.engine.weights == cast and req.finished
    rows = server.cost_ledger(wall_s=1.0, n_devices=1)
    assert rows["weights"] == cast
    assert set(rows) == {"serve_prefill", "serve_verify", "weights"}


def test_children_lie_inside_parents(profiled_session):
    ring = profiled_session["ring"]
    by_sid = {e["sid"]: e for e in ring}
    assert len(by_sid) == len(ring)             # ids unique in the ring
    seen = set()
    for e in ring:
        if e["name"] not in PARENTS:
            continue
        parent = by_sid.get(e.get("parent"))
        pname = parent["name"] if parent else None
        assert pname in PARENTS[e["name"]], (e["name"], pname)
        seen.add((e["name"], pname))
        if parent is not None:
            # wall anchors are time.time() readings: microseconds of slack
            assert e["t"] >= parent["t"] - 1e-4, (e, parent)
            assert e["t"] + e["dur"] <= parent["t"] + parent["dur"] + 1e-4
    # both feed arms ran: the prefetch generator under next_batch, the
    # eager arm's transfer under run_step
    assert ("data.h2d", "train.next_batch") in seen
    assert ("data.h2d", "train.run_step") in seen
    assert ("serve.fetch_wait", "serve.spec_round") in seen


def test_data_workers_spans_lie_on_their_own_threads(profiled_session):
    """Inside a session the fed loop's two data workers book
    ``data.assemble`` through FOLLOW: in the ring under the worker's thread
    id, with no parent of the step loop's, whose own spans all share one."""
    ring = profiled_session["ring"]
    made = [e for e in ring if e["name"] == "data.assemble"]
    loop_tids = {e["tid"] for e in ring if e["name"] in TRAIN_SPANS}
    assert made and len(loop_tids) == 1
    assert all("parent" not in e and e["cat"] == "data" for e in made)
    assert not {e["tid"] for e in made} & loop_tids
    assert {e["args"]["worker"] for e in made} <= {0, 1}


def test_request_spans_are_the_requests_own_stamps(profiled_session):
    ring, reqs = profiled_session["ring"], profiled_session["reqs"]
    assert not any(n in profiled_session["plane"] for n in REQUEST_SPANS)
    spans = {}
    for e in ring:
        if e["name"] in REQUEST_SPANS:
            assert e["cat"] == "request"
            spans.setdefault(e["trace"], {})[e["name"]] = e
    assert trace_lib.request_trace_id(4242) in spans   # the caller's id
    assert len(spans) == len(reqs)
    for r in reqs:
        got = spans[r.trace_id or trace_lib.request_trace_id(r.id)]
        assert set(got) == set(REQUEST_SPANS)
        assert r.submit_t <= r.admit_t <= r.submit_t + r.ttft_s <= r.finish_t
        q, f, d = (got[n] for n in REQUEST_SPANS)
        assert q["args"] == {"id": r.id, "prompt_len": r.prompt_len}
        assert d["args"] == {"id": r.id, "n_tokens": len(r.tokens)}
        # span and field cannot disagree: to the microsecond
        assert q["dur"] == pytest.approx(r.admit_t - r.submit_t, abs=1e-6)
        assert q["dur"] + f["dur"] == pytest.approx(r.ttft_s, abs=1e-6)
        assert d["dur"] == pytest.approx(
            r.finish_t - r.submit_t - r.ttft_s, abs=1e-6)
        assert f["t"] == pytest.approx(q["t"] + q["dur"], abs=1e-3)
    # the EOS request stopped early and was swept
    assert len(reqs[0].tokens) == profiled_session["eos_tokens"] < 6


def test_armed_server_books_into_its_shard_under_the_routers_id(tmp_path):
    """--trace/DPT_TRACE with no profiler session (a fleet replica): the
    scheduler's spans and the request's life land in the shard, under the
    trace id the router minted; the ring stays empty."""
    import jax

    from distributed_pipeline_tpu.parallel import make_mesh
    from distributed_pipeline_tpu.serving import DecodeServer

    trace_lib.clear_recorded()
    wl = _tiny_gpt2()
    tr = trace_lib.tracer_for(str(tmp_path), 0, armed=True, proc="r1.rank0")
    server = DecodeServer(wl, wl.init_params(jax.random.PRNGKey(3)),
                          decode_slots=2, page_size=4, max_prompt_len=8,
                          max_len=16, mesh=make_mesh(dp=8), tracer=tr)
    tid = trace_lib.request_trace_id(77)
    req = server.submit(np.arange(1, 6, dtype=np.int32), 3, trace_id=tid)
    server.drain()
    tr.close()
    events = trace_lib.read_trace(trace_lib.trace_path(str(tmp_path), 0))
    names = {e["name"] for e in events}
    assert {"serve.step", "serve.admit", "serve.prefill_dispatch",
            "serve.decode_dispatch", "serve.fetch", "serve.fetch_wait"} \
        <= names
    life = {e["name"]: e for e in events if e.get("trace") == tid}
    assert set(life) == set(REQUEST_SPANS)
    assert life["request.queue"]["dur"] + life["request.first_token"]["dur"] \
        == pytest.approx(req.ttft_s, abs=1e-6)
    assert all(e["sid"].startswith("r1.rank0:") for e in events)
    assert trace_lib.recorded() == []


def test_profile_window_leaves_host_spans_beside_the_trace(tmp_path):
    """TrainLoop's own profiler window (--profile_dir): on stopping it the
    ring is dumped as host_spans.jsonl, readable by read_trace."""
    from distributed_pipeline_tpu.utils import logger

    trace_lib.clear_recorded()
    loop = _tiny_loop(_tiny_gpt2(), str(tmp_path / "run"),
                      profile_dir=str(tmp_path / "prof"),
                      profile_steps="1:3", prefetch_depth=2, dispatch_lag=1)
    loop.learning_steps = 4
    with logger.scoped_configure(format_strs=[]):
        loop.run_loop()
    events = trace_lib.read_trace(str(tmp_path / "prof" /
                                      "host_spans.jsonl"))
    trace_lib.clear_recorded()
    names = [e["name"] for e in events]
    assert names.count("train.run_step") == 2      # loop steps 1 and 2
    assert {"train.next_batch", "train.dispatch", "step"} <= set(names)


def test_router_and_status_import_path_imports_no_jax():
    """obs/trace.py follows the profiler only if jax is ALREADY imported:
    the router, the fleet parent, the exporter and the status CLI stay
    jax-free, and their unarmed tracer is off for good."""
    import subprocess
    import sys

    code = (
        "import sys\n"
        "from distributed_pipeline_tpu.obs import trace, export\n"
        "from distributed_pipeline_tpu.serving import router, fleet\n"
        "from distributed_pipeline_tpu.run import status\n"
        "tr = trace.tracer_for('', 'router')\n"
        "assert tr is trace.FOLLOW and not tr.enabled\n"
        "assert tr.span('x') is trace._NULL_SPAN\n"
        "assert tr.complete('x', 'c', 0.0, 1.0) == ''\n"
        "assert tr.instant('x') == '' and not trace.recorded()\n"
        "assert 'jax' not in sys.modules, 'jax was imported'\n")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    done = subprocess.run([sys.executable, "-c", code], cwd=root,
                          capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr


def test_trainloop_traced_spans_match_goodput_boundaries(tmp_path,
                                                         monkeypatch):
    """DPT_TRACE arms the trainer; step/save/restore/compile spans land
    in the rank shard, and the compile span re-books the exact seconds
    the goodput ledger got."""
    monkeypatch.setenv(trace_lib.TRACE_ENV, "1")

    from distributed_pipeline_tpu.data import load_data_from_args
    from distributed_pipeline_tpu.models import create_model_from_config
    from distributed_pipeline_tpu.parallel import make_mesh
    from distributed_pipeline_tpu.utils import logger
    from distributed_pipeline_tpu.utils.trainer import TrainLoop

    wl = create_model_from_config(
        model_family="gpt2", vocab_size=64, seq_len=16, hidden_size=32,
        num_layers=2, num_heads=2, dtype="float32")
    data = load_data_from_args("train", batch_size=8,
                               dataset="synthetic-lm", seq_len=16,
                               vocab_size=64, seed=0)
    loop = TrainLoop(model=wl, data=data, batch_size=8, lr=1e-3,
                     learning_steps=2, log_interval=10 ** 9,
                     save_interval=10 ** 9, mesh=make_mesh(dp=8),
                     checkpoint_dir=str(tmp_path), seed=5)
    assert loop.tracer.enabled
    with logger.scoped_configure(format_strs=[]):
        loop.run_loop()
    events = trace_lib.read_trace(trace_lib.trace_path(str(tmp_path), 0))
    by = {}
    for e in events:
        by.setdefault(e["name"], []).append(e)
    assert [e["args"]["step"] for e in by["step"]] == [1, 2]
    assert by["save"] and by["restore"]
    compile_total = sum(e["dur"] for e in by["compile"])
    assert compile_total == pytest.approx(loop.goodput.get("compile_s"))
    assert sum(e["dur"] for e in by["restore"]) == pytest.approx(
        loop.goodput.get("restore_s"))


def test_profile_steps_window_parsing(tmp_path):
    from distributed_pipeline_tpu.data import load_data_from_args
    from distributed_pipeline_tpu.models import create_model_from_config
    from distributed_pipeline_tpu.parallel import make_mesh
    from distributed_pipeline_tpu.utils.trainer import TrainLoop

    wl = create_model_from_config(
        model_family="gpt2", vocab_size=64, seq_len=16, hidden_size=32,
        num_layers=2, num_heads=2, dtype="float32")

    def build(profile_steps):
        data = load_data_from_args("train", batch_size=8,
                                   dataset="synthetic-lm", seq_len=16,
                                   vocab_size=64, seed=0)
        return TrainLoop(model=wl, data=data, batch_size=8, lr=1e-3,
                         learning_steps=100, log_interval=10 ** 9,
                         save_interval=10 ** 9, mesh=make_mesh(dp=8),
                         checkpoint_dir="", seed=5,
                         profile_steps=profile_steps)

    assert build("")._profile_window == (3, 8)
    assert build("5:12")._profile_window == (5, 12)
    with pytest.raises(ValueError, match="profile_steps"):
        build("12:5")
    with pytest.raises(ValueError, match="profile_steps"):
        build("nope")


# ================================================================= export

def _fake_run_dir(tmp_path):
    d = str(tmp_path / "run")
    os.makedirs(d, exist_ok=True)
    tr = trace_lib.tracer_for(d, 0, armed=True)
    t0 = time.time() - 30
    tr.complete("step", "train", t0 + 1, 0.5, args={"step": 1})
    tr.complete("save", "ckpt", t0 + 2, 0.2, args={"step": 1})
    tr.close()
    goodput.append_attempt(d, {
        "attempt": 0, "rc": -9, "t_spawn": t0, "t_exit": t0 + 5,
        "duration_s": 5.0, "downtime_s": 0.0, "steps": 3,
        "hung": True, "hang_s": 2.0, "hang_kind": "stall"})
    goodput.append_attempt(d, {
        "attempt": 1, "rc": 0, "t_spawn": t0 + 6, "t_exit": t0 + 12,
        "duration_s": 6.0, "downtime_s": 1.0, "steps": 5})
    with open(goodput.beacon_path(d, 0), "w") as f:
        json.dump({"step": 8, "t": t0 + 11.5, "attempt": 1,
                   "goodput": {"goodput": 0.8, "wall_s": 6.0}}, f)
    return d


def test_chrome_trace_schema_validity(tmp_path):
    """Every event carries the Chrome-trace required keys with sane
    types; pids have process_name metadata; the payload JSON-serializes
    (what Perfetto actually loads)."""
    d = _fake_run_dir(tmp_path)
    ct = export_lib.chrome_trace(d)
    json.dumps(ct)  # loadable
    events = ct["traceEvents"]
    assert events
    named_pids = set()
    for e in events:
        assert {"name", "ph", "pid", "tid"} <= set(e)
        assert e["ph"] in ("X", "i", "M")
        if e["ph"] == "M":
            if e["name"] == "process_name":
                named_pids.add(e["pid"])
            continue
        assert isinstance(e["ts"], (int, float)) and e["ts"] >= 0
        if e["ph"] == "X":
            assert isinstance(e["dur"], (int, float)) and e["dur"] >= 0
    data_pids = {e["pid"] for e in events if e["ph"] != "M"}
    assert data_pids and data_pids <= named_pids
    names = {e["name"] for e in events}
    # untraced artifacts export too: attempts + watchdog + beacon ride in
    assert {"attempt 0", "attempt 1", "downtime", "watchdog_kill",
            "last_beacon", "step", "save"} <= names


def test_prometheus_snapshot_run_dir(tmp_path):
    d = _fake_run_dir(tmp_path)
    lines = export_lib.prometheus_lines(d, now=time.time())
    text = "\n".join(lines)
    assert 'dpt_beacon_step{rank="0"} 8' in text
    assert "dpt_attempts_total 2" in text
    assert 'dpt_goodput_seconds{category="hang"} 2' in text
    # textfile format: every sample line is `name{labels} value`
    for line in lines:
        if line.startswith("#"):
            continue
        name, value = line.rsplit(" ", 1)
        float(value)
        assert name[0].isalpha()


def test_status_cli_run_dir_and_export(tmp_path, capsys):
    d = _fake_run_dir(tmp_path)
    snap = status_lib.main([d])
    out = capsys.readouterr().out
    assert snap["kind"] == "run" and snap["attempts"] == 2
    assert "rank" in out and "goodput" in out
    # --export writes the Perfetto JSON via obs.export
    out_path = str(tmp_path / "t.json")
    prom_path = str(tmp_path / "m.prom")
    summary = status_lib.main([d, "--export", out_path,
                               "--prom", prom_path])
    assert summary["events"] > 0
    with open(out_path) as f:
        assert json.load(f)["traceEvents"]
    assert os.path.getsize(prom_path) > 0


def test_export_cli_main(tmp_path, capsys):
    d = _fake_run_dir(tmp_path)
    summary = export_lib.main([d])
    assert os.path.exists(os.path.join(d, "trace.json"))
    assert summary["kind"] == "run" and summary["events"] > 0
    assert json.loads(capsys.readouterr().out.strip())["events"] \
        == summary["events"]


# ====================================================== fleet e2e (traced)

def _fake_ckpt(base, step, salt):
    d = os.path.join(str(base), f"model_{step:06d}")
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(d, "_CHECKPOINT_METADATA"), "w") as f:
        f.write("{}")
    with open(os.path.join(d, "params.json"), "w") as f:
        json.dump({"step": step, "salt": salt}, f)
    return d


@pytest.mark.chaos
def test_traced_fleet_kill_and_swap_export_one_timeline(tmp_path,
                                                        monkeypatch):
    """The acceptance e2e: a kill_replica fleet run under DPT_TRACE plus
    one hot-swap exports as ONE timeline in which (a) the injected kill
    is visible (nonzero-rc attempt span + respawn on the victim's pid),
    (b) the replayed request's serve span runs on a SIBLING replica
    under the SAME trace id the router journaled, and (c) the hot-swap
    drain/load windows appear on every replica."""
    monkeypatch.setenv(trace_lib.TRACE_ENV, "1")
    ckpt = tmp_path / "ckpts"
    _fake_ckpt(ckpt, 1, salt=3)
    _fake_ckpt(ckpt, 2, salt=9)
    plan = {"faults": [{"kind": "kill_replica", "step": 1, "rank": 1,
                        "sig": "SIGKILL"}]}
    monkeypatch.setenv(CHAOS_PLAN_ENV, json.dumps(plan))
    fleet_dir = str(tmp_path / "fleet")
    fleet = ServingFleet(
        fleet_dir, 3, "tests._fleet_child",
        ["--checkpoint_dir", str(ckpt), "--step", "1",
         "--token_interval_s", "0.01"],
        max_restarts=3, restart_backoff_s=0.1, restart_backoff_max_s=0.5,
        monitor_interval=0.02)
    fleet.start()
    router = Router(fleet.clients(),
                    goodput.serving_journal_path(fleet_dir))
    swap_report = {}
    try:
        deadline = time.time() + 20
        while len(fleet.ready_replicas()) < 3 and time.time() < deadline:
            time.sleep(0.02)
        assert len(fleet.ready_replicas()) == 3, "fleet never came up"
        for i in range(9):
            router.submit(np.arange(i + 1, i + 5, dtype=np.int32), 12)
        swap_armed = False
        deadline = time.time() + 60
        while time.time() < deadline:
            router.poll()
            if not swap_armed and router.completed >= 3:
                swap_armed = True
                fleet.begin_hot_swap(str(ckpt), step=2,
                                     drain_timeout_s=20,
                                     swap_timeout_s=20)
            if fleet.swap_active:
                rep = fleet.step_swap(router)
                if rep is not None:
                    swap_report.update(rep)
            if (router.all_done() and not fleet.swap_active
                    and swap_armed and swap_report):
                break
            time.sleep(0.02)
    finally:
        fleet.stop()
    assert router.completed == 9 and router.replayed >= 1
    assert swap_report.get("ok") is True, swap_report

    ct = export_lib.chrome_trace(fleet_dir)
    json.dumps(ct)
    events = [e for e in ct["traceEvents"] if e["ph"] != "M"]
    pid_name = {e["pid"]: e["args"]["name"]
                for e in ct["traceEvents"]
                if e["ph"] == "M" and e["name"] == "process_name"}
    victim_pid = next(p for p, n in pid_name.items() if n == "replica_1")
    router_pid = next(p for p, n in pid_name.items() if n == "router")

    # (a) the kill: the victim's timeline shows a nonzero-rc attempt
    # span AND a later respawned attempt
    victim_attempts = [e for e in events if e["pid"] == victim_pid
                       and e["cat"] == "supervise"
                       and e["name"].startswith("attempt")]
    assert len(victim_attempts) >= 2
    assert any(e["args"].get("rc") not in (0, None)
               for e in victim_attempts)

    # (b) one shared trace id per request, replayed onto a live worker:
    # the replayed request's journal spans (router pid) and its serve
    # span (worker pid) carry the SAME id. The serving replica is
    # normally a sibling; a RESPAWNED victim is also a legal health-
    # gated target (on a slow box the respawn can beat the router's
    # replay poll), so the pin is "a worker span exists and matches the
    # replica the router journaled the completion on", not "never the
    # victim's pid".
    replayed = next(r for r in router.records.values() if r.replays > 0)
    tid = replayed.trace_id
    tid_events = [e for e in events
                  if e.get("args", {}).get("trace_id") == tid]
    assert any(e["pid"] == router_pid and e["name"] == "replayed_work"
               for e in tid_events)
    serve_spans = [e for e in tid_events if e["name"] == "serve"]
    assert serve_spans, "worker serve span missing for replayed request"
    assert all(e["pid"] != router_pid for e in serve_spans)
    assert {e["args"]["replica"] for e in serve_spans} \
        == {replayed.replica}

    # (c) hot-swap drain + load windows on every replica's swap track,
    # and a post-swap ready instant at the new params version
    for rid in range(3):
        pid = next(p for p, n in pid_name.items()
                   if n == f"replica_{rid}")
        names = {e["name"] for e in events
                 if e["pid"] == pid and e["cat"] == "swap"}
        assert {"drain", "swap"} <= names, (rid, names)
    assert any(e["name"] == "ready"
               and e["args"].get("params_step") == 2 for e in events)

    # the scheduler's own tick spans stand where the worker's guessed
    # `engine` track stood: a serve.step span on the `serve` track of a
    # replica's pid, and nothing left under `engine`
    ticks = [e for e in events if e["name"] == "serve.step"]
    assert ticks and all(e["cat"] == "serve" and e["pid"] != router_pid
                         for e in ticks)
    assert not any(e["cat"] == "engine" for e in events)

    # span ids stay unique across the MERGED fleet timeline: the worker
    # labels are replica-qualified (r1.rank0) and attempt-qualified
    # (.aN), so neither N replicas writing their own trace_rank0.jsonl
    # nor a respawned attempt appending to the victim's shard collide
    sids = [e["args"]["span_id"] for e in events
            if "span_id" in e.get("args", {})]
    assert sids and len(sids) == len(set(sids))

    # the ledger still accounts every replica-second with tracing on
    agg = goodput.aggregate_serving(fleet_dir)
    assert agg["accounted_frac"] == pytest.approx(1.0, abs=0.05)

    # live telemetry over the same dir: per-replica serving snapshot in
    # the Prometheus textfile + the status table's fleet view
    prom = "\n".join(export_lib.prometheus_lines(fleet_dir))
    assert "dpt_replica_serving_seconds" in prom
    assert 'dpt_requests_total{state="replayed"}' in prom
    snap = status_lib.fleet_status(fleet_dir)
    assert snap["completed"] == 9 and snap["replayed"] >= 1
    assert snap["ttft_p95_s"] is not None
    assert {r["params_step"] for r in snap["replicas"]} == {2}


# ---- the chunked-prefill family (ISSUE 29): one more live span, and the
# programs' counters booked on the fetch that brought them

def _tiny_deepseek():
    from distributed_pipeline_tpu.models import create_model_from_config
    arch = {"hidden_size": 32, "n_layers": 2, "n_dense_layers": 1,
            "num_attention_heads": 2, "q_lora_rank": 16, "kv_lora_rank": 8,
            "qk_nope_head_dim": 8, "qk_rope_head_dim": 8, "v_head_dim": 8,
            "index_n_heads": 4, "index_head_dim": 16, "index_topk": 6,
            "intermediate_size": 64, "moe_intermediate_size": 16,
            "n_routed_experts": 8, "n_routed_experts_held": 4, "n_group": 2,
            "topk_group": 1, "num_experts_per_tok": 2,
            "rope_scaling": {"factor": 40,
                             "original_max_position_embeddings": 16}}
    return create_model_from_config(model_family="deepseek_v32",
                                    vocab_size=64, seq_len=48,
                                    dtype="float32", arch=arch)


def test_chunked_prefill_span_and_program_counters(tmp_path):
    """``serve.prefill_chunk`` (tokens, slot) lies inside ``serve.step`` in
    the host plane and the ring; the five counters of the model's programs
    come back with the tokens and are booked, by program, as arguments of
    the ``serve.fetch`` that brought them: their sums over the ring are the
    server's own ``counted``."""
    import glob

    import jax
    from jax.profiler import ProfileData

    from distributed_pipeline_tpu.models.deepseek_v32 import COUNTERS
    from distributed_pipeline_tpu.serving import DecodeServer

    wl = _tiny_deepseek()
    server = DecodeServer(wl, wl.init_params(jax.random.PRNGKey(1)),
                          decode_slots=2, page_size=4, max_prompt_len=24,
                          max_len=48)
    assert server.engine.prefill_chunk == 4      # the engine's own: a page
    warm = server.submit(np.arange(1, 20, dtype=np.int32), 5)
    server.drain()
    assert warm.finished and trace_lib.recorded() == []
    server.reset_stats()
    trace_lib.clear_recorded()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    reqs = [server.submit(np.arange(1, 20, dtype=np.int32), 6),
            server.submit(np.arange(3, 14, dtype=np.int32), 4)]
    server.drain()
    jax.profiler.stop_trace()
    ring = trace_lib.recorded()
    trace_lib.clear_recorded()
    assert all(r.finished for r in reqs)
    chunks = [e for e in ring if e["name"] == "serve.prefill_chunk"]
    assert sorted(e["args"]["tokens"] for e in chunks) == [3, 3] + 6 * [4]
    assert {e["args"]["slot"] for e in chunks} == {0, 1}
    by_sid = {e["sid"]: e for e in ring}
    assert all(by_sid[e["parent"]]["name"] == "serve.step" for e in chunks)
    found = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = [e.name for pl in ProfileData.from_file(found[0]).planes
             if pl.name.startswith("/host:CPU")
             for line in pl.lines for e in line.events]
    assert names.count("serve.prefill_chunk") == len(chunks)
    fetches = [e["args"] for e in ring if e["name"] == "serve.fetch"]
    assert sum(a["n_tokens"] for a in fetches) == 10
    for program in ("prefill", "decode"):
        for name in COUNTERS:
            booked = sum(a[program][name] for a in fetches)
            assert booked == server.counted[program][name] > 0, name
    layers = 2
    assert server.counted["prefill"]["kv_rows_live"] == layers * (
        sum(range(1, 20)) + sum(range(1, 12)))
    assert server.counted["decode"]["kv_rows_attended"] == layers * 6 * (
        5 + 3)


def test_gpt2_tick_asks_the_profiler_as_often_as_before(monkeypatch):
    """The counters and the chunk span cost a GPT-2 tick nothing: a decode
    tick asks ``is_enabled()`` 6 times (serve.step and its args, the decode
    dispatch, fetch and its args, fetch_wait), counted on PR 28's tree and
    on this one."""
    import jax

    from distributed_pipeline_tpu.serving import DecodeServer

    asked = []

    class Annotation:
        @staticmethod
        def is_enabled():
            asked.append(1)
            return False

    wl = _tiny_gpt2()
    server = DecodeServer(wl, wl.init_params(jax.random.PRNGKey(3)),
                          decode_slots=2, page_size=4, max_prompt_len=8,
                          max_len=16)
    server.submit(np.arange(1, 6, dtype=np.int32), 6)
    server.step()
    server.step()
    monkeypatch.setattr(trace_lib, "_ANNOTATION", Annotation)
    server.step()
    monkeypatch.undo()
    server.drain()
    assert len(asked) == 6, len(asked)
