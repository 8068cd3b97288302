"""Host-side layers, read from the program's OWN spans: while the traced
sub-window's ``jax.profiler`` session is on, ``TrainLoop`` and ``DecodeServer``
append every span they close to the in-memory ring of
``distributed_pipeline_tpu/obs/trace.py`` (``recorded()``; the same spans sit in
the xplane's host plane). run.py calls a reader in the program's own process
after the run, so the ring holds exactly the traced sub-window's spans. An
event is ``{"name", "t", "dur", "sid", "parent", "args", ...}``; a span's
self time is its duration less what the named spans beneath it cover.

A program without the ring (a commit before the spans) reads as nothing."""


def ring():
    try:
        from distributed_pipeline_tpu.obs import trace
    except ImportError:
        return []
    recorded = getattr(trace, "recorded", None)
    return recorded() if recorded is not None else []


def named(events, name):
    return [e for e in events if e.get("name") == name]


def seconds_less_beneath(events, outer, inner):
    """Summed duration of the ``outer`` spans, and of the ``inner`` spans
    that lie beneath one of them at any depth (by the ``parent`` links; an
    ``inner`` span whose chain reaches no ``outer`` span takes nothing off).
    Returns (outer seconds, inner seconds beneath), or None with no
    ``outer`` span at all."""
    by_sid = {e["sid"]: e for e in events if "sid" in e}
    outers = named(events, outer)
    if not outers:
        return None
    beneath = 0.0
    for e in named(events, inner):
        seen = set()
        up = by_sid.get(e.get("parent"))
        while up is not None and up["sid"] not in seen:
            if up.get("name") == outer:
                beneath += e["dur"]
                break
            seen.add(up["sid"])
            up = by_sid.get(up.get("parent"))
    return sum(e["dur"] for e in outers), beneath


def window_s(ctx):
    s = ctx.get("trace")
    return s.window_s if s is not None and s.window_s > 0 else None


def share_of_window(ctx, events, outer, inner=None):
    w = window_s(ctx)
    got = seconds_less_beneath(events, outer, inner)
    if w is None or got is None:
        return None
    return 100.0 * (got[0] - got[1]) / w


def queue_share(events):
    """Over the requests with BOTH spans in the ring: the part of submit ->
    first token that passed before admission."""
    queue = {e["args"]["id"]: e["dur"] for e in named(events, "request.queue")}
    first = {e["args"]["id"]: e["dur"]
             for e in named(events, "request.first_token")}
    both = queue.keys() & first.keys()
    total = sum(queue[i] + first[i] for i in both)
    if not both or total <= 0:
        return None
    return 100.0 * sum(queue[i] for i in both) / total


# ---- the four metrics (layer_metrics/<name>.json names one each)

def input_wait_share_train(ctx):
    """Time the step loop stood in ``TrainLoop.next_batch``."""
    return share_of_window(ctx, ring(), "train.next_batch")


def host_step_share_train(ctx):
    """The host's own work a step: ``run_step`` less the time it waited for
    the device (``train.metrics_wait``)."""
    return share_of_window(ctx, ring(), "train.run_step",
                           "train.metrics_wait")


def sched_host_share_serve(ctx):
    """The scheduler's own work a tick: ``serve.step`` less every wait for
    the device beneath it (``serve.fetch_wait``)."""
    return share_of_window(ctx, ring(), "serve.step", "serve.fetch_wait")


def ttft_queue_share(ctx):
    return queue_share(ring())
