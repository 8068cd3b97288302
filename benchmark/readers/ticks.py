"""The step loops' own account of every tick, read after the run.

``DecodeServer`` and ``TrainLoop`` book every tick (one entry of the step to
the next: a tick part and the caller's part between two ticks), whether or
not anything traces, into a ``StallBreakdown`` that the program keeps under
the loop's name (``distributed_pipeline_tpu/utils/perf.py::tick_account``:
``serve``, ``train``) after the driver has let the loop go. Its ``summary()``
is of the loop's steady part (from the first fetched token; from the first
completed step) over the WHOLE run, not the traced 3 s: these metrics are
about rare ticks.

One thing in a traced run is the harness's own doing: ``profiler.start()`` and
``profiler.stop()`` lie between two ticks, and the second reduces the trace for
seconds. The account marks a stalled tick in which a session started or
stopped (``session_edge``); those ticks are set aside here, out of every sum.

A program without the account (a commit before it) reads as nothing."""

DECODE_PROGRAM = "jit_decode_fn"   # jit name of DecodeEngine's decode step


def account(name):
    try:
        from distributed_pipeline_tpu.utils import perf
    except ImportError:
        return None
    find = getattr(perf, "tick_account", None)
    return find(name) if find is not None else None


def steady(name):
    """The account's summary less the ticks at a profiler session's edges:
    ``seconds`` (tick + between, summed), ``between_s``, ``stall_s`` (seconds
    over the kind's median, summed over the stalled ticks), by kind
    ``[count, seconds]``, dispatches and dry dispatches. None without an
    account or before its first steady tick."""
    acct = account(name)
    if acct is None:
        return None
    s = acct.summary()
    if not s["ticks"]:
        return None
    out = {"seconds": s["seconds"], "between_s": s["between_s"],
           "stall_s": s["stalls"]["seconds"],
           "kinds": {k: [row["count"], row["seconds"]]
                     for k, row in s["kinds"].items()},
           "dispatches": sum(s["dispatches"].values()),
           "dry": sum(s["dry"].values())}
    for r in s["stalls"]["records"]:
        if not r["session_edge"]:
            continue
        period = r["wall_s"] + r["between_s"]
        out["seconds"] -= period
        out["between_s"] -= r["between_s"]
        out["stall_s"] -= r["excess_s"]
        kind = out["kinds"][r["kind"]]
        kind[0] -= 1
        kind[1] -= period
    return out if out["seconds"] > 0 else None


def stall_share(name):
    s = steady(name)
    if s is None:
        return None
    # (the records are rounded to a microsecond: taking the edges' excess
    # off the sum can leave a few of them below nothing)
    return 100.0 * max(s["stall_s"], 0.0) / s["seconds"]


def dry_share(name):
    s = steady(name)
    if s is None or not s["dispatches"]:
        return None
    return 100.0 * s["dry"] / s["dispatches"]


# ---- the six metrics (layer_metrics/<name>.json names one each)

def tick_stall_share_serve(ctx):
    """Seconds the stalled ticks ran over their kind's median, of all the
    loop's seconds."""
    return stall_share("serve")


def step_stall_share_train(ctx):
    return stall_share("train")


def device_dry_dispatch_share_serve(ctx):
    """Dispatches before which the newest result in flight was ready
    already: the device had nothing queued."""
    return dry_share("serve")


def device_dry_dispatch_share_train(ctx):
    """Over the measured window's steps, the last ``counters["steps"]`` of
    the account's ring (one tuple a tick, its last field the tick's dry
    dispatches): set-up's followed steps fetch every loss, so each of them
    finds the device drained, and that is the harness's doing."""
    acct = account("train")
    steps = int((ctx.get("counters") or {}).get("steps") or 0)
    if acct is None or steps <= 0:
        return None
    window = list(acct.ticks)[-steps:]
    return 100.0 * sum(t[5] for t in window) / len(window) if window else None


def tick_between_share_serve(ctx):
    """The caller's part of the loop: return of one ``step()`` to the entry
    of the next."""
    s = steady("serve")
    return None if s is None else 100.0 * s["between_s"] / s["seconds"]


def traced_ticks(acct, window_s):
    """The ring's tuples (entry, kind, tick s, CPU s, between s, dry) of the
    ticks inside the traced window: it closed with the tick at whose back
    ``profiler.stop()`` lies (the newest record at a session's edge; the
    ring's last tuple is tick number ``n_ticks``) and was ``window_s``
    long. That tick itself is left out: its between part is the harness
    reducing the trace."""
    ring = list(acct.ticks)
    edges = [r for r in acct.stalls if r["session_edge"]]
    if not edges:
        return []
    i = len(ring) - 1 - (acct.n_ticks - edges[-1]["tick"])
    if not 0 <= i < len(ring):
        return []
    closed = ring[i][0] + ring[i][2]
    return [t for t in ring[:i] if t[0] >= closed - window_s]


def decode_tick_device_share_serve(ctx):
    """The decode program's device seconds a step over the mean period of
    the decode-only ticks of the SAME traced window (a run's other ticks
    are mostly the drain's, where a sparse step is cheaper): 100 % when
    such a tick IS its program; nothing where the window held none."""
    acct, trace = account("serve"), ctx.get("trace")
    if acct is None or trace is None:
        return None
    periods = [t[2] + t[4] for t in traced_ticks(acct, trace.window_s)
               if t[1] == "decode"]
    dev_s, dev_n = trace.module_seconds(DECODE_PROGRAM)
    if not periods or dev_n <= 0 or dev_s <= 0:
        return None
    return 100.0 * (dev_s / dev_n) / (sum(periods) / len(periods))
