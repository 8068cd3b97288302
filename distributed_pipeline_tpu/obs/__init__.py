"""Observability: span tracing + timeline export + live telemetry.

The robustness stack (PRs 8-11) made failures survivable; this package
makes them *explainable*. Three layers, all import-light (no jax — the
launcher, router, and status CLI run in processes that never pay a
backend import, the same discipline as :mod:`..chaos`):

* :mod:`.trace`  — nestable spans and instant events with explicit
  (never wall-clock-defaulted) span/trace IDs, appended to per-process
  ``trace_rank{k}.jsonl`` shards in the run dir. A zero-cost no-op path
  (:data:`~.trace.NULL`) makes tracing-off free: no span objects, no
  writes, no branches beyond one attribute check. Unarmed, the spans
  inside the train step and the serving tick FOLLOW the profiler
  (:data:`~.trace.FOLLOW`): during a ``jax.profiler`` session each is a
  ``TraceAnnotation`` in the xplane and an event in an in-memory ring.
* :mod:`.export` — folds a run (or fleet) dir's trace shards + beacons +
  ``attempts.jsonl`` + the router ``journal.jsonl`` (+ the cost ledger
  as counter tracks) into ONE Chrome-trace-event / Perfetto-loadable
  timeline (one pid per process/replica, one track per category) plus a
  Prometheus-textfile metrics snapshot.
* :mod:`.ledger` — the per-compiled-program COST LEDGER (ISSUE 14):
  XLA ``cost_analysis``/``memory_analysis`` extraction, the HLO
  collective-bytes tally, and the roofline MFU-gap attribution
  (``mfu + gap_host + gap_comms + gap_memory_bound + gap_residual == 1``
  exactly), snapshotted to ``<run_dir>/perf_ledger.json`` behind
  ``--cost_ledger`` and rendered by ``run/perf_report.py``.
* ``run/status.py`` — the live, read-only fleet status CLI built on the
  same readers.

Arming: set ``DPT_TRACE=1`` (rides the launcher's worker env to every
attempt of every ring) or pass ``--trace true`` to run/train.py /
run/serve.py. The trace and the goodput ledger can never disagree:
instrumented code books each span from the SAME measured seconds it
hands to :class:`~..utils.perf.GoodputTracker` / StallBreakdown /
:class:`~..serving.fleet.ServingTracker`.
"""

from . import trace

__all__ = ["trace"]
