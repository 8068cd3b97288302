"""Serving settings (``run/serve.py``).

Same declarative config surface as training (config/base.py): every field
is a ``--flag``, round-trips through JSON, and documents itself in
``--help``. The knobs mirror the serving stack's layers — engine geometry
(slots/pages/lengths), sampling, workload (prompt file or an arrival
process), the sanitizer switch, and (ISSUE 11) the multi-replica fleet:
traffic process, router health gates, per-replica supervision, and
checkpoint hot-swap.
"""

from __future__ import annotations

from typing import Literal

from .base import ArgparseCompatibleBaseModel as S
from .base import item as _


class ServeSettings(S):
    """Continuous-batching decode service over a trained run directory."""

    checkpoint_path: str = _(..., "run directory written by run.train")
    step: int = _(0, "checkpoint step to load (0 = newest)")
    ema: str = _("", "EMA rate to serve (e.g. 0.99); empty = raw params")

    decode_slots: int = _(8, "compiled decode batch size: decode always "
                             "runs at this many slots (inactive slots are "
                             "masked), so the executable never "
                             "re-specializes to occupancy")
    page_size: int = _(16, "tokens per KV-cache page")
    max_pages: int = _(0, "total pages in the per-layer KV pool (incl. the "
                          "reserved trash page); 0 = full residency "
                          "(decode_slots * ceil(max_len/page_size) + 1). "
                          "Smaller pools admit fewer concurrent long "
                          "requests instead of OOMing")
    max_prompt_len: int = _(0, "compiled prefill length — prompts pad up "
                               "to it (0 = max_len/2)")
    max_len: int = _(0, "longest prompt+generation per slot "
                        "(0 = the model's seq_len)")
    max_new_tokens: int = _(64, "generation budget per request")
    prefill_batch: int = _(0, "prompts prefilled per admission dispatch "
                              "(0 = the engine's token budget: 512 // "
                              "max_prompt_len rows, at least 1, at most "
                              "min(decode_slots, 8))")
    decode_span: int = _(4, "tokens generated per decode dispatch (a "
                            "lax.scan inside the executable): amortizes "
                            "host dispatch over span tokens; admission "
                            "happens at span granularity and a request "
                            "ending mid-span wastes up to span-1 "
                            "slot-steps")
    dispatch_lag: int = _(2, "decode dispatches kept in flight before the "
                             "host fetches tokens: bookkeeping overlaps "
                             "device execution; EOS detection lags by "
                             "this many dispatches")

    temperature: float = _(0.0, "0 = greedy; > 0 samples")
    top_k: int = _(0, "restrict sampling to the k most likely tokens")
    top_p: float = _(0.0, "nucleus sampling mass (0 = off)")
    seed: int = _(0, "sampling seed")
    eos_id: int = _(-1, "finish a request early at this token id (-1 = "
                        "off; observed one lagged step late)")

    prompt_file: str = _("", "JSONL requests, one {\"prompt_ids\": [...]} "
                             "per line (optional \"max_new_tokens\"); "
                             "empty = synthetic workload")
    synthetic_requests: int = _(32, "synthetic workload: request count")
    synthetic_prompt_len: int = _(0, "synthetic prompt length "
                                     "(0 = max_prompt_len)")
    arrival_every_steps: int = _(0, "legacy step-cadence arrival knob "
                                    "(traffic='steps' only): enqueue one "
                                    "request every N scheduler steps "
                                    "(0 = all queued at start)")
    out: str = _("", "write per-request JSONL results here")
    cost_ledger: bool = _(False, "per-executable cost ledger (obs/"
                                 "ledger.py): extract FLOPs/bytes/"
                                 "collective accounting off the prefill/"
                                 "decode AOT executables and attach the "
                                 "decode roofline MFU-gap attribution "
                                 "(+ prompt-padding / slot-occupancy "
                                 "waste) to the summary JSON")
    sanitize: bool = _(False, "runtime sanitizer: count XLA compiles "
                              "(recompile_count must stay 0 in steady "
                              "state — prefill/decode compile exactly "
                              "once) and disallow implicit host<->device "
                              "transfers during dispatch")
    decode_impl: Literal["auto", "pallas", "xla"] = _(
        "auto", "decode-step attention kernel (ops/flash_decode.py): "
                "'pallas' streams K/V pages straight from the paged pool "
                "through a flash-decode kernel (no gathered copy); 'xla' "
                "is the gather+dot reference; 'auto' picks pallas on TPU "
                "where a pool row is whole lane tiles (H*Dh % 128 == 0, "
                "page_size % 16 == 0) and xla elsewhere")
    kv_quant: Literal["fp", "int8"] = _(
        "fp", "paged KV pool storage (ISSUE 20): 'int8' quantizes K/V at "
              "page granularity with [P] fp32 per-page scales — pool "
              "bytes drop ~4x (f32) / ~2x (bf16), so decode slots and "
              "prefix-cache capacity double at fixed HBM; decode logits "
              "carry the documented divergence bound instead of "
              "bit-identity (prefill logits are unchanged)")
    spec_tokens: int = _(0, "speculative decoding (ISSUE 20): draft K "
                            "tokens per round and verify them in ONE "
                            "target dispatch; greedy output is token-"
                            "identical to the non-speculative path. "
                            "0 = off")
    spec_draft: Literal["ngram", "model"] = _(
        "ngram", "draft source: 'ngram' = host-side prompt-lookup "
                 "(zero model flops — the CPU-friendly arm); 'model' = "
                 "early-exit engine over the target's first draft_layers "
                 "blocks (weights shared, no training)")
    draft_layers: int = _(2, "spec_draft='model': how many leading target "
                             "blocks the draft model keeps")
    serve_quant: Literal["off", "int8"] = _(
        "off", "quantize replica WEIGHTS at load and at every hot-swap "
               "restore (serving/quantize.py): int8 storage round-trip "
               "with per-channel scales and a round-trip error guard — "
               "a corrupt/pathological checkpoint raises inside the "
               "worker, so the hot-swap canary aborts instead of the "
               "fleet taking bad weights")
    prefix_cache: bool = _(False, "shared-prefix KV page reuse: requests "
                                  "whose prompts open with the same token "
                                  "run share the paged-KV pages holding "
                                  "that prefix (refcounted; evicted LRU "
                                  "under pool pressure)")
    trace: bool = _(False, "span tracing (obs/): replicas book per-request "
                           "serve spans (router-propagated trace ids), "
                           "engine prefill/decode spans, and hot-swap "
                           "drain/load windows into per-replica "
                           "trace_rank0.jsonl shards; export the whole "
                           "fleet as ONE Perfetto timeline with python -m "
                           "distributed_pipeline_tpu.obs.export "
                           "<fleet_dir>; DPT_TRACE arms it too; off = "
                           "zero-cost no-op")

    # ------------------------------------------------- traffic (ISSUE 11)
    traffic: Literal["steps", "poisson", "bursty", "diurnal"] = _(
        "steps", "arrival process: 'steps' keeps the legacy "
                 "scheduler-step cadence; poisson/bursty/diurnal are "
                 "seeded wall-clock processes (serving/traffic.py) — "
                 "same seed, same schedule, every process")
    rate_rps: float = _(8.0, "mean arrival rate (requests/second) for the "
                             "wall-clock traffic processes")
    burst_every_s: float = _(2.0, "bursty traffic: seconds between bursts")
    burst_size: int = _(8, "bursty traffic: arrivals per burst")
    diurnal_period_s: float = _(30.0, "diurnal traffic: ramp period "
                                      "(a compressed day/night cycle)")
    diurnal_floor: float = _(0.2, "diurnal traffic: trough rate as a "
                                  "fraction of rate_rps")
    shared_prefix_len: int = _(0, "synthetic prompts open with this many "
                                  "SHARED tokens (the prefix-cache "
                                  "workload; 0 = fully random prompts)")

    # --------------------------------------------------- fleet (ISSUE 11)
    replicas: int = _(0, "serve through a fleet of N replicas (each its "
                         "own supervised worker process behind the "
                         "request router) instead of one in-process "
                         "server; 0 = single-replica legacy path")
    fleet_dir: str = _("", "fleet working dir (journal + per-replica "
                           "run dirs); empty = <checkpoint_path>/fleet")
    fleet_worker_dir: str = _("", "INTERNAL: run as a fleet replica "
                                  "worker against this replica dir "
                                  "(set by the fleet supervisor)")
    replica_id: int = _(-1, "INTERNAL: this worker's replica index")
    replica_platform: str = _(
        "auto", "jax backend the replica workers pin (ISSUE 13 "
                "satellite): 'auto' inherits the PARENT's platform "
                "(JAX_PLATFORMS in the fleet parent's environment — cpu "
                "under the test/dev rings, unset on a TPU host so "
                "replicas see the real chips); 'cpu' forces the dev-ring "
                "behavior (virtual devices); any "
                "other value pins that platform; '' = never pin")
    hang_timeout_s: float = _(10.0, "per-replica hang watchdog: a replica "
                                    "whose beacons freeze this long is "
                                    "SIGKILLed and its in-flight requests "
                                    "replay on a sibling; must exceed the "
                                    "slowest legitimate tick + swap-"
                                    "restore gap. 0 disables")
    fleet_max_restarts: int = _(3, "per-replica restart budget (sliding "
                                   "window, launcher semantics)")
    fleet_backoff_s: float = _(0.25, "per-replica restart backoff base")
    stale_beacon_s: float = _(10.0, "router health gate: stop placing NEW "
                                    "requests on a replica whose newest "
                                    "beacon is older than this")
    fleet_deadline_s: float = _(300.0, "hard wall-clock cap on the fleet "
                                       "run; anything unfinished is "
                                       "reported dropped (acceptance "
                                       "is zero)")
    chaos_plan: str = _("", "serving chaos schedule (JSON / @file; kinds "
                            "kill_replica / stall_replica / "
                            "corrupt_swap_checkpoint); also honors the "
                            "DPT_CHAOS_PLAN env like training")
    serve_transport: Literal["file", "socket"] = _(
        "file", "replica data-plane transport (ISSUE 17): 'file' = "
                "atomic-rename mailboxes + beacon-mtime liveness (the "
                "proven single-host default); 'socket' = length-prefixed "
                "JSON frames over TCP + heartbeat liveness (replicas can "
                "live on other hosts). The ctrl plane (ready/swap/stop/"
                "beacons) stays file-based either way, so hot-swap, the "
                "hang watchdog and goodput accounting are identical")
    route_affinity: bool = _(
        False, "prefix-affinity routing: place each request on the "
               "replica whose advertised prefix-cache index matches the "
               "most leading page-aligned prompt blocks (falls back to "
               "least-loaded on ties/cold prefixes); pair with "
               "--prefix_cache for the fleet-wide cache win")

    # ----------------------------------------------- autoscale (ISSUE 17)
    autoscale: bool = _(
        False, "SLO-driven autoscaler (serving/autoscale.py): grow the "
               "replica set when backlog/TTFT breach the SLO, shrink it "
               "via the drain path when idle; --replicas is the "
               "INITIAL size")
    autoscale_min: int = _(1, "autoscaler floor (never drain below this "
                              "many active replicas)")
    autoscale_max: int = _(0, "autoscaler ceiling (0 = the initial "
                              "--replicas count, i.e. scale-down only)")
    autoscale_slo_ttft_s: float = _(
        10.0, "the TTFT SLO target: windowed p95 above this (or backlog "
              "above autoscale_up_backlog per ready replica) scales UP")
    autoscale_up_backlog: float = _(
        2.0, "scale-up pressure threshold: pending requests per ready "
             "replica")
    autoscale_down_frac: float = _(
        0.5, "hysteresis band: scale DOWN only when backlog is zero and "
             "windowed p95 TTFT sits below down_frac * slo (strictly "
             "below the up threshold, so bursts can't flap the fleet)")
    autoscale_cooldown_s: float = _(
        5.0, "minimum seconds between structural changes (either "
             "direction)")
    autoscale_window_s: float = _(
        30.0, "trailing window over completed requests feeding the "
              "p95-TTFT signal")

    # -------------------------------------------- disaggregation (ISSUE 16)
    disagg: int = _(0, "disaggregated prefill/decode serving (mpmd/"
                       "disagg.py): the --replicas workers become PREFILL-"
                       "only workers that stream each admitted request's "
                       "paged-KV pages + first token over a StageLink to a "
                       "separately supervised DECODE ring; requests still "
                       "enter through the router. Value = decode ring "
                       "count (only 1 is supported); 0 = colocated "
                       "(every replica prefills and decodes)")
    disagg_role: str = _("", "INTERNAL: 'prefill' or 'decode' — set on the "
                             "worker argv by the disaggregated fleet parent")
    disagg_links: str = _("", "INTERNAL: StageLink directory shared by the "
                              "prefill and decode workers")
    disagg_peers: int = _(0, "INTERNAL: number of prefill workers whose "
                             "kv/tok links the decode worker polls")

    # ------------------------------------------------ hot-swap (ISSUE 11)
    swap_after_requests: int = _(0, "trigger a zero-downtime checkpoint "
                                    "hot-swap once this many requests "
                                    "have completed (0 = no swap)")
    swap_step: int = _(0, "hot-swap target step (0 = newest finalized "
                          "checkpoint at swap time)")
    drain_timeout_s: float = _(60.0, "hot-swap: max wait for one "
                                     "replica's outstanding requests to "
                                     "finish before the swap aborts")
    swap_timeout_s: float = _(120.0, "hot-swap: max wait for one replica "
                                     "to load + ack the new checkpoint")
