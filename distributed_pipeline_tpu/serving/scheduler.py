"""DecodeServer: continuous batching over the prefill/decode engine.

``run/sample.py`` (pre-serving) ran generation in LOCKSTEP batches: every
prompt starts together, the batch ends when the longest generation ends,
and a new request waits for the whole batch to drain. A serving loop keeps
the compiled decode batch FULL instead: every step, queued requests are
admitted into whatever slots are free (prefill batched opportunistically),
decode always runs at the compiled slot count with an active mask, and a
finished request frees its slot and pages immediately for the next one.

Host/device split (the async-dispatch pattern from the trainer's lagged
metrics, PR 5): the host dispatches decode step N, then fetches step N-1's
token vector — blocking on N-1 while N executes, so scheduler bookkeeping
(admission, page accounting, output assembly) overlaps device time instead
of serializing behind it. Completion is COUNT-based (each request's
generation budget is known at admission), so the host never has to sync on
content to schedule; an optional EOS id finishes a request early, observed
one lagged step late by construction.

A model with a CHUNKED prefill (``engine.chunked``; models/deepseek_v32.py)
is admitted the same way (slot and worst-case pages at once) but its prompt
is written a chunk a tick, oldest request first, beside that tick's decode
step: a long prompt stalls the decoding slots for one chunk, not for itself.
The slot joins the decode batch with the dispatch of its last chunk.

Invariants the tests pin (tests/test_serving.py):

* no slot or page leaks — after drain, every slot is free and the page
  pool is back to full;
* bounded completion — pages for a request's WORST CASE (prompt + budget)
  are reserved at admission, so an admitted request can always run to
  completion without preempting anyone;
* late arrivals preempt nothing — an admission only ever touches free
  slots/pages, so in-flight requests' outputs are unchanged (greedy
  decode: token-for-token).
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import sys
import time
from typing import Any, Deque, List, Optional

import jax
import numpy as np

from ..obs import trace as trace_lib
from ..utils.perf import EventStats, RecompileMonitor, SanitizeReport, \
    StallBreakdown, device_peak_flops
from ..utils.perf import transformer_decode_flops_per_token \
    as decode_flops_per_token
from .engine import DecodeEngine
from .paged_kv import TRASH_PAGE, PageManager, PrefixCache
from .spec import DRAFT_KINDS, ngram_propose, truncated_draft

__all__ = ["Request", "DecodeServer", "one_shot_decode"]

# The server's own account of its ticks (utils/perf.py::StallBreakdown,
# always on). The phases lie on the boundaries of the serve.* spans of the
# same names (admit holds the prefill dispatch; fetch_host is serve.fetch
# less its fetch_wait); a dispatch's bit says what kind of tick it makes.
TICK_PHASES = ("sweep", "admit", "prefill_chunk", "decode_dispatch",
               "fetch_wait", "fetch_host")
TICK_DISPATCHES = (("prefill", 1), ("chunk", 1), ("decode", 2), ("spec", 4))
_PREFILL, _CHUNK, _DECODE, _SPEC = range(4)
TICK_KINDS = ("idle", "prefill", "decode", "prefill+decode") + ("spec",) * 4


@dataclasses.dataclass
class Request:
    """One generation request and its lifecycle bookkeeping."""

    id: int
    prompt: np.ndarray              # int32 [prompt_len]
    max_new_tokens: int
    g_max: int = 0                  # tokens this request WILL generate
    # (min(max_new_tokens, max_len - prompt_len), fixed at submit — the
    # single cap admission, release, and fetch-truncation all share)
    eos_id: Optional[int] = None
    submit_t: float = 0.0           # perf_counter readings, all three:
    admit_t: Optional[float] = None   # slot and pages reserved
    finish_t: Optional[float] = None  # `finished` turned true
    tokens: List[int] = dataclasses.field(default_factory=list)
    ttft_s: Optional[float] = None  # submit -> first token FETCHED
    finished: bool = False          # output collection complete
    trace_id: Optional[str] = None  # the router's, when it minted one

    @property
    def prompt_len(self) -> int:
        return int(self.prompt.shape[0])


@dataclasses.dataclass
class _SlotState:
    """Host mirror of one decode slot (no device fetch needed to
    schedule): dispatch-side generation count and position."""

    req: Request
    pages: np.ndarray               # page ids reserved for this request
    # its ring in the window pool (a model with window layers), else None
    window_pages: Optional[np.ndarray] = None
    generated: int = 1              # prefill produced token #1
    position: int = 0               # index of the token currently in state
    # (chunked prefill: prompt rows written so far, until the slot decodes)


class DecodeServer:
    """Continuous-batching decode service over a :class:`DecodeEngine`.

    ``submit()`` enqueues requests; ``step()`` advances the world by one
    decode step (admitting first, fetching last); ``drain()`` runs until
    everything submitted has completed. ``sanitize=True`` mirrors the
    trainer's runtime sanitizer: every XLA compile counts into
    ``recompile_count`` (steady state must freeze it — the two phase
    executables compile exactly once) and dispatches run under
    ``jax.transfer_guard("disallow")``.

    Tokens are fetched ``dispatch_lag`` dispatches behind, so that the
    host's bookkeeping, and a stall of the host shorter than the work queued
    on the device, cost the chip nothing. Given none, the server asks the
    model what its deployment states (``model.dispatch_lag``) and fetches
    one dispatch behind where it states nothing. A budget is spent by count
    at dispatch, so a deeper queue frees no slot later: it costs a first
    token, and a client that waits for its answer, that many dispatches.

    ``spec_tokens = K > 0`` turns on SPECULATIVE decoding: each round a
    draft (``spec_draft``: host-side "ngram" prompt-lookup, or "model" — an
    early-exit engine over the target's first ``draft_layers`` blocks)
    proposes K tokens per slot and ONE verify dispatch yields the target's
    pick at every link (serving/spec.py for the acceptance contract —
    greedy output is token-identical to the non-speculative path). Spec
    rounds are synchronous (the verify result IS next round's input), so
    ``dispatch_lag`` overlap doesn't apply; the win is K+1 target steps
    per dispatch, paid back at the accept rate.
    """

    def __init__(self, workload, params, *, decode_slots: int = 8,
                 page_size: int = 16, max_pages: int = 0,
                 max_prompt_len: int = 0, max_len: int = 0,
                 prefill_batch: int = 0, decode_span: int = 1,
                 temperature: float = 0.0,
                 top_k: int = 0, top_p: float = 0.0, seed: int = 0,
                 rng: Optional[jax.Array] = None, eos_id: Optional[int] = None,
                 mesh=None, sanitize: bool = False,
                 dispatch_lag: Optional[int] = None,
                 prefix_cache: bool = False,
                 decode_impl: str = "auto", kv_quant: str = "fp",
                 spec_tokens: int = 0, spec_draft: str = "ngram",
                 draft_layers: int = 2, tracer: Any = None) -> None:
        # Spans of the tick and of each request's life (obs/trace.py).
        # Given none, the server follows the profiler: off — one
        # is_enabled() a boundary — until a jax.profiler session is on.
        self.tracer = tracer if tracer is not None else trace_lib.FOLLOW
        max_len = max_len or workload.seq_len
        max_prompt_len = max_prompt_len or max(2, max_len // 2)
        pages_per_slot = -(-max_len // page_size)
        if max_pages <= 0:
            # full residency default: every slot can hold max_len — the
            # pool-smaller-than-worst-case regime is opt-in via max_pages
            max_pages = 1 + decode_slots * pages_per_slot
        self.sanitize = sanitize
        self._recompiles = RecompileMonitor(capture_sites=sanitize)
        # Evidence sidecar (ISSUE 19 runtime bridge): guard trips and
        # steady-state recompiles accumulate here; run/serve.py finalizes
        # with write_sanitize_report() so the static pass can
        # cross-reference (analysis --runtime-evidence, GL013).
        self.sanitize_report = SanitizeReport()
        self._recompiles_at_first_token: Optional[int] = None
        self._sanitizer_reported = False
        if sanitize:
            self._recompiles.install()
        if spec_tokens > 0 and spec_draft not in DRAFT_KINDS:
            raise ValueError(f"spec_draft must be one of {DRAFT_KINDS}, "
                             f"got {spec_draft!r}")
        self.spec_tokens = spec_tokens
        self.spec_draft = spec_draft
        self._draft_layers = draft_layers
        try:
            self.engine = DecodeEngine(
                workload, params, decode_slots=decode_slots,
                page_size=page_size, max_pages=max_pages,
                max_prompt_len=max_prompt_len, max_len=max_len,
                prefill_batch=prefill_batch, decode_span=decode_span,
                temperature=temperature,
                top_k=top_k, top_p=top_p, rng=rng, seed=seed, mesh=mesh,
                transfer_guard=sanitize, decode_impl=decode_impl,
                kv_quant=kv_quant, spec_tokens=spec_tokens,
                tracer=self.tracer)
            if self.engine.chunked and prefix_cache:
                raise NotImplementedError(
                    "the prefix cache skips whole prompt prefills; a "
                    "chunked prefill would have to start mid-prompt")
            self._draft_engine: Optional[DecodeEngine] = None
            self._draft_fpt = 0.0
            if spec_tokens > 0 and spec_draft == "model":
                # Early-exit draft over the target's first draft_layers
                # blocks, on a STATIC full-residency pool: slot s owns
                # pages [1 + s*pps, 1 + (s+1)*pps) forever, so the draft
                # needs no allocator and rollback is just the host state
                # push each round (accepted draft K/V is valid by the
                # acceptance rule: d_j == g_{j-1}). Views of the leaves
                # the TARGET holds: one serving copy of the weights for
                # both (the draft engine's own pass finds nothing to cast).
                dwl, dparams = truncated_draft(workload, self.engine.params,
                                               draft_layers)
                pps = self.engine.pages_per_slot
                self._draft_engine = DecodeEngine(
                    dwl, dparams, decode_slots=decode_slots,
                    page_size=page_size,
                    max_pages=1 + decode_slots * pps,
                    max_prompt_len=max_prompt_len, max_len=max_len,
                    prefill_batch=prefill_batch, decode_span=1,
                    temperature=0.0, seed=seed, mesh=mesh,
                    transfer_guard=sanitize, decode_impl=decode_impl,
                    kv_quant=kv_quant, tracer=self.tracer)
                self._draft_tables = np.arange(
                    1, 1 + decode_slots * pps,
                    dtype=np.int32).reshape(decode_slots, pps)
                self._draft_engine.set_block_tables(self._draft_tables)
                self._draft_fpt = decode_flops_per_token(
                    dwl.param_count(dparams))
        except BaseException:
            self._recompiles.uninstall()  # failed build must not leak the
            raise                         # process-global 'jax' log handler
        self.mgr = PageManager(max_pages, page_size)
        # a model with window layers keeps their rows in a pool of its own,
        # a ring of pages a slot, whatever the request's length: a second
        # allocator and a second block table, one a kind of cache
        wpps = self.engine.window_pages_per_slot
        self.window_mgr = PageManager(self.engine.max_window_pages,
                                      page_size) if wpps else None
        self.window_tables = np.zeros((decode_slots, wpps), np.int32)
        # Shared-prefix page reuse (ISSUE 11 satellite): requests whose
        # prompts open with the same token run share the pages holding
        # that prefix's K/V (refcounted — see PrefixCache for why replay/
        # eviction can never free a page a live slot still reads).
        self.prefix = PrefixCache(self.mgr) if prefix_cache else None
        s = decode_slots
        self.block_tables = np.zeros((s, self.engine.pages_per_slot),
                                     np.int32)  # all TRASH_PAGE
        self.active = np.zeros((s,), np.int32)
        self.slots: List[Optional[_SlotState]] = [None] * s
        self.queue: Deque[Request] = collections.deque()
        self.default_eos_id = eos_id
        # decode dispatches in flight before the host fetches: the
        # caller's, else what the model's deployment states, else one
        if dispatch_lag is None:
            dispatch_lag = getattr(workload.model, "dispatch_lag", 1)
        self.dispatch_lag = max(0, int(dispatch_lag))
        # lagged fetch ring: (device tokens handle, [(slot, Request)] whose
        # token in that vector is NEW)
        self._ring: Deque[Any] = collections.deque()
        self._dirty = False     # block tables / active changed since put
        self._needs_sweep = False  # a fetch EOS-finished a request whose
        # slot is still held (count-based completions release inline)
        self._req_counter = 0
        self.ttft = EventStats()
        self.decode_steps = 0
        self.prefill_steps = 0
        self.tokens_fetched = 0
        # Cost-ledger occupancy/padding counters: actual vs padded
        # prompt tokens per prefill dispatch, and active vs compiled
        # slot-steps per decode dispatch — the serving-side
        # padding_waste_frac inputs (obs/ledger.py).
        self.workload = workload
        self.prompt_tokens_prefilled = 0
        self.prefill_token_slots = 0
        self.slot_steps_active = 0
        self.slot_steps_total = 0
        # Speculative gauges: per-round draft proposals vs matches (the
        # fleet accept_rate surface) — every FETCHED token still counts
        # through tokens_fetched, which in spec mode is by definition the
        # accepted-token count.
        self.spec_rounds = 0
        self.draft_proposed = 0
        self.draft_accepted = 0
        # chunked prefill (a model that brings its own): slots whose
        # prompt is still being written, oldest first, one chunk a tick
        self._prefilling: Deque[int] = collections.deque()
        # what the model's programs count (engine.n_counters int32 behind
        # each fetched token vector), summed by the program that counted
        names = tuple(getattr(workload.model, "counters", ()))
        self.counted = {"prefill": dict.fromkeys(names, 0),
                        "decode": dict.fromkeys(names, 0)}
        # every tick, booked whether or not anything traces; found again
        # as perf.tick_account("serve") once the server is gone, and not
        # cleared by reset_stats (its steady point is the first token)
        self.ticks = StallBreakdown(
            "serve", phases=TICK_PHASES, waits=("fetch_wait",),
            dispatches=TICK_DISPATCHES, kinds=TICK_KINDS,
            tracer=self.tracer)

    # ----------------------------------------------------------- gauges etc.

    @property
    def compile_time_s(self) -> float:
        return self.engine.compile_time_s

    @property
    def recompile_count(self) -> int:
        return self._recompiles.count

    def stop_sanitizer(self) -> int:
        """Detach the process-global sanitizer hooks; returns the final
        compile count. Idempotent; no-op when sanitize was off. Compiles
        observed after the first fetched token (the serving steady-state
        boundary — both phase executables exist by then) become
        ``steady_recompile`` violations in the evidence report."""
        self._recompiles.uninstall()
        if self.sanitize and not self._sanitizer_reported:
            self._sanitizer_reported = True
            if self._recompiles_at_first_token is not None:
                self.sanitize_report.note_recompiles(
                    self._recompiles, self._recompiles_at_first_token)
            # where an untraced run shows what its ticks were
            self.ticks.close()
            print(self.ticks.report_line(), file=sys.stderr, flush=True)
        return self._recompiles.count

    def write_sanitize_report(self, out_dir: str) -> str:
        """Finalize the evidence (stop_sanitizer, folding steady
        recompiles in) and drop the sanitize_report.json sidecar in
        ``out_dir``. Returns the written path, "" when sanitize was off
        or the write failed (best-effort by design)."""
        if not self.sanitize:
            return ""
        self.stop_sanitizer()
        return self.sanitize_report.write(out_dir)

    def set_params(self, params) -> None:
        """Hot-swap surface: replace the target's weights — through the
        engine's own ``set_params``, so the swapped tree is held in the
        same serving form (and dtypes) the executables were compiled
        against — AND rebuild the model draft's early-exit views from
        what the target now holds (the draft leaves are references into
        that tree, so this is re-indexing, not a second restore or cast).
        Callers that poke ``engine.params`` directly would leave a model
        draft proposing from stale weights — harmless for correctness
        (every token is target-verified) but a silent accept-rate
        regression."""
        self.engine.set_params(params)
        if self._draft_engine is not None:
            _, dparams = truncated_draft(self.workload, self.engine.params,
                                         self._draft_layers)
            self._draft_engine.set_params(dparams)

    @property
    def free_slots(self) -> int:
        return sum(1 for s in self.slots if s is None)

    @property
    def busy(self) -> bool:
        """Anything queued, in flight, or awaiting fetch."""
        return bool(self.queue or any(s is not None for s in self.slots)
                    or self._ring)

    @property
    def time_to_first_token_s(self) -> float:
        """Mean submit->first-token latency over completed TTFTs."""
        return self.ttft.summary()["mean"]

    def reset_stats(self) -> None:
        """Zero the serving gauges (a warmup, then the timed window)."""
        self.ttft = EventStats()
        self.decode_steps = 0
        self.prefill_steps = 0
        self.tokens_fetched = 0
        self.prompt_tokens_prefilled = 0
        self.prefill_token_slots = 0
        self.slot_steps_active = 0
        self.slot_steps_total = 0
        self.spec_rounds = 0
        self.draft_proposed = 0
        self.draft_accepted = 0
        for group in self.counted.values():
            group.update(dict.fromkeys(group, 0))

    @property
    def accept_rate(self) -> float:
        """Fraction of proposed draft tokens the target accepted."""
        return (self.draft_accepted / self.draft_proposed
                if self.draft_proposed > 0 else 0.0)

    def prefix_stats(self) -> dict:
        """Prefix-cache gauges (empty dict when the cache is off)."""
        return self.prefix.stats() if self.prefix is not None else {}

    def cost_ledger(self, *, wall_s: float, n_devices: int = 1) -> dict:
        """Per-executable cost-ledger rows (obs/ledger.py) for the two
        serving phases. The DECODE row carries the full roofline MFU-gap
        attribution — tokens/s over ``wall_s`` against the forward-only
        2N FLOPs/token roofline, slot-occupancy waste as its padding
        term — while the PREFILL row carries the extraction plus the
        prompt-padding waste (prefill runs at the compiled
        [prefill_batch, max_prompt_len] shape regardless of actual
        prompt lengths; the rows are the engine's token budget unless
        the caller named them, so a lone admission pads one row of 512
        and not eight). A ``weights`` row says what the engine holds of
        the tree it was given. ``n_devices`` defaults to 1: decode state is
        replicated, so the service rate IS the per-chip rate (the
        measure_decode rationale)."""
        from ..obs import ledger as ledger_lib

        n_params = self.workload.param_count(self.engine.params)
        fpt = decode_flops_per_token(n_params)
        device_kind = getattr(jax.devices()[0], "device_kind", "cpu")
        rows: dict = {}
        for name, aot in self.engine.executables().items():
            if aot.compiled is None:
                continue
            row = {"program": f"serve_{name}",
                   **ledger_lib.extract_cost(aot.compiled)}
            if name == "decode":
                tokens_per_s = (self.tokens_fetched / wall_s
                                if wall_s > 0 else 0.0)
                steps_per_s = (self.decode_steps / wall_s
                               if wall_s > 0 else 0.0)
                occupancy_waste = (
                    1.0 - self.slot_steps_active / self.slot_steps_total
                    if self.slot_steps_total > 0 else 0.0)
                row.update({
                    "flops_per_token": fpt,
                    "n_params": n_params,
                    "tokens_per_s": tokens_per_s,
                    "steps_per_s": steps_per_s,
                    "decode_span": self.engine.decode_span,
                    # page-pool residency gauge: the int8 KV criterion is
                    # ledger-verified (int8 arm <= 0.55x fp at equal
                    # geometry)
                    "kv_pool_bytes": self.engine.kv_pool_bytes(),
                    "kv_quant": self.engine.kv_quant,
                })
                if self.spec_tokens > 0:
                    tf = max(1, self.tokens_fetched)
                    # draft-flops accounting: what the device ACTUALLY
                    # spent per fetched (= accepted) token — verify runs
                    # K+1 target steps per round and the draft its own
                    # model (0 flops for ngram) — so the roofline stays
                    # honest about speculative overhead
                    row.update({
                        "spec_tokens": self.spec_tokens,
                        "spec_draft": self.spec_draft,
                        "accept_rate": self.accept_rate,
                        "accepted_tokens_per_s": tokens_per_s,
                        "accepted_tokens_per_s_per_chip":
                            tokens_per_s / max(1, n_devices),
                        "draft_flops_per_token": self._draft_fpt,
                        "spec_flops_per_fetched_token":
                            fpt * self.slot_steps_active / tf
                            + self._draft_fpt * self.draft_proposed / tf,
                    })
                row.update(ledger_lib.roofline_attribution(
                    tokens_per_s=tokens_per_s, flops_per_token=fpt,
                    peak_flops=device_peak_flops(), n_devices=n_devices,
                    steps_per_s=steps_per_s,
                    collective_bytes_per_step=row.get(
                        "collective_bytes_per_step", 0.0),
                    bytes_accessed=row.get("bytes_accessed", 0.0),
                    device_kind=device_kind,
                    padding_waste_frac=occupancy_waste))
            else:
                row["padding_waste_frac"] = (
                    1.0 - self.prompt_tokens_prefilled
                    / self.prefill_token_slots
                    if self.prefill_token_slots > 0 else 0.0)
            rows[f"serve_{name}"] = row
        # what the engine holds of the tree it was given (its serving
        # form): bytes in, bytes held, leaves cast
        rows["weights"] = dict(self.engine.weights)
        return rows

    # ------------------------------------------------------------ lifecycle

    def set_rng(self, key: jax.Array) -> None:
        self.engine.set_rng(key)

    def submit(self, prompt: np.ndarray, max_new_tokens: int,
               eos_id: Optional[int] = None,
               trace_id: Optional[str] = None) -> Request:
        prompt = np.ascontiguousarray(prompt, np.int32).ravel()
        if not 1 <= prompt.shape[0] <= self.engine.max_prompt_len:
            raise ValueError(
                f"prompt length {prompt.shape[0]} outside [1, "
                f"max_prompt_len={self.engine.max_prompt_len}]")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        g_max = min(max_new_tokens,
                    self.engine.max_len - int(prompt.shape[0]))
        if g_max < 1:
            raise ValueError(
                f"prompt of {prompt.shape[0]} tokens leaves no room to "
                f"generate under max_len={self.engine.max_len}")
        total = prompt.shape[0] + g_max
        if self.mgr.pages_for(total) > self.mgr.capacity:
            raise ValueError(
                f"request needs {self.mgr.pages_for(total)} pages but the "
                f"pool holds {self.mgr.capacity}; raise max_pages or lower "
                f"max_new_tokens")
        self._req_counter += 1
        req = Request(id=self._req_counter, prompt=prompt,
                      max_new_tokens=max_new_tokens, g_max=g_max,
                      eos_id=self.default_eos_id if eos_id is None else eos_id,
                      submit_t=time.perf_counter(), trace_id=trace_id)
        self.queue.append(req)
        return req

    def submit_prefilled(self, prompt: np.ndarray, max_new_tokens: int, *,
                         first_token: int, kv_pages: dict,
                         eos_id: Optional[int] = None,
                         submit_t: Optional[float] = None
                         ) -> Optional[Request]:
        """Admit a request whose prefill ran on ANOTHER engine (the
        disaggregated serving path, mpmd/disagg.py): ``kv_pages`` is an
        ``DecodeEngine.extract_pages`` payload covering the prompt's
        ``pages_for(prompt_len)`` pages, ``first_token`` the token the
        prefill worker already picked at ``position = prompt_len``.

        Unlike :meth:`submit` this admits IMMEDIATELY (no queue): the KV
        payload is only valid against the page ids allocated here, so
        deferring admission would mean holding the payload host-side
        anyway — returning None (no free slot / pool exhausted) pushes
        the backpressure onto the caller's StageLink instead, which is
        the flow-control channel the transfer already has. Pages come
        straight from the PageManager (never the prefix cache: the
        transferred pages hold remote state the local prefill executable
        never wrote, so publishing them as a shareable prefix would hand
        sharers pages this server cannot reproduce)."""
        prompt = np.ascontiguousarray(prompt, np.int32).ravel()
        if not 1 <= prompt.shape[0] <= self.engine.max_prompt_len:
            raise ValueError(
                f"prompt length {prompt.shape[0]} outside [1, "
                f"max_prompt_len={self.engine.max_prompt_len}]")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        g_max = min(max_new_tokens,
                    self.engine.max_len - int(prompt.shape[0]))
        if g_max < 1:
            raise ValueError(
                f"prompt of {prompt.shape[0]} tokens leaves no room to "
                f"generate under max_len={self.engine.max_len}")
        total = prompt.shape[0] + g_max
        if self.mgr.pages_for(total) > self.mgr.capacity:
            raise ValueError(
                f"request needs {self.mgr.pages_for(total)} pages but the "
                f"pool holds {self.mgr.capacity}; raise max_pages or lower "
                f"max_new_tokens")
        n_filled = self.mgr.pages_for(prompt.shape[0])
        got = {k: v.shape[0] for k, v in kv_pages.items()}
        if any(n != n_filled for n in got.values()):
            raise ValueError(f"kv_pages rows {got} != pages_for(prompt_len)"
                             f"={n_filled}")
        free = [s for s in range(len(self.slots)) if self.slots[s] is None]
        if not free:
            return None
        pages = self.mgr.alloc(self.mgr.pages_for(total))
        if pages is None:
            return None
        slot = free[0]
        self._req_counter += 1
        req = Request(id=self._req_counter, prompt=prompt,
                      max_new_tokens=max_new_tokens, g_max=g_max,
                      eos_id=self.default_eos_id if eos_id is None else eos_id,
                      submit_t=(time.perf_counter() if submit_t is None
                                else submit_t))
        self.engine.ingest_pages(pages[:n_filled], kv_pages)
        self.engine.set_slot_state(slot, first_token, req.prompt_len)
        self.block_tables[slot, :] = TRASH_PAGE
        self.block_tables[slot, :len(pages)] = pages
        self.active[slot] = 1
        self.slots[slot] = _SlotState(req=req, pages=pages,
                                      position=req.prompt_len)
        self._dirty = True
        # the transferred first token is this request's first FETCHED
        # token too (the colocated path attributes it from the prefill
        # ring; there is no local prefill dispatch to ride here)
        now = time.perf_counter()
        req.tokens.append(int(first_token))
        self.tokens_fetched += 1
        req.admit_t = max(now, req.submit_t)  # submit_t may be the caller's
        self._book_admitted(req)
        self._book_first_token(req, req.admit_t)
        if (req.eos_id is not None and int(first_token) == req.eos_id) \
                or len(req.tokens) >= req.g_max:
            self._finish(req, req.admit_t)
        if req.finished or req.g_max <= 1:
            self._release(slot)
        return req

    # --------------------------------------------- a request's life, stamped
    # where it happens: the three fields are set always (they are counters
    # of ttft_s's kind), the three request.* spans are booked from the same
    # numbers under the request's trace id, so span and field cannot
    # disagree and request.queue + request.first_token IS ttft_s.

    def _request_span(self, name: str, req: Request, t0: float, t1: float,
                      **args: Any) -> None:
        self.tracer.complete(
            name, "request", trace_lib.wall_at(t0), t1 - t0,
            trace_id=req.trace_id or trace_lib.request_trace_id(req.id),
            args={"id": req.id, **args})

    def _book_admitted(self, req: Request) -> None:
        if self.tracer.enabled:
            self._request_span("request.queue", req, req.submit_t,
                               req.admit_t, prompt_len=req.prompt_len)

    def _book_first_token(self, req: Request, now: float) -> None:
        req.ttft_s = now - req.submit_t
        self.ttft.add(req.ttft_s)
        self.ticks.mark_steady()    # (the first one counts)
        if self.tracer.enabled:
            self._request_span("request.first_token", req, req.admit_t, now)

    def _finish(self, req: Request, now: float) -> None:
        req.finished = True
        req.finish_t = now
        if self.tracer.enabled and req.ttft_s is not None:
            self._request_span("request.decode", req,
                               req.submit_t + req.ttft_s, now,
                               n_tokens=len(req.tokens))

    def _release(self, slot: int) -> None:
        st = self.slots[slot]
        if st is None:
            return
        if self.prefix is not None:
            # shared prefix pages stay cache-resident for the next
            # sharer; only the slot's private tail frees now
            to_free = self.prefix.release(st.req.prompt, st.pages)
            if to_free.size:
                self.mgr.free(to_free)
        else:
            self.mgr.free(st.pages)
        if st.window_pages is not None:
            self.window_mgr.free(st.window_pages)
            self.window_tables[slot, :] = TRASH_PAGE
        self.block_tables[slot, :] = TRASH_PAGE
        self.active[slot] = 0
        self.slots[slot] = None
        self._dirty = True

    def _reserve_pages(self, req: Request) -> Optional[np.ndarray]:
        """All-or-nothing worst-case page reservation for one admission.
        With the prefix cache on, the cached full-page prompt prefix is
        slot-ref'd (not re-allocated) and only the remainder comes from
        the pool — evicting idle cache entries under pressure before
        giving up."""
        total = req.prompt_len + req.g_max
        n_total = self.mgr.pages_for(total)
        if self.prefix is None:
            return self.mgr.alloc(n_total)
        shared, covered = self.prefix.acquire(req.prompt)
        need = n_total - len(shared)
        fresh = (self.mgr.alloc(need) if need > 0
                 else np.zeros((0,), np.int32))
        if fresh is None:
            self.prefix.evict_for(need)
            fresh = self.mgr.alloc(need)
        if fresh is None:
            if shared:
                # roll the acquire back. If the evict_for above dropped
                # the shared pages' entries, ours were the last refs and
                # release hands the orphans back — free them, or the
                # pool shrinks a page per failed admission
                back = self.prefix.release(req.prompt[:covered],
                                           np.asarray(shared, np.int32))
                if back.size:
                    self.mgr.free(back)
            return None
        pages = np.concatenate(
            [np.asarray(shared, np.int32), fresh]) if shared else fresh
        self.prefix.publish(req.prompt, pages, n_acquired=len(shared))
        return pages

    def _reserve_window(self, req: Request) -> Optional[np.ndarray]:
        """The request's ring in the window pool: as many pages as its
        whole length needs, at most a slot's ring (None: the pool cannot
        cover them). The pool has a ring a slot, so with a free slot this
        succeeds; the check keeps a smaller pool honest."""
        n = min(self.engine.window_pages_per_slot,
                self.window_mgr.pages_for(req.prompt_len + req.g_max))
        return self.window_mgr.alloc(n)

    def _admit(self) -> bool:
        """Admit queued requests into free slots, up to one prefill batch.
        All-or-nothing page reservation per request (worst case: prompt +
        budget), head-of-line: a request that doesn't fit WAITS — it never
        preempts pages or slots from in-flight requests."""
        if not self.queue:
            return False  # hot path: nothing to admit, skip the slot scan
        tr = self.tracer
        t0 = time.perf_counter()
        with tr.span("serve.admit", "serve") as sp:
            batch = self._admit_batch()
            if tr.enabled:
                # known only now: in the ring and the shard, not in the
                # xplane (an annotation takes its arguments when it opens)
                # (a budget of one has left its slot again by now)
                held = [st for st in (self.slots[slot] for slot, _ in batch)
                        if st is not None]
                sp.args = {"n": len(batch), "prompt_tokens": sum(
                    req.prompt_len for _, req in batch),
                    # pages reserved, by the kind of cache
                    "pages_full": sum(len(st.pages) for st in held),
                    "pages_window": sum(len(st.window_pages) for st in held
                                        if st.window_pages is not None)}
        self.ticks.phase("admit", time.perf_counter() - t0)
        return bool(batch)

    def _admit_batch(self) -> List[tuple]:
        free = [s for s in range(len(self.slots)) if self.slots[s] is None]
        batch: List[tuple] = []
        while (self.queue and free
               and len(batch) < self.engine.prefill_batch):
            req = self.queue[0]
            pages = self._reserve_pages(req)
            if pages is None:
                break  # pool exhausted: wait for completions to free pages
            window_pages = None
            if self.window_mgr is not None:
                window_pages = self._reserve_window(req)
                if window_pages is None:
                    self.mgr.free(pages)      # all or nothing, both kinds
                    break
            slot = free.pop(0)
            self.queue.popleft()
            req.admit_t = time.perf_counter()
            self._book_admitted(req)
            if self.engine.chunked:
                # the slot is held but decodes nothing yet: its table row
                # stays trash (a decode step writes every slot's row)
                # until the last chunk of its prompt is dispatched
                self.slots[slot] = _SlotState(req=req, pages=pages,
                                              window_pages=window_pages)
                self._prefilling.append(slot)
                batch.append((slot, req))
                continue
            self.block_tables[slot, :] = TRASH_PAGE
            self.block_tables[slot, :len(pages)] = pages
            self.active[slot] = 1
            self.slots[slot] = _SlotState(req=req, pages=pages,
                                          position=req.prompt_len)
            self._dirty = True
            batch.append((slot, req))
        if not batch or self.engine.chunked:
            return batch
        bp, lp = self.engine.prefill_batch, self.engine.max_prompt_len
        ids = np.zeros((bp, lp), np.int32)
        lens = np.zeros((bp,), np.int32)
        smap = np.full((bp,), -1, np.int32)
        stables = np.zeros((bp, self.engine.pages_per_slot), np.int32)
        for i, (slot, req) in enumerate(batch):
            ids[i, :req.prompt_len] = req.prompt
            lens[i] = req.prompt_len
            smap[i] = slot
            stables[i] = self.block_tables[slot]
        self.ticks.dispatched(_PREFILL, self._newest())
        with self.tracer.span("serve.prefill_dispatch", "serve"):
            toks = self.engine.prefill(ids, lens, smap, stables)
        if self._draft_engine is not None:
            # mirror the admission into the draft pool (its own static
            # tables); the draft's first-token pick is irrelevant — every
            # spec round pushes the authoritative host state first
            dstables = np.zeros_like(stables)
            for i, (slot, _) in enumerate(batch):
                dstables[i] = self._draft_tables[slot]
            self._draft_engine.prefill(ids, lens, smap, dstables)
        self.prefill_steps += 1
        # padding accounting: actual prompt tokens vs the padded
        # [prefill_batch, max_prompt_len] shape the executable ran at
        self.prompt_tokens_prefilled += int(lens.sum())
        self.prefill_token_slots += bp * lp
        self._ring.append((toks, list(batch), "prefill"))
        # a budget-1 request is already complete at dispatch level
        for slot, _ in batch:
            st = self.slots[slot]
            if st is not None and st.generated >= st.req.g_max:
                self._release(slot)
        return batch

    def _prefill_chunk(self) -> None:
        """Dispatch the next chunk of the oldest prompt still being
        written. Its last chunk picks the first token on the device and
        the slot joins this tick's decode step."""
        slot = self._prefilling[0]
        st = self.slots[slot]
        req, size = st.req, self.engine.prefill_chunk
        start = st.position
        n_valid = min(size, req.prompt_len - start)
        is_last = start + n_valid >= req.prompt_len
        ids = np.zeros((size,), np.int32)
        ids[:n_valid] = req.prompt[start:start + n_valid]
        table_row = np.zeros((self.engine.pages_per_slot,), np.int32)
        table_row[:len(st.pages)] = st.pages
        window_row = None
        if st.window_pages is not None:
            window_row = np.zeros((self.engine.window_pages_per_slot,),
                                  np.int32)
            window_row[:len(st.window_pages)] = st.window_pages
        tr = self.tracer
        self.ticks.dispatched(_CHUNK, self._newest())
        t0 = time.perf_counter()
        with tr.span("serve.prefill_chunk", "serve", args={
                "tokens": n_valid, "slot": slot} if tr.enabled else None):
            out = self.engine.prefill_one_chunk(
                ids, start, n_valid, slot, table_row, is_last, window_row)
        self.ticks.phase("prefill_chunk", time.perf_counter() - t0)
        self.prefill_steps += 1
        self.prompt_tokens_prefilled += n_valid
        self.prefill_token_slots += size
        st.position = start + n_valid
        self._ring.append((out, [(slot, req)] if is_last else [], "prefill"))
        if not is_last:
            return
        self._prefilling.popleft()
        if st.generated >= req.g_max:
            self._release(slot)        # a budget of one: done at dispatch
            return
        self.block_tables[slot, :] = TRASH_PAGE
        self.block_tables[slot, :len(st.pages)] = st.pages
        if window_row is not None:
            self.window_tables[slot] = window_row
        self.active[slot] = 1
        self._dirty = True

    def step(self) -> bool:
        """One scheduler tick: sweep EOS completions -> admit -> dispatch
        decode -> lagged fetch. Returns False when nothing advanced (idle:
        no queue, no active slots, no pending fetches). Under sanitize the
        tick runs inside the evidence watcher: the engine's own transfer
        guard still raises on an implicit transfer, but the trip's site
        lands in the report on the way out."""
        tr = self.tracer
        on = tr.enabled
        queued, active = len(self.queue), int(np.count_nonzero(self.active))
        self.ticks.begin(queued, active, len(self._ring),
                         self._recompiles.count, on)
        try:
            with (self.sanitize_report.watch() if self.sanitize
                  else contextlib.nullcontext()), \
                tr.span("serve.step", "serve", args={
                    "queued": queued, "active": active} if on else None):
                return self._step_inner()
        finally:
            self.ticks.end()

    def _newest(self) -> Any:
        """The newest result still in flight (None: nothing is): what the
        account asks ``is_ready()`` of before a dispatch."""
        return self._ring[-1][0] if self._ring else None

    def _sweep(self) -> None:
        """EOS sweep: requests finished by content (observed at fetch, one
        step late) release their slot before new work is admitted. Only
        when a fetch actually flagged one — count-based completions
        release inline at dispatch time."""
        if not self._needs_sweep:
            return
        t0 = time.perf_counter()
        with self.tracer.span("serve.sweep", "serve"):
            for slot, st in enumerate(self.slots):
                if st is not None and st.req.finished:
                    self._release(slot)
            self._needs_sweep = False
        self.ticks.phase("sweep", time.perf_counter() - t0)

    def _step_inner(self) -> bool:
        self._sweep()
        # admit until the queue, the free slots, or the page pool runs out
        # (several prefill batches per tick when a burst arrives): decode
        # windows then run at full occupancy instead of ramping one
        # prefill batch per window
        dispatched = False
        while self._admit():
            dispatched = True
        if self._prefilling:
            self._prefill_chunk()
            dispatched = True
        if self.spec_tokens > 0:
            # speculative path: synchronous rounds (the verify result IS
            # next round's input), so drain the prefill ring first — the
            # round needs every slot's current token host-side — and
            # sweep any EOS the fetch flagged before dispatching
            if self._ring:
                self._fetch(0)
            self._sweep()
            if self.active.any():
                with self.tracer.span("serve.spec_round", "serve"):
                    self._spec_round()
                dispatched = True
            if self.sanitize and self._recompiles_at_first_token is None \
                    and self.tokens_fetched > 0:
                self._recompiles_at_first_token = self._recompiles.count
            return dispatched
        if self.active.any():
            self.ticks.dispatched(_DECODE, self._newest())
            t0 = time.perf_counter()
            with self.tracer.span("serve.decode_dispatch", "serve"):
                if self._dirty:
                    self.engine.set_block_tables(
                        self.block_tables, self.window_tables
                        if self.window_mgr is not None else None)
                    self.engine.set_active(self.active)
                    self._dirty = False
                snap = [(s, st.req) for s, st in enumerate(self.slots)
                        if st is not None and self.active[s]]
                toks = self.engine.decode()
            self.ticks.phase("decode_dispatch", time.perf_counter() - t0)
            span = self.engine.decode_span
            self.decode_steps += 1
            # occupancy accounting: active vs compiled slot-steps this
            # dispatch (inactive slots run anyway, writing to trash —
            # the decode-side padding waste)
            self.slot_steps_active += int(self.active.sum()) * span
            self.slot_steps_total += len(self.slots) * span
            self._ring.append((toks, snap, "decode"))
            for s, _ in snap:
                st = self.slots[s]
                # mirrors advance by the full span (the device does,
                # unconditionally, while the slot is active); a budget hit
                # mid-span overshoots harmlessly — see DecodeEngine
                st.generated += span
                st.position += span
                if st.generated >= st.req.g_max:  # budget spent:
                    self._release(s)          # completion, no fetch needed
            dispatched = True
        # Lagged on busy ticks (the overlap); full drain on idle ticks —
        # with nothing left to dispatch there is no step to hide the
        # fetch behind, and drain() must be able to terminate.
        self._fetch(self.dispatch_lag if dispatched else 0)
        if self.sanitize and self._recompiles_at_first_token is None \
                and self.tokens_fetched > 0:
            # serving steady-state boundary: everything compiled so far
            # was warmup; growth beyond this snapshot is a violation
            self._recompiles_at_first_token = self._recompiles.count
        return dispatched or bool(self._ring)

    def _spec_round(self) -> None:
        """One speculative round: propose K -> verify in one dispatch ->
        walk acceptance -> roll back host mirrors. Page/slot bookkeeping
        is untouched relative to the sequential path: pages were reserved
        worst-case at admission, rejected links only wrote rows past the
        live position inside those reserved pages (or trash), and the
        rolled-back position masks them until they are overwritten — the
        decode-span overshoot contract, so no leak is possible (tested:
        tests/test_spec_decode.py)."""
        if self._dirty:
            self.engine.set_block_tables(self.block_tables)
            self.engine.set_active(self.active)
            if self._draft_engine is not None:
                self._draft_engine.set_active(self.active)
            self._dirty = False
        S = len(self.slots)
        K = self.spec_tokens
        cur_tok = np.zeros((S,), np.int32)
        cur_pos = np.zeros((S,), np.int32)
        snap: List[tuple] = []
        for s, st in enumerate(self.slots):
            if st is None or not self.active[s]:
                continue
            cur_tok[s] = st.req.tokens[-1]   # last fetched = current state
            cur_pos[s] = st.position
            snap.append((s, st))
        draft = np.zeros((K, S), np.int32)
        if self._draft_engine is not None:
            # chain K greedy draft steps: the draft engine feeds its own
            # picks (decode_fn advances its state), exactly the chain the
            # target will verify
            self._draft_engine.set_decode_state(cur_tok, cur_pos)
            handles = [self._draft_engine.decode() for _ in range(K)]
            t0 = time.perf_counter()
            with self.tracer.span("serve.fetch_wait", "serve"):
                for j, h in enumerate(handles):
                    draft[j] = np.asarray(jax.device_get(h))
            self.ticks.phase("fetch_wait", time.perf_counter() - t0)
        else:
            for s, st in snap:
                hist = np.concatenate(
                    [st.req.prompt, np.asarray(st.req.tokens, np.int32)])
                draft[:, s] = ngram_propose(hist, K)
        # (a round is synchronous: nothing is in flight, every one is dry)
        self.ticks.dispatched(_SPEC, self._newest())
        verified = self.engine.verify(draft, cur_tok, cur_pos)
        t0 = time.perf_counter()
        with self.tracer.span("serve.fetch_wait", "serve"):
            seq = np.asarray(jax.device_get(verified))
        now = time.perf_counter()
        self.ticks.phase("fetch_wait", now - t0)
        self.decode_steps += 1
        self.spec_rounds += 1
        self.slot_steps_active += len(snap) * (K + 1)
        self.slot_steps_total += S * (K + 1)
        for s, st in snap:
            req = st.req
            kept = 0
            matched = 0
            for j in range(K + 1):
                tok = int(seq[j, s])
                # row j is valid only while every earlier draft link
                # matched; the walk below never reaches an invalid row
                req.tokens.append(tok)
                self.tokens_fetched += 1
                kept += 1
                if (req.eos_id is not None and tok == req.eos_id) \
                        or len(req.tokens) >= req.g_max:
                    # EOS inside an accepted prefix wins over the draft
                    self._finish(req, now)
                    break
                if j < K and int(draft[j, s]) == tok:
                    matched += 1
                    continue
                break                        # first mismatch: reject suffix
            st.generated += kept
            st.position += kept
            self.draft_proposed += K
            self.draft_accepted += matched
            if req.finished:
                self._release(s)

    def _fetch(self, lag: int) -> None:
        """Drain the fetch ring down to ``lag`` entries, attributing each
        fetched token vector to its snapshot's requests. The device_get here
        is the only host<->device sync in the loop — and it blocks on step
        N-lag while step N executes (the PR 5 overlap)."""
        if len(self._ring) <= lag:
            return
        tr = self.tracer
        fetched0 = self.tokens_fetched
        n_counted = self.engine.n_counters
        counted0 = ({k: dict(v) for k, v in self.counted.items()}
                    if n_counted and tr.enabled else None)
        t_in = time.perf_counter()
        waited = 0.0
        with tr.span("serve.fetch", "serve") as sp:
            while len(self._ring) > lag:
                toks_dev, snap, program = self._ring.popleft()
                t0 = time.perf_counter()
                with tr.span("serve.fetch_wait", "serve"):
                    # the host WAITING for the device, and nothing else
                    arr = np.asarray(jax.device_get(toks_dev))
                waited += time.perf_counter() - t0
                rows = arr if arr.ndim == 2 else arr[None]  # [span|1, S]
                if n_counted:
                    # the program's counters came with its tokens
                    group = self.counted[program]
                    for name, v in zip(group, rows[:, -n_counted:].sum(0)):
                        group[name] += int(v)
                now = time.perf_counter()
                for slot, req in snap:
                    if req.finished:
                        continue
                    for row in rows:
                        tok = int(row[slot])
                        req.tokens.append(tok)
                        self.tokens_fetched += 1
                        if req.ttft_s is None:
                            self._book_first_token(req, now)
                        if req.eos_id is not None and tok == req.eos_id:
                            self._finish(req, now)
                            self._needs_sweep = True  # slot may still be held
                        elif len(req.tokens) >= req.g_max:
                            self._finish(req, now)  # overshoot rows discarded
                        if req.finished:
                            break
            if tr.enabled:
                sp.args = {"n_tokens": self.tokens_fetched - fetched0}
                if counted0 is not None:
                    sp.args.update({
                        program: {k: v - counted0[program][k]
                                  for k, v in group.items()}
                        for program, group in self.counted.items()})
        self.ticks.phase("fetch_wait", waited)
        self.ticks.phase("fetch_host", time.perf_counter() - t_in - waited)

    def drain(self) -> None:
        """Run until every submitted request has completed and every token
        has been fetched. Bounded by construction: admitted requests hold
        reserved pages, so each completes in ``g_max`` steps, freeing
        capacity for the queue."""
        while self.busy:
            if not self.step():
                break
        self._fetch(0)


def one_shot_decode(workload, params, ids: np.ndarray, prompt_len: int, *,
                    temperature: float = 0.0, top_k: int = 0,
                    top_p: float = 0.0, rng: Optional[jax.Array] = None,
                    seed: int = 0, page_size: int = 0, mesh=None,
                    server: Optional[DecodeServer] = None) -> np.ndarray:
    """Batch continuation through the SERVING path: the same prefill/decode
    executables that serve traffic, driven as one lockstep batch — one code
    path for one-shot (run/sample.py) and served decode.

    ``ids`` int [B, L]: positions < ``prompt_len`` are the prompts; the
    suffix is regenerated out to L. Greedy output is token-for-token
    identical to ``models.sampling.gpt2_decode`` (tested); stochastic
    decoding folds the key per slot position (the serving convention).
    Pass ``server`` to reuse compiled executables across calls (the
    engine's state fully recycles between drained batches); by default one
    is built with a single page per slot (``page_size = L``)."""
    ids = np.ascontiguousarray(ids, np.int32)
    b, l = ids.shape
    if not 1 <= prompt_len < l:
        if prompt_len == l:
            return ids.copy()
        raise ValueError(f"prompt_len {prompt_len} outside [1, {l}]")
    if server is None:
        # max_prompt_len = L: the prefill runs at the same padded length as
        # gpt2_decode's full-ids prefill, so the masked-softmax reductions
        # have identical shapes and greedy outputs match token for token
        server = DecodeServer(
            workload, params, decode_slots=b, page_size=page_size or l,
            max_prompt_len=l, max_len=l, prefill_batch=b,
            temperature=temperature, top_k=top_k, top_p=top_p,
            rng=rng, seed=seed, mesh=mesh)
    elif rng is not None:
        server.set_rng(rng)
    reqs = [server.submit(ids[i, :prompt_len],
                          max_new_tokens=l - prompt_len) for i in range(b)]
    server.drain()
    out = ids.copy()
    for i, req in enumerate(reqs):
        gen = np.asarray(req.tokens, np.int32)
        out[i, prompt_len:prompt_len + len(gen)] = gen
    return out
