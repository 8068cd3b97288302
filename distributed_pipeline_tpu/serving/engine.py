"""DecodeEngine: the prefill/decode phase split as two AOT executables.

``models/sampling.py::gpt2_decode`` is one monolithic jit: prefill and the
whole generation fori_loop compile together, the loop runs in lockstep for
the batch, and a new prompt means a new full trace. Serving wants the two
phases APART (the standard TPU serving recipe — PAPERS: "Fine-Tuning and
Serving Gemma 4 31B on Google Cloud TPU"):

* ``prefill``     — one causal forward over a fixed-shape prompt batch that
  writes the prompts' K/V into the paged pool, picks each request's first
  token, and merges it into the decode state at the requests' target slots;
* ``decode_step`` — ONE token for every decode slot: per-slot positions
  (each slot at its own depth), paged attention over each slot's live
  prefix, in-executable sampling, functional state out.

Both are compiled exactly once via the same ``lower()/compile()`` machinery
the trainer uses (utils/perf.AOTStep, PR 3) with pinned ``out_shardings``
(under a mesh) so no hidden step-2 recompile can sneak in —
``compile_time_s`` is surfaced per executable and the sanitizer's
``recompile_count`` stays 0 across a served run. State (paged KV pool,
token/position vectors) is a functional chain: each call consumes the
previous call's outputs, the big cache buffer is donated, and the host only
ever touches state through explicit ``device_put``/``device_get`` — so the
whole engine runs clean under ``jax.transfer_guard("disallow")``.

The scheduler (serving/scheduler.py) drives this engine; a fused
flash-decode Pallas kernel later replaces the gather inside
``decode_step`` without touching this seam (ROADMAP item 4).
"""

from __future__ import annotations

import contextlib
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..models.sampling import _truncate_logits
from ..obs import trace as trace_lib
from ..parallel.sharding import replicated
from ..utils.perf import AOTStep, tree_bytes

__all__ = ["DecodeEngine"]

# Positions one prefill dispatch of the flax-backbone family computes when the
# caller names no ``prefill_batch``: the rows are this budget over
# ``max_prompt_len`` (at least one, at most min(decode_slots, 8)). A row of
# 512 positions is already compute-bound for every GPT-2 preset, so further
# rows buy no rate and a dummy row costs a whole row's time. Read on the v5e,
# GPT-2-large, a closed loop of 16 clients that brings 1.1 requests an
# admission (PERF.md section 6, PR 32): [1, 512] 11.0 ms a dispatch and
# 2,163 tokens/s, [2, 512] 22.0 ms and 1,877, [8, 512] 66.4 ms and 1,166.
PREFILL_TOKENS = 512


def _slot_picker(temperature: float, top_k: int, top_p: float):
    """Per-slot token picker ``(logits [*, V], positions [*], slots [*],
    rng) -> int32 [*]``. Greedy at temperature <= 0; otherwise categorical
    with the SAME truncation as the batch decoder (models/sampling.py) and
    the key folded per (slot, position) — position alone would hand every
    slot at the same depth the identical Gumbel noise, making duplicate
    prompts decode identical "samples". Prefill rows fold by their TARGET
    slot, so a request's sampling stream is consistent from its first
    token through every decode step in that slot."""
    if temperature <= 0.0:
        return lambda logits, pos, slots, rng: jnp.argmax(
            logits, axis=-1).astype(jnp.int32)

    def pick(logits: jnp.ndarray, pos: jnp.ndarray, slots: jnp.ndarray,
             rng: jax.Array) -> jnp.ndarray:
        l = _truncate_logits(logits.astype(jnp.float32) / temperature,
                             top_k, top_p)
        keys = jax.vmap(lambda s, p: jax.random.fold_in(
            jax.random.fold_in(rng, s), p))(slots, pos)
        return jax.vmap(jax.random.categorical)(keys, l).astype(jnp.int32)

    return pick


def _is_window(path) -> bool:
    """A window layer's ring pool in a chunked-prefill model's cache:
    ``cache_shapes`` names that leaf "window"."""
    return getattr(path[-1], "key", None) == "window"


class DecodeEngine:
    """Device half of the serving stack: paged-cache decode state plus the
    two AOT executables that advance it.

    Parameters
    ----------
    workload, params : the model and its parameter tree, as training left
        it or as a server already holds it. The engine keeps the model's
        SERVING FORM of the tree (``model.serving_variables``, made once
        here and at each :meth:`set_params`; ``self.params``), and every
        executable compiles against and is called with that: of the flax
        causal LM the block matrices and the tied head's table in the
        compute dtype — the cast each use makes inside a program, made
        once, so a decode step reads half the bytes of float32 masters and
        logits are bit for bit the same — beside float32 embeddings and
        LayerNorm leaves. A leaf that already has its dtype is the
        caller's own array (never copied; its sharding is what the
        executables compile against), and a model that declares no
        serving form is held untouched. ``weights`` says what was done:
        ``bytes_in``, ``bytes_serving``, ``leaves_cast``. Either the
        named-blocks flax causal
        LM, whose backbone holds the paged K/V branch, or a model that
        brings its own paged-cache functions (``chunked_prefill``,
        ``cache_shapes``, ``prefill_chunk``, ``decode_step``:
        models/deepseek_v32.py); for the latter the prefill executable
        takes ONE chunk of one prompt (``prefill_chunk`` tokens) and the
        scheduler walks a prompt chunk by chunk, a chunk a tick. Such a
        model may also say that some of its layers keep only a window of
        rows (``window_rows(chunk)`` > 0): those layers' rows live in a
        second pool where every slot owns a RING of
        ``window_pages_per_slot`` pages, addressed through a second block
        table (``set_block_tables(table, window)``).
    decode_slots : compiled decode batch size S. Decode ALWAYS runs at S
        (inactive slots write to the trash page and their outputs are
        ignored) — the executable never re-specializes to occupancy.
    page_size, max_pages : paged KV pool geometry, per layer.
    max_prompt_len : compiled prefill length (prompts pad up to it).
    max_len : longest prompt+generation a slot can hold (caps the block
        table width; <= the model's trained seq_len for position bounds).
    prefill_batch : compiled prefill batch size (queued prompts batch
        opportunistically up to it; short admissions pad with dummy rows).
        0 = the engine's own token budget: ``PREFILL_TOKENS //
        max_prompt_len`` rows, at least one and at most
        ``min(decode_slots, 8)`` — ONE row at a ``max_prompt_len`` of 512,
        eight at 64. A burst is admitted by several dispatches in one tick.
    decode_span : tokens generated per decode DISPATCH (a lax.scan of
        decode steps inside the executable, token chain on device). Host
        dispatch cost amortizes over span tokens — the lever when steps
        are sub-millisecond and the host loop is the bottleneck. Slots
        whose budget ends mid-span overshoot harmlessly (writes stay in
        their own reserved pages or the trash page; outputs past budget
        are discarded at fetch) at the cost of up to span-1 wasted
        slot-steps, and admission happens at span granularity.
    """

    def __init__(self, workload, params, *, decode_slots: int,
                 page_size: int, max_pages: int, max_prompt_len: int,
                 max_len: int = 0, prefill_batch: int = 0,
                 decode_span: int = 1,
                 temperature: float = 0.0, top_k: int = 0, top_p: float = 0.0,
                 rng: Optional[jax.Array] = None, seed: int = 0,
                 mesh=None, transfer_guard: bool = False,
                 decode_impl: str = "auto", kv_quant: str = "fp",
                 spec_tokens: int = 0,
                 on_compile: Optional[Callable[[str, float], None]] = None,
                 tracer: Any = None):
        model = workload.model
        # by what the model can do, not by its family's name: either it
        # brings its own paged-cache functions and a chunked prefill
        # (models/deepseek_v32.py), or it is the flax causal LM whose
        # backbone holds the paged K/V branch
        self.chunked = bool(getattr(model, "chunked_prefill", False))
        if not self.chunked and not hasattr(model, "paged_pages"):
            raise ValueError(
                f"DecodeEngine needs a causal LM with a paged cache (a "
                f"`paged_pages` field or `chunked_prefill`); the "
                f"{workload.family!r} family's model has neither")
        if self.chunked and (spec_tokens > 0 or kv_quant != "fp"):
            raise NotImplementedError(
                "speculative verify and the int8 pool are the flax "
                "backbone's; a chunked-prefill model has neither yet")
        if getattr(model, "scan_layers", False):
            raise NotImplementedError(
                "paged decode needs per-layer named blocks; scan_layers "
                "models decode through models/sampling.py::gpt2_decode")
        max_len = max_len or workload.seq_len
        if not 1 <= max_len <= workload.seq_len:
            raise ValueError(f"max_len {max_len} must be in [1, seq_len="
                             f"{workload.seq_len}] (position table bound)")
        if not 2 <= max_prompt_len <= max_len:
            # >= 2: a length-1 prefill is shape-ambiguous with a decode step
            raise ValueError(f"max_prompt_len {max_prompt_len} must be in "
                             f"[2, max_len={max_len}]")
        self.decode_slots = decode_slots
        self.page_size = page_size
        self.max_pages = max_pages
        self.max_prompt_len = max_prompt_len
        self.max_len = max_len
        self.pages_per_slot = -(-max_len // page_size)
        self.prefill_batch = prefill_batch or min(
            decode_slots, 8, max(1, PREFILL_TOKENS // max_prompt_len))
        # chunked prefill: one chunk of ONE prompt a dispatch; the length
        # is the engine's own (a prompt of max_prompt_len in at most 16,
        # 1024 at most: at 2048 the prompts' last chunks were 15 % padding
        # and a token took as long, PERF.md PR 29)
        self.prefill_chunk = min(max_prompt_len, max(page_size, min(
            1024, -(-max_prompt_len // 16)))) if self.chunked else 0
        # a model whose window layers keep `window_rows` rows a slot,
        # whatever the slot's length: a ring of pages a slot in a pool of
        # full residency beside the paged pool (never more than a slot's
        # whole length)
        window_rows = (model.window_rows(self.prefill_chunk)
                       if hasattr(model, "window_rows") else 0)
        self.window_pages_per_slot = min(
            self.pages_per_slot, -(-window_rows // page_size))
        self.max_window_pages = 1 + decode_slots * self.window_pages_per_slot
        # int32 counters a program returns behind its tokens (one fetch)
        self.n_counters = len(getattr(model, "counters", ()))
        if decode_span < 1:
            raise ValueError(f"decode_span must be >= 1, got {decode_span}")
        self.decode_span = decode_span
        if spec_tokens < 0:
            raise ValueError(f"spec_tokens must be >= 0, got {spec_tokens}")
        self.spec_tokens = spec_tokens
        if kv_quant not in ("fp", "int8"):
            raise ValueError(f"kv_quant must be fp|int8, got {kv_quant!r}")
        self.kv_quant = kv_quant
        if max_pages < 2:
            raise ValueError(f"max_pages must be >= 2 (page 0 is the trash "
                             f"page), got {max_pages}")
        self.mesh = mesh
        self._guard = transfer_guard
        self._serving_form = getattr(model, "serving_variables", None)
        self._tracer = tracer if tracer is not None else trace_lib.FOLLOW
        self.set_params(params)
        self.compile_time_s = 0.0
        self.compile_times: dict = {}  # per program: serve_prefill, ...
        self._on_compile = on_compile

        s = decode_slots
        bp = self.prefill_batch
        pick = _slot_picker(temperature, top_k, top_p)
        if self.chunked:
            dm = None

            def slot_logits(p, cache, tokens, positions, tables, active):
                cache, logits, counted, _ = model.decode_step(
                    p["params"], cache, tokens, positions, tables[0],
                    active, *tables[1:])
                return logits, cache, counted
        else:
            # decode=True + paged_pages selects the paged attention branch;
            # inference never drops MoE tokens (models/sampling.py
            # rationale); decode_impl picks the decode-step attention
            # kernel behind the ROADMAP-reserved seam (ops/flash_decode.py
            # dispatch rules)
            dm = model.clone(decode=True, moe_no_drop=True,
                             paged_pages=max_pages, page_size=page_size,
                             decode_impl=decode_impl, kv_quant=kv_quant)

            def slot_logits(p, cache, tokens, positions, block_table,
                            active):
                del active
                logits, mvars = dm.apply({**p, "cache": cache},
                                         tokens[:, None], None,
                                         cache_index=positions,
                                         block_table=block_table,
                                         mutable=["cache"])
                return logits, mvars["cache"], None

        def prefill_fn(p, cache, ids, prompt_lens, slot_map, slot_tables,
                       tokens, positions, key):
            """ids [Bp, Lp] zero-padded prompts; slot_map [Bp] target decode
            slot (-1 = dummy padding row); slot_tables [Bp, pages_per_slot]
            the target slots' block-table rows (all-trash for dummies).
            Writes prompt K/V into the pool, picks each request's first
            token (position = prompt_len, same fold convention as
            gpt2_decode), and scatters token/position into the decode state
            at the target slots (dummy rows drop)."""
            pad = (jnp.arange(ids.shape[1])[None, :]
                   < prompt_lens[:, None]).astype(jnp.int32)
            logits, mvars = dm.apply({**p, "cache": cache}, ids, pad,
                                     block_table=slot_tables,
                                     mutable=["cache"])
            last_idx = jnp.maximum(prompt_lens - 1, 0)
            last = jnp.take_along_axis(
                logits, last_idx[:, None, None], axis=1)[:, 0]   # [Bp, V]
            # fold by target slot (dummies clamp to 0: picked then dropped)
            first = pick(last, prompt_lens, jnp.maximum(slot_map, 0), key)
            safe = jnp.where(slot_map >= 0, slot_map, s)  # s = out of bounds
            tokens = tokens.at[safe].set(first.astype(tokens.dtype),
                                         mode="drop")
            positions = positions.at[safe].set(prompt_lens, mode="drop")
            return mvars["cache"], tokens, positions

        def prefill_chunk_fn(p, cache, ids, meta, table_rows, tokens,
                             positions, key):
            """One chunk of one prompt: ``ids`` [C] (zero-padded), ``meta``
            = (first position, valid tokens, target slot, 1 on the
            prompt's last chunk), ``table_rows`` the slot's pages (and,
            behind them, its ring in the window pool). Writes
            the chunk's cache rows; on the last chunk picks the request's
            first token and merges token/position into the decode state at
            the slot (same fold as above). Returns the state and, for the
            one fetch, the tokens with the chunk's counters behind them."""
            start, n_valid, slot, is_last = meta[0], meta[1], meta[2], meta[3]
            cache, logits, counted = model.prefill_chunk(
                p["params"], cache, ids, start, n_valid, *table_rows)
            prompt_len = start + n_valid
            first = pick(logits[None], prompt_len[None], slot[None], key)[0]
            safe = jnp.where(is_last > 0, slot, s)        # s = out of bounds
            tokens = tokens.at[safe].set(first.astype(tokens.dtype),
                                         mode="drop")
            positions = positions.at[safe].set(prompt_len, mode="drop")
            return (cache, tokens, positions,
                    jnp.concatenate([tokens, counted]))

        def decode_fn(p, cache, tokens, positions, block_table, active, key):
            """``decode_span`` tokens for every slot: each inner step feeds
            each slot's current token at its own position, writes its K/V
            page entry, attends over its live prefix, and samples the next
            token (folded at the position it will occupy). Inactive slots
            write to trash and keep their state frozen. Returns the new
            state plus the picked tokens — [S] at span 1, [span, S] above
            (the scheduler's fetch attributes rows in order); a model that
            counts appends its counters to each row."""

            slot_ids = jnp.arange(s, dtype=jnp.int32)

            def one(cache, tokens, positions):
                logits, cache, counted = slot_logits(
                    p, cache, tokens, positions, block_table, active)
                nxt_pos = positions + 1
                nxt = pick(logits if logits.ndim == 2 else logits[:, 0],
                           nxt_pos, slot_ids, key)
                tokens = jnp.where(active > 0, nxt.astype(tokens.dtype),
                                   tokens)
                positions = jnp.where(active > 0, nxt_pos, positions)
                out = tokens if counted is None else jnp.concatenate(
                    [tokens, counted])
                return cache, tokens, positions, out

            if decode_span == 1:
                return one(cache, tokens, positions)

            def body(carry, _):
                c, t, q, out = one(*carry)
                return (c, t, q), out

            (cache, tokens, positions), seq = jax.lax.scan(
                body, (cache, tokens, positions), None, length=decode_span)
            return cache, tokens, positions, seq

        def verify_fn(p, cache, draft, tokens, positions, block_table,
                      active, key):
            """Speculative verify: ONE forward runs the whole chain
            ``[current, draft_1..draft_K]`` as a length-(K+1) span through
            the model (backbone span branch) and returns the target's pick
            at every link — [K+1, S]. Every link's K/V is written at its
            own position before the B*(K+1) pseudo-slot attention reads
            the live prefix plus the earlier links — the same rows a
            sequential K+1-step replay would read, at the op count of ONE
            decode step (this is speculative decoding's wall-clock win;
            the earlier lax.scan formulation cost K+1 sequential model
            applies and could never beat its non-speculative twin on an
            op-bound backend). Row j's pick folds per (slot, position)
            exactly like decode_fn, so the accepted stream is
            token-identical to the non-speculative path, greedy or
            sampled (scheduler acceptance walk). Rejected links' writes
            land past the live position in the slot's own reserved pages
            (the decode-span overshoot contract); budget-final overshoot
            past the position table clamps to the last addressable cell
            inside the span writers (serving/paged_kv.py) rather than
            wrapping into a live lower cell — clamped picks are always
            past-budget and discarded by the host walk. State vectors are
            NOT threaded back: the host owns rollback and pushes (token,
            position) before every round (set_decode_state); inactive
            slots' picks are garbage the scheduler never attributes."""
            del active  # state is host-pushed; dead rows discard at fetch
            kp1 = spec_tokens + 1
            chain = jnp.concatenate(
                [tokens[:, None], draft.T.astype(tokens.dtype)], axis=1)
            logits, mvars = dm.apply({**p, "cache": cache}, chain, None,
                                     cache_index=positions,
                                     block_table=block_table,
                                     mutable=["cache"])
            # one flattened pick over all S*(K+1) rows: the fold is still
            # per (slot, position), so each row picks exactly what the
            # sequential path would at that coordinate
            pos_f = (positions[:, None] + 1
                     + jnp.arange(kp1, dtype=jnp.int32)[None, :])
            slot_f = jnp.broadcast_to(
                jnp.arange(s, dtype=jnp.int32)[:, None], (s, kp1))
            seq = pick(logits.reshape(s * kp1, -1), pos_f.reshape(-1),
                       slot_f.reshape(-1), key).reshape(s, kp1).T
            return mvars["cache"], seq

        # Cache structure WITHOUT compiling an init variant: eval_shape the
        # first-call (variable-creating) apply, then zero-fill. Every real
        # prefill/decode then shares one with-cache signature.
        if self.chunked:
            cache_abs = model.cache_shapes(max_pages, page_size,
                                           self.max_window_pages)
        else:
            ids0 = jax.ShapeDtypeStruct((bp, max_prompt_len), jnp.int32)
            pad0 = jax.ShapeDtypeStruct((bp, max_prompt_len), jnp.int32)
            bt0 = jax.ShapeDtypeStruct((bp, self.pages_per_slot), jnp.int32)
            cache_abs = jax.eval_shape(
                lambda p, i, m, bt: dm.apply(p, i, m, block_table=bt,
                                             mutable=["cache"])[1]["cache"],
                self.params, ids0, pad0, bt0)

        okw_p: dict = {}
        okw_d: dict = {}
        if mesh is not None:
            # Pinned output shardings: the functional state keeps ONE layout
            # across every call, so the AOT executables can never meet a
            # drifted input sharding (the step-2-recompile class the trainer
            # kills the same way). Replicated state is the correctness-first
            # baseline; a TP pages layout rides the flash-decode kernel
            # later (ROADMAP item 4).
            rep = replicated(mesh)
            cache_rep = jax.tree_util.tree_map(lambda _: rep, cache_abs)
            okw_p["out_shardings"] = (cache_rep, rep, rep) + (
                (rep,) if self.chunked else ())
            okw_d["out_shardings"] = (cache_rep, rep, rep, rep)
        # pin_signature: every arg shape is fixed by construction (slots,
        # prefill batch, table width are compiled-in), so the per-call
        # signature walk over the params tree is pure overhead on the
        # one-dispatch-per-token hot path
        self._prefill_step = AOTStep(
            jax.jit(prefill_chunk_fn if self.chunked else prefill_fn,
                    donate_argnums=(1,), **okw_p),
            "serve_prefill", on_compile=self._note_compile,
            pin_signature=True)
        self._decode_step = AOTStep(
            jax.jit(decode_fn, donate_argnums=(1,), **okw_d),
            "serve_decode", on_compile=self._note_compile,
            pin_signature=True)
        self._verify_step = None
        if spec_tokens > 0:
            okw_v: dict = {}
            if mesh is not None:
                rep = replicated(mesh)
                cache_rep = jax.tree_util.tree_map(lambda _: rep, cache_abs)
                okw_v["out_shardings"] = (cache_rep, rep)
            self._verify_step = AOTStep(
                jax.jit(verify_fn, donate_argnums=(1,), **okw_v),
                "serve_verify", on_compile=self._note_compile,
                pin_signature=True)

        # Device state (functional chain; cache is donated through it).
        # Eager construction happens HERE, at wiring time — dispatches later
        # run under the transfer guard, where only explicit puts are legal.
        self.cache = jax.tree_util.tree_map(
            lambda a: jnp.zeros(a.shape, a.dtype), cache_abs)
        self.tokens = self._put(np.zeros((s,), np.int32))
        self.positions = self._put(np.zeros((s,), np.int32))
        self.set_block_tables(
            np.zeros((s, self.pages_per_slot), np.int32),
            np.zeros((s, self.window_pages_per_slot), np.int32)
            if self.window_pages_per_slot else None)
        self._active = self._put(np.zeros((s,), np.int32))
        key = rng if rng is not None else jax.random.PRNGKey(seed)
        self._key = self._put_key(key)
        if mesh is not None:
            rep = replicated(mesh)
            self.cache = jax.device_put(self.cache,
                                        jax.tree_util.tree_map(
                                            lambda _: rep, cache_abs))

    # ------------------------------------------------------------ plumbing

    def executables(self) -> dict:
        """The two AOT step wrappers keyed by phase name — the handles
        the cost ledger (obs/ledger.py) extracts ``cost_analysis()``/
        HLO text from (each wrapper's ``.compiled`` is None until its
        first dispatch builds it)."""
        out = {"prefill": self._prefill_step, "decode": self._decode_step}
        if self._verify_step is not None:
            out["verify"] = self._verify_step
        return out

    def _put(self, x: np.ndarray) -> jax.Array:
        # COPY first: callers hand over host mirrors they keep mutating in
        # place (the scheduler's block tables and active mask: release ->
        # TRASH_PAGE) while earlier dispatches are still in flight, and
        # the CPU backend's device_put may alias a suitably aligned numpy
        # buffer instead of copying it. In-flight steps then read the
        # mutated table: the last tokens of a request went stale,
        # depending on page geometry, dispatch lag and timing.
        x = np.array(x)
        if self.mesh is not None:
            return jax.device_put(x, replicated(self.mesh))
        return jax.device_put(x)

    def _put_key(self, key: jax.Array) -> jax.Array:
        return (jax.device_put(key, replicated(self.mesh))
                if self.mesh is not None else key)

    def _ctx(self):
        if self.mesh is None and not self._guard:
            return contextlib.nullcontext()  # hot path: no ctx machinery
        ctx = contextlib.ExitStack()
        if self.mesh is not None:
            ctx.enter_context(self.mesh)
        if self._guard:
            ctx.enter_context(jax.transfer_guard("disallow"))
        return ctx

    def _note_compile(self, name: str, seconds: float) -> None:
        self.compile_time_s += seconds
        self.compile_times[name] = self.compile_times.get(name, 0.0) + seconds
        if self._on_compile is not None:
            self._on_compile(name, seconds)

    def set_params(self, params) -> None:
        """Hold ``params`` in the model's serving form (class docstring):
        construction and the hot swap come through here, so a swapped
        tree meets the executables' pinned signature with the dtypes the
        first one had. Leaf by leaf and outside any jit: what is right
        already stays the caller's buffer."""
        with self._tracer.span("serve.weights", "serve") as sp:
            held = (params if self._serving_form is None
                    else self._serving_form(params))
            given = {id(x): x for x in jax.tree_util.tree_leaves(params)}
            kept = {id(x): x for x in jax.tree_util.tree_leaves(held)}
            self.weights = {
                "bytes_in": tree_bytes(list(given.values())),
                "bytes_serving": tree_bytes(list(kept.values())),
                "leaves_cast": len(kept.keys() - given.keys())}
            sp.args = dict(self.weights)
            self.params = held

    def set_rng(self, key: jax.Array) -> None:
        """Swap the sampling key (a dispatch ARGUMENT, so no recompile)."""
        self._key = self._put_key(key)

    def set_block_tables(self, table: np.ndarray,
                         window: Optional[np.ndarray] = None) -> None:
        """Refresh the device block-table mirror (admission/free changed
        the host copy). Shape must stay [S, pages_per_slot]; ``window``
        [S, window_pages_per_slot] the slots' rings, of a model with
        window layers. The decode program takes the flax backbone's table
        as an array; of a chunked-prefill model a tuple, the rings behind
        the pages."""
        self._block_table = self._put(np.ascontiguousarray(table, np.int32))
        if self.chunked:
            self._block_table = (self._block_table,) + (
                () if window is None else (
                    self._put(np.ascontiguousarray(window, np.int32)),))

    def set_active(self, active: np.ndarray) -> None:
        self._active = self._put(np.ascontiguousarray(active, np.int32))

    # ---------------------------------------------- page migration (disagg)

    def _pool_leaves(self) -> list:
        """(path-key, leaf) pairs for the paged K/V pool leaves of the
        cache pytree. The pools are the only 3-D
        ``[max_pages, page_size, H * Dh]`` leaves (backbone
        ``_paged_attention`` creates exactly ``pages_k``/``pages_v`` per
        layer; a payload row is one token's heads side by side), and
        ``jax.tree_util.keystr`` names each deterministically
        — a decode engine built from the same model config on ANOTHER
        process derives the same keys, which is what makes the
        extract/ingest wire format stable across a StageLink."""
        flat, _ = jax.tree_util.tree_flatten_with_path(self.cache)
        return [(jax.tree_util.keystr(path), leaf) for path, leaf in flat
                if not _is_window(path) and (
                    (getattr(leaf, "ndim", 0) == 3
                     and leaf.shape[0] == self.max_pages
                     and leaf.shape[1] == self.page_size)
                    # int8 pools: the [P] per-page scale sidecars are page
                    # state too — they ride the same extract/ingest wire
                    or (getattr(leaf, "ndim", 0) == 1
                        and leaf.shape[0] == self.max_pages))]

    def kv_pool_bytes(self) -> int:
        """Device bytes the paged KV pool holds (pages + scale sidecars,
        every layer; the window pool's rings too) — the ledger's page-pool
        gauge: the int8 arm must land at <= 0.55x the fp arm at equal
        geometry (ISSUE 20)."""
        leaves = [leaf for _, leaf in self._pool_leaves()]
        # a window layer's rings (its cache entry's one leaf, "window") do
        # not migrate, so they are no `_pool_leaves`; they are pool all the
        # same
        leaves += [leaf for path, leaf in
                   jax.tree_util.tree_flatten_with_path(self.cache)[0]
                   if _is_window(path)]
        return tree_bytes(leaves)

    def extract_pages(self, page_ids: np.ndarray) -> Dict[str, np.ndarray]:
        """Pull the contents of ``page_ids`` out of every pool leaf as
        host arrays keyed by leaf path — the KV payload a disaggregated
        prefill worker ships to a decode server (mpmd/disagg.py). Page
        ids are POSITIONAL in the result: row i holds page ``page_ids[i]``
        — the receiver scatters the same rows at ITS OWN allocated ids."""
        idx = np.ascontiguousarray(page_ids, np.int32)
        return {key: np.asarray(jax.device_get(leaf[idx]))
                for key, leaf in self._pool_leaves()}

    def ingest_pages(self, page_ids: np.ndarray,
                     pools: Dict[str, np.ndarray]) -> None:
        """Scatter transferred pool pages (an :meth:`extract_pages`
        payload) into this engine's cache at ``page_ids``. Functional
        ``.at[].set`` update: in-flight decode handles keep the array
        version they were dispatched with, same as every other state
        transition here. Raises on a key mismatch — that means the
        prefill and decode engines were built from different models."""
        mine = {key for key, _ in self._pool_leaves()}
        if set(pools) != mine:
            raise ValueError(
                f"pool-leaf mismatch: payload has {sorted(pools)} but this "
                f"engine has {sorted(mine)} (prefill/decode model drift?)")
        idx = jnp.asarray(np.ascontiguousarray(page_ids, np.int32))

        def _scatter(path, leaf):
            key = jax.tree_util.keystr(path)
            if key not in pools:
                return leaf
            return leaf.at[idx].set(jnp.asarray(pools[key], leaf.dtype))

        with self._ctx():
            self.cache = jax.tree_util.tree_map_with_path(_scatter,
                                                          self.cache)

    def set_slot_state(self, slot: int, token: int, position: int) -> None:
        """Seed one slot's decode state by hand — the disaggregated
        admission path's stand-in for the scatter at the tail of the
        prefill executable (the transferred request arrives with its
        first token and position already picked by the prefill worker).
        Host round-trip on purpose: admission is off the decode hot path."""
        toks = np.asarray(jax.device_get(self.tokens)).copy()
        pos = np.asarray(jax.device_get(self.positions)).copy()
        toks[slot] = int(token)
        pos[slot] = int(position)
        self.tokens = self._put(toks)
        self.positions = self._put(pos)

    def set_decode_state(self, tokens: np.ndarray,
                         positions: np.ndarray) -> None:
        """Push the full [S] (token, position) state from host mirrors —
        the speculative scheduler's rollback primitive: after a partial
        rejection the host simply declares the post-acceptance state
        before the next round's dispatch (the device vectors advanced
        through the whole draft inside verify and are never read back)."""
        self.tokens = self._put(np.ascontiguousarray(tokens, np.int32))
        self.positions = self._put(np.ascontiguousarray(positions, np.int32))

    # ------------------------------------------------------------- phases

    def prefill(self, ids: np.ndarray, prompt_lens: np.ndarray,
                slot_map: np.ndarray, slot_tables: np.ndarray) -> jax.Array:
        """Run the prefill executable for one admission batch. Returns the
        post-merge tokens vector (a device handle — NOT donated, so the
        scheduler's lagged fetch can read it later)."""
        with self._ctx():
            self.cache, self.tokens, self.positions = self._prefill_step(
                self.params, self.cache,
                self._put(np.ascontiguousarray(ids, np.int32)),
                self._put(np.ascontiguousarray(prompt_lens, np.int32)),
                self._put(np.ascontiguousarray(slot_map, np.int32)),
                self._put(np.ascontiguousarray(slot_tables, np.int32)),
                self.tokens, self.positions, self._key)
        return self.tokens

    def prefill_one_chunk(self, ids: np.ndarray, start: int, n_valid: int,
                          slot: int, table_row: np.ndarray, is_last: bool,
                          window_row: Optional[np.ndarray] = None
                          ) -> jax.Array:
        """Run the chunked-prefill executable for ONE chunk of one prompt
        (``ids`` [prefill_chunk], zero-padded past ``n_valid``; positions
        ``start ..`` of the request bound for ``slot``; ``window_row`` the
        slot's ring, of a model with window layers). Returns the
        fetchable handle: the post-merge tokens [S] with the chunk's
        counters behind them."""
        rows = (table_row,) if window_row is None else (table_row,
                                                        window_row)
        with self._ctx():
            (self.cache, self.tokens, self.positions,
             out) = self._prefill_step(
                self.params, self.cache,
                self._put(np.ascontiguousarray(ids, np.int32)),
                self._put(np.array([start, n_valid, slot, int(is_last)],
                                   np.int32)),
                tuple(self._put(np.ascontiguousarray(r, np.int32))
                      for r in rows),
                self.tokens, self.positions, self._key)
        return out

    def decode(self) -> jax.Array:
        """Advance every slot by ``decode_span`` token(s) (dispatch only —
        the host does not wait; fetches happen through the returned handle,
        k dispatches behind). Returns the picked-token handle: [S] at
        span 1, [span, S] above."""
        with self._ctx():
            (self.cache, self.tokens, self.positions,
             toks) = self._decode_step(
                self.params, self.cache, self.tokens, self.positions,
                self._block_table, self._active, self._key)
        return toks

    def verify(self, draft: np.ndarray, tokens: Optional[np.ndarray] = None,
               positions: Optional[np.ndarray] = None) -> jax.Array:
        """Speculatively verify a [spec_tokens, S] draft in one dispatch.
        Returns the [spec_tokens + 1, S] target-pick handle; the host
        walks acceptance. ``tokens``/``positions`` [S] declare the round's
        (current token, position) state straight from the host mirrors —
        rollback after a partial rejection is just declaring the
        post-acceptance state here, no separate :meth:`set_decode_state`
        push (the device vectors advanced through the whole prior draft
        inside verify and are never read back). Omitted, the engine's own
        state vectors are used (decode interleave)."""
        if self._verify_step is None:
            raise RuntimeError("engine built with spec_tokens=0")
        with self._ctx():
            self.cache, seq = self._verify_step(
                self.params, self.cache,
                self._put(np.ascontiguousarray(draft, np.int32)),
                self.tokens if tokens is None else self._put(
                    np.ascontiguousarray(tokens, np.int32)),
                self.positions if positions is None else self._put(
                    np.ascontiguousarray(positions, np.int32)),
                self._block_table, self._active, self._key)
        return seq
