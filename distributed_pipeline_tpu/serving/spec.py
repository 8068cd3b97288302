"""Speculative-decode drafting: proposers that guess the next K tokens.

The tentpole split (ISSUE 20 / ROADMAP item 4): the TARGET model verifies a
K-token draft in ONE ``decode_span``-style dispatch (serving/engine.py
``verify``), so every accepted draft token is a target-model step the
scheduler did not have to dispatch. The draft side is pluggable and lives
here; two arms ship:

* ``ngram`` — prompt-lookup decoding (host-side, zero model flops): propose
  the continuation that followed the most recent earlier occurrence of the
  current suffix in ``prompt + generated``. Exact-match repetition —
  retrieval prompts, code, template-y text, and greedy loops — verifies at
  high accept rates; fresh text just verifies 1 token/round like the
  non-speculative path. This is the CPU-friendly draft: its
  speedup is pure dispatch amortization, no second model.
* ``model`` — a truncated-layer draft: the FIRST ``draft_layers`` blocks of
  the target plus its embeddings/ln_f/tied head, run as a second (much
  smaller) DecodeEngine. No training needed, weights are views of the
  target's (early-exit drafting). The scheduler drives it one greedy token
  at a time, K times per round, then hands the chain to the target.

Acceptance semantics live in the SCHEDULER (the standard speculative
contract): the verify dispatch replays the chain ``[current, d_1..d_K]``
through the target's cached decode step, which yields the target's own
pick at every position. Token ``g_0`` is always kept (it is exactly the
non-speculative step's output); ``g_j`` is kept while every earlier draft
token matched (``d_m == g_{m-1}``). Greedy decoding is therefore
TOKEN-IDENTICAL to the non-speculative path by induction; with temperature
the picks reuse the engine's per-(slot, position) fold, so the sampled
stream is identical too — rejection just discards the suffix the device
already wrote into reserved pages (the decode-span overshoot contract:
stale rows sit past the live position, masked until overwritten).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import numpy as np

__all__ = ["ngram_propose", "truncated_draft", "DRAFT_KINDS"]

DRAFT_KINDS = ("ngram", "model")


def ngram_propose(history: np.ndarray, k: int, max_ngram: int = 2
                  ) -> np.ndarray:
    """Prompt-lookup draft: K tokens, from the continuation after the most
    recent EARLIER occurrence of the current suffix (longest ngram first,
    down to the bare current token). No match -> repeat the current token
    (a free guess; wrong costs nothing, greedy loops make it right)."""
    h = np.asarray(history, np.int64).ravel()
    n = h.shape[0]
    out = np.full(k, h[-1] if n else 0, np.int32)
    for ng in range(min(max_ngram, n), 0, -1):
        suffix = h[n - ng:]
        # candidate start positions of an earlier occurrence, latest first
        starts = np.flatnonzero(h[:n - 1] == suffix[0])
        for s in starts[::-1]:
            if s + ng >= n:  # the "earlier" occurrence IS the suffix itself
                continue
            if np.array_equal(h[s:s + ng], suffix):
                cont = h[s + ng:s + ng + k]
                out[:cont.shape[0]] = cont.astype(np.int32)
                if cont.shape[0] < k and cont.shape[0] > 0:
                    out[cont.shape[0]:] = int(cont[-1])
                return out
        # no occurrence at this ngram width: relax to a shorter suffix
    return out


def truncated_draft(workload: Any, params: Any,
                    draft_layers: int) -> Tuple[Any, Any]:
    """Early-exit draft model: the target's first ``draft_layers`` blocks
    with its own embeddings, final LN and tied head — a Workload + params
    pair a second DecodeEngine can run directly. Params are VIEWS of the
    target leaves (no copy): the draft rides hot-swaps for free when the
    caller rebuilds it from the swapped tree."""
    if workload.family != "gpt2":
        raise ValueError(f"truncated_draft needs the gpt2 family, got "
                         f"{workload.family!r}")
    model = workload.model
    if getattr(model, "scan_layers", False):
        raise ValueError("truncated_draft needs named per-layer blocks; "
                         "scan_layers stacks them")
    n = int(draft_layers)
    if not 1 <= n < model.num_layers:
        raise ValueError(f"draft_layers must be in [1, {model.num_layers}),"
                         f" got {n}")
    dmodel = model.clone(num_layers=n)
    p = params["params"]
    backbone = {k: v for k, v in p["backbone"].items()
                if not k.startswith("block_")}
    for i in range(n):
        backbone[f"block_{i}"] = p["backbone"][f"block_{i}"]
    dparams = dict(params)
    dparams["params"] = {**{k: v for k, v in p.items() if k != "backbone"},
                         "backbone": backbone}
    dwl = dataclasses.replace(workload, model=dmodel, num_layers=n)
    return dwl, dparams
