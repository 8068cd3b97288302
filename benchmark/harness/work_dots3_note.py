"""Operations and bytes the dots3-note-prev share NEEDS, by layer kind, from
the configuration's sizes and the program's counters (models/deepseek_v32.py
``COUNTERS`` + ``WINDOW_COUNTERS``, brought back with the tokens and booked
on ``serve.fetch``).

Counted is what the mathematics requires of this chip: every token passes
each layer's attention matrices (its kind's sizes, the headwise gate among
them), a full layer's indexer matrices, the dense, shared-expert and router
matrices and (a decode step) the head once; a routed assignment that fell on
a held expert passes that expert's three; a full layer's indexer multiplies
each query with every LIVE key it scores and its attention with the rows it
ATTENDS (at most ``index_topk``); a sliding layer's attention multiplies each
query with the rows inside its window. All in the un-absorbed form (the
absorbed form a decode step may use costs more operations and never counts
more). Rows and assignments come from the counters, so a program cannot
raise a share by scoring, attending or routing more than it must."""

from __future__ import annotations

from typing import Any, Dict

KINDS = {"full_attention": "", "sliding_attention": "swa_"}


def kind_sizes(cfg: Dict[str, Any], pre: str) -> Dict[str, float]:
    """One layer kind (``pre`` "" or "swa_"): its attention matrices'
    parameters, and what a (query, key) pair and a cached row cost."""
    d, h = cfg["hidden_size"], cfg[pre + "num_attention_heads"]
    q, c = cfg[pre + "q_lora_rank"], cfg[pre + "kv_lora_rank"]
    dn, dr, dv = (cfg[pre + "qk_nope_head_dim"], cfg[pre + "qk_rope_head_dim"],
                  cfg[pre + "v_head_dim"])
    gate = d * h if cfg.get(pre + "attention_gate_type") == "headwise" else 0
    return {"attn": (d * q + q * h * (dn + dr) + d * (c + dr)
                     + c * h * (dn + dv) + h * dv * d + gate),
            "pair_flops": 2.0 * h * (dn + dr + dv),
            "row": c + dr}


def sizes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """Matrix parameters by the piece that uses them."""
    d = cfg["hidden_size"]
    full, sliding = kind_sizes(cfg, ""), kind_sizes(cfg, "swa_")
    index = (cfg["q_lora_rank"] * cfg["index_n_heads"]
             * cfg["index_head_dim"] + d * cfg["index_head_dim"]
             + d * cfg["index_n_heads"])
    types = cfg["layer_types"][:cfg["n_layers"]]
    n_full = sum(t == "full_attention" for t in types)
    n_moe = cfg["n_layers"] - cfg["first_k_dense_replace"]
    expert = 3 * d * cfg["moe_intermediate_size"]
    return {
        # what every token passes, all layers together, head apart
        "token": (n_full * (full["attn"] + index)
                  + (len(types) - n_full) * sliding["attn"]
                  + cfg["first_k_dense_replace"] * 3 * d
                  * cfg["intermediate_size"]
                  + n_moe * (expert * cfg["n_shared_experts"]
                             + d * cfg["n_routed_experts"])),
        "expert": expert, "head": d * cfg["vocab_size"],
        "full": full, "sliding": sliding,
        "scored_flops": 2.0 * cfg["index_n_heads"] * cfg["index_head_dim"]}


def flops_needed(cfg: Dict[str, Any], *, tokens: float, head_tokens: float,
                 counted: Dict[str, float]) -> float:
    """``tokens`` through the layers, ``head_tokens`` of them through the
    head; ``counted`` the counters' sums over the same span."""
    s = sizes(cfg)
    return (2.0 * (tokens * s["token"] + head_tokens * s["head"]
                   + counted["expert_assignments_held"] * s["expert"])
            + counted["index_rows_scored"] * s["scored_flops"]
            + counted["kv_rows_attended"] * s["full"]["pair_flops"]
            + counted["window_rows_attended"] * s["sliding"]["pair_flops"])


def decode_bytes_needed(cfg: Dict[str, Any], *, steps: float,
                        counted: Dict[str, float], itemsize: int = 2
                        ) -> float:
    """Bytes ``steps`` decode steps must read: the matrices every step
    passes, the experts that saw a token (counter), the indexer key of every
    row scored, a full layer's latent row of every row attended and a
    sliding layer's of every row inside a window."""
    s = sizes(cfg)
    return itemsize * (
        steps * (s["token"] + s["head"])
        + counted["experts_touched"] * s["expert"]
        + counted["index_rows_scored"] * cfg["index_head_dim"]
        + counted["kv_rows_attended"] * s["full"]["row"]
        + counted["window_rows_attended"] * s["sliding"]["row"])


def prefill_attention_needed(cfg: Dict[str, Any], *, attended_rows: float,
                             window_rows: float, chunk_tokens: float,
                             itemsize: int = 2) -> Dict[str, float]:
    """What the prefill's attention needs, both layer kinds through the one
    kernel: ``attended_rows`` (query, key) pairs of the full layers (the
    indexer's selection) and ``window_rows`` of the sliding layers (inside
    the band), summed over layers (the counters): QK^T and PV over every head
    of the kind, un-absorbed. A key's latent row is read once a CHUNK,
    whatever the number of its queries that attend it, so the bytes are the
    pairs over the mean tokens a chunk. The kernel walks whole blocks;
    only the pairs that count are counted."""
    s = sizes(cfg)
    per_chunk = itemsize / max(chunk_tokens, 1.0)
    return {"flops": (attended_rows * s["full"]["pair_flops"]
                      + window_rows * s["sliding"]["pair_flops"]),
            "bytes": per_chunk * (attended_rows * s["full"]["row"]
                                  + window_rows * s["sliding"]["row"])}


def index_scores_needed(cfg: Dict[str, Any], *, scored_rows: float,
                        chunk_tokens: float, itemsize: int = 2
                        ) -> Dict[str, float]:
    """What the full layers' indexer needs for ``scored_rows`` (query, key)
    pairs summed over layers (the counter): one dot of ``index_head_dim`` a
    head a pair; a key is read once a chunk (as above)."""
    return {"flops": scored_rows * sizes(cfg)["scored_flops"],
            "bytes": scored_rows / max(chunk_tokens, 1.0) * itemsize
            * cfg["index_head_dim"]}
