"""Operations and bytes the DeepSeek-V3.2-Exp share NEEDS, from the
configuration's sizes and the program's counters (models/deepseek_v32.py::
COUNTERS, brought back with the tokens and booked on ``serve.fetch``).

Counted is what the mathematics requires of this chip: every token passes
the attention, indexer, dense, shared-expert, router and (a decode step)
head matrices once; a routed assignment that fell on a held expert passes
that expert's three; the indexer multiplies each query with every LIVE key
it scores; attention multiplies it with the rows it ATTENDS (at most
``index_topk``), in the un-absorbed form (the absorbed form a decode step may
use costs more operations and never counts more). Rows and assignments come
from the counters, so a program cannot raise a share by scoring or routing
more than it must."""

from __future__ import annotations

from typing import Any, Dict


def sizes(cfg: Dict[str, Any]) -> Dict[str, float]:
    """Matrix parameters by the piece that uses them."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    q, c = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    attn = (d * q + q * h * qk + d * (c + cfg["qk_rope_head_dim"])
            + c * h * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
            + h * cfg["v_head_dim"] * d)
    index = (q * cfg["index_n_heads"] * cfg["index_head_dim"]
             + d * cfg["index_head_dim"] + d * cfg["index_n_heads"])
    n_moe = cfg["n_layers"] - cfg["first_k_dense_replace"]
    expert = 3 * d * cfg["moe_intermediate_size"]
    return {
        # what every token passes, all layers together, head apart
        "token": (cfg["n_layers"] * (attn + index)
                  + cfg["first_k_dense_replace"] * 3 * d
                  * cfg["intermediate_size"]
                  + n_moe * (expert * cfg["n_shared_experts"]
                             + d * cfg["n_routed_experts"])),
        "expert": expert,
        "head": d * cfg["vocab_size"]}


def flops_needed(cfg: Dict[str, Any], *, tokens: float, head_tokens: float,
                 counted: Dict[str, float]) -> float:
    """``tokens`` through the layers, ``head_tokens`` of them through the
    head; ``counted`` the counters' sums over the same span."""
    s = sizes(cfg)
    per_scored = 2.0 * cfg["index_n_heads"] * cfg["index_head_dim"]
    per_attended = 2.0 * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
        + cfg["v_head_dim"])
    return (2.0 * (tokens * s["token"] + head_tokens * s["head"]
                   + counted["expert_assignments_held"] * s["expert"])
            + counted["index_rows_scored"] * per_scored
            + counted["kv_rows_attended"] * per_attended)


def decode_bytes_needed(cfg: Dict[str, Any], *, steps: float,
                        counted: Dict[str, float], itemsize: int = 2
                        ) -> float:
    """Bytes ``steps`` decode steps must read: the matrices every step
    passes, the experts that saw a token (counter), the indexer key of every
    row scored and the latent row of every row attended."""
    s = sizes(cfg)
    return itemsize * (
        steps * (s["token"] + s["head"])
        + counted["experts_touched"] * s["expert"]
        + counted["index_rows_scored"] * cfg["index_head_dim"]
        + counted["kv_rows_attended"] * (cfg["kv_lora_rank"]
                                         + cfg["qk_rope_head_dim"]))


def prefill_attention_needed(cfg: Dict[str, Any], *, attended_rows: float,
                             chunk_tokens: float, itemsize: int = 2
                             ) -> Dict[str, float]:
    """What the prefill's attention needs for ``attended_rows`` (query, key)
    pairs summed over layers (the counter): QK^T and PV over every head in
    the un-absorbed form. A key's latent row is read once a CHUNK, whatever
    the number of its queries that attend it, so the bytes are the pairs
    over the mean tokens a chunk. The kernel walks whole blocks of the live
    context, selected or not; only the selected pairs count."""
    h = cfg["num_attention_heads"]
    per_pair = 2.0 * h * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
                          + cfg["v_head_dim"])
    return {"flops": attended_rows * per_pair,
            "bytes": attended_rows / max(chunk_tokens, 1.0) * itemsize * (
                cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])}


def index_scores_needed(cfg: Dict[str, Any], *, scored_rows: float,
                        chunk_tokens: float, itemsize: int = 2
                        ) -> Dict[str, float]:
    """What the indexer needs for ``scored_rows`` (query, key) pairs summed
    over layers (the counter): one dot of ``index_head_dim`` a head a pair;
    a key is read once a chunk (as above)."""
    return {"flops": scored_rows * 2.0 * cfg["index_n_heads"]
            * cfg["index_head_dim"],
            "bytes": scored_rows / max(chunk_tokens, 1.0) * itemsize
            * cfg["index_head_dim"]}
