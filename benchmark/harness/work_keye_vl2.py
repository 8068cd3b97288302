"""Operations and bytes the Keye-VL-2.0 stage NEEDS, from the configuration's
sizes and the program's counters (models/deepseek_v32.py ``COUNTERS`` +
models/keye_vl2.py ``GROUPED_COUNTERS``, brought back with the tokens and
booked on ``serve.fetch``).

Counted is what the mathematics requires of this chip: every token passes
each layer's attention, indexer and router matrices and (where a token is
sampled) the head once; a routed assignment passes its expert's three
matrices; the indexer multiplies each query with every LIVE key it scores;
attention multiplies it with the rows it ATTENDS (at most ``topk``), every
query head against its key head's key and value. Rows, assignments and
touched experts come from the counters, so a program cannot raise a share by
scoring, attending, routing or padding more than it must: rows a grouped
product multiplies beyond the assignments, K/V rows gathered beyond the
attended, experts read without a row, and the zeros that pad a stored
indexer key to whole lane tiles count for nothing."""

from __future__ import annotations

from typing import Any, Dict

def sizes(cfg: Dict[str, Any]) -> Dict[str, float]:
    """Matrix parameters by the piece that uses them."""
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    h, g = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    sa = cfg["sa_config"]
    j, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    attn = d * h * dh + 2 * d * g * dh + h * dh * d
    index = d * j * di + d * di + d * j
    return {
        # what every token passes, all layers together, head apart
        "token": cfg["n_layers"] * (attn + index + d * cfg["num_experts"]),
        "expert": 3 * d * cfg["moe_intermediate_size"],
        "head": d * cfg["vocab_size"],
        "scored_flops": 2.0 * j * di,
        "pair_flops": 2.0 * h * (dh + dh),
        "kv_row": 2 * g * dh, "index_key": di}


def flops_needed(cfg: Dict[str, Any], *, tokens: float, head_tokens: float,
                 counted: Dict[str, float]) -> float:
    """``tokens`` through the layers, ``head_tokens`` of them through the
    head; ``counted`` the counters' sums over the same span."""
    s = sizes(cfg)
    return (2.0 * (tokens * s["token"] + head_tokens * s["head"]
                   + counted["expert_assignments_held"] * s["expert"])
            + counted["index_rows_scored"] * s["scored_flops"]
            + counted["kv_rows_attended"] * s["pair_flops"])


def decode_bytes_needed(cfg: Dict[str, Any], *, steps: float,
                        counted: Dict[str, float], itemsize: int = 2
                        ) -> float:
    """Bytes ``steps`` decode steps must read: the matrices every step
    passes, the experts that saw a token (counter), the indexer key of
    every row scored (``indexer_head_dim`` numbers, as the prefill's
    reader counts it: not the padding the pool stores it with) and the K/V
    row of every row attended."""
    s = sizes(cfg)
    return itemsize * (
        steps * (s["token"] + s["head"])
        + counted["experts_touched"] * s["expert"]
        + counted["index_rows_scored"] * s["index_key"]
        + counted["kv_rows_attended"] * s["kv_row"])


def prefill_attention_needed(cfg: Dict[str, Any], *, attended_rows: float,
                             chunk_tokens: float, itemsize: int = 2
                             ) -> Dict[str, float]:
    """What the prefill's attention needs for ``attended_rows`` (query, key)
    pairs summed over layers (the counter): QK^T and PV over every query
    head. A key's K/V row is read once a CHUNK, whatever the number of its
    queries and query heads that attend it, so the bytes are the pairs over
    the mean tokens a chunk. The kernel walks whole blocks of the live
    context, a key head's block once a query head; only the selected pairs
    and one read a row count."""
    s = sizes(cfg)
    return {"flops": attended_rows * s["pair_flops"],
            "bytes": attended_rows / max(chunk_tokens, 1.0) * itemsize
            * s["kv_row"]}


def index_scores_needed(cfg: Dict[str, Any], *, scored_rows: float,
                        chunk_tokens: float, itemsize: int = 2
                        ) -> Dict[str, float]:
    """What the indexer needs for ``scored_rows`` (query, key) pairs summed
    over layers (the counter): one dot of ``indexer_head_dim`` a head a
    pair; a key is read once a chunk (as above), at the width the
    mathematics needs."""
    s = sizes(cfg)
    return {"flops": scored_rows * s["scored_flops"],
            "bytes": scored_rows / max(chunk_tokens, 1.0) * itemsize
            * s["index_key"]}


def grouped_experts_needed(cfg: Dict[str, Any], *, assignments: float,
                           experts_touched: float, itemsize: int = 2
                           ) -> Dict[str, float]:
    """What the expert layer's grouped products need for ``assignments``
    routed (token, expert) pairs that fell on ``experts_touched`` experts
    (both summed over layers and calls: the counters): three matrices a
    pair; an expert's matrices read once a call it is touched in, a row
    read once (``itemsize``) and its result written once (float32). Tile
    padding and rows of tiles that hold none count for nothing."""
    s = sizes(cfg)
    d = cfg["hidden_size"]
    return {"flops": 2.0 * assignments * s["expert"],
            "bytes": itemsize * experts_touched * s["expert"]
            + assignments * d * (itemsize + 4)}
