"""Operations and bytes that an algorithm NEEDS for a call, from its shapes.

These are the numerators of every roofline share and MFU the benchmark
reports. They count what the mathematics requires (causal attention: the
lower triangle, not what a block grid computes; recomputation never counts),
so a kernel cannot raise its share by doing more work than needed."""

from __future__ import annotations

from typing import Dict


def train_flops_per_token(n_params: int, n_layer: int, n_embd: int,
                          seq_len: int) -> float:
    """Forward + backward FLOPs a trained token requires: 6N for the weight
    matmuls plus 12*l*h*s for attention's score and value matmuls (PaLM,
    appendix B). Copied from
    utils/perf.py::transformer_train_flops_per_token."""
    return 6.0 * n_params + 12.0 * n_layer * n_embd * seq_len


def decode_flops_per_token(n_params: int) -> float:
    """Forward FLOPs a served token (prompt or output) requires: 2N."""
    return 2.0 * n_params


def flash_attention_fwd(batch: int, heads: int, seq: int, head_dim: int,
                        itemsize: int = 2) -> Dict[str, float]:
    """Causal attention forward over [batch, heads, seq, head_dim]: QK^T and
    PV over the lower triangle (seq*(seq+1)/2 pairs, 2 FLOPs a multiply-add
    each, two matmuls); reads q, k, v and writes o once."""
    pairs = seq * (seq + 1) / 2.0
    flops = batch * heads * 2 * (2.0 * pairs * head_dim)
    bytes_ = 4.0 * batch * heads * seq * head_dim * itemsize
    return {"flops": flops, "bytes": bytes_}


def flash_attention_bwd(batch: int, heads: int, seq: int, head_dim: int,
                        itemsize: int = 2) -> Dict[str, float]:
    """Causal attention backward as flash attention needs it: the scores are
    recomputed (one matmul) and dV, dP, dQ, dK follow (four), each over the
    lower triangle; reads q, k, v, o, do and writes dq, dk, dv. The
    recomputation is part of the algorithm here (no [seq, seq] matrix is
    kept), so it counts."""
    pairs = seq * (seq + 1) / 2.0
    flops = batch * heads * 5 * (2.0 * pairs * head_dim)
    bytes_ = 8.0 * batch * heads * seq * head_dim * itemsize
    return {"flops": flops, "bytes": bytes_}


def fused_adamw_ema(n_params: int, n_ema: int, itemsize: int = 4
                    ) -> Dict[str, float]:
    """One AdamW + EMA update: reads params, grads, m, v and each EMA copy,
    writes params, m, v and each EMA copy. About 14 FLOPs an element for
    Adam and 3 for each EMA lerp: bandwidth-bound by a wide margin."""
    reads = 4 + n_ema
    writes = 3 + n_ema
    return {"flops": float(n_params) * (14 + 3 * n_ema),
            "bytes": float(n_params) * (reads + writes) * itemsize}


def kv_bytes_per_token(n_layer: int, n_embd: int, itemsize: int = 2) -> int:
    """Bytes of K and V cache one token occupies over all layers."""
    return n_layer * 2 * n_embd * itemsize


def decode_step_bytes(weight_bytes: float, live_kv_tokens: float,
                      n_layer: int, n_embd: int, kv_itemsize: int = 2
                      ) -> float:
    """Bytes one decode step must read: every weight once, and the K and V
    of every live token of every slot."""
    return weight_bytes + live_kv_tokens * kv_bytes_per_token(
        n_layer, n_embd, kv_itemsize)


def roofline_seconds(flops: float, bytes_: float, peak_flops: float,
                     peak_bytes_s: float) -> Dict[str, object]:
    """Least time the chip could take, and which bound sets it."""
    t_c = flops / peak_flops
    t_m = bytes_ / peak_bytes_s
    return {"seconds": max(t_c, t_m),
            "bound": "compute" if t_c >= t_m else "memory"}
