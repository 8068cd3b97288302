"""dots3-note-prev (``model_family="dots3_note"``) as ONE CHIP'S SHARE of an
expert-parallel deployment: the configuration only. Its layers run through
models/deepseek_v32.py's :class:`LatentMoEModel`, the one implementation of
latent attention, the lightning indexer, the routing and the held-expert
layer; this file says what its two kinds of layer are.

``layer_types[i]`` is ``"full_attention"`` (DeepSeek-V3.2's MLA with the
indexer, at the source's ``num_attention_heads`` / ``q_lora_rank`` /
``kv_lora_rank`` / ``rope_theta``) or ``"sliding_attention"`` (the same
equations at the ``swa_*`` sizes: a query sees itself and the
``sliding_window_size - 1`` rows before it, no indexer). Both carry a
headwise output gate (``attention_gate_type`` "headwise") and, with
``apply_mla_qkv_lora_rescale``, the latent rescale ``sqrt(hidden / rank)``
after the latents' norms, each kind with its own ranks. ``rope_scaling`` is
null: plain RoPE, softmax scale ``qk_head_dim ** -0.5``. The router has no
group keys: one group, the best ``num_experts_per_tok`` of all.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Tuple

import numpy as np

from .deepseek_v32 import LayerKind, check_held_experts

__all__ = ["Dots3NoteConfig"]

FULL, SLIDING = "full_attention", "sliding_attention"


def _inv_freq(theta: float, dim: int) -> Tuple[float, ...]:
    """Plain RoPE (``rope_scaling`` null), as float32 holds it."""
    freqs = theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    return tuple(map(float, (1.0 / freqs).astype(np.float32)))


@dataclasses.dataclass(frozen=True)
class Dots3NoteConfig:
    """The source's ``config.json`` keys (same names), the cut to one chip's
    share (``n_layers``, ``n_routed_experts_held``, ``expert_offset``), and
    nothing else."""

    vocab_size: int = 152064
    hidden_size: int = 5120
    n_layers: int = 46
    n_dense_layers: int = 1
    layer_types: Tuple[str, ...] = (FULL,) + (
        FULL, SLIDING, SLIDING, SLIDING) * 11 + (FULL,)
    sliding_window_size: int = 513
    num_attention_heads: int = 128
    q_lora_rank: int = 1024
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 80000000.0
    swa_num_attention_heads: int = 64
    swa_q_lora_rank: int = 1024
    swa_kv_lora_rank: int = 1024
    swa_qk_nope_head_dim: int = 192
    swa_qk_rope_head_dim: int = 64
    swa_v_head_dim: int = 128
    swa_rope_theta: float = 50000.0
    attention_gate_type: str = "headwise"
    swa_attention_gate_type: str = "headwise"
    apply_mla_qkv_lora_rescale: bool = True
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    intermediate_size: int = 13824
    moe_intermediate_size: int = 1536
    n_routed_experts: int = 256
    n_routed_experts_held: int = 256
    expert_offset: int = 0
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    routed_scaling_factor: float = 1.0
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 524288
    initializer_range: float = 0.006

    n_group = 1        # the source has no group keys: `route` takes the
    topk_group = 1     # best of all experts

    @classmethod
    def from_arch(cls, arch: Dict[str, Any], **over: Any
                  ) -> "Dots3NoteConfig":
        """From a dict of the source's keys (a benchmark configuration file,
        ``training_args.json``'s ``arch``); keys this class does not know
        are ignored."""
        flat = {**arch, **{k: v for k, v in over.items() if v}}
        names = {f.name for f in dataclasses.fields(cls)}
        flat = {k: v for k, v in flat.items() if k in names}
        if "layer_types" in flat:
            flat["layer_types"] = tuple(flat["layer_types"])
        cfg = cls(**flat)
        check_held_experts(cfg)
        kinds = cfg.layer_types[:cfg.n_layers]
        if len(kinds) < cfg.n_layers or set(kinds) - {FULL, SLIDING}:
            raise ValueError(
                f"layer_types must name {cfg.n_layers} layers as {FULL!r} "
                f"or {SLIDING!r}, got {kinds}")
        for gate in (cfg.attention_gate_type, cfg.swa_attention_gate_type):
            if gate not in ("headwise", "none", None):
                raise ValueError(f"unknown attention gate {gate!r}")
        return cfg

    def layer(self, i: int) -> LayerKind:
        sliding = self.layer_types[i] == SLIDING
        pre = "swa_" if sliding else ""

        def size(name: str) -> Any:
            return getattr(self, pre + name)
        q_rank, kv_rank = size("q_lora_rank"), size("kv_lora_rank")
        rescale = self.apply_mla_qkv_lora_rescale
        return LayerKind(
            heads=size("num_attention_heads"), q_lora_rank=q_rank,
            kv_lora_rank=kv_rank,
            qk_nope_head_dim=size("qk_nope_head_dim"),
            qk_rope_head_dim=size("qk_rope_head_dim"),
            v_head_dim=size("v_head_dim"),
            inv_freq=_inv_freq(float(size("rope_theta")),
                               size("qk_rope_head_dim")),
            softmax_scale=(size("qk_nope_head_dim")
                           + size("qk_rope_head_dim")) ** -0.5,
            window=self.sliding_window_size if sliding else 0,
            indexer=not sliding,
            gate=size("attention_gate_type") == "headwise",
            q_rescale=math.sqrt(self.hidden_size / q_rank) if rescale
            else 1.0,
            kv_rescale=math.sqrt(self.hidden_size / kv_rank) if rescale
            else 1.0)
