"""What joins a dots3-note-prev configuration file to the program: the flags
the program builds the model from (the source's keys and the cut, as the
factory's ``arch``), the model's sizes under family-neutral names (the full
layers' where the two kinds differ), and the parameter tree. The reference's
nested weight names ARE the program's, leaf for leaf and shape for shape, so
the tree is handed over as it is: a 9 GB tree is never copied."""

from __future__ import annotations

from typing import Any, Dict

ARCH_KEYS = (
    "hidden_size", "n_layers", "layer_types", "sliding_window_size",
    "num_attention_heads", "q_lora_rank", "kv_lora_rank",
    "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "rope_theta",
    "swa_num_attention_heads", "swa_q_lora_rank", "swa_kv_lora_rank",
    "swa_qk_nope_head_dim", "swa_qk_rope_head_dim", "swa_v_head_dim",
    "swa_rope_theta", "attention_gate_type", "swa_attention_gate_type",
    "apply_mla_qkv_lora_rescale", "index_n_heads", "index_head_dim",
    "index_topk", "intermediate_size", "moe_intermediate_size",
    "n_routed_experts", "n_routed_experts_held", "expert_offset",
    "n_shared_experts", "num_experts_per_tok", "routed_scaling_factor",
    "rms_norm_eps", "max_position_embeddings", "initializer_range")


def program_flags(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """``create_model_from_config`` / ``run.serve`` flags, less the sequence
    length (the driver passes ``dims()["positions"]``)."""
    arch = {k: cfg[k] for k in ARCH_KEYS if k in cfg}
    arch["n_dense_layers"] = cfg["first_k_dense_replace"]
    return {"model_family": "dots3_note", "model_size": "base",
            "vocab_size": cfg["vocab_size"], "dtype": cfg["dtype"],
            "arch": arch}


def dims(cfg: Dict[str, Any]) -> Dict[str, int]:
    return {"layers": cfg["n_layers"], "width": cfg["hidden_size"],
            "heads": cfg["num_attention_heads"],
            "head_dim": cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
            "positions": cfg["max_position_embeddings"],
            "vocab": cfg["vocab_size"]}


def to_program_tree(w: Dict[str, Any], cfg: Dict[str, Any]) -> Dict[str, Any]:
    return {"params": w}


def from_program_tree(tree: Dict[str, Any], cfg: Dict[str, Any]
                      ) -> Dict[str, Any]:
    return tree["params"]
