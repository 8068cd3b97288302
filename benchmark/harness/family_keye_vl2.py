"""What joins a Keye-VL-2.0 configuration file to the program: the flags the
program builds the model from (the source's keys and the cut, as the
factory's ``arch``), the model's sizes under family-neutral names, and the
parameter tree. The reference's nested weight names ARE the program's, leaf
for leaf and shape for shape, so the tree is handed over as it is: an 8.7 GB
tree is never copied."""

from __future__ import annotations

from typing import Any, Dict

ARCH_KEYS = (
    "hidden_size", "n_layers", "num_attention_heads", "num_key_value_heads",
    "head_dim", "num_experts", "num_experts_per_tok",
    "moe_intermediate_size", "norm_topk_prob", "sa_config", "rope_scaling",
    "rope_theta", "rms_norm_eps", "max_position_embeddings",
    "initializer_range", "embedding_initializer_range", "dispatch_lag")


def program_flags(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """``create_model_from_config`` / ``run.serve`` flags, less the sequence
    length (the driver passes ``dims()["positions"]``)."""
    return {"model_family": "keye_vl2", "model_size": "base",
            "vocab_size": cfg["vocab_size"], "dtype": cfg["dtype"],
            "arch": {k: cfg[k] for k in ARCH_KEYS if k in cfg}}


def dims(cfg: Dict[str, Any]) -> Dict[str, int]:
    return {"layers": cfg["n_layers"], "width": cfg["hidden_size"],
            "heads": cfg["num_attention_heads"],
            "head_dim": cfg["head_dim"],
            "positions": cfg["max_position_embeddings"],
            "vocab": cfg["vocab_size"]}


def to_program_tree(w: Dict[str, Any], cfg: Dict[str, Any]) -> Dict[str, Any]:
    return {"params": w}


def from_program_tree(tree: Dict[str, Any], cfg: Dict[str, Any]
                      ) -> Dict[str, Any]:
    return tree["params"]
