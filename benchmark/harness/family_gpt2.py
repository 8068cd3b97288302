"""What joins a GPT-2 configuration file to the program: the flags the
program builds the model from, the model's sizes under family-neutral names
(for the readers), and the renaming between the reference's flat weight names
and the parameter tree the program's GPT-2 expects. Shapes are the same on
both sides (the fused qkv is [D, 3, H, Dh], the output projection
[H, Dh, D]); only names differ."""

from __future__ import annotations

from typing import Any, Dict

_LAYER = {"ln1.g": ("ln1", "scale"), "ln1.b": ("ln1", "bias"),
          "attn.wqkv": ("attn", "qkv"), "attn.wo": ("attn", "out"),
          "ln2.g": ("ln2", "scale"), "ln2.b": ("ln2", "bias"),
          "mlp.wi": ("mlp", "wi"), "mlp.wo": ("mlp", "wo")}


def program_flags(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """``create_model_from_config`` / ``run.train`` flags, less the sequence
    length (the traffic's)."""
    return {"model_family": "gpt2", "model_size": "base",
            "hidden_size": cfg["n_embd"], "num_layers": cfg["n_layer"],
            "num_heads": cfg["n_head"], "vocab_size": cfg["vocab_size"],
            "dtype": cfg["dtype"]}


def dims(cfg: Dict[str, Any]) -> Dict[str, int]:
    return {"layers": cfg["n_layer"], "width": cfg["n_embd"],
            "heads": cfg["n_head"], "head_dim": cfg["n_embd"] // cfg["n_head"],
            "positions": cfg["n_positions"], "vocab": cfg["vocab_size"]}


def to_program_tree(w: Dict[str, Any], cfg: Dict[str, Any]) -> Dict[str, Any]:
    backbone: Dict[str, Any] = {
        "ln_f": {"scale": w["lnf.g"], "bias": w["lnf.b"]}}
    for i in range(cfg["n_layer"]):
        blk: Dict[str, Any] = {}
        for ref, (mod, leaf) in _LAYER.items():
            blk.setdefault(mod, {})[leaf] = w[f"h{i}.{ref}"]
        backbone[f"block_{i}"] = blk
    return {"params": {"backbone": backbone, "pos_emb": w["wpe"],
                       "word_emb": {"embedding": w["wte"]}}}


def from_program_tree(tree: Dict[str, Any], cfg: Dict[str, Any]
                      ) -> Dict[str, Any]:
    p = tree["params"]
    w = {"wte": p["word_emb"]["embedding"], "wpe": p["pos_emb"],
         "lnf.g": p["backbone"]["ln_f"]["scale"],
         "lnf.b": p["backbone"]["ln_f"]["bias"]}
    for i in range(cfg["n_layer"]):
        blk = p["backbone"][f"block_{i}"]
        for ref, (mod, leaf) in _LAYER.items():
            w[f"h{i}.{ref}"] = blk[mod][leaf]
    return w
