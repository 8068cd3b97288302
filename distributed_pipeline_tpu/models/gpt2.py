"""GPT-2-style causal language model.

The second workload family (BASELINE.md config 4): proves the framework's
model/loss plug-in surface (``create_model_from_config`` +
``compute_losses``) is model-agnostic, i.e. not welded to diffusion.
Reference stub being filled: ``/root/reference/utils/initialization.py:18-27``.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..ops.xent import token_cross_entropy
from .backbone import EMBED, TransformerBackbone, as_dtype, serving_blocks

__all__ = ["GPT2Model", "gpt2_losses"]


class GPT2Model(nn.Module):
    """Decoder-only causal LM with weight-tied output head.

    ``decode=True`` (via ``model.clone(decode=True)``) enables the KV-cache
    generation path: a full-length prefill call, then single-token calls
    with ``cache_index=i`` (position embedding taken at i) — see
    backbone.SelfAttention and models/sampling.py."""

    vocab_size: int
    seq_len: int
    hidden_size: int = 1024
    num_layers: int = 24
    num_heads: int = 16
    dtype: jnp.dtype = jnp.bfloat16
    remat: bool = False
    attention_impl: str = "auto"
    decode: bool = False
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_every: int = 2
    moe_capacity_factor: float = 1.25
    moe_no_drop: bool = False
    scan_layers: bool = False
    pp_chunks: int = 4
    pp_schedule: str = "1f1b"  # training schedule under a pipe > 1 mesh
    pp_virtual: int = 2  # virtual stages/device (pp_schedule="interleaved")
    scan_unroll: int = 0  # layer-scan unroll (pipeline.scan_unroll_for)
    paged_pages: int = 0  # serving: paged KV-cache pool size (0 = dense)
    page_size: int = 0
    decode_impl: str = "auto"  # paged decode-step kernel (flash-decode/xla)
    kv_quant: str = "fp"  # "int8": quantized page pool + per-page scales

    @nn.compact
    def __call__(self, ids: jnp.ndarray,
                 pad_mask: Optional[jnp.ndarray] = None,
                 cache_index: Optional[jnp.ndarray] = None,
                 block_table: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        B, L = ids.shape
        word_emb = nn.Embed(
            self.vocab_size, self.hidden_size,
            embedding_init=nn.with_logical_partitioning(
                nn.initializers.normal(0.02), ("vocab", EMBED)),
            param_dtype=jnp.float32, name="word_emb")
        # pos_emb stays replicated: like the table's hidden dim, sharding
        # it over fsdp would push fsdp onto h's hidden dim (it adds
        # directly into the activation) and fight the batch sharding
        pos_emb = self.param(
            "pos_emb", nn.with_logical_partitioning(
                nn.initializers.normal(0.02), (None, None)),
            (self.seq_len, self.hidden_size), jnp.float32)
        if cache_index is not None and L == 1:
            idx = jnp.asarray(cache_index, jnp.int32)
            if idx.ndim == 0:
                pos = jax.lax.dynamic_slice(
                    pos_emb, (idx, 0), (1, self.hidden_size))[None]
            else:
                # per-slot positions (continuous-batching decode): each
                # slot sits at its own depth, so the embedding is a gather
                pos = jnp.take(pos_emb, idx, axis=0)[:, None, :]
        elif cache_index is not None:
            # speculative-verify span: per-slot chains at idx..idx+L-1
            # (backbone span branch); budget-final overshoot clamps to
            # the table edge — those links' picks are discarded anyway
            idx = jnp.asarray(cache_index, jnp.int32)
            span = jnp.minimum(idx[:, None]
                               + jnp.arange(L, dtype=jnp.int32)[None, :],
                               self.seq_len - 1)
            pos = jnp.take(pos_emb, span, axis=0)        # [B, L, D]
        else:
            pos = pos_emb[None, :L]
        h = (word_emb(ids) + pos).astype(self.dtype)
        if pad_mask is None:
            pad_mask = jnp.ones_like(ids)
        h = TransformerBackbone(self.num_layers, self.num_heads, self.dtype,
                                self.remat, causal=True,
                                attention_impl=self.attention_impl,
                                decode=self.decode,
                                moe_experts=self.moe_experts,
                                moe_top_k=self.moe_top_k,
                                moe_every=self.moe_every,
                                moe_capacity_factor=self.moe_capacity_factor,
                                moe_no_drop=self.moe_no_drop,
                                scan_layers=self.scan_layers,
                                pp_chunks=self.pp_chunks,
                                scan_unroll=self.scan_unroll,
                                paged_pages=self.paged_pages,
                                page_size=self.page_size,
                                decode_impl=self.decode_impl,
                                kv_quant=self.kv_quant,
                                name="backbone")(h, pad_mask, cache_index,
                                                 block_table)
        # Tied LM head in compute dtype: bf16 [B, L, V] logits cost half the
        # HBM traffic of f32; softmax stats go to f32 downstream (ops/xent.py).
        # A server brings the cast table with it (serving_variables): the
        # lookup above keeps the float32 one, whose sum rounds once.
        head = (self.get_variable("serving", "head")
                if self.has_variable("serving", "head")
                else word_emb.embedding)
        return jnp.einsum("bld,vd->blv", h, head.astype(self.dtype))

    def serving_variables(self, variables):
        """The variables a server holds, from what training leaves: the
        block matrices in the compute dtype (backbone.serving_blocks) and,
        in a ``serving`` collection of its own, the tied head's copy of
        the table in that dtype. The cast is the one each use makes inside
        a program, made once, so logits come out bit for bit; a leaf that
        is right already is the same object (a served tree comes back
        with every leaf its own), and a described tree
        (``ShapeDtypeStruct`` leaves) is re-described. ``params`` keeps
        the float32 table, position embedding and LayerNorm leaves, and
        every parameter once."""
        p = variables["params"]
        head = variables.get("serving", {}).get(
            "head", nn.meta.unbox(p["word_emb"]["embedding"]))
        return {**variables,
                "params": {**p, "backbone": serving_blocks(p["backbone"],
                                                           self.dtype)},
                "serving": {"head": as_dtype(head, self.dtype)}}


def gpt2_losses(model: GPT2Model, params, batch: Dict[str, jnp.ndarray],
                rng: jax.Array) -> Dict[str, jnp.ndarray]:
    """Next-token cross-entropy over the loss span — the non-diffusion
    ``compute_losses`` path (reference hook, utils/trainer.py:23-25).
    ``rng`` is unused but kept for loss-fn signature uniformity."""
    del rng
    from ..parallel.ring import current_mesh

    mesh = current_mesh()
    if (mesh is not None and mesh.shape.get("pipe", 1) > 1
            and model.scan_layers and model.moe_experts == 0
            and mesh.shape.get("sequence", 1) == 1
            and model.pp_schedule in ("1f1b", "interleaved")):
        # (MoE and ring-in-stage pipe runs take the AD GPipe stream below
        # instead — the 1F1B engine has no MoE/sequence stage path)
        # training under a pipe mesh: the 1F1B streaming schedule computes
        # loss AND grads in one pass (models/schedule_1f1b.py)
        from .schedule_1f1b import gpt2_1f1b_losses
        return gpt2_1f1b_losses(model, params, batch)
    ids = batch["input_ids"]
    pad_mask = batch["pad_mask"]
    loss_mask = (batch["input_mask"] * pad_mask)[:, 1:].astype(jnp.float32)

    logits, mvars = model.apply(params, ids, pad_mask, mutable=["losses"])
    logits = logits[:, :-1]  # predict ids[:, 1:]
    targets = ids[:, 1:]
    nll = token_cross_entropy(logits, targets)
    denom = jnp.maximum(loss_mask.sum(), 1.0)
    loss = (nll * loss_mask).sum() / denom
    # Teacher-forced next-token accuracy: the right quality gauge when the
    # data has irreducible noise (greedy-decode-vs-gold caps out once the
    # gold draws its first unpredictable token and the histories fork).
    hit = (jnp.argmax(logits, axis=-1) == targets).astype(jnp.float32)
    acc = (hit * loss_mask).sum() / denom
    out = {"loss": loss, "nll": loss, "acc": acc,
           "ppl": jnp.exp(jnp.minimum(loss, 20.0))}
    if jax.tree_util.tree_leaves(mvars.get("losses", {})):  # static: MoE model
        from .moe import MOE_AUX_WEIGHT, moe_aux_from
        aux = moe_aux_from(mvars)
        out["moe_aux"] = aux
        out["loss"] = loss + MOE_AUX_WEIGHT * aux
    return out
