"""Shared transformer backbone (flax.linen).

The reference leaves the model entirely to the user
(``/root/reference/models/__init__.py`` is empty;
``utils/initialization.py:18-27`` is a stub). This backbone powers both
concrete workloads that fill those stubs: the DiffuSeq denoiser
(bidirectional) and the GPT-2 causal LM.

TPU-first choices:
* bf16 activations / f32 params, f32 softmax and layernorm statistics;
* all matmuls batched [B, L, D] x [D, *] so XLA tiles them on the MXU;
* attention via ops.dot_product_attention (XLA / pallas-flash / ring);
* optional ``jax.checkpoint`` (remat) per block to trade FLOPs for HBM;
* logical sharding annotations (``nn.with_logical_partitioning``) on every
  weight, mapped to mesh axes by parallel/sharding.py — the same model
  definition runs DP, FSDP, and TP without code changes.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..ops import dot_product_attention

__all__ = ["TransformerBackbone", "Block", "Mlp", "SelfAttention",
           "as_dtype", "serving_blocks"]

# Logical axis names; parallel/sharding.py maps them onto mesh axes
# ("embed" -> fsdp, "mlp"/"heads"/"kv" -> tensor, etc.).
EMBED = "embed"
MLP = "mlp"
HEADS = "heads"
KV = "kv"


def _dense_init(fan_in: int):
    return nn.initializers.normal(stddev=fan_in ** -0.5)


def as_dtype(leaf, dtype):
    """``leaf`` in ``dtype``: the SAME object where it already is (a tree
    that is right is never copied), a described leaf (``ShapeDtypeStruct``)
    re-described on its sharding, an array cast — round to nearest even,
    what ``.astype`` inside a program does. A flax metadata box keeps its
    box."""
    def one(x):
        if x.dtype == dtype:
            return x
        if isinstance(x, jax.ShapeDtypeStruct):
            return jax.ShapeDtypeStruct(x.shape, dtype, sharding=x.sharding)
        return x.astype(dtype)
    return jax.tree_util.tree_map(one, leaf)


def serving_blocks(backbone, dtype):
    """A named-blocks backbone's parameters as a server holds them: every
    matrix its module casts to the compute dtype before its only use
    (``MATMUL_PARAMS``, declared beside those uses) already in ``dtype``,
    so that a serving program reads half the bytes and casts nothing. The
    modules keep their ``.astype(self.dtype)`` — a no-op on this tree, and
    what training on float32 masters needs. LayerNorm leaves and an MoE
    router are used in float32 and stay as they are. Stacked
    (``scan_layers``) weights have no ``block_<i>`` entry and pass
    through: the engine does not serve them."""
    from .moe import MoEMlp  # function-level: moe imports backbone
    cast = {"attn": SelfAttention.MATMUL_PARAMS, "mlp": Mlp.MATMUL_PARAMS,
            "moe": MoEMlp.MATMUL_PARAMS}
    return {name: sub if not name.startswith("block_") else {
                mod: {k: as_dtype(v, dtype) if k in cast.get(mod, ()) else v
                      for k, v in leaves.items()}
                for mod, leaves in sub.items()}
            for name, sub in backbone.items()}


class SelfAttention(nn.Module):
    """Multi-head self-attention. QKV fused into one [D, 3, H, Dh] matmul
    (one MXU pass instead of three).

    ``decode=True`` adds an autoregressive KV cache (the "cache" variable
    collection): a full-length call is the PREFILL (runs normal causal
    attention and writes every position's K/V), and a single-token call with
    ``cache_index=i`` writes position i and attends to cache[0..i] — O(L)
    work per generated token instead of a full O(L^2) re-forward. The
    caller threads ``cache_index``; no mutable step counter hides in the
    module (jit/scany-friendly).

    ``paged_pages > 0`` (with ``decode=True``) switches the cache to the
    PAGED layout behind the serving layer (serving/paged_kv.py): K/V live in
    a shared pool of fixed-size pages (``pages_k``/``pages_v`` variables,
    [paged_pages, page_size, H * Dh]: a token's heads in one lane-dense
    row, serving/paged_kv.py says why) indirected through a per-slot
    ``block_table`` [B, pages_per_slot] argument, and ``cache_index`` is a
    PER-SLOT position vector [B] — each decode slot sits at its own depth,
    which is what continuous batching needs. Page 0 is the trash page:
    writes from padded/inactive slots land there and are never read (reads
    are masked to each slot's live prefix)."""

    num_heads: int
    dtype: jnp.dtype = jnp.bfloat16
    causal: bool = False
    attention_impl: str = "auto"
    decode: bool = False
    paged_pages: int = 0
    page_size: int = 0
    # Paged DECODE-step kernel (ops/flash_decode.py): "auto" -> flash-decode
    # on TPU / XLA gather elsewhere; "pallas"/"xla" force. Distinct from
    # attention_impl, which picks the full-sequence (train/prefill) kernel.
    decode_impl: str = "auto"
    # "int8": store the paged pool quantized per page with [P] fp32 scale
    # sidecars (serving/paged_kv.py q8 writers) — halves pool bytes; decode
    # reads dequantize per page. Prefill attention still runs on the local
    # fp k/v, so prefill logits are unchanged; decode logits carry the
    # documented quantization divergence instead of bit-identity.
    kv_quant: str = "fp"

    # cast to ``dtype`` before their only use (the two einsums below)
    MATMUL_PARAMS = ("qkv", "out")

    @nn.compact
    def __call__(self, x: jnp.ndarray,
                 pad_mask: Optional[jnp.ndarray],
                 cache_index: Optional[jnp.ndarray] = None,
                 block_table: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        B, L, D = x.shape
        H = self.num_heads
        assert D % H == 0, f"hidden {D} not divisible by heads {H}"
        Dh = D // H
        qkv_w = self.param(
            "qkv", nn.with_logical_partitioning(_dense_init(D), (EMBED, None, HEADS, KV)),
            (D, 3, H, Dh), jnp.float32)
        out_w = self.param(
            "out", nn.with_logical_partitioning(_dense_init(D), (HEADS, KV, EMBED)),
            (H, Dh, D), jnp.float32)
        qkv = jnp.einsum("bld,dthk->tbhlk", x, qkv_w.astype(self.dtype))
        q, k, v = qkv[0], qkv[1], qkv[2]
        if self.decode and self.paged_pages > 0:
            if block_table is None:
                raise ValueError("paged decode (paged_pages > 0) needs a "
                                 "block_table")
            o = self._paged_attention(q, k, v, pad_mask, cache_index,
                                      block_table)
        elif self.decode:
            o = self._cached_attention(q, k, v, pad_mask, cache_index)
        else:
            if block_table is not None:
                raise ValueError("block_table is only meaningful for paged "
                                 "decode (decode=True, paged_pages > 0)")
            o = dot_product_attention(q, k, v, pad_mask, causal=self.causal,
                                      impl=self.attention_impl)
        return jnp.einsum("bhlk,hkd->bld", o, out_w.astype(self.dtype))

    def _paged_attention(self, q, k, v, pad_mask, cache_index, block_table):
        # function-level import: paged_kv is a leaf module (jax-only), so
        # models <- serving here is a cycle-free convenience, same pattern
        # as Block's moe import
        from ..ops.flash_decode import (paged_decode_attention,
                                        paged_span_attention)
        from ..serving.paged_kv import (write_prompt_kv, write_prompt_kv_q8,
                                        write_span_kv, write_span_kv_q8,
                                        write_token_kv, write_token_kv_q8)
        B, H, L, Dh = q.shape
        quant = self.kv_quant == "int8"
        pool_dtype = jnp.int8 if quant else k.dtype
        pk = self.variable("cache", "pages_k", jnp.zeros,
                           (self.paged_pages, self.page_size, H * Dh),
                           pool_dtype)
        pv = self.variable("cache", "pages_v", jnp.zeros,
                           (self.paged_pages, self.page_size, H * Dh),
                           pool_dtype)
        sk = sv = None
        if quant:  # [P] per-page fp32 scale sidecars
            sk = self.variable("cache", "scales_k", jnp.zeros,
                               (self.paged_pages,), jnp.float32)
            sv = self.variable("cache", "scales_v", jnp.zeros,
                               (self.paged_pages,), jnp.float32)
        if L > 1 and cache_index is None:
            # prefill: write the prompt's K/V into its slots' pages;
            # attention itself runs on the local (contiguous) k/v — exactly
            # the dense prefill computation, so logits match it bitwise
            # (int8 included: quantization touches only the POOL copy)
            valid = pad_mask if pad_mask is not None else jnp.ones(
                (B, L), jnp.int32)
            if quant:
                pk.value, sk.value = write_prompt_kv_q8(
                    pk.value, sk.value, block_table, k, valid)
                pv.value, sv.value = write_prompt_kv_q8(
                    pv.value, sv.value, block_table, v, valid)
            else:
                pk.value = write_prompt_kv(pk.value, block_table, k, valid)
                pv.value = write_prompt_kv(pv.value, block_table, v, valid)
            return dot_product_attention(q, k, v, pad_mask, causal=True,
                                         impl=self.attention_impl)
        if cache_index is None or jnp.ndim(cache_index) != 1:
            raise ValueError("paged decode needs a per-slot cache_index "
                             "vector [B]")
        idx = jnp.asarray(cache_index, jnp.int32)
        if L > 1:
            # speculative-verify span (serving/engine.verify_fn): each
            # slot's L chain links occupy positions idx..idx+L-1. Write
            # every link's K/V first (span writers clamp budget-final
            # overshoot to the last addressable cell), then one span
            # attention dispatch: link j's query sits at position idx+j
            # and its position mask reads the live prefix PLUS the
            # earlier links — exactly the rows a sequential K+1-step
            # replay would read, at the op count of ONE decode step.
            if quant:
                pk.value, sk.value = write_span_kv_q8(
                    pk.value, sk.value, block_table, k, idx)
                pv.value, sv.value = write_span_kv_q8(
                    pv.value, sv.value, block_table, v, idx)
            else:
                pk.value = write_span_kv(pk.value, block_table, k, idx)
                pv.value = write_span_kv(pv.value, block_table, v, idx)
            addr = block_table.shape[1] * self.page_size
            pos = jnp.minimum(idx[:, None]
                              + jnp.arange(L, dtype=jnp.int32)[None, :],
                              addr - 1)                          # [B, L]
            return paged_span_attention(
                q, pk.value, pv.value, block_table, pos,
                impl=self.decode_impl,
                scales_k=sk.value if quant else None,
                scales_v=sv.value if quant else None)
        if quant:
            pk.value, sk.value = write_token_kv_q8(
                pk.value, sk.value, block_table, k[:, :, 0], idx)
            pv.value, sv.value = write_token_kv_q8(
                pv.value, sv.value, block_table, v[:, :, 0], idx)
        else:
            pk.value = write_token_kv(pk.value, block_table, k[:, :, 0], idx)
            pv.value = write_token_kv(pv.value, block_table, v[:, :, 0], idx)
        # The decode_step seam: positions beyond each slot's own depth hold
        # trash/stale pages and are masked (causality IS this mask for one
        # query row). The XLA path gathers a dense [B, H, Lmax, Dh] view
        # and masks it — bit-identical to the dense cache path at equal
        # padded length; the pallas path (ops/flash_decode.py) reads live
        # pages straight from the pool, matching to float tolerance
        # (greedy-token identical — tests/test_kernels.py).
        o = paged_decode_attention(
            q[:, :, 0], pk.value, pv.value, block_table, idx,
            impl=self.decode_impl,
            scales_k=sk.value if quant else None,
            scales_v=sv.value if quant else None)
        return o[:, :, None]

    def _cached_attention(self, q, k, v, pad_mask, cache_index):
        B, H, L, Dh = q.shape
        # Cache dims come from the first (prefill, full-length) call.
        ck = self.variable("cache", "key", jnp.zeros, k.shape, k.dtype)
        cv = self.variable("cache", "value", jnp.zeros, v.shape, v.dtype)
        Lmax = ck.value.shape[2]
        if L == Lmax:  # prefill: populate the whole cache
            ck.value, cv.value = k, v
            return dot_product_attention(q, k, v, pad_mask, causal=True,
                                         impl=self.attention_impl)
        if L != 1:
            raise ValueError(
                f"decode calls take the full length ({Lmax}, prefill) or a "
                f"single token, got {L}")
        if cache_index is None:
            raise ValueError("single-token decode needs cache_index")
        idx = jnp.asarray(cache_index, jnp.int32)
        ck.value = jax.lax.dynamic_update_slice(
            ck.value, k, (0, 0, idx, 0))
        cv.value = jax.lax.dynamic_update_slice(
            cv.value, v, (0, 0, idx, 0))
        # Positions beyond idx hold stale/unwritten entries; mask them.
        # (Causality IS this mask — no triangle needed for one query row.)
        live = (jnp.arange(Lmax) <= idx).astype(jnp.int32)[None, :]
        live = jnp.broadcast_to(live, (B, Lmax))
        if pad_mask is not None:
            live = live * pad_mask
        return dot_product_attention(q, ck.value, cv.value, live,
                                     causal=False, impl="xla")


class Mlp(nn.Module):
    """GELU MLP, expansion 4x."""

    dtype: jnp.dtype = jnp.bfloat16
    expand: int = 4

    MATMUL_PARAMS = ("wi", "wo")  # cast to ``dtype`` before their only use

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        D = x.shape[-1]
        wi = self.param("wi", nn.with_logical_partitioning(_dense_init(D), (EMBED, MLP)),
                        (D, self.expand * D), jnp.float32)
        wo = self.param("wo", nn.with_logical_partitioning(
            _dense_init(self.expand * D), (MLP, EMBED)),
            (self.expand * D, D), jnp.float32)
        h = jnp.einsum("bld,dm->blm", x, wi.astype(self.dtype))
        h = nn.gelu(h, approximate=True)
        return jnp.einsum("blm,md->bld", h, wo.astype(self.dtype))


class Block(nn.Module):
    """Pre-LN transformer block (LN in f32 for stability).

    ``moe_experts > 0`` swaps the dense MLP for a top-k routed
    mixture-of-experts (models/moe.py) — expert weights shard over the
    mesh's ``expert`` axis."""

    num_heads: int
    dtype: jnp.dtype = jnp.bfloat16
    causal: bool = False
    attention_impl: str = "auto"
    decode: bool = False
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_no_drop: bool = False
    paged_pages: int = 0
    page_size: int = 0
    decode_impl: str = "auto"
    kv_quant: str = "fp"

    @nn.compact
    def __call__(self, x: jnp.ndarray,
                 pad_mask: Optional[jnp.ndarray],
                 cache_index: Optional[jnp.ndarray] = None,
                 block_table: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        h = nn.LayerNorm(dtype=jnp.float32, name="ln1")(x).astype(self.dtype)
        x = x + SelfAttention(self.num_heads, self.dtype, self.causal,
                              self.attention_impl, self.decode,
                              paged_pages=self.paged_pages,
                              page_size=self.page_size,
                              decode_impl=self.decode_impl,
                              kv_quant=self.kv_quant,
                              name="attn")(h, pad_mask, cache_index,
                                           block_table)
        h = nn.LayerNorm(dtype=jnp.float32, name="ln2")(x).astype(self.dtype)
        if self.moe_experts > 0:
            from .moe import MoEMlp  # function-level: moe imports backbone
            x = x + MoEMlp(self.moe_experts, self.moe_top_k,
                           capacity_factor=self.moe_capacity_factor,
                           dtype=self.dtype, no_drop=self.moe_no_drop,
                           name="moe")(h, pad_mask)
        else:
            x = x + Mlp(self.dtype, name="mlp")(h)
        return x


class TransformerBackbone(nn.Module):
    """Stack of pre-LN blocks over already-embedded inputs [B, L, D].

    Token/position/time embedding is workload-specific and lives in the
    concrete models (diffuseq.py / gpt2.py); the backbone is the shared
    FLOPs-dominant trunk.
    """

    num_layers: int
    num_heads: int
    dtype: jnp.dtype = jnp.bfloat16
    remat: bool = False
    causal: bool = False
    attention_impl: str = "auto"
    decode: bool = False
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_every: int = 2  # MoE replaces the MLP in every moe_every-th block
    moe_capacity_factor: float = 1.25
    moe_no_drop: bool = False
    scan_layers: bool = False  # stacked weights: lax.scan over layers, and
    # GPipe pipeline streaming when the mesh has a pipe axis > 1
    pp_chunks: int = 4
    scan_unroll: int = 0  # layer-scan unroll (pipeline.scan_unroll_for)
    paged_pages: int = 0  # serving: paged KV cache pool size (0 = dense)
    page_size: int = 0
    decode_impl: str = "auto"  # paged decode-step kernel (SelfAttention)
    kv_quant: str = "fp"  # "int8": quantized page pool + per-page scales

    @nn.compact
    def __call__(self, x: jnp.ndarray,
                 pad_mask: Optional[jnp.ndarray] = None,
                 cache_index: Optional[jnp.ndarray] = None,
                 block_table: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        if self.scan_layers:
            if block_table is not None or self.paged_pages > 0:
                raise NotImplementedError(
                    "paged decode needs per-layer named blocks; stacked "
                    "(scan_layers) models use the dense cache path")
            if self.moe_experts > 0:
                from .pipeline import MoEScanBlocks
                x = MoEScanBlocks(
                    self.num_layers, self.num_heads, x.shape[-1],
                    dtype=self.dtype, causal=self.causal,
                    moe_experts=self.moe_experts, moe_top_k=self.moe_top_k,
                    moe_every=self.moe_every,
                    capacity_factor=self.moe_capacity_factor,
                    moe_no_drop=self.moe_no_drop, remat=self.remat,
                    attention_impl=self.attention_impl,
                    scan_unroll=self.scan_unroll,
                    pp_chunks=self.pp_chunks,
                    name="blocks")(x, pad_mask, cache_index)
            else:
                from .pipeline import PipelinedBlocks
                x = PipelinedBlocks(
                    self.num_layers, self.num_heads, x.shape[-1],
                    dtype=self.dtype, causal=self.causal, remat=self.remat,
                    pp_chunks=self.pp_chunks,
                    attention_impl=self.attention_impl,
                    decode=self.decode,
                    scan_unroll=self.scan_unroll,
                    name="blocks")(x, pad_mask, cache_index)
            return nn.LayerNorm(dtype=jnp.float32,
                                name="ln_f")(x).astype(self.dtype)
        block_cls = Block
        if self.remat:
            block_cls = nn.remat(Block, prevent_cse=False,
                                 static_argnums=())  # save HBM: recompute in bwd
        for i in range(self.num_layers):
            is_moe = (self.moe_experts > 0
                      and i % self.moe_every == self.moe_every - 1)
            x = block_cls(self.num_heads, self.dtype, self.causal,
                          self.attention_impl, self.decode,
                          moe_experts=self.moe_experts if is_moe else 0,
                          moe_top_k=self.moe_top_k,
                          moe_capacity_factor=self.moe_capacity_factor,
                          moe_no_drop=self.moe_no_drop,
                          paged_pages=self.paged_pages,
                          page_size=self.page_size,
                          decode_impl=self.decode_impl,
                          kv_quant=self.kv_quant,
                          name=f"block_{i}")(x, pad_mask, cache_index,
                                             block_table)
        return nn.LayerNorm(dtype=jnp.float32, name="ln_f")(x).astype(self.dtype)
