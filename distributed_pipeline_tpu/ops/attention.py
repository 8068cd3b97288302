"""Attention kernels: one entry point, multiple TPU implementations.

The reference delegates all device kernels to cuDNN/cuBLAS through torch ops
(SURVEY.md §2.1). The TPU-native equivalents live here behind a single
dispatcher so models never hard-code a kernel choice:

* ``impl="xla"``    — einsum softmax attention; XLA fuses it onto the MXU and
                      is the strong baseline for seq_len <= ~1k.
* ``impl="pallas"`` — FlashAttention-style blocked kernel written in Pallas
                      (ops/flash_attention.py); O(L) memory, wins at long L.
* ``impl="ring"``   — ring attention over the ``sequence`` mesh axis for
                      context parallelism (parallel/ring.py): K/V shards
                      rotate via ``ppermute`` with online-softmax folding.
* ``impl="auto"``   — ring when the ambient mesh has a sequence axis > 1,
                      else pallas on TPU for long sequences, else XLA.

The interface is structural — ``(q, k, v, pad_mask [B, L], causal)`` — not a
dense additive bias: materializing a [B, 1, L, L] bias in HBM would defeat the
O(L)-memory kernels. The XLA path expands the mask to a bias internally
(cheap: it fuses). All impls take [B, H, L, Dh] tensors and are numerically
interchangeable (tests assert pallas vs xla parity).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

__all__ = ["dot_product_attention", "resolve_attention_impl",
           "make_attention_bias", "causal_bias"]

NEG_INF = -1e9  # large-negative in bf16-safe range; -inf would NaN the softmax
# on fully-masked rows


def causal_bias(L: int, dtype=jnp.float32) -> jnp.ndarray:
    tri = jnp.tril(jnp.ones((L, L), dtype=bool))
    return jnp.where(tri, 0.0, NEG_INF).astype(dtype)[None, None]


def make_attention_bias(pad_mask: jnp.ndarray, causal: bool = False,
                        dtype=jnp.float32) -> jnp.ndarray:
    """Expand a [B, L] validity mask (optionally + causal triangle) into an
    additive [B, 1, Lq, Lk] bias — used by the XLA path only."""
    b = (1 - pad_mask[:, None, None, :]).astype(dtype) * NEG_INF
    if causal:
        b = b + causal_bias(pad_mask.shape[-1], dtype)
    return b


def _xla_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   pad_mask: Optional[jnp.ndarray],
                   causal: bool) -> jnp.ndarray:
    """Reference einsum attention. Logits stay in the activation dtype (bf16
    on TPU: the [B, H, L, L] tensor at half the HBM traffic of f32 — worth
    ~8% of a DiffuSeq-base step; MXU accumulation is f32 internally either
    way); softmax statistics are then taken in f32 — the max/exp-sum convert
    fuses into the reduction, so only the quantization of the logits
    themselves (~0.4% relative) is at bf16 precision."""
    dh = q.shape[-1]
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * jnp.asarray(
        dh ** -0.5, q.dtype)
    if pad_mask is not None:
        logits = logits + make_attention_bias(pad_mask, causal, logits.dtype)
    elif causal:
        logits = logits + causal_bias(q.shape[-2], logits.dtype)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def resolve_attention_impl(impl: str, seq_len: int, mesh=None) -> str:
    """The arm ``impl`` runs at this sequence length on this mesh — the ONE
    rule, shared by the dispatcher below and by whoever reports the arm
    (trainer evidence, chip_smoke.py). ``auto``: ring when the mesh has a
    sequence axis > 1, else the pallas flash kernel on TPU from ~1k
    context up (O(L) instead of O(L^2) HBM in both directions; below that
    the dense XLA path keeps its small [L, L] logits), else XLA."""
    if impl != "auto":
        return impl
    if mesh is not None and mesh.shape.get("sequence", 1) > 1:
        return "ring"  # sequence-parallel mesh: attention must ring
    on_tpu = jax.default_backend() == "tpu"
    return "pallas" if (on_tpu and seq_len >= 1024) else "xla"


def _flash_on_mesh(q, k, v, pad_mask, causal, mesh):
    """The flash kernel on a mesh of more than one device. Mosaic kernels
    cannot be partitioned by GSPMD ("wrap the call in a shard_map"), so
    this is that shard_map: batch and heads split as ring attention
    splits them, the sequence whole on every device."""
    from jax.sharding import PartitionSpec as P

    from ..parallel.ring import batch_and_head_axes
    from ..utils.jax_compat import shard_map
    from .flash_attention import flash_attention

    if mesh.shape.get("sequence", 1) > 1:
        raise ValueError(
            "attention_impl 'pallas' keeps the whole sequence on each "
            "device and cannot run on a mesh with sequence > 1; use "
            "'ring' (or 'auto', which picks it)")
    batch, heads = batch_and_head_axes(mesh, q.shape[0], q.shape[1])
    qkv = P(batch, heads, None, None)
    if pad_mask is None:
        return shard_map(
            lambda q_, k_, v_: flash_attention(q_, k_, v_, None, causal),
            mesh=mesh, in_specs=(qkv,) * 3, out_specs=qkv,
            check_vma=False)(q, k, v)
    return shard_map(
        lambda q_, k_, v_, m_: flash_attention(q_, k_, v_, m_, causal),
        mesh=mesh, in_specs=(qkv,) * 3 + (P(batch, None),), out_specs=qkv,
        check_vma=False)(q, k, v, pad_mask)


def dot_product_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                          pad_mask: Optional[jnp.ndarray] = None,
                          causal: bool = False,
                          impl: str = "auto") -> jnp.ndarray:
    """Multi-head attention on [B, H, L, Dh] tensors.

    ``pad_mask`` is [B, L] (1 = real token); ``impl`` selects the kernel
    (module docstring); "auto" resolves by :func:`resolve_attention_impl`.
    """
    from ..parallel.ring import current_mesh
    mesh = current_mesh()
    impl = resolve_attention_impl(impl, q.shape[-2], mesh)
    if impl == "xla":
        return _xla_attention(q, k, v, pad_mask, causal)
    if impl == "pallas":
        # inside someone else's shard_map body (pipeline stages) the axes
        # are already manual: the kernel is per-device there, as it is
        if (mesh is not None and mesh.size > 1
                and not jax.sharding.get_abstract_mesh().manual_axes):
            return _flash_on_mesh(q, k, v, pad_mask, causal, mesh)
        from .flash_attention import flash_attention
        return flash_attention(q, k, v, pad_mask, causal)
    if impl == "ring":
        from ..parallel.ring import ring_attention_sharded
        return ring_attention_sharded(q, k, v, pad_mask, causal)
    if impl == "ring_shard":
        # already INSIDE a shard_map body with the "sequence" axis bound
        # (ring-in-stage: a pipe stage whose activations are sequence-
        # sharded) — call the per-device ring directly; the "ring" impl's
        # own shard_map wrapper cannot nest here.
        from ..parallel.ring import ring_attention
        return ring_attention(q, k, v, pad_mask, causal)
    raise ValueError(f"unknown attention impl: {impl}")
