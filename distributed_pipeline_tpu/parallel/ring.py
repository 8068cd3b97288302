"""Ring attention: sequence/context parallelism over the ``sequence`` mesh axis.

The reference has no long-context machinery at all (SURVEY.md §5.7 — its max
context is whatever the user model fits on one GPU). Here long context is
first-class: activations shard over sequence ([B, H, L/n, Dh] per chip) and
attention runs as a ring — each chip holds its query shard, while key/value
shards rotate around the ``sequence`` axis via ``ppermute`` (ICI
neighbor-to-neighbor, the topology TPU ICI is best at). Per hop, a chip
runs the Pallas flash kernel (ops/flash_attention.py) on (its query shard x
the visiting K/V block) and folds the block's normalized output into a
running online-softmax state using the kernel's log-sum-exp, so

* memory per chip stays O(L/n): the flash kernel streams the block through
  VMEM (never materializing the [L/n, L/n] score matrix the dense fallback
  would), and the fold state is O(L/n);
* compute and communication overlap naturally (the next block can be in
  flight while the current one multiplies);
* the math is EXACTLY softmax attention — tests assert parity with the
  dense XLA path, gradients included (``ppermute`` and the flash kernel's
  LSE output are both differentiable).

Causal masking: the diagonal hop (block from this chip's own shard) runs the
kernel with its causal flag; blocks from earlier shards attend fully; blocks
from later shards contribute nothing (zero output, -inf LSE — weight 0 in
the fold). The three cases select via ``lax.switch`` on the traced source
index — safe per-device branching, because every branch is chip-local
compute (no collectives inside), so no SPMD rendezvous can diverge; the
``ppermute`` rotating the carry stays unconditional every hop.

Usage: inside ``shard_map`` (models get there via
``ops.attention.dot_product_attention(impl="ring")`` which wraps this in a
``shard_map`` over the ambient mesh).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e9

__all__ = ["ring_attention", "ring_attention_sharded", "current_mesh"]


def current_mesh():
    """The ambient ``with mesh:`` context's mesh (None outside one)."""
    from jax._src.mesh import thread_resources
    m = thread_resources.env.physical_mesh
    return None if m.empty else m


def batch_and_head_axes(mesh, B: int, H: int):
    """Mesh axes a [B, H, ...] attention operand can split over inside a
    ``shard_map``: batch over the data-like axes and heads over ``tensor``,
    each only where the axis size divides (a B=1 init trace must still work
    on a dp>1 mesh — axes that don't divide fall back to replication).
    Returns PartitionSpec entries ``(batch, heads)``."""
    batch_axes, rem = [], B
    for a in ("data", "fsdp", "expert"):  # mirror mesh.batch_spec
        if mesh.shape.get(a, 1) > 1 and rem % mesh.shape[a] == 0:
            batch_axes.append(a)
            rem //= mesh.shape[a]
    tp = mesh.shape.get("tensor", 1)
    return (tuple(batch_axes) or None,
            "tensor" if tp > 1 and H % tp == 0 else None)


def _dense_block_attn(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                      kmask: Optional[jnp.ndarray], causal: bool,
                      q_off: jnp.ndarray, k_off: jnp.ndarray,
                      sm_scale: float) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Dense einsum fallback for one (q-shard x kv-block) piece ->
    (normalized out, lse), f32 stats. Shapes: q [B,H,Lq,D], k/v [B,H,Lk,D].
    Materializes the [Lq, Lk] score block — kept only as the reference
    implementation the flash path is tested against."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * sm_scale
    if kmask is not None:
        s = s + (1.0 - kmask.astype(jnp.float32))[:, None, None, :] * NEG_INF
    if causal:
        Lq, Lk = q.shape[-2], k.shape[-2]
        rows = q_off + jax.lax.broadcasted_iota(jnp.int32, (Lq, Lk), 0)
        cols = k_off + jax.lax.broadcasted_iota(jnp.int32, (Lq, Lk), 1)
        s = jnp.where((rows >= cols)[None, None], s, NEG_INF)
    live = s > NEG_INF / 2
    m = jnp.max(s, axis=-1)                                   # [B,H,Lq]
    p = jnp.where(live, jnp.exp(s - m[..., None]), 0.0)
    l = jnp.sum(p, axis=-1)                                   # [B,H,Lq]
    pv = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v,
                    preferred_element_type=jnp.float32)
    out = pv / jnp.maximum(l, 1e-20)[..., None]
    lse = m + jnp.log(jnp.maximum(l, 1e-20))
    return out, lse


def _flash_block_attn(q, k, v, kmask, causal, my, src,
                      block_q: int, block_k: int):
    """One hop through the Pallas flash kernel -> (normalized out, lse).

    ``my``/``src`` are traced shard indices; under causal attention they
    select diagonal (causal kernel), past (full kernel), or future (zero
    contribution) — chip-local branching only, see module docstring."""
    from ..ops.flash_attention import flash_attention_lse

    if not causal:
        return flash_attention_lse(q, k, v, kmask, False, block_q, block_k)

    B, H, Lq, D = q.shape

    def diag(_):
        return flash_attention_lse(q, k, v, kmask, True, block_q, block_k)

    def past(_):
        return flash_attention_lse(q, k, v, kmask, False, block_q, block_k)

    def future(_):
        return (jnp.zeros((B, H, Lq, D), q.dtype),
                jnp.full((B, H, Lq), NEG_INF, jnp.float32))

    idx = jnp.where(src == my, 0, jnp.where(src < my, 1, 2))
    return jax.lax.switch(idx, (diag, past, future), None)


def ring_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   pad_mask: Optional[jnp.ndarray] = None,
                   causal: bool = False,
                   axis_name: str = "sequence",
                   use_flash: bool = True,
                   block_q: int = 1024, block_k: int = 1024) -> jnp.ndarray:
    """Exact attention over sequence-sharded [B, H, L_local, Dh] inputs.
    Must run inside ``shard_map`` with ``axis_name`` bound.

    Each hop yields a NORMALIZED block output plus its LSE; the cross-hop
    fold re-weights by ``exp(lse - m_run)`` so the final result is exactly
    global softmax attention. ``use_flash=False`` selects the dense einsum
    per-hop reference (O((L/n)^2) score memory — tests only)."""
    n = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    L_local = q.shape[-2]
    sm_scale = q.shape[-1] ** -0.5
    perm = [(i, (i + 1) % n) for i in range(n)]  # rotate kv around the ring
    q_off = my * L_local

    def hop(carry, i):
        k_blk, v_blk, mask_blk, acc, m_run, l_run = carry
        src = (my - i) % n                # shard that produced this kv block
        if use_flash:
            out_blk, lse_blk = _flash_block_attn(
                q, k_blk, v_blk, mask_blk, causal, my, src, block_q, block_k)
        else:
            out_blk, lse_blk = _dense_block_attn(
                q, k_blk, v_blk, mask_blk, causal,
                q_off, src * L_local, sm_scale)
        m_new = jnp.maximum(m_run, lse_blk)
        alpha = jnp.exp(m_run - m_new)
        beta = jnp.exp(lse_blk - m_new)
        acc = acc * alpha[..., None] + out_blk.astype(jnp.float32) * beta[..., None]
        l_run = l_run * alpha + beta
        k_nxt = jax.lax.ppermute(k_blk, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_blk, axis_name, perm)
        mask_nxt = (jax.lax.ppermute(mask_blk, axis_name, perm)
                    if mask_blk is not None else None)
        return (k_nxt, v_nxt, mask_nxt, acc, m_new, l_run), None

    B, H, _, D = q.shape
    acc0 = jnp.zeros((B, H, L_local, D), jnp.float32)
    m0 = jnp.full((B, H, L_local), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, L_local), jnp.float32)
    (_, _, _, acc, _, l), _ = jax.lax.scan(
        hop, (k, v, pad_mask, acc0, m0, l0), jnp.arange(n))
    return (acc / jnp.maximum(l, 1e-20)[..., None]).astype(q.dtype)


def ring_attention_sharded(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                           pad_mask: Optional[jnp.ndarray] = None,
                           causal: bool = False,
                           mesh=None,
                           use_flash: bool = True) -> jnp.ndarray:
    """Ring attention on GLOBAL [B, H, L, Dh] arrays: wraps
    :func:`ring_attention` in ``shard_map`` over the ambient (or given) mesh,
    sharding batch over (data, fsdp), heads over tensor, sequence over the
    ring axis."""
    from jax.sharding import PartitionSpec as P
    from ..utils.jax_compat import shard_map

    mesh = mesh if mesh is not None else current_mesh()
    if mesh is None:
        raise ValueError("ring attention needs a mesh: run inside `with mesh:`"
                         " or pass mesh=")
    B, H, L, _ = q.shape
    sp = mesh.shape["sequence"]
    if L % sp:
        raise ValueError(f"sequence length {L} not divisible by the "
                         f"sequence mesh axis ({sp})")
    batch, heads = batch_and_head_axes(mesh, B, H)
    qkv_spec = P(batch, heads, "sequence", None)
    mask_spec = P(batch, "sequence")

    if pad_mask is None:
        fn = shard_map(
            functools.partial(ring_attention, pad_mask=None, causal=causal,
                              use_flash=use_flash),
            mesh=mesh, in_specs=(qkv_spec,) * 3, out_specs=qkv_spec,
            check_vma=False)
        return fn(q, k, v)
    fn = shard_map(
        functools.partial(ring_attention, causal=causal, use_flash=use_flash),
        mesh=mesh, in_specs=(qkv_spec,) * 3 + (mask_spec,),
        out_specs=qkv_spec, check_vma=False)
    return fn(q, k, v, pad_mask)
