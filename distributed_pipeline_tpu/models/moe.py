"""Mixture-of-Experts MLP with top-k routing and expert parallelism.

The reference has no MoE (SURVEY.md §2.2 — no parallelism beyond DDP at
all); this exceeds it with the TPU-native formulation (GShard / Switch
Transformer recipe, reimplemented from the algorithm):

* **Dense dispatch**: routing is expressed as einsums against one-hot
  dispatch/combine tensors ``[B, L, E, C]`` — no ragged shapes, no gather
  loops, everything tiles onto the MXU and jits with static shapes.
* **Expert parallelism as sharding**: expert weights carry a leading
  ``expert`` logical axis mapped to the mesh's ``expert`` axis
  (parallel/sharding.py); activations are batch-sharded. XLA derives the
  dispatch/combine all-to-alls from those shardings — no hand-written
  collectives, same philosophy as the rest of the framework.
* **Capacity + residual overflow**: each expert processes at most
  ``C = ceil(L/E * capacity_factor * k)`` tokens per sequence; overflow
  tokens fall through on the residual path (standard Switch behavior).
* **Load-balancing aux loss** (Switch eq. 4): ``E * sum_e f_e * p_e``,
  sowed into the ``"losses"`` variable collection; the workload losses
  (diffuseq_losses / gpt2_losses) pick it up and add
  ``moe_aux_weight * aux`` to the objective.
"""

from __future__ import annotations

import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from .backbone import EMBED, MLP, _dense_init

EXPERT = "expert"

MOE_AUX_WEIGHT = 0.01  # Switch Transformer's load-balance loss coefficient

__all__ = ["MoEMlp", "moe_mlp_fwd", "EXPERT", "MOE_AUX_WEIGHT",
           "moe_aux_from"]


def moe_aux_from(variables: Dict) -> jnp.ndarray:
    """Sum the MoE load-balance terms sowed into the "losses" collection
    (zero-leaf list for dense models — callers gate on the STATIC structure)."""
    leaves = jax.tree_util.tree_leaves(variables.get("losses", {}))
    return sum(leaves) if leaves else jnp.zeros(())


class MoEMlp(nn.Module):
    """Top-k routed mixture of GELU MLP experts (drop-in for backbone.Mlp).

    Routing, dispatch, expert compute, and combine are all einsums over
    statically-shaped one-hot tensors; see module docstring.

    Capacity slots are claimed in STRICT positional priority — position j's
    k-th choice outranks everything at positions > j — so whether a token is
    dropped depends only on earlier positions. That keeps routing causal
    (safe under a causal LM: future tokens cannot change position j's
    output) at the cost of interleaving the two top-k claim orders.

    ``no_drop=True`` (inference: models get there via
    ``model.clone(moe_no_drop=True)`` in models/sampling.py) bypasses
    capacity entirely and computes the exact per-token top-k mixture — the
    standard train-with-capacity / infer-without-dropping split, and what
    makes cached and uncached decoding bit-identical."""

    num_experts: int
    top_k: int = 2
    capacity_factor: float = 1.25
    dtype: jnp.dtype = jnp.bfloat16
    expand: int = 4
    no_drop: bool = False

    # cast to ``dtype`` before their only use (moe_mlp_fwd's expert
    # einsums); the router multiplies in float32 and is not among them
    MATMUL_PARAMS = ("wi", "wo")

    @nn.compact
    def __call__(self, x: jnp.ndarray,
                 pad_mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        D = x.shape[-1]
        E = self.num_experts

        router_w = self.param(
            "router", nn.with_logical_partitioning(
                _dense_init(D), (EMBED, None)),
            (D, E), jnp.float32)
        wi = self.param(
            "wi", nn.with_logical_partitioning(
                _dense_init(D), (EXPERT, EMBED, MLP)),
            (E, D, self.expand * D), jnp.float32)
        wo = self.param(
            "wo", nn.with_logical_partitioning(
                _dense_init(self.expand * D), (EXPERT, MLP, EMBED)),
            (E, self.expand * D, D), jnp.float32)

        y, aux, dispatch = moe_mlp_fwd(
            {"router": router_w, "wi": wi, "wo": wo}, x, pad_mask,
            top_k=self.top_k, capacity_factor=self.capacity_factor,
            dtype=self.dtype, no_drop=self.no_drop)
        self.sow("losses", "moe_aux", aux,
                 init_fn=lambda: jnp.zeros(()), reduce_fn=jnp.add)
        if dispatch is not None:
            # Observable for tests (materializes only under mutable=
            # ["intermediates"]): the [B, L, E, C] one-hot routing plan.
            self.sow("intermediates", "dispatch", dispatch)
        return y


def moe_mlp_fwd(mp: Dict[str, jnp.ndarray], x: jnp.ndarray,
                pad_mask: Optional[jnp.ndarray], *, top_k: int,
                capacity_factor: float, dtype: jnp.dtype,
                no_drop: bool = False, return_stats: bool = False):
    """The MoE MLP as a pure function of its param dict ``{"router":
    [D, E] f32, "wi": [E, D, M], "wo": [E, M, D]}`` — the single
    implementation behind :class:`MoEMlp` (named blocks) AND the stacked
    scan-layers path (pipeline.MoEScanBlocks), which slices per-group
    weights out of a leading layers axis. Returns ``(y, aux_loss,
    dispatch-or-None)``; the caller owns sowing.

    ``return_stats=True`` returns the RAW load-balance sums instead of the
    finished aux scalar: ``(F [E], P [E], n)`` with ``F`` the top-1
    dispatch counts, ``P`` the router-prob sums over live tokens, ``n``
    the live-token count — so a sharded caller (the pipeline stages,
    whose batch is a shard_map-local chunk) can psum them over its batch
    axes and form ``aux = E * sum_e (F/n)(P/n)`` from GLOBAL statistics.
    Only ``P`` is differentiable (``F``/``n`` come from argmax one-hots
    and the pad mask); manual-vjp callers seed its cotangent with
    ``E * F/n^2`` accordingly."""
    B, L, D = x.shape
    E = mp["wi"].shape[0]
    K = min(top_k, E)
    C = max(1, math.ceil(L / E * capacity_factor * K))
    router_w, wi, wo = mp["router"], mp["wi"], mp["wo"]

    # Router in f32 (tiny op; softmax statistics want the precision).
    logits = jnp.einsum("bld,de->ble", x.astype(jnp.float32), router_w)
    probs = jax.nn.softmax(logits, axis=-1)              # [B, L, E]

    # Pad tokens must neither claim expert capacity nor steer the
    # load-balance statistics (seq2seq batches pad heavily; all pads
    # share one embedding and would pile onto one expert).
    live = (jnp.ones((B, L), jnp.float32) if pad_mask is None
            else pad_mask.astype(jnp.float32))

    # Iterative top-k: pick, mask out, repeat (K is tiny and static).
    remaining = probs
    gates, masks = [], []
    for _ in range(K):
        idx = jnp.argmax(remaining, axis=-1)             # [B, L]
        mask = jax.nn.one_hot(idx, E, dtype=jnp.float32)  # [B, L, E]
        remaining = remaining * (1.0 - mask)
        mask = mask * live[..., None]  # pads claim nothing
        gates.append((probs * mask).sum(-1))             # [B, L]
        masks.append(mask)

    # Switch load-balancing loss: E * sum_e (token fraction to e) *
    # (mean router prob of e), over the k=0 assignment — masked means
    # over REAL tokens only.
    n_live = jnp.maximum(live.sum(), 1.0)
    F_sum = masks[0].sum(axis=(0, 1))                    # [E]
    P_sum = (probs * live[..., None]).sum(axis=(0, 1))   # [E]
    # stats carry the RAW live count — accumulating callers sum counts
    # across chunks/shards before the aux division, and a per-chunk clamp
    # would inflate the global denominator for all-pad chunks (the final
    # max(n, 1) belongs to moe_aux_from_stats, applied once)
    aux = ((F_sum, P_sum, live.sum()) if return_stats
           else E * jnp.sum(F_sum / n_live * (P_sum / n_live)))

    if no_drop:
        # Exact per-token mixture: every expert computed for every
        # token, combined by normalized top-k gates. E x the MLP FLOPs,
        # used on (cheap) inference paths only.
        gate_mat = sum(g[..., None] * m for g, m in zip(gates, masks))
        denom_all = jnp.maximum(sum(gates), 1e-9)        # [B, L]
        w = gate_mat / denom_all[..., None]              # [B, L, E]
        h = jnp.einsum("bld,edm->belm", x.astype(dtype),
                       wi.astype(dtype))
        h = nn.gelu(h, approximate=True)
        out = jnp.einsum("belm,emd->beld", h, wo.astype(dtype))
        y = jnp.einsum("ble,beld->bld", w.astype(dtype), out)
        return y.astype(x.dtype), aux, None

    # Capacity: interleave the K claim streams in (position, k) order —
    # [B, L, K, E] -> [B, L*K, E] position-major — so slot occupancy at
    # position j counts ONLY claims from positions <= j (causality).
    claims = jnp.stack(masks, axis=2).reshape(B, L * K, E)
    pos = jnp.cumsum(claims, axis=1) - claims            # [B, L*K, E]
    keep_flat = claims * (pos < C)
    slot_idx = (pos * keep_flat).sum(-1).astype(jnp.int32)
    slot_flat = jax.nn.one_hot(slot_idx, C, dtype=jnp.float32)
    keep = keep_flat.reshape(B, L, K, E)
    slot = slot_flat.reshape(B, L, K, C)

    # Normalize kept gates so the combine weights sum to <= 1.
    kept_gate = [g * keep[:, :, k].sum(-1) for k, g in enumerate(gates)]
    denom = jnp.maximum(sum(kept_gate), 1e-9)
    combine = jnp.zeros((B, L, E, C), jnp.float32)
    for k, g in enumerate(gates):
        w = (g / denom)[..., None] * keep[:, :, k]       # [B, L, E]
        combine = combine + w[..., None] * slot[:, :, k][:, :, None, :]
    dispatch = (combine > 0).astype(x.dtype)

    # Dispatch -> expert MLPs -> combine. The expert (e) dim of wi/wo is
    # sharded over the mesh's expert axis; ein-summing it against
    # batch-sharded activations is what makes XLA emit the all-to-alls.
    xin = jnp.einsum("blec,bld->ebcd", dispatch, x.astype(dtype))
    h = jnp.einsum("ebcd,edm->ebcm", xin, wi.astype(dtype))
    h = nn.gelu(h, approximate=True)
    out = jnp.einsum("ebcm,emd->ebcd", h, wo.astype(dtype))
    y = jnp.einsum("blec,ebcd->bld", combine.astype(dtype), out)
    return y.astype(x.dtype), aux, dispatch
