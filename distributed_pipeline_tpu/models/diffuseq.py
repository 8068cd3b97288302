"""DiffuSeq: seq2seq text diffusion in embedding space.

The concrete implementation of the workload the reference scaffold targets
(its trainer derives from DiffuSeq's ``train_util.py``,
``/root/reference/utils/trainer.py:1-4``; model/loss left as user stubs at
``utils/initialization.py:18-27`` and ``utils/trainer.py:23-31``).

Training scheme (DiffuSeq, ICLR 2023 — reimplemented TPU-first, not copied):
tokens embed into a low-dim continuous space; the TARGET span is diffused
with Gaussian noise at a sampled timestep while the SOURCE span stays clean
("partial noising" — the source conditions the denoiser through full
bidirectional attention); a transformer predicts x_0; the objective is
x0-MSE on the target span + a decodability NLL through the weight-tied
rounding head + a prior-matching ||sqrt(abar_T) x_0||^2 term.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..ops.xent import token_cross_entropy
from .backbone import EMBED, TransformerBackbone, _dense_init
from .diffusion import DiffusionSchedule

__all__ = ["DiffuSeqModel", "diffuseq_losses", "timestep_embedding"]


def _pin_batch(x: jnp.ndarray) -> jnp.ndarray:
    """Pin an activation to pure batch sharding (data x fsdp on dim 0, every
    other dim replicated). The backbone kernels ZeRO-shard their EMBED input
    dims over fsdp; left to propagation, GSPMD pushes that hidden-dim
    sharding back onto the residual stream where it collides with the batch
    sharding and the partitioner falls back to "Involuntary full
    rematerialization" on every LayerNorm broadcast (dp x fsdp x tp meshes).
    Pinning the stream keeps activations batch-sharded and turns the weight
    shards into per-layer all-gathers instead. No-op without a mesh."""
    from ..parallel.ring import current_mesh

    mesh = current_mesh()
    if mesh is None or "data" not in mesh.shape or "fsdp" not in mesh.shape:
        return x
    spec = jax.sharding.PartitionSpec(("data", "fsdp"))
    return jax.lax.with_sharding_constraint(x, spec)


def timestep_embedding(t: jnp.ndarray, dim: int,
                       max_period: float = 10_000.0) -> jnp.ndarray:
    """Sinusoidal timestep features [B, dim] (f32; tiny op, precision cheap)."""
    half = dim // 2
    freqs = jnp.exp(-jnp.log(max_period) * jnp.arange(half, dtype=jnp.float32) / half)
    args = t.astype(jnp.float32)[:, None] * freqs[None]
    emb = jnp.concatenate([jnp.cos(args), jnp.sin(args)], axis=-1)
    if dim % 2:
        emb = jnp.pad(emb, ((0, 0), (0, 1)))
    return emb


class DiffuSeqModel(nn.Module):
    """Denoiser: (x_t [B,L,E], t [B], pad_mask [B,L]) -> x0_hat [B,L,E].

    The word embedding doubles as the rounding head (weight tying), so the
    embedding space stays decodable — the core DiffuSeq trick.
    """

    vocab_size: int
    seq_len: int
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    emb_dim: int = 128
    dtype: jnp.dtype = jnp.bfloat16
    remat: bool = False
    attention_impl: str = "auto"
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

    def setup(self) -> None:
        # dim1 is the low-dim diffusion embedding SPACE (emb_dim), not the
        # model hidden dim — annotating it EMBED would shard it over fsdp
        # and every [B, L, emb] activation (x_start/x_t/noise) would inherit
        # a last-dim fsdp sharding that fights their batch sharding
        # (data x fsdp on dim0): the SPMD partitioner then falls back to
        # "Involuntary full rematerialization" (full replication) on every
        # reshard. The table still shards over vocab -> tensor.
        self.word_emb = nn.Embed(
            self.vocab_size, self.emb_dim,
            embedding_init=nn.with_logical_partitioning(
                nn.initializers.normal(0.02), ("vocab", None)),
            param_dtype=jnp.float32, name="word_emb")
        self.in_proj = nn.Dense(
            self.hidden_size, kernel_init=nn.with_logical_partitioning(
                _dense_init(self.emb_dim), (None, EMBED)),
            param_dtype=jnp.float32, dtype=self.dtype, name="in_proj")
        self.time_mlp = nn.Sequential([
            nn.Dense(4 * self.hidden_size, param_dtype=jnp.float32,
                     dtype=jnp.float32),
            nn.silu,
            nn.Dense(self.hidden_size, param_dtype=jnp.float32,
                     dtype=jnp.float32),
        ])
        self.pos_emb = self.param(
            "pos_emb", nn.with_logical_partitioning(
                nn.initializers.normal(0.02), (None, EMBED)),
            (self.seq_len, self.hidden_size), jnp.float32)
        self.backbone = TransformerBackbone(
            self.num_layers, self.num_heads, self.dtype, self.remat,
            causal=False, attention_impl=self.attention_impl,
            moe_experts=self.moe_experts, moe_top_k=self.moe_top_k,
            moe_every=self.moe_every,
            moe_capacity_factor=self.moe_capacity_factor,
            moe_no_drop=self.moe_no_drop,
            scan_layers=self.scan_layers, pp_chunks=self.pp_chunks,
            scan_unroll=self.scan_unroll,
            name="backbone")
        self.out_proj = nn.Dense(
            self.emb_dim, kernel_init=nn.with_logical_partitioning(
                _dense_init(self.hidden_size), (EMBED, None)),
            param_dtype=jnp.float32, dtype=self.dtype, name="out_proj")

    def embed(self, ids: jnp.ndarray) -> jnp.ndarray:
        """Token ids -> embedding-space points x_0, f32 [B, L, E]."""
        return self.word_emb(ids)

    def logits(self, x: jnp.ndarray) -> jnp.ndarray:
        """Rounding head: embedding-space points -> vocab logits via the tied
        embedding matrix. The matmul runs in the model compute dtype (bf16 on
        TPU — MXU accumulates in f32 internally) so the [B, L, V] output
        costs half the HBM traffic of an f32 head; softmax statistics are
        taken in f32 downstream (ops/xent.py)."""
        emb = self.word_emb.embedding
        return jnp.einsum("...e,ve->...v", x.astype(self.dtype),
                          emb.astype(self.dtype))

    def init_variables(self, ids: jnp.ndarray, t: jnp.ndarray,
                       pad_mask: jnp.ndarray) -> jnp.ndarray:
        """Init-time entry touching every submodule (``__call__`` alone never
        reaches ``word_emb``, so ``model.init`` must trace through here)."""
        x = self.embed(ids)
        return self.logits(self(x, t, pad_mask))

    def __call__(self, x_t: jnp.ndarray, t: jnp.ndarray,
                 pad_mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        B, L, _ = x_t.shape
        h = self.in_proj(x_t.astype(self.dtype))
        h = h + self.time_mlp(timestep_embedding(t, self.hidden_size))[:, None, :].astype(self.dtype)
        h = h + self.pos_emb[None, :L].astype(self.dtype)
        h = _pin_batch(h)
        h = self.backbone(h, pad_mask)  # bidirectional, pad-masked
        h = _pin_batch(h)
        return self.out_proj(h).astype(jnp.float32)


def _masked_mean(x: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """Mean of per-position values [B, L] over mask==1 positions."""
    m = mask.astype(x.dtype)
    return jnp.sum(x * m) / jnp.maximum(jnp.sum(m), 1.0)


def diffuseq_losses(model: DiffuSeqModel, schedule: DiffusionSchedule,
                    params, batch: Dict[str, jnp.ndarray],
                    rng: jax.Array) -> Dict[str, jnp.ndarray]:
    """The DiffuSeq training objective as a pure function — this is the
    concrete ``compute_losses`` the reference declares as a user hook
    (``utils/trainer.py:23-25``). Returns a dict whose ``"loss"`` entry is
    optimized; the rest are logged (reference ``log_loss_dict`` hook)."""
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
        from .schedule_1f1b import diffuseq_1f1b_losses
        return diffuseq_1f1b_losses(model, schedule, params, batch, rng)
    ids = batch["input_ids"]
    tgt_mask = batch["input_mask"].astype(jnp.float32)   # diffused span
    pad_mask = batch["pad_mask"]
    B = ids.shape[0]

    rng_t, rng_noise = jax.random.split(rng)
    x_start = model.apply(params, ids, method=DiffuSeqModel.embed)  # [B,L,E] f32
    t = schedule.sample_t(rng_t, B)
    noise = jax.random.normal(rng_noise, x_start.shape, x_start.dtype)
    x_noisy = schedule.q_sample(x_start, t, noise)
    # Partial noising: target span diffuses, source span anchors.
    x_t = jnp.where(tgt_mask[..., None] > 0, x_noisy, x_start)

    x0_hat, mvars = model.apply(params, x_t, t, pad_mask,
                                mutable=["losses"])

    mse = _masked_mean(jnp.mean((x0_hat - x_start) ** 2, axis=-1), tgt_mask)
    tT = _masked_mean(schedule.mean_flat_tT(x_start), tgt_mask)
    logits = model.apply(params, x_start, method=DiffuSeqModel.logits)
    decoder_nll = _masked_mean(token_cross_entropy(logits, ids), tgt_mask)

    loss = mse + tT + decoder_nll
    out = {"loss": loss, "mse": mse, "tT": tT, "decoder_nll": decoder_nll}
    if jax.tree_util.tree_leaves(mvars.get("losses", {})):  # static: MoE model
        from .moe import MOE_AUX_WEIGHT, moe_aux_from
        aux = moe_aux_from(mvars)
        out["moe_aux"] = aux
        out["loss"] = loss + MOE_AUX_WEIGHT * aux
    return out
