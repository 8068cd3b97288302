"""DeepSeek-V3.2-Exp as ONE CHIP'S SHARE of an expert-parallel deployment.

The family the serving stack runs beside GPT-2 (``model_family=
"deepseek_v32"``): RMSNorm, multi-head latent attention (low-rank query,
one compressed KV latent and one decoupled rope key a token), the lightning
indexer (learned sparse attention: every query attends only to the
``index_topk`` positions its indexer scores highest), a leading dense SwiGLU
layer, and expert layers whose router is as wide as published (sigmoid
scores, bias-corrected group-limited top-k) while only the experts HELD HERE
(``n_routed_experts_held``, from ``expert_offset``) are computed, beside the
shared expert. What the absent experts would add is left out, dropless: no
capacity, no dropped token, nothing stands in for the other chips.

Plain functions over a plain parameter tree (no flax module): the serving
engine calls two of them on a paged cache that holds, a layer, latent rows
``[pages, page_size, kv_lora_rank + qk_rope_head_dim]`` and indexer-key rows
``[pages, page_size, index_head_dim]`` (lane-dense, PERF.md PR 28):

* :meth:`DeepseekV32Model.prefill_chunk` — one CHUNK of one prompt: writes
  the chunk's rows, scores the whole live context with the indexer, finds
  each query's top-k threshold, and attends block by block over the live
  context (un-absorbed MLA, online softmax; work follows the live length,
  not the compiled maximum);
* :meth:`DeepseekV32Model.decode_step` — one token for every slot: scores
  every live row, takes the top-k rows out of the latent pool and attends
  over them in the latent space (``W_kvb`` absorbed into query and output).

:meth:`DeepseekV32Model.apply` (the cache-free forward of the tests) is
the prefill chunk over a private one-slot cache, so there is one set of layer
equations. Arithmetic: the residual stream, every norm, the router, the
index scores and the softmax are float32; matmul operands are ``dtype``
(bfloat16 as served) with float32 accumulation; cache rows are ``dtype``.
RoPE layouts (the file of the configuration states them too): MLA rotates
interleaved pairs in place, the indexer rotates split halves; YaRN's
``mscale`` enters through the softmax scale, as the source has it.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import mla_attention

__all__ = ["DeepseekV32Config", "DeepseekV32Model", "COUNTERS", "route"]

# what a program counts beside its tokens (int32, same order everywhere):
# routed assignments that fell on held experts, held experts that saw a
# token (summed over expert layers), rows the indexer scored, latent rows
# attention read, rows that were live (the last three summed over layers)
COUNTERS = ("expert_assignments_held", "experts_touched",
            "index_rows_scored", "kv_rows_attended", "kv_rows_live")
NEG = mla_attention.NEG   # "masked" in float32 score space (finite)
TRASH_PAGE = 0       # serving/paged_kv.py's reserved page
KV_BLOCK = 512       # rows of context the chunked prefill reads a step


@dataclasses.dataclass(frozen=True)
class DeepseekV32Config:
    """The source's ``config.json`` keys (same names), the cut to one chip's
    share, and nothing else."""

    vocab_size: int = 129280
    hidden_size: int = 7168
    n_layers: int = 61
    n_dense_layers: int = 3
    num_attention_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    n_routed_experts: int = 256
    n_routed_experts_held: int = 256
    expert_offset: int = 0
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_factor: float = 40.0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_original_max_position_embeddings: int = 4096
    rope_mscale_all_dim: float = 1.0
    max_position_embeddings: int = 163840
    initializer_range: float = 0.006

    @classmethod
    def from_arch(cls, arch: Dict[str, Any], **over: Any
                  ) -> "DeepseekV32Config":
        """From a dict of the source's keys (a benchmark configuration file,
        ``training_args.json``'s ``arch``); keys this class does not know
        are ignored, ``rope_scaling`` is flattened."""
        flat = dict(arch)
        for k, v in (flat.pop("rope_scaling", None) or {}).items():
            flat["rope_" + k] = v
        flat.update({k: v for k, v in over.items() if v})
        names = {f.name for f in dataclasses.fields(cls)}
        cfg = cls(**{k: v for k, v in flat.items() if k in names})
        held = cfg.n_routed_experts_held
        if not (0 < held and cfg.expert_offset + held
                <= cfg.n_routed_experts):
            raise ValueError(
                f"held experts [{cfg.expert_offset}, "
                f"{cfg.expert_offset + held}) lie outside the router's "
                f"{cfg.n_routed_experts}")
        if cfg.n_routed_experts % cfg.n_group:
            raise ValueError("n_routed_experts must divide by n_group")
        return cfg

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_width(self) -> int:
        """One cached latent row: the KV latent and the shared rope key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_row(self) -> int:
        """The row as the pool stores it: whole lane tiles (the chip pads
        a 576-wide row to 640 lanes anyway, and relays a pool whose rows
        are not whole tiles in every program that indexes it by page)."""
        return -(-self.latent_width // 128) * 128

    @property
    def softmax_scale(self) -> float:
        m = 1.0
        if self.rope_factor > 1.0:
            m = 0.1 * self.rope_mscale_all_dim * math.log(
                self.rope_factor) + 1.0
        return self.qk_head_dim ** -0.5 * m * m


# ------------------------------------------------------------ small pieces

def yarn_inv_freq(cfg: DeepseekV32Config) -> np.ndarray:
    """YaRN's blend of interpolated and extrapolated rotary frequencies
    (the source's ``find_correction_range`` / ``linear_ramp``)."""
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta
    freqs = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if cfg.rope_factor <= 1.0:
        return (1.0 / freqs).astype(np.float32)
    orig = cfg.rope_original_max_position_embeddings

    def correction_dim(rotations: float) -> float:
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (
            2 * math.log(base))
    low = max(math.floor(correction_dim(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(correction_dim(cfg.rope_beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    extrapolated = 1.0 - ramp
    inv = (1.0 / (cfg.rope_factor * freqs)) * (1.0 - extrapolated) \
        + (1.0 / freqs) * extrapolated
    return inv.astype(np.float32)


def _angles(cfg: DeepseekV32Config, positions: jnp.ndarray
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(
        yarn_inv_freq(cfg))[None, :]
    return jnp.cos(ang), jnp.sin(ang)


def rope_interleaved(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray
                     ) -> jnp.ndarray:
    """Rotate pairs (x0, x1), (x2, x3), ... in place. ``x`` [T, ..., R],
    ``cos`` / ``sin`` [T, R/2]."""
    shape = x.shape
    x = x.reshape(shape[:-1] + (shape[-1] // 2, 2))
    a, b = x[..., 0], x[..., 1]
    extra = (1,) * (x.ndim - 3)
    c = cos.reshape(cos.shape[:1] + extra + cos.shape[1:])
    s = sin.reshape(sin.shape[:1] + extra + sin.shape[1:])
    return jnp.stack([a * c - b * s, b * c + a * s], -1).reshape(shape)


def rope_halves(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray
                ) -> jnp.ndarray:
    """Rotate (x[i], x[i + R/2]): the indexer's layout."""
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    extra = (1,) * (x.ndim - 2)
    c = cos.reshape(cos.shape[:1] + extra + cos.shape[1:])
    s = sin.reshape(sin.shape[:1] + extra + sin.shape[1:])
    return jnp.concatenate([a * c - b * s, b * c + a * s], -1)


def rms_norm(x: jnp.ndarray, g: jnp.ndarray, eps: float) -> jnp.ndarray:
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def layer_norm(x: jnp.ndarray, g: jnp.ndarray, b: jnp.ndarray, eps: float
               ) -> jnp.ndarray:
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g.astype(jnp.float32) \
        + b.astype(jnp.float32)


def route(cfg: DeepseekV32Config, scores: jnp.ndarray, bias: jnp.ndarray
          ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Group-limited top-k over the published router width.

    ``scores`` [T, E] sigmoid scores (float32), ``bias`` [E] the
    ``e_score_correction_bias``. The choice is made on ``scores + bias``:
    a group's score is the sum of its two best, the best ``topk_group``
    groups stay, the ``num_experts_per_tok`` best experts inside them are
    taken. The weights are the UNBIASED scores of the taken, normalised,
    times ``routed_scaling_factor``. Returns (expert ids [T, k] int32,
    weights [T, k] float32)."""
    t, e = scores.shape
    g = cfg.n_group
    choice = scores + bias[None, :].astype(jnp.float32)
    grouped = choice.reshape(t, g, e // g)
    best = _best_first(grouped.reshape(t * g, e // g), 2)
    group_score = jnp.take_along_axis(
        grouped, best.reshape(t, g, 2), axis=2).sum(-1)          # [T, G]
    keep = _best_first(group_score, cfg.topk_group)              # [T, kg]
    group_on = jnp.any(keep[:, :, None] == jnp.arange(g)[None, None, :], 1)
    masked = jnp.where(group_on[:, :, None], grouped, -jnp.inf).reshape(t, e)
    ids = _best_first(masked, cfg.num_experts_per_tok)
    w = jnp.take_along_axis(scores, ids, axis=1)
    w = w / jnp.sum(w, -1, keepdims=True) * cfg.routed_scaling_factor
    return ids, w


def _best_first(x: jnp.ndarray, k: int) -> jnp.ndarray:
    """Indices of the k largest of each row, best first, of equal values
    the lower index first (``lax.top_k``'s order) — by k rounds of argmax:
    for the router's few picks out of a few hundred that is a handful of
    small fused reductions where ``top_k`` is a sort (0.46 ms a layer for
    16 tokens on the v5e, PERF.md PR 29)."""
    cols = jnp.arange(x.shape[-1], dtype=jnp.int32)
    picks = []
    for _ in range(k):
        i = jnp.argmax(x, -1).astype(jnp.int32)
        picks.append(i)
        x = jnp.where(cols[None, :] == i[:, None], -jnp.inf, x)
    return jnp.stack(picks, -1)


def _sortable(x: jnp.ndarray) -> jnp.ndarray:
    """float32 -> uint32 with the same order."""
    u = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    return jnp.where(u >> 31 == 1, ~u, u | jnp.uint32(0x80000000))


# -------------------------------------------------------------- the model

@dataclasses.dataclass(frozen=True)
class DeepseekV32Model:
    """The functions of one configuration. ``dtype`` is the type of the
    weights, the cache rows and the matmul operands."""

    cfg: DeepseekV32Config
    seq_len: int
    dtype: Any = jnp.bfloat16
    # the tests' hook: "xla" or "interpret" forces that arm of the prefill's
    # two Pallas-backed pieces; "auto" is what every caller runs
    kernel_impl: str = "auto"

    chunked_prefill = True   # what DecodeEngine asks a model
    counters = COUNTERS      # what its programs return behind the tokens

    @property
    def vocab_size(self) -> int:
        return self.cfg.vocab_size

    # ---------------------------------------------------------- parameters

    def param_shapes(self) -> Dict[str, Any]:
        c = self.cfg
        d, h = c.hidden_size, c.num_attention_heads
        attn = {
            "attn_norm": (d,), "wq_a": (d, c.q_lora_rank),
            "q_norm": (c.q_lora_rank,),
            "wq_b": (c.q_lora_rank, h * c.qk_head_dim),
            "wkv_a": (d, c.latent_width), "kv_norm": (c.kv_lora_rank,),
            "wk_b": (c.kv_lora_rank, h * c.qk_nope_head_dim),
            "wv_b": (c.kv_lora_rank, h * c.v_head_dim),
            "wo": (h * c.v_head_dim, d),
            "idx_wq_b": (c.q_lora_rank, c.index_n_heads * c.index_head_dim),
            "idx_wk": (d, c.index_head_dim),
            "idx_k_norm_g": (c.index_head_dim,),
            "idx_k_norm_b": (c.index_head_dim,),
            "idx_w": (d, c.index_n_heads), "mlp_norm": (d,)}
        f, fe, e = (c.intermediate_size, c.moe_intermediate_size,
                    c.n_routed_experts_held)
        fs = fe * c.n_shared_experts
        dense = {"w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}
        moe = {"router": (d, c.n_routed_experts),
               "router_bias": (c.n_routed_experts,),
               "shared_gate": (d, fs), "shared_up": (d, fs),
               "shared_down": (fs, d),
               "experts_gate": (e, d, fe), "experts_up": (e, d, fe),
               "experts_down": (e, fe, d)}
        out: Dict[str, Any] = {"embed": (c.vocab_size, d),
                               "head": (c.vocab_size, d), "norm_f": (d,)}
        for i in range(c.n_layers):
            out[f"layer_{i}"] = {
                **attn, **(dense if i < c.n_dense_layers else moe)}
        return out

    def init(self, rng: jax.Array, *_example: Any) -> Dict[str, Any]:
        """``{"params": tree}``: normal(0, initializer_range) matrices, unit
        norm scales, zero biases (the router's correction bias float32)."""
        std = self.cfg.initializer_range
        flat, treedef = jax.tree_util.tree_flatten_with_path(
            self.param_shapes(), is_leaf=lambda x: isinstance(x, tuple))
        leaves = []
        for i, (path, shape) in enumerate(flat):
            name = path[-1].key
            if name == "router_bias":
                leaves.append(jnp.zeros(shape, jnp.float32))
            elif name.endswith(("norm", "norm_g", "norm_f")):
                leaves.append(jnp.ones(shape, self.dtype))
            elif name.endswith("norm_b"):
                leaves.append(jnp.zeros(shape, self.dtype))
            else:
                leaves.append((std * jax.random.normal(
                    jax.random.fold_in(rng, i), shape, jnp.float32)
                ).astype(self.dtype))
        return {"params": jax.tree_util.tree_unflatten(treedef, leaves)}

    # --------------------------------------------------------------- cache

    def cache_shapes(self, max_pages: int, page_size: int) -> Dict[str, Any]:
        c = self.cfg
        return {f"layer_{i}": {
            "latent": jax.ShapeDtypeStruct(
                (max_pages, page_size, c.latent_row), self.dtype),
            "index_k": jax.ShapeDtypeStruct(
                (max_pages, page_size, c.index_head_dim), self.dtype)}
            for i in range(c.n_layers)}

    # ----------------------------------------------------------- the maths

    def _mm(self, a: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
        return jnp.dot(a.astype(self.dtype), w.astype(self.dtype),
                       preferred_element_type=jnp.float32)

    def _block_attend(self, q_t, k, v_t, bias, carry):
        """One context block of the prefill's attention
        (ops/mla_attention.py)."""
        impl = self._on_chip(q_t.shape[2], k.shape[1])
        if impl == "xla":
            return mla_attention.block_attend_xla(
                q_t, k, v_t, bias, carry, scale=self.cfg.softmax_scale)
        return mla_attention.block_attend(
            q_t, k, v_t, bias, carry, scale=self.cfg.softmax_scale,
            interpret=impl == "interpret")

    def _logits(self, p, hidden: jnp.ndarray) -> jnp.ndarray:
        """The untied head, stored [V, D] like the embedding: its rows are
        whole lane tiles, a [D, V] slice of the vocabulary's are not."""
        return jnp.einsum("td,vd->tv", hidden.astype(self.dtype), p["head"],
                          preferred_element_type=jnp.float32)

    def _swiglu(self, h: jnp.ndarray, wg, wu, wd) -> jnp.ndarray:
        a = jax.nn.silu(self._mm(h, wg)) * self._mm(h, wu)
        return self._mm(a, wd)

    def _queries(self, lp, h, cos, sin):
        """Normalised layer input -> (q_nope [T, H, n], q_rope [T, H, r]
        roped, indexer q [T, J, di] roped, indexer head weights [T, J])."""
        c = self.cfg
        t = h.shape[0]
        c_q = rms_norm(self._mm(h, lp["wq_a"]), lp["q_norm"], c.rms_norm_eps)
        q = self._mm(c_q, lp["wq_b"]).reshape(
            t, c.num_attention_heads, c.qk_head_dim)
        q_nope = q[..., :c.qk_nope_head_dim]
        q_rope = rope_interleaved(q[..., c.qk_nope_head_dim:], cos, sin)
        qi = self._mm(c_q, lp["idx_wq_b"]).reshape(
            t, c.index_n_heads, c.index_head_dim)
        r = c.qk_rope_head_dim
        qi = jnp.concatenate(
            [rope_halves(qi[..., :r], cos, sin), qi[..., r:]], -1)
        wi = self._mm(h, lp["idx_w"]) * (
            c.index_n_heads ** -0.5 * c.index_head_dim ** -0.5)
        return q_nope, q_rope, qi, wi

    def _rows(self, lp, h, cos, sin):
        """What a token leaves in the cache: latent row (the normalised KV
        latent, the roped shared key, zeros up to whole lane tiles) and
        indexer key [T, di], both in the cache's type."""
        c = self.cfg
        kv = self._mm(h, lp["wkv_a"])
        c_kv = rms_norm(kv[:, :c.kv_lora_rank], lp["kv_norm"],
                        c.rms_norm_eps)
        k_r = rope_interleaved(kv[:, c.kv_lora_rank:], cos, sin)
        ki = layer_norm(self._mm(h, lp["idx_wk"]), lp["idx_k_norm_g"],
                        lp["idx_k_norm_b"], 1e-6)
        r = c.qk_rope_head_dim
        ki = jnp.concatenate(
            [rope_halves(ki[:, :r], cos, sin), ki[:, r:]], -1)
        fill = jnp.zeros((h.shape[0], c.latent_row - c.latent_width),
                         jnp.float32)
        return (jnp.concatenate([c_kv, k_r, fill], -1).astype(self.dtype),
                ki.astype(self.dtype))

    def _on_chip(self, *sizes: int) -> str:
        """Which arm a Pallas-backed piece takes: the kernel on the chip
        where the sizes are whole tiles, plain XLA elsewhere (the CPU, a
        tiny test shape); ``kernel_impl`` forces one."""
        if self.kernel_impl != "auto":
            return self.kernel_impl
        aligned = all(n % 128 == 0 for n in sizes)
        return "pallas" if (aligned and jax.default_backend() == "tpu"
                            ) else "xla"

    def _index_scores(self, qi_t, wi_t, ki_rows) -> jnp.ndarray:
        """I[s, t] = sum_j w[t, j] relu(q[t, j] . k[s]) for one block of
        keys, TRANSPOSED: ``qi_t`` [J, di, T], ``wi_t`` [J, 1, T],
        ``ki_rows`` [S, di] -> [S, T] float32."""
        impl = self._on_chip(qi_t.shape[2], ki_rows.shape[0])
        if impl == "xla":
            return mla_attention.index_scores_xla(qi_t, wi_t, ki_rows)
        return mla_attention.index_scores(qi_t, wi_t, ki_rows,
                                          interpret=impl == "interpret")

    def _ffn(self, lp, i: int, h: jnp.ndarray, live: jnp.ndarray,
             decode: bool = False
             ) -> Tuple[jnp.ndarray, jnp.ndarray, Optional[jnp.ndarray]]:
        """Feed-forward of layer ``i`` on normalised ``h`` [T, D]; ``live``
        [T] bool marks the tokens that count; ``decode`` picks the form of
        the held experts' pass for a handful of tokens (a decode step)
        rather than a chunk. Returns (output float32, counters [2] int32,
        routed expert ids [T, k] or None)."""
        c = self.cfg
        if i < c.n_dense_layers:
            return (self._swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"]),
                    jnp.zeros((2,), jnp.int32), None)
        scores = jax.nn.sigmoid(jnp.dot(
            h, lp["router"].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        ids, w = route(c, scores, lp["router_bias"])
        held = c.expert_offset + jnp.arange(c.n_routed_experts_held)
        hit = (ids[:, :, None] == held[None, None, :]) & live[:, None, None]
        held_w = jnp.sum(jnp.where(hit, w[:, :, None], 0.0), 1)   # [T, E_h]
        held_m = jnp.any(hit, 1)
        counts = jnp.sum(held_m, 0).astype(jnp.int32)             # [E_h]
        y = self._swiglu(h, lp["shared_gate"], lp["shared_up"],
                         lp["shared_down"])
        t = h.shape[0]
        hb = h.astype(self.dtype)

        if decode:
            # a handful of tokens: an expert no token chose is skipped (a
            # `cond` an expert, its matrices not read), the others take
            # every token, weighted. A step's time follows the routing: six
            # seeds on the chip spread by 0.35 % in tokens/s against 0.14 %
            # for one batched pass over all held experts, which read 2.8 GB
            # more a step and served 6 % fewer tokens (PERF.md PR 29)
            for e in range(c.n_routed_experts_held):
                y = jax.lax.cond(
                    counts[e] > 0,
                    lambda y, e=e: y + held_w[:, e:e + 1] * self._swiglu(
                        hb, lp["experts_gate"][e], lp["experts_up"][e],
                        lp["experts_down"][e]),
                    lambda y: y, y)
        else:
            # a prefill chunk: each expert takes its routed rows out (a
            # slice of `rows`, four times its mean share) and all experts
            # run as one batched pass; rows go out and come back through
            # one-hot matmuls (exact: float32 accumulation, the result split
            # in two bfloat16 terms). An expert routed more tokens than its
            # slice holds takes EVERY token instead, alone (a `cond` an
            # expert: a hot expert costs one expert's pass, not sixteen):
            # nothing is ever dropped.
            rows = min(t, max(8, -(-4 * t * c.num_experts_per_tok
                                   // c.n_routed_experts)))
            fits = counts <= rows                                    # [E]
            take = jax.vmap(lambda m: jnp.nonzero(
                m, size=rows, fill_value=0)[0])(held_m.T)        # [E, rows]
            ok = (jnp.arange(rows)[None, :] < counts[:, None]) \
                & fits[:, None]
            onehot = ((take[:, :, None] == jnp.arange(t)[None, None, :])
                      & ok[:, :, None]).astype(self.dtype)       # [E, r, T]
            x_e = jnp.einsum("ert,td->erd", onehot, hb,
                             preferred_element_type=jnp.float32
                             ).astype(self.dtype)
            a = jax.nn.silu(jnp.einsum(
                "erd,edf->erf", x_e, lp["experts_gate"],
                preferred_element_type=jnp.float32)) * jnp.einsum(
                "erd,edf->erf", x_e, lp["experts_up"],
                preferred_element_type=jnp.float32)
            out = jnp.einsum("erf,efd->erd", a.astype(self.dtype),
                             lp["experts_down"],
                             preferred_element_type=jnp.float32)
            out = out * jnp.take_along_axis(held_w.T, take, 1)[:, :, None]
            hi = out.astype(self.dtype)
            lo = (out - hi.astype(jnp.float32)).astype(self.dtype)
            y = y + sum(jnp.einsum(
                "ert,erd->td", onehot, part,
                preferred_element_type=jnp.float32) for part in (hi, lo))
            for e in range(c.n_routed_experts_held):
                y = jax.lax.cond(
                    fits[e], lambda y: y,
                    lambda y, e=e: y + held_w[:, e:e + 1] * self._swiglu(
                        hb, lp["experts_gate"][e], lp["experts_up"][e],
                        lp["experts_down"][e]), y)
        stats = jnp.stack([jnp.sum(counts), jnp.sum(counts > 0)]).astype(
            jnp.int32)
        return y, stats, ids

    # ------------------------------------------------ prefill (and forward)

    def _chunk_hidden(self, p, cache, ids, start, n_valid, table_row,
                      collect: bool = False):
        """One chunk of one sequence through every layer.

        ``ids`` [C] the chunk's tokens (zero-padded past ``n_valid``), at
        positions ``start ..``; ``table_row`` [n_pages] the sequence's
        pages. Writes the chunk's rows first, then reads the whole live
        context (the chunk's own rows among it) back from the pool, so
        prefill sees exactly the rows decode will. Returns (final-normed
        hidden [C, D] float32, cache, counters [5] int32, aux)."""
        c = self.cfg
        n = ids.shape[0]
        ps = cache["layer_0"]["latent"].shape[1]
        kb = max(ps, KV_BLOCK // ps * ps)       # rows a context block
        pb = kb // ps
        n_blocks_max = -(-(table_row.shape[0] * ps) // kb)
        pad_pages = n_blocks_max * pb - table_row.shape[0]
        table = jnp.concatenate(
            [table_row, jnp.full((pad_pages,), TRASH_PAGE, jnp.int32)])
        l_max = n_blocks_max * kb
        pos = start + jnp.arange(n, dtype=jnp.int32)
        valid = jnp.arange(n) < n_valid
        live_len = start + n_valid
        n_blocks = (live_len + kb - 1) // kb
        cos, sin = _angles(c, pos)
        # where the chunk's rows go (padded tail -> the trash page)
        page = jnp.where(valid, table[jnp.minimum(pos // ps,
                                                  table.shape[0] - 1)],
                         TRASH_PAGE)
        off = pos % ps
        k_sel = min(c.index_topk, l_max)
        x = p["embed"][ids].astype(jnp.float32)
        counters = jnp.zeros((len(COUNTERS),), jnp.int32)
        aux: Dict[str, Any] = {"selected": [], "experts": []}
        heads, dn, dv = (c.num_attention_heads, c.qk_nope_head_dim,
                         c.v_head_dim)

        for i in range(c.n_layers):
            lp, lc = p[f"layer_{i}"], cache[f"layer_{i}"]
            h = rms_norm(x, lp["attn_norm"], c.rms_norm_eps)
            q_nope, q_rope, qi, wi = self._queries(lp, h, cos, sin)
            lat_rows, idx_rows = self._rows(lp, h, cos, sin)
            lat = lc["latent"].at[page, off].set(lat_rows)
            idx = lc["index_k"].at[page, off].set(idx_rows)
            cache = {**cache, f"layer_{i}": {"latent": lat, "index_k": idx}}

            def block_pages(b):
                return jax.lax.dynamic_slice(table, (b * pb,), (pb,))

            def key_pos(b):
                return b * kb + jnp.arange(kb, dtype=jnp.int32)

            # pass 1: index scores of the live context, block by block,
            # kept TRANSPOSED ([keys, queries]: a block is whole rows, and
            # the attention kernel wants its mask that way)
            qi_t = qi.astype(self.dtype).transpose(1, 2, 0)      # [J, di, n]
            wi_t = wi.T[:, None, :]                              # [J, 1, n]

            def score_block(b, buf):
                s = self._index_scores(
                    qi_t, wi_t, idx[block_pages(b)].reshape(kb, -1))
                s = jnp.where(key_pos(b)[:, None] <= pos[None, :], s, NEG)
                return jax.lax.dynamic_update_slice(buf, s, (b * kb, 0))
            scores = jax.lax.fori_loop(
                0, n_blocks, score_block,
                jnp.full((l_max, n), NEG, jnp.float32))

            # each query's k-th largest score, exactly: the sortable keys'
            # digits from the top, four bits a pass (a radix select over
            # the live blocks: 8 passes, 15 counts each, one read a block)
            def kth_key(keys):
                def digit(j, prefix):
                    shift = (28 - 4 * j).astype(jnp.uint32)
                    cands = prefix[None, :] | (
                        jnp.arange(1, 16, dtype=jnp.uint32)[:, None] << shift)

                    def count(b, acc):
                        blk = jax.lax.dynamic_slice(
                            keys, (b * kb, 0), (kb, n))
                        return acc + jnp.sum(
                            blk[None] >= cands[:, None, :], 1,
                            dtype=jnp.int32)
                    cnt = jax.lax.fori_loop(
                        0, n_blocks, count, jnp.zeros((15, n), jnp.int32))
                    # counts fall as the digit rises: as many digits reach
                    # k as the largest that does
                    best = jnp.sum(cnt >= k_sel, 0).astype(jnp.uint32)
                    return prefix | (best << shift)
                return jax.lax.fori_loop(
                    0, 8, digit, jnp.zeros((n,), jnp.uint32))
            keys = _sortable(scores)
            # with no more live rows than k every causal row is selected
            threshold = jax.lax.cond(
                live_len > k_sel, kth_key,
                lambda keys: jnp.zeros((n,), jnp.uint32), keys)
            selected = (keys >= threshold[None, :]) & (scores > NEG)

            def break_ties(selected):
                """Equal scores at the threshold: the earliest positions
                take the places left, as ``lax.top_k`` (decode) does."""
                above = (keys > threshold[None, :]) & (scores > NEG)
                equal = selected & ~above
                left = k_sel - jnp.sum(above, 0, dtype=jnp.int32)
                rank = jnp.cumsum(equal, 0, dtype=jnp.int32)
                return above | (equal & (rank <= left[None, :]))
            selected = jax.lax.cond(
                jnp.max(jnp.sum(selected, 0, dtype=jnp.int32)) > k_sel,
                break_ties, lambda sel: sel, selected)

            # pass 2: attention over the selected rows, block by block, an
            # online softmax carried through the walk. Transposed layout
            # (keys on the rows, queries on the lanes: ops/mla_attention.py)
            q_t = jnp.concatenate([q_nope, q_rope], -1).astype(
                self.dtype).transpose(1, 2, 0)                 # [H, dq, n]
            wk = lp["wk_b"].reshape(c.kv_lora_rank, heads, dn)
            wv = lp["wv_b"].reshape(c.kv_lora_rank, heads, dv)

            def attend_block(b, carry):
                m, l, acc, n_att = carry
                rows = lat[block_pages(b)].reshape(kb, -1)
                c_kv = rows[:, :c.kv_lora_rank]
                k_r = rows[:, c.kv_lora_rank:c.latent_width]
                k = jnp.concatenate([
                    jnp.einsum("sc,chn->hsn", c_kv, wk,
                               preferred_element_type=jnp.float32
                               ).astype(self.dtype),
                    jnp.broadcast_to(k_r[None], (heads,) + k_r.shape)], -1)
                v_t = jnp.einsum("sc,chv->hvs", c_kv, wv,
                                 preferred_element_type=jnp.float32
                                 ).astype(self.dtype)
                sel = jax.lax.dynamic_slice(selected, (b * kb, 0), (kb, n))
                m, l, acc = self._block_attend(
                    q_t, k, v_t, jnp.where(sel, 0.0, NEG), (m, l, acc))
                return m, l, acc, n_att + jnp.sum(sel & valid[None, :],
                                                  dtype=jnp.int32)
            _, l, acc, n_att = jax.lax.fori_loop(
                0, n_blocks, attend_block,
                (jnp.full((heads, 1, n), NEG, jnp.float32),
                 jnp.zeros((heads, 1, n), jnp.float32),
                 jnp.zeros((heads, dv, n), jnp.float32),
                 jnp.zeros((), jnp.int32)))
            o = (acc / l).transpose(2, 0, 1).reshape(n, heads * dv)
            x = x + self._mm(o, lp["wo"])
            h = rms_norm(x, lp["mlp_norm"], c.rms_norm_eps)
            y, stats, expert_ids = self._ffn(lp, i, h, valid)
            x = x + y
            n_live = jnp.sum(jnp.where(valid, pos + 1, 0), dtype=jnp.int32)
            counters = counters + jnp.stack(
                [stats[0], stats[1], n_live, n_att, n_live])
            if collect:
                aux["selected"].append(selected.T)
                aux["experts"].append(expert_ids)
        return (rms_norm(x, p["norm_f"], c.rms_norm_eps), cache, counters,
                aux)

    def prefill_chunk(self, p, cache, ids, start, n_valid, table_row):
        """-> (cache, logits [V] float32 of the chunk's last valid token,
        counters [5] int32)."""
        hidden, cache, counters, _ = self._chunk_hidden(
            p, cache, ids, start, n_valid, table_row)
        # the head over a tile of rows that holds the last valid one: a
        # single row would become a float32 multiply-and-reduce over the
        # whole head matrix
        rows = min(8, hidden.shape[0])
        first = jnp.clip(n_valid - rows, 0, hidden.shape[0] - rows)
        tile = jax.lax.dynamic_slice_in_dim(hidden, first, rows, 0)
        logits = self._logits(p, tile)
        return cache, logits[jnp.maximum(n_valid - 1, 0) - first], counters

    def apply(self, variables, ids, pad_mask=None, *, collect: bool = False):
        """Cache-free forward: ``ids`` [B, T] -> logits [B, T, V] float32
        (the prefill chunk over a private one-slot cache, a sequence at a
        time). ``pad_mask`` is accepted for the factory's calling
        convention and must be all ones. With ``collect`` also the
        per-layer selection masks and routed expert ids."""
        del pad_mask
        p = variables["params"]
        t = ids.shape[1]
        ps = min(16, t)
        n_pages = -(-t // ps)
        cache0 = jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype),
            self.cache_shapes(n_pages + 1, ps))
        table = jnp.arange(1, n_pages + 1, dtype=jnp.int32)

        def one(row):
            hidden, _, _, aux = self._chunk_hidden(
                p, cache0, row, jnp.int32(0), jnp.int32(t), table,
                collect=collect)
            return self._logits(p, hidden), aux
        logits, aux = jax.vmap(one)(ids)
        return (logits, aux) if collect else logits

    # -------------------------------------------------------------- decode

    def decode_step(self, p, cache, tokens, positions, block_table, active,
                    collect: bool = False):
        """One token for every slot. ``tokens`` / ``positions`` [S]: the
        token in each slot's state and the index it is written at;
        ``block_table`` [S, n_pages]; ``active`` [S]. Returns (cache,
        logits [S, V] float32, counters [5] int32, aux)."""
        c = self.cfg
        s_n = tokens.shape[0]
        ps = cache["layer_0"]["latent"].shape[1]
        l_max = block_table.shape[1] * ps
        k_sel = min(c.index_topk, l_max)
        cos, sin = _angles(c, positions)
        page = jnp.take_along_axis(
            block_table, jnp.minimum(positions // ps,
                                     block_table.shape[1] - 1)[:, None],
            axis=1)[:, 0]
        off = positions % ps
        live = active > 0
        key_pos = jnp.arange(l_max, dtype=jnp.int32)
        causal = key_pos[None, :] <= positions[:, None]          # [S, L]
        heads, dn, dv = (c.num_attention_heads, c.qk_nope_head_dim,
                         c.v_head_dim)
        x = p["embed"][tokens].astype(jnp.float32)
        counters = jnp.zeros((len(COUNTERS),), jnp.int32)
        aux: Dict[str, Any] = {"selected": [], "experts": []}
        n_live = jnp.sum(jnp.where(live, positions + 1, 0), dtype=jnp.int32)

        for i in range(c.n_layers):
            lp, lc = p[f"layer_{i}"], cache[f"layer_{i}"]
            h = rms_norm(x, lp["attn_norm"], c.rms_norm_eps)
            q_nope, q_rope, qi, wi = self._queries(lp, h, cos, sin)
            lat_rows, idx_rows = self._rows(lp, h, cos, sin)
            lat = lc["latent"].at[page, off].set(lat_rows)
            idx = lc["index_k"].at[page, off].set(idx_rows)
            cache = {**cache, f"layer_{i}": {"latent": lat, "index_k": idx}}
            # score every live row of every slot, take the k best
            ki = idx[block_table].reshape(s_n, l_max, -1)
            sc = jnp.einsum("sjd,sld->sjl", qi.astype(self.dtype), ki,
                            preferred_element_type=jnp.float32)
            sc = jnp.sum(jax.nn.relu(sc) * wi[:, :, None], axis=1)
            sc = jnp.where(causal, sc, NEG)
            top, sel = jax.lax.top_k(sc, k_sel)                  # [S, K]
            ok = top > NEG
            phys = jnp.take_along_axis(block_table, sel // ps, axis=1) * ps \
                + sel % ps
            rows = lat.reshape(-1, lat.shape[-1])[phys]          # [S, K, c+r]
            # attention in the latent space: W_kvb absorbed on both sides
            q_lat = jnp.einsum(
                "shn,chn->shc", q_nope.astype(self.dtype),
                lp["wk_b"].reshape(c.kv_lora_rank, heads, dn),
                preferred_element_type=jnp.float32)
            qq = jnp.concatenate(
                [q_lat, q_rope, jnp.zeros(
                    q_rope.shape[:-1] + (c.latent_row - c.latent_width,),
                    jnp.float32)], -1).astype(self.dtype)
            s = jnp.einsum("shd,skd->shk", qq, rows,
                           preferred_element_type=jnp.float32
                           ) * c.softmax_scale
            s = jnp.where(ok[:, None, :], s, NEG)
            pr = jax.nn.softmax(s, -1)
            o_lat = jnp.einsum("shk,skc->shc", pr.astype(self.dtype),
                               rows[..., :c.kv_lora_rank],
                               preferred_element_type=jnp.float32)
            o = jnp.einsum("shc,chv->shv", o_lat.astype(self.dtype),
                           lp["wv_b"].reshape(c.kv_lora_rank, heads, dv),
                           preferred_element_type=jnp.float32)
            x = x + self._mm(o.reshape(s_n, heads * dv), lp["wo"])
            h = rms_norm(x, lp["mlp_norm"], c.rms_norm_eps)
            y, stats, expert_ids = self._ffn(lp, i, h, live, decode=True)
            x = x + y
            n_att = jnp.sum(ok & live[:, None], dtype=jnp.int32)
            counters = counters + jnp.stack(
                [stats[0], stats[1], n_live, n_att, n_live])
            if collect:
                aux["selected"].append(jnp.where(ok, sel, -1))
                aux["experts"].append(expert_ids)
        logits = self._logits(p, rms_norm(x, p["norm_f"], c.rms_norm_eps))
        return cache, logits, counters, aux
