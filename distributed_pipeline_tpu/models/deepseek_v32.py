"""Latent-attention expert models as ONE CHIP'S SHARE of an expert-parallel
deployment: DeepSeek-V3.2-Exp (``model_family="deepseek_v32"``, configured
here) and dots3-note-prev (``"dots3_note"``, configured in
models/dots3_note.py) through ONE set of layer equations
(:class:`LatentMoEModel`). A configuration describes each layer's attention
by a :class:`LayerKind` (heads, ranks, head sizes, rotary frequencies, a
window or none, the indexer or none, a headwise output gate or none, a latent
rescale or none); DeepSeek-V3.2-Exp is the case "every layer full, no gate,
no rescale, YaRN".

What the serving stack runs beside GPT-2: RMSNorm, multi-head latent attention (low-rank query,
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
``[pages, page_size, index_head_dim]`` (lane-dense, PERF.md PR 28); a WINDOW
layer (``LayerKind.window`` w: a query sees itself and the w - 1 rows before
it, and has no indexer) holds instead latent rows ``[window_pages, page_size,
row]`` in a pool of its own, where a slot's pages are a RING: position p
lies in the slot's ring page ``(p // page_size) % ring``, so a slot keeps
window + one prefill chunk of rows resident whatever its length
(:meth:`LatentMoEModel.window_rows`):

* :meth:`LatentMoEModel.prefill_chunk` — one CHUNK of one prompt: writes
  the chunk's rows, scores the whole live context with the indexer, finds
  each query's top-k threshold, and attends block by block over the live
  context (un-absorbed MLA, online softmax; work follows the live length,
  not the compiled maximum); a window layer walks only the blocks its
  band touches;
* :meth:`LatentMoEModel.decode_step` — one token for every slot: scores
  every live row, takes the top-k rows out of the latent pool and attends
  over them in the latent space (``W_kvb`` absorbed into query and output);
  a window layer attends the same way over the pages that hold its window.

:meth:`LatentMoEModel.apply` (the cache-free forward of the tests) is
the prefill chunk over a private one-slot cache, so there is one set of layer
equations. Arithmetic: the residual stream, every norm, the router, the
index scores and the softmax are float32; matmul operands are ``dtype``
(bfloat16 as served) with float32 accumulation; cache rows are ``dtype``.
RoPE layouts (the file of the configuration states them too): MLA rotates
interleaved pairs in place, the indexer rotates split halves; YaRN's
``mscale`` enters through the softmax scale, as the source has it.

The prefill's selection (index scores block by block, the radix select for
each query's k-th score, the tie-break) and the choice of arm of the two
Pallas-backed pieces live in models/sparse_select.py, shared with
models/keye_vl2.py, which also takes ``route`` (and with it
``_best_first``), ``rms_norm``, ``layer_norm``, ``rope_halves``, ``_angles``,
``init_params`` and ``last_valid_logits`` from here.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import sparse_select
from .sparse_select import NEG

__all__ = ["DeepseekV32Config", "LatentMoEModel", "LayerKind", "COUNTERS",
           "WINDOW_COUNTERS", "route"]

# what a program counts beside its tokens (int32, same order everywhere):
# routed assignments that fell on held experts, held experts that saw a
# token (summed over expert layers), rows the indexer scored, latent rows
# attention read, rows that were live (the last three summed over layers)
COUNTERS = ("expert_assignments_held", "experts_touched",
            "index_rows_scored", "kv_rows_attended", "kv_rows_live")
# behind them, of a model with window layers: rows its window layers read,
# and rows a cache that never frees would hold live for them (context x
# window layers); the three above then count the full layers only
WINDOW_COUNTERS = ("window_rows_attended", "window_rows_live")
TRASH_PAGE = 0       # serving/paged_kv.py's reserved page
KV_BLOCK = 512       # rows of context the chunked prefill reads a step


@dataclasses.dataclass(frozen=True)
class LayerKind:
    """One layer's latent attention, as the shared equations read it."""

    heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    inv_freq: Tuple[float, ...]     # rotary frequencies [rope/2]
    softmax_scale: float
    window: int = 0         # w > 0: a query sees itself and the w - 1 before
    indexer: bool = True    # lightning-indexer top-k (never with a window)
    gate: bool = False      # head j's output times sigmoid(h W_g)[t, j]
    q_rescale: float = 1.0  # the query latent, after its norm, times this
    kv_rescale: float = 1.0  # the KV latent, after its norm, times this

    def __post_init__(self) -> None:
        if self.window and self.indexer:
            raise ValueError("a window layer has no indexer")

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


def check_held_experts(cfg: Any) -> None:
    held = cfg.n_routed_experts_held
    if not (0 < held and cfg.expert_offset + held <= cfg.n_routed_experts):
        raise ValueError(
            f"held experts [{cfg.expert_offset}, "
            f"{cfg.expert_offset + held}) lie outside the router's "
            f"{cfg.n_routed_experts}")
    if cfg.n_routed_experts % cfg.n_group:
        raise ValueError("n_routed_experts must divide by n_group")


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
        check_held_experts(cfg)
        return cfg

    @property
    def softmax_scale(self) -> float:
        m = 1.0
        if self.rope_factor > 1.0:
            m = 0.1 * self.rope_mscale_all_dim * math.log(
                self.rope_factor) + 1.0
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 \
            * m * m

    def layer(self, i: int) -> LayerKind:
        """Every layer is full: all earlier rows, through the indexer."""
        del i
        return LayerKind(
            heads=self.num_attention_heads, q_lora_rank=self.q_lora_rank,
            kv_lora_rank=self.kv_lora_rank,
            qk_nope_head_dim=self.qk_nope_head_dim,
            qk_rope_head_dim=self.qk_rope_head_dim,
            v_head_dim=self.v_head_dim,
            inv_freq=tuple(map(float, yarn_inv_freq(self))),
            softmax_scale=self.softmax_scale)


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


def _angles(inv_freq: Tuple[float, ...], positions: jnp.ndarray
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(
        inv_freq, jnp.float32)[None, :]
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


def route(cfg: Any, scores: jnp.ndarray, bias: jnp.ndarray
          ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Group-limited top-k over the published router width.

    ``scores`` [T, E] sigmoid scores (float32), ``bias`` [E] the
    ``e_score_correction_bias``. The choice is made on ``scores + bias``:
    a group's score is the sum of its two best, the best ``topk_group``
    groups stay, the ``num_experts_per_tok`` best experts inside them are
    taken. The weights are the UNBIASED scores of the taken, normalised,
    times ``routed_scaling_factor``. With one group (``n_group`` 1: a
    source without the group keys) the best experts of all are taken.
    Returns (expert ids [T, k] int32, weights [T, k] float32)."""
    t, e = scores.shape
    g = cfg.n_group
    choice = scores + bias[None, :].astype(jnp.float32)
    if g == 1:
        masked = choice
    else:
        grouped = choice.reshape(t, g, e // g)
        best = _best_first(grouped.reshape(t * g, e // g), 2)
        group_score = jnp.take_along_axis(
            grouped, best.reshape(t, g, 2), axis=2).sum(-1)      # [T, G]
        keep = _best_first(group_score, cfg.topk_group)          # [T, kg]
        group_on = jnp.any(
            keep[:, :, None] == jnp.arange(g)[None, None, :], 1)
        masked = jnp.where(group_on[:, :, None], grouped,
                           -jnp.inf).reshape(t, e)
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


def last_valid_logits(hidden: jnp.ndarray, n_valid, logits_fn
                      ) -> jnp.ndarray:
    """Logits of a chunk's last valid row: the head (``logits_fn``) over a
    tile of rows that holds it — a single row would become a float32
    multiply-and-reduce over the whole head matrix."""
    rows = min(8, hidden.shape[0])
    first = jnp.clip(n_valid - rows, 0, hidden.shape[0] - rows)
    tile = jax.lax.dynamic_slice_in_dim(hidden, first, rows, 0)
    return logits_fn(tile)[jnp.maximum(n_valid - 1, 0) - first]


def init_params(shapes: Dict[str, Any], rng: jax.Array, std: float,
                dtype: Any, embed_std: Optional[float] = None
                ) -> Dict[str, Any]:
    """A parameter tree of ``shapes`` (nested dicts of tuples): normal(0,
    ``std``) matrices (``embed`` at ``embed_std`` where given), unit norm
    scales, zero biases (a router's correction bias float32)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    leaves = []
    for i, (path, shape) in enumerate(flat):
        name = path[-1].key
        if name == "router_bias":
            leaves.append(jnp.zeros(shape, jnp.float32))
        elif name.endswith(("norm", "norm_g", "norm_f")):
            leaves.append(jnp.ones(shape, dtype))
        elif name.endswith("norm_b"):
            leaves.append(jnp.zeros(shape, dtype))
        else:
            scale = embed_std if name == "embed" and embed_std else std
            leaves.append((scale * jax.random.normal(
                jax.random.fold_in(rng, i), shape, jnp.float32)
            ).astype(dtype))
    return jax.tree_util.tree_unflatten(treedef, leaves)


# -------------------------------------------------------------- the model

@dataclasses.dataclass(frozen=True)
class LatentMoEModel:
    """The functions of one configuration (``cfg``: :class:`DeepseekV32Config`
    or models/dots3_note.py's, which describe their layers through
    ``cfg.layer(i)``). ``dtype`` is the type of the weights, the cache rows
    and the matmul operands."""

    cfg: Any
    seq_len: int
    dtype: Any = jnp.bfloat16
    # the tests' hook: "xla" or "interpret" forces that arm of the prefill's
    # two Pallas-backed pieces; "auto" is what every caller runs
    kernel_impl: str = "auto"

    chunked_prefill = True   # what DecodeEngine asks a model

    @functools.cached_property
    def kinds(self) -> Tuple[LayerKind, ...]:
        return tuple(self.cfg.layer(i) for i in range(self.cfg.n_layers))

    @property
    def counters(self) -> Tuple[str, ...]:
        """What its programs return behind the tokens."""
        windowed = any(k.window for k in self.kinds)
        return COUNTERS + (WINDOW_COUNTERS if windowed else ())

    def window_rows(self, chunk: int) -> int:
        """Rows a slot must keep resident in a window layer when prompts are
        written ``chunk`` tokens a dispatch (the widest window and the chunk
        whose queries read it), whatever the slot's length; 0 for a model
        without window layers. DecodeEngine sizes the slots' rings by it."""
        widest = max(k.window for k in self.kinds)
        return widest + chunk if widest else 0

    @property
    def vocab_size(self) -> int:
        return self.cfg.vocab_size

    # ---------------------------------------------------------- parameters

    def param_shapes(self) -> Dict[str, Any]:
        c = self.cfg
        d = c.hidden_size

        def attn(k: LayerKind) -> Dict[str, Any]:
            out = {
                "attn_norm": (d,), "wq_a": (d, k.q_lora_rank),
                "q_norm": (k.q_lora_rank,),
                "wq_b": (k.q_lora_rank, k.heads * k.qk_head_dim),
                "wkv_a": (d, k.latent_width), "kv_norm": (k.kv_lora_rank,),
                "wk_b": (k.kv_lora_rank, k.heads * k.qk_nope_head_dim),
                "wv_b": (k.kv_lora_rank, k.heads * k.v_head_dim),
                "wo": (k.heads * k.v_head_dim, d), "mlp_norm": (d,)}
            if k.indexer:
                out.update({
                    "idx_wq_b": (k.q_lora_rank,
                                 c.index_n_heads * c.index_head_dim),
                    "idx_wk": (d, c.index_head_dim),
                    "idx_k_norm_g": (c.index_head_dim,),
                    "idx_k_norm_b": (c.index_head_dim,),
                    "idx_w": (d, c.index_n_heads)})
            if k.gate:
                out["wo_gate"] = (d, k.heads)
            return out
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
        for i, k in enumerate(self.kinds):
            out[f"layer_{i}"] = {
                **attn(k), **(dense if i < c.n_dense_layers else moe)}
        return out

    def init(self, rng: jax.Array, *_example: Any) -> Dict[str, Any]:
        """``{"params": tree}``: normal(0, initializer_range) matrices, unit
        norm scales, zero biases (the router's correction bias float32)."""
        return {"params": init_params(
            self.param_shapes(), rng, self.cfg.initializer_range,
            self.dtype)}

    # --------------------------------------------------------------- cache

    def cache_shapes(self, max_pages: int, page_size: int,
                     window_pages: int = 0) -> Dict[str, Any]:
        """Per-layer state of two kinds: a full layer's latent and
        indexer-key rows in the paged pool (``max_pages``), a window
        layer's latent rows in the window pool (``window_pages``: the
        slots' rings and the trash page)."""
        c = self.cfg

        def rows(pages: int, width: int) -> jax.ShapeDtypeStruct:
            return jax.ShapeDtypeStruct((pages, page_size, width),
                                        self.dtype)
        return {f"layer_{i}": (
            {"window": rows(window_pages, k.latent_row)} if k.window else
            {"latent": rows(max_pages, k.latent_row),
             "index_k": rows(max_pages, c.index_head_dim)})
            for i, k in enumerate(self.kinds)}

    # ----------------------------------------------------------- the maths

    def _mm(self, a: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
        return jnp.dot(a.astype(self.dtype), w.astype(self.dtype),
                       preferred_element_type=jnp.float32)

    def _logits(self, p, hidden: jnp.ndarray) -> jnp.ndarray:
        """The untied head, stored [V, D] like the embedding: its rows are
        whole lane tiles, a [D, V] slice of the vocabulary's are not."""
        return jnp.einsum("td,vd->tv", hidden.astype(self.dtype), p["head"],
                          preferred_element_type=jnp.float32)

    def _swiglu(self, h: jnp.ndarray, wg, wu, wd) -> jnp.ndarray:
        a = jax.nn.silu(self._mm(h, wg)) * self._mm(h, wu)
        return self._mm(a, wd)

    def _angles_by_kind(self, positions) -> Dict[Tuple[float, ...], Any]:
        """(cos, sin) of the positions at each distinct set of rotary
        frequencies among the layers, keyed by ``LayerKind.inv_freq``."""
        return {f: _angles(f, positions)
                for f in dict.fromkeys(k.inv_freq for k in self.kinds)}

    def _queries(self, lp, k: LayerKind, h, cos, sin):
        """Normalised layer input -> (q_nope [T, H, n], q_rope [T, H, r]
        roped, indexer q [T, J, di] roped, indexer head weights [T, J]; the
        last two None in a layer without the indexer)."""
        c = self.cfg
        t = h.shape[0]
        c_q = rms_norm(self._mm(h, lp["wq_a"]), lp["q_norm"], c.rms_norm_eps)
        if k.q_rescale != 1.0:
            c_q = c_q * k.q_rescale
        q = self._mm(c_q, lp["wq_b"]).reshape(t, k.heads, k.qk_head_dim)
        q_nope = q[..., :k.qk_nope_head_dim]
        q_rope = rope_interleaved(q[..., k.qk_nope_head_dim:], cos, sin)
        if not k.indexer:
            return q_nope, q_rope, None, None
        qi = self._mm(c_q, lp["idx_wq_b"]).reshape(
            t, c.index_n_heads, c.index_head_dim)
        r = k.qk_rope_head_dim
        qi = jnp.concatenate(
            [rope_halves(qi[..., :r], cos, sin), qi[..., r:]], -1)
        wi = self._mm(h, lp["idx_w"]) * (
            c.index_n_heads ** -0.5 * c.index_head_dim ** -0.5)
        return q_nope, q_rope, qi, wi

    def _rows(self, lp, k: LayerKind, h, cos, sin):
        """What a token leaves in the cache: latent row (the normalised KV
        latent, the roped shared key, zeros up to whole lane tiles) and
        indexer key [T, di] (None without the indexer), in the cache's
        type."""
        c = self.cfg
        kv = self._mm(h, lp["wkv_a"])
        c_kv = rms_norm(kv[:, :k.kv_lora_rank], lp["kv_norm"],
                        c.rms_norm_eps)
        if k.kv_rescale != 1.0:
            c_kv = c_kv * k.kv_rescale
        k_r = rope_interleaved(kv[:, k.kv_lora_rank:], cos, sin)
        fill = jnp.zeros((h.shape[0], k.latent_row - k.latent_width),
                         jnp.float32)
        lat = jnp.concatenate([c_kv, k_r, fill], -1).astype(self.dtype)
        if not k.indexer:
            return lat, None
        ki = layer_norm(self._mm(h, lp["idx_wk"]), lp["idx_k_norm_g"],
                        lp["idx_k_norm_b"], 1e-6)
        r = k.qk_rope_head_dim
        ki = jnp.concatenate(
            [rope_halves(ki[:, :r], cos, sin), ki[:, r:]], -1)
        return lat, ki.astype(self.dtype)

    def _project_out(self, lp, k: LayerKind, h, o) -> jnp.ndarray:
        """Heads' outputs ``o`` [T, H, dv] float32 (each times its gate,
        where the layer has one) through ``W_o``."""
        if k.gate:
            o = o * jax.nn.sigmoid(self._mm(h, lp["wo_gate"]))[:, :, None]
        return self._mm(o.reshape(o.shape[0], k.heads * k.v_head_dim),
                        lp["wo"])

    def _block_kv(self, k: LayerKind, wk, wv, rows):
        """A block's cached rows [K, row] -> (keys [H, K, dq], values
        transposed [H, dv, K]) in the un-absorbed form; ``wk`` [c, H, n],
        ``wv`` [c, H, dv] the layer's ``W_kvb`` a head."""
        c_kv = rows[:, :k.kv_lora_rank]
        k_r = rows[:, k.kv_lora_rank:k.latent_width]
        keys = jnp.concatenate([
            jnp.einsum("sc,chn->hsn", c_kv, wk,
                       preferred_element_type=jnp.float32
                       ).astype(self.dtype),
            jnp.broadcast_to(k_r[None], (k.heads,) + k_r.shape)], -1)
        v_t = jnp.einsum("sc,chv->hvs", c_kv, wv,
                         preferred_element_type=jnp.float32
                         ).astype(self.dtype)
        return keys, v_t

    def _attend_latent(self, lp, k: LayerKind, q_nope, q_rope, rows, ok):
        """A decode step's attention in the latent space (``W_kvb``
        absorbed on both sides): ``rows`` [S, K, row] each slot's cached
        rows, ``ok`` [S, K] which of them count -> [S, H, dv] float32."""
        dn, dv = k.qk_nope_head_dim, k.v_head_dim
        q_lat = jnp.einsum(
            "shn,chn->shc", q_nope.astype(self.dtype),
            lp["wk_b"].reshape(k.kv_lora_rank, k.heads, dn),
            preferred_element_type=jnp.float32)
        qq = jnp.concatenate(
            [q_lat, q_rope, jnp.zeros(
                q_rope.shape[:-1] + (k.latent_row - k.latent_width,),
                jnp.float32)], -1).astype(self.dtype)
        s = jnp.einsum("shd,skd->shk", qq, rows,
                       preferred_element_type=jnp.float32
                       ) * k.softmax_scale
        s = jnp.where(ok[:, None, :], s, NEG)
        pr = jax.nn.softmax(s, -1)
        o_lat = jnp.einsum("shk,skc->shc", pr.astype(self.dtype),
                           rows[..., :k.kv_lora_rank],
                           preferred_element_type=jnp.float32)
        return jnp.einsum("shc,chv->shv", o_lat.astype(self.dtype),
                          lp["wv_b"].reshape(k.kv_lora_rank, k.heads, dv),
                          preferred_element_type=jnp.float32)

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
                      window_row=None, collect: bool = False):
        """One chunk of one sequence through every layer.

        ``ids`` [C] the chunk's tokens (zero-padded past ``n_valid``), at
        positions ``start ..``; ``table_row`` [n_pages] the sequence's
        pages in the paged pool, ``window_row`` [ring] its ring of pages in
        the window pool (a model with window layers). Writes the chunk's
        rows first, then reads the live context (the chunk's own rows among
        it) back from the pools, so prefill sees exactly the rows decode
        will. Returns (final-normed hidden [C, D] float32, cache, counters
        int32, aux)."""
        c = self.cfg
        n = ids.shape[0]
        ps = jax.tree_util.tree_leaves(cache)[0].shape[1]
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
        angles = self._angles_by_kind(pos)
        # where the chunk's rows go (padded tail -> the trash page)
        page = jnp.where(valid, table[jnp.minimum(pos // ps,
                                                  table.shape[0] - 1)],
                         TRASH_PAGE)
        off = pos % ps
        k_sel = min(c.index_topk, l_max)
        x = p["embed"][ids].astype(jnp.float32)
        n_live = jnp.sum(jnp.where(valid, pos + 1, 0), dtype=jnp.int32)

        def key_pos(b):
            return b * kb + jnp.arange(kb, dtype=jnp.int32)

        def walk(k, lp, q_nope, q_rope, first, block_rows, block_mask):
            """Attention over context blocks ``first .. n_blocks``, an
            online softmax carried through the walk. Transposed layout
            (keys on the rows, queries on the lanes: ops/mla_attention.py).
            ``block_rows(b)`` [kb, row] the block's cached rows,
            ``block_mask(b)`` [kb, n] which (key, query) pairs count.
            Returns (heads' outputs [n, H, dv], pairs counted)."""
            q_t = jnp.concatenate([q_nope, q_rope], -1).astype(
                self.dtype).transpose(1, 2, 0)                 # [H, dq, n]
            wk = lp["wk_b"].reshape(k.kv_lora_rank, k.heads,
                                    k.qk_nope_head_dim)
            wv = lp["wv_b"].reshape(k.kv_lora_rank, k.heads, k.v_head_dim)

            def attend_block(b, carry):
                m, l, acc, n_att = carry
                keys, v_t = self._block_kv(k, wk, wv, block_rows(b))
                sel = block_mask(b)
                m, l, acc = sparse_select.block_attend(
                    self.kernel_impl, q_t, keys, v_t,
                    jnp.where(sel, 0.0, NEG), (m, l, acc), k.softmax_scale)
                return m, l, acc, n_att + jnp.sum(sel & valid[None, :],
                                                  dtype=jnp.int32)
            _, l, acc, n_att = jax.lax.fori_loop(
                first, n_blocks, attend_block,
                (jnp.full((k.heads, 1, n), NEG, jnp.float32),
                 jnp.zeros((k.heads, 1, n), jnp.float32),
                 jnp.zeros((k.heads, k.v_head_dim, n), jnp.float32),
                 jnp.zeros((), jnp.int32)))
            return (acc / l).transpose(2, 0, 1), n_att

        def full_layer(k, lp, lc, h):
            """Every earlier row through the indexer's top-k."""
            cos, sin = angles[k.inv_freq]
            q_nope, q_rope, qi, wi = self._queries(lp, k, h, cos, sin)
            lat_rows, idx_rows = self._rows(lp, k, h, cos, sin)
            lat = lc["latent"].at[page, off].set(lat_rows)
            idx = lc["index_k"].at[page, off].set(idx_rows)

            def block_pages(b):
                return jax.lax.dynamic_slice(table, (b * pb,), (pb,))

            # pass 1: index scores of the live context and each query's
            # top-k among them (models/sparse_select.py: scored block by
            # block, a radix select for the k-th score, ties to the earlier
            # position), kept TRANSPOSED ([keys, queries])
            scores = sparse_select.score_context(
                self.kernel_impl, qi.astype(self.dtype).transpose(1, 2, 0),
                wi.T[:, None, :],
                lambda b: idx[block_pages(b)].reshape(kb, -1), pos, kb,
                n_blocks, l_max)
            selected = sparse_select.select_top_k(
                scores, live_len, k_sel, kb, n_blocks)

            # pass 2: attention over the selected rows
            o, n_att = walk(
                k, lp, q_nope, q_rope, 0,
                lambda b: lat[block_pages(b)].reshape(kb, -1),
                lambda b: jax.lax.dynamic_slice(selected, (b * kb, 0),
                                                (kb, n)))
            return (o, {"latent": lat, "index_k": idx}, selected.T,
                    {"index_rows_scored": n_live, "kv_rows_attended": n_att,
                     "kv_rows_live": n_live})

        def window_layer(k, lp, lc, h):
            """The query and the ``window - 1`` rows before it, out of the
            slot's ring: only the blocks the chunk's band touches are
            walked, whatever the context."""
            cos, sin = angles[k.inv_freq]
            ring = window_row.shape[0]
            q_nope, q_rope, _, _ = self._queries(lp, k, h, cos, sin)
            lat_rows, _ = self._rows(lp, k, h, cos, sin)
            win = lc["window"].at[
                jnp.where(valid, window_row[(pos // ps) % ring], TRASH_PAGE),
                off].set(lat_rows)

            def block_rows(b):
                # a ring page holds ONE of the logical pages that map to
                # it; the band below admits only rows written for the
                # position asked (an overwritten row lies behind every
                # query's window, one not yet written ahead of every query)
                ring_pages = (b * pb + jnp.arange(pb, dtype=jnp.int32)) % ring
                return win[window_row[ring_pages]].reshape(kb, -1)

            def band(b):
                behind = pos[None, :] - key_pos(b)[:, None]
                return (behind >= 0) & (behind < k.window)
            first = jnp.maximum(start - (k.window - 1), 0) // kb
            o, n_att = walk(k, lp, q_nope, q_rope, first, block_rows, band)
            return (o, {"window": win}, None,
                    {"window_rows_attended": n_att,
                     "window_rows_live": n_live})

        x, cache, counters, aux = self._layers(
            p, cache, x, window_layer, full_layer, valid, collect=collect)
        return (rms_norm(x, p["norm_f"], c.rms_norm_eps), cache, counters,
                aux)

    def _layers(self, p, cache, x, window_layer, full_layer, live, *,
                decode: bool = False, collect: bool = False):
        """The residual stream through every layer: attention by the
        caller's function for the layer's kind (``(k, lp, lc, h)`` ->
        heads' outputs [T, H, dv], the layer's cache, what was selected,
        rows counted by counter name), the output gate and projection, the
        feed-forward. Returns (x, cache, counters int32 in
        ``self.counters``' order, aux)."""
        c = self.cfg
        zero = jnp.zeros((), jnp.int32)
        counters = jnp.zeros((len(self.counters),), jnp.int32)
        aux: Dict[str, Any] = {"selected": [], "experts": []}
        for i, k in enumerate(self.kinds):
            lp = p[f"layer_{i}"]
            h = rms_norm(x, lp["attn_norm"], c.rms_norm_eps)
            o, lc, selected, counted = (
                window_layer if k.window else full_layer)(
                    k, lp, cache[f"layer_{i}"], h)
            cache = {**cache, f"layer_{i}": lc}
            x = x + self._project_out(lp, k, h, o)
            h = rms_norm(x, lp["mlp_norm"], c.rms_norm_eps)
            y, stats, expert_ids = self._ffn(lp, i, h, live, decode=decode)
            x = x + y
            counted.update(expert_assignments_held=stats[0],
                           experts_touched=stats[1])
            counters = counters + jnp.stack(
                [counted.get(name, zero) for name in self.counters])
            if collect:
                aux["selected"].append(selected)
                aux["experts"].append(expert_ids)
        return x, cache, counters, aux

    def prefill_chunk(self, p, cache, ids, start, n_valid, table_row,
                      window_row=None):
        """-> (cache, logits [V] float32 of the chunk's last valid token,
        counters int32)."""
        hidden, cache, counters, _ = self._chunk_hidden(
            p, cache, ids, start, n_valid, table_row, window_row)
        return cache, last_valid_logits(
            hidden, n_valid, lambda tile: self._logits(p, tile)), counters

    def apply(self, variables, ids, pad_mask=None, *, collect: bool = False):
        """Cache-free forward: ``ids`` [B, T] -> logits [B, T, V] float32
        (the prefill chunk over a private one-slot cache, a sequence at a
        time; a window layer's ring is as long as the sequence).
        ``pad_mask`` is accepted for the factory's calling convention and
        must be all ones. With ``collect`` also the per-layer selection
        masks (None for a window layer) and routed expert ids."""
        del pad_mask
        p = variables["params"]
        t = ids.shape[1]
        ps = min(16, t)
        n_pages = -(-t // ps)
        cache0 = jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype),
            self.cache_shapes(n_pages + 1, ps, n_pages + 1))
        table = jnp.arange(1, n_pages + 1, dtype=jnp.int32)

        def one(row):
            hidden, _, _, aux = self._chunk_hidden(
                p, cache0, row, jnp.int32(0), jnp.int32(t), table, table,
                collect=collect)
            return self._logits(p, hidden), aux
        logits, aux = jax.vmap(one)(ids)
        return (logits, aux) if collect else logits

    # -------------------------------------------------------------- decode

    def decode_step(self, p, cache, tokens, positions, block_table, active,
                    window_table=None, collect: bool = False):
        """One token for every slot. ``tokens`` / ``positions`` [S]: the
        token in each slot's state and the index it is written at;
        ``block_table`` [S, n_pages]; ``window_table`` [S, ring] the slots'
        rings (a model with window layers); ``active`` [S]. Returns (cache,
        logits [S, V] float32, counters int32, aux)."""
        c = self.cfg
        s_n = tokens.shape[0]
        ps = jax.tree_util.tree_leaves(cache)[0].shape[1]
        l_max = block_table.shape[1] * ps
        k_sel = min(c.index_topk, l_max)
        angles = self._angles_by_kind(positions)
        page = jnp.take_along_axis(
            block_table, jnp.minimum(positions // ps,
                                     block_table.shape[1] - 1)[:, None],
            axis=1)[:, 0]
        off = positions % ps
        live = active > 0
        key_pos = jnp.arange(l_max, dtype=jnp.int32)
        causal = key_pos[None, :] <= positions[:, None]          # [S, L]
        x = p["embed"][tokens].astype(jnp.float32)
        n_live = jnp.sum(jnp.where(live, positions + 1, 0), dtype=jnp.int32)

        def full_layer(k, lp, lc, h):
            cos, sin = angles[k.inv_freq]
            q_nope, q_rope, qi, wi = self._queries(lp, k, h, cos, sin)
            lat_rows, idx_rows = self._rows(lp, k, h, cos, sin)
            lat = lc["latent"].at[page, off].set(lat_rows)
            idx = lc["index_k"].at[page, off].set(idx_rows)
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
            o = self._attend_latent(lp, k, q_nope, q_rope, rows, ok)
            n_att = jnp.sum(ok & live[:, None], dtype=jnp.int32)
            return (o, {"latent": lat, "index_k": idx},
                    jnp.where(ok, sel, -1),
                    {"index_rows_scored": n_live, "kv_rows_attended": n_att,
                     "kv_rows_live": n_live})

        def window_layer(k, lp, lc, h):
            cos, sin = angles[k.inv_freq]
            ring = window_table.shape[1]
            q_nope, q_rope, _, _ = self._queries(lp, k, h, cos, sin)
            lat_rows, _ = self._rows(lp, k, h, cos, sin)
            last = positions // ps                    # the newest logical page
            win = lc["window"].at[
                jnp.take_along_axis(window_table, (last % ring)[:, None],
                                    axis=1)[:, 0], off].set(lat_rows)
            # the pages a window can touch, oldest first
            n_wp = min(ring, (k.window - 2) // ps + 2)
            logical = last[:, None] - (n_wp - 1) + jnp.arange(
                n_wp, dtype=jnp.int32)[None, :]                  # [S, n_wp]
            rows = win[jnp.take_along_axis(window_table, logical % ring,
                                           axis=1)]
            rows = rows.reshape(s_n, n_wp * ps, -1)
            key_at = (logical[:, :, None] * ps + jnp.arange(
                ps, dtype=jnp.int32)[None, None, :]).reshape(s_n, -1)
            behind = positions[:, None] - key_at
            ok = (key_at >= 0) & (behind >= 0) & (behind < k.window)
            o = self._attend_latent(lp, k, q_nope, q_rope, rows, ok)
            n_att = jnp.sum(ok & live[:, None], dtype=jnp.int32)
            return (o, {"window": win}, None,
                    {"window_rows_attended": n_att,
                     "window_rows_live": n_live})

        x, cache, counters, aux = self._layers(
            p, cache, x, window_layer, full_layer, live, decode=True,
            collect=collect)
        logits = self._logits(p, rms_norm(x, p["norm_f"], c.rms_norm_eps))
        return cache, logits, counters, aux
