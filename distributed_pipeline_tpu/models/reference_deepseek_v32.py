"""Plain reference for the DeepSeek-V3.2-Exp configurations: float32
``jax.numpy``, matmuls at ``highest`` precision, no kernel, no cache, no
absorbed projection, one sequence at a time.

It imports nothing of the program and takes nothing the program has made. It
makes its own weights from the seed (``make_weights``; the driver hands the
same arrays to the program, in the configuration's ``param_dtype``) and
computes the forward pass of ONE CHIP'S SHARE of the deployment the
configuration file states: every width as published, the router over all
``n_routed_experts``, and of the routed experts only the
``n_routed_experts_held`` from ``expert_offset`` (a Python loop over them,
each over the rows routed to it);
what the absent experts would add is left out, as in the program. Logits are
over the vocabulary slice.

A layer, for a sequence ``x`` [T, D] (all norms RMSNorm, eps
``rms_norm_eps``):

* MLA, un-absorbed: ``c_q = norm(h W_qa)``, ``q = c_q W_qb`` -> heads of
  (nope | rope); ``[c_kv | k_r] = h W_kva``, ``c_kv = norm(c_kv)``;
  ``k_nope = c_kv W_kb``, ``v = c_kv W_vb`` a head; rope (YaRN frequencies,
  INTERLEAVED pairs rotated in place) on ``q_rope`` and on the one shared
  ``k_r``; scores ``(q_nope k_nope + q_rope k_r) * scale`` with ``scale =
  qk_head_dim**-0.5 * mscale**2``, ``mscale = 0.1 * mscale_all_dim *
  ln(factor) + 1``;
* lightning indexer: ``q_I = c_q W_Iq`` -> ``index_n_heads`` heads,
  ``k_I = LayerNorm(h W_Ik)`` (eps 1e-6), rope on the first
  ``qk_rope_head_dim`` dims of both in SPLIT HALVES, ``w = h W_Iw *
  index_n_heads**-0.5 * index_head_dim**-0.5``; ``I[t, s] = sum_j w[t, j]
  relu(q_I[t, j] . k_I[s])`` for ``s <= t``, as a dense [T, T] score;
  query ``t`` attends only to its ``index_topk`` highest (``lax.top_k``:
  of equal scores the earlier position first), to all while ``t <
  index_topk``. No Hadamard rotation, no FP8 (the file's ``assumed``);
* feed-forward: SwiGLU, dense in the leading ``first_k_dense_replace`` layers; after
  them the shared expert plus the held routed experts: ``s = sigmoid(h
  W_r)``; on ``s + bias`` a group's score is the sum of its two best, the
  best ``topk_group`` of ``n_group`` groups stay, the ``num_experts_per_tok``
  best experts in them are taken; weights ``s_e / sum_taken s *
  routed_scaling_factor``.

Everything is computed in blocks of rows (``BLOCK``) and a few heads at a
time, each piece its own small jitted function called from Python, so that
a sequence of 16,768 positions fits beside the bfloat16 weights (11 GB) on
one chip: only one layer's weights are ever cast to float32, an expert at a
time.

``precision`` selects the arithmetic of every matmul operand: ``float32``
(the reference) and the lower precisions that serve as the control of "how
correct is decided": ``bfloat16``, ``fp8`` (e4m3, per-tensor scale) and
``int8``; they round both operands of every matmul, multiply with float32
accumulation and keep every matmul's result and the residual stream in
bfloat16, as reference_gpt2.py's do. The router, the index scores' head sum
and the selections stay float32 in every precision.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Weights = Dict[str, Any]
PRECISIONS = ("float32", "bfloat16", "fp8", "int8")
BLOCK = 256          # rows a block (queries, tokens through an MLP)
HEAD_GROUP = 32      # heads whose keys and values are live at once
BUCKET = 2048        # served_gaps pads a request to a multiple of this
NEG = -1e30


# ------------------------------------------------------------------ weights

def param_shapes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    lat = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    attn = {
        "attn_norm": (d,), "wq_a": (d, cfg["q_lora_rank"]),
        "q_norm": (cfg["q_lora_rank"],),
        "wq_b": (cfg["q_lora_rank"], h * qk),
        "wkv_a": (d, lat), "kv_norm": (cfg["kv_lora_rank"],),
        "wk_b": (cfg["kv_lora_rank"], h * cfg["qk_nope_head_dim"]),
        "wv_b": (cfg["kv_lora_rank"], h * cfg["v_head_dim"]),
        "wo": (h * cfg["v_head_dim"], d),
        "idx_wq_b": (cfg["q_lora_rank"],
                     cfg["index_n_heads"] * cfg["index_head_dim"]),
        "idx_wk": (d, cfg["index_head_dim"]),
        "idx_k_norm_g": (cfg["index_head_dim"],),
        "idx_k_norm_b": (cfg["index_head_dim"],),
        "idx_w": (d, cfg["index_n_heads"]), "mlp_norm": (d,)}
    f, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    e, fs = cfg["n_routed_experts_held"], fe * cfg["n_shared_experts"]
    dense = {"w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}
    moe = {"router": (d, cfg["n_routed_experts"]),
           "router_bias": (cfg["n_routed_experts"],),
           "shared_gate": (d, fs), "shared_up": (d, fs),
           "shared_down": (fs, d),
           "experts_gate": (e, d, fe), "experts_up": (e, d, fe),
           "experts_down": (e, fe, d)}
    out: Dict[str, Any] = {"embed": (cfg["vocab_size"], d),
                           "head": (cfg["vocab_size"], d), "norm_f": (d,)}
    for i in range(cfg["n_layers"]):
        out[f"layer_{i}"] = {
            **attn, **(dense if i < cfg["first_k_dense_replace"] else moe)}
    return out


def _leaves(shapes: Dict[str, Any]):
    for name, v in shapes.items():
        if isinstance(v, dict):
            for sub, shape in v.items():
                yield (name, sub), shape
        else:
            yield (name,), v


def param_count(cfg: Dict[str, Any]) -> int:
    """The parameters HELD HERE (the chip's share)."""
    return int(sum(math.prod(s) for _, s in _leaves(param_shapes(cfg))))


def seed_arg(seed: int) -> np.ndarray:
    """``--seed`` as the uint32 ``make_weights`` takes; pass it as an
    ARGUMENT of the jitted call (a seed in a closure is a new program)."""
    return np.uint32(int(seed) % (2 ** 32))


def make_weights(cfg: Dict[str, Any], seed) -> Weights:
    """Weights from the seed (``seed_arg``; may be traced) in the
    configuration's ``param_dtype``, drawn in that type (no float32 copy of
    an 11 GB tree): normal(0, initializer_range) for every matrix, norm
    scales 1, the indexer's LayerNorm bias and the router's correction bias
    0 (the latter float32, as the source keeps it)."""
    std = float(cfg.get("initializer_range", 0.006))
    dtype = jnp.dtype(cfg.get("param_dtype", "bfloat16"))
    key = jax.random.PRNGKey(seed)
    out: Weights = {}
    for i, (path, shape) in enumerate(_leaves(param_shapes(cfg))):
        name = path[-1]
        if name == "router_bias":
            leaf = jnp.zeros(shape, jnp.float32)
        elif name.endswith(("norm", "norm_g", "norm_f")):
            leaf = jnp.ones(shape, dtype)
        elif name.endswith("norm_b"):
            leaf = jnp.zeros(shape, dtype)
        else:
            leaf = (jax.random.normal(jax.random.fold_in(key, i), shape,
                                      dtype) * jnp.asarray(std, dtype))
        node = out
        for part in path[:-1]:
            node = node.setdefault(part, {})
        node[name] = leaf
    return out


# ------------------------------------------------------------- arithmetic

def _round_to(x: jax.Array, precision: str) -> jax.Array:
    """A matmul operand on the precision's grid, then bfloat16 (the 8-bit
    forms with a per-tensor absmax scale)."""
    x = x.astype(jnp.float32)
    if precision == "bfloat16":
        return x.astype(jnp.bfloat16)
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    if precision == "fp8":
        scale = amax / 448.0
        q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    else:
        scale = amax / 127.0
        q = jnp.round(x / scale) * scale
    return q.astype(jnp.bfloat16)


def _keep(x: jax.Array, precision: str) -> jax.Array:
    """An activation as the precision keeps it: bfloat16 below float32."""
    if precision == "float32":
        return x
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _mm(eq: str, a: jax.Array, b: jax.Array, precision: str) -> jax.Array:
    if precision == "float32":
        return jnp.einsum(eq, a.astype(jnp.float32), b.astype(jnp.float32),
                          precision=jax.lax.Precision.HIGHEST)
    return _keep(jnp.einsum(eq, _round_to(a, precision),
                            _round_to(b, precision),
                            preferred_element_type=jnp.float32), precision)


def _rms(x: jax.Array, g: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def _layer_norm(x: jax.Array, g: jax.Array, b: jax.Array, eps: float
                ) -> jax.Array:
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g.astype(jnp.float32) \
        + b.astype(jnp.float32)


def yarn_inv_freq(cfg: Dict[str, Any]) -> np.ndarray:
    dim, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    rs = cfg.get("rope_scaling") or {}
    factor = float(rs.get("factor", 1.0))
    freqs = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if factor <= 1.0:
        return (1.0 / freqs).astype(np.float32)
    orig = rs["original_max_position_embeddings"]

    def correction_dim(rotations: float) -> float:
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (
            2 * math.log(base))
    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    inv = (1.0 / (factor * freqs)) * ramp + (1.0 / freqs) * (1.0 - ramp)
    return inv.astype(np.float32)


def softmax_scale(cfg: Dict[str, Any]) -> float:
    rs = cfg.get("rope_scaling") or {}
    factor = float(rs.get("factor", 1.0))
    m = 1.0
    if factor > 1.0:
        m = 0.1 * float(rs.get("mscale_all_dim", 1.0)) * math.log(factor) + 1
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


def _rope_interleaved(x, cos, sin):
    """x [T, (H,) R], cos/sin [T, R/2]: pairs (x0, x1), (x2, x3), ..."""
    shape = x.shape
    x = x.reshape(shape[:-1] + (shape[-1] // 2, 2))
    a, b = x[..., 0], x[..., 1]
    if a.ndim == 3:
        cos, sin = cos[:, None], sin[:, None]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], -1).reshape(
        shape)


def _rope_halves(x, cos, sin):
    """x [T, (J,) R]: (x[i], x[i + R/2])."""
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    if a.ndim == 3:
        cos, sin = cos[:, None], sin[:, None]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def route(cfg: Dict[str, Any], scores: jax.Array, bias: jax.Array
          ) -> Tuple[jax.Array, jax.Array]:
    """scores [T, E] (sigmoid, float32) -> (expert ids [T, k], weights
    [T, k]): group-limited top-k on ``scores + bias``, weights from the
    unbiased scores."""
    t, e = scores.shape
    g = cfg["n_group"]
    choice = scores + bias[None, :]
    grouped = choice.reshape(t, g, e // g)
    group_score = jnp.sort(grouped, -1)[..., -2:].sum(-1)
    order = jnp.argsort(-group_score, -1, stable=True)
    keep = jnp.zeros((t, g), bool).at[
        jnp.arange(t)[:, None], order[:, :cfg["topk_group"]]].set(True)
    masked = jnp.where(keep[:, :, None], grouped, -jnp.inf).reshape(t, e)
    ids = jnp.argsort(-masked, -1, stable=True)[
        :, :cfg["num_experts_per_tok"]]
    w = jnp.take_along_axis(scores, ids, axis=1)
    return ids, w / w.sum(-1, keepdims=True) * cfg["routed_scaling_factor"]


def _blocks(fn, n_rows: int, *rows):
    """``fn`` over blocks of BLOCK rows of each of ``rows`` ([T, ...],
    T a multiple of BLOCK or less than it), results stacked back."""
    if n_rows <= BLOCK:
        return fn(*rows)
    n = n_rows // BLOCK
    out = jax.lax.map(lambda r: fn(*r), tuple(
        r.reshape((n, BLOCK) + r.shape[1:]) for r in rows))
    return jax.tree_util.tree_map(
        lambda o: o.reshape((n_rows,) + o.shape[2:]), out)


# ----------------------------------------------------------------- forward

class _Forward:
    """The pieces of one configuration in one precision, each jitted once."""

    def __init__(self, cfg: Dict[str, Any], precision: str) -> None:
        if precision not in PRECISIONS:
            raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
        self.cfg, self.precision = cfg, precision
        self.eps = float(cfg.get("rms_norm_eps", 1e-6))
        self.inv_freq = yarn_inv_freq(cfg)
        self.pre = jax.jit(self._pre)
        self.select = jax.jit(self._select)
        self.attend = jax.jit(self._attend, static_argnames=("g0",))
        self.dense = jax.jit(self._dense)
        self.routed = jax.jit(self._routed)
        self.expert = jax.jit(self._expert)
        self.head = jax.jit(self._head)

    def mm(self, eq, a, b):
        return _mm(eq, a, b, self.precision)

    # -- attention

    def _pre(self, lw, x):
        """x [T, D] -> what attention and the indexer need of every row."""
        cfg, t = self.cfg, x.shape[0]
        ang = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.asarray(
            self.inv_freq)[None]
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        r, c = cfg["qk_rope_head_dim"], cfg["kv_lora_rank"]

        def rows(x, cos, sin):
            h = _rms(x, lw["attn_norm"], self.eps)
            c_q = _rms(self.mm("td,dq->tq", h, lw["wq_a"]), lw["q_norm"],
                       self.eps)
            kv = self.mm("td,dc->tc", h, lw["wkv_a"])
            c_kv = _rms(kv[:, :c], lw["kv_norm"], self.eps)
            k_r = _rope_interleaved(kv[:, c:], cos, sin)
            k_i = _layer_norm(self.mm("td,dk->tk", h, lw["idx_wk"]),
                              lw["idx_k_norm_g"], lw["idx_k_norm_b"], 1e-6)
            k_i = jnp.concatenate(
                [_rope_halves(k_i[:, :r], cos, sin), k_i[:, r:]], -1)
            w_i = self.mm("td,dj->tj", h, lw["idx_w"]) * (
                cfg["index_n_heads"] ** -0.5 * cfg["index_head_dim"] ** -0.5)
            return c_q, c_kv, k_r, k_i, w_i
        c_q, c_kv, k_r, k_i, w_i = _blocks(rows, t, x, cos, sin)
        return c_q, c_kv, k_r, k_i, w_i, cos, sin

    def _select(self, lw, c_q, k_i, w_i, cos, sin):
        """The dense [T, T] index score and its explicit top-k mask."""
        cfg, t = self.cfg, c_q.shape[0]
        r, k = cfg["qk_rope_head_dim"], min(cfg["index_topk"], c_q.shape[0])
        key_pos = jnp.arange(t)

        def rows(c_q, w_i, cos, sin, q_pos):
            q = self.mm("tq,qe->te", c_q, lw["idx_wq_b"]).reshape(
                c_q.shape[0], cfg["index_n_heads"], cfg["index_head_dim"])
            q = jnp.concatenate(
                [_rope_halves(q[..., :r], cos, sin), q[..., r:]], -1)
            s = self.mm("tjd,sd->tjs", q, k_i)
            score = jnp.sum(jax.nn.relu(s) * w_i[:, :, None], 1)    # [t, T]
            score = jnp.where(key_pos[None, :] <= q_pos[:, None], score, NEG)
            top, idx = jax.lax.top_k(score, k)
            return jnp.zeros(score.shape, bool).at[
                jnp.arange(score.shape[0])[:, None], idx].set(top > NEG)
        return _blocks(rows, t, c_q, w_i, cos, sin, key_pos)

    def _attend(self, lw, x, c_q, c_kv, k_r, selected, cos, sin, *, g0: int):
        """Heads [g0, g0 + HEAD_GROUP): their share of the attention output
        through their rows of W_o, added to x."""
        cfg = self.cfg
        h_all = cfg["num_attention_heads"]
        g = min(HEAD_GROUP, h_all - g0)
        dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
        t = x.shape[0]
        wq = lw["wq_b"].reshape(-1, h_all, dn + dr)[:, g0:g0 + g]
        wk = lw["wk_b"].reshape(-1, h_all, dn)[:, g0:g0 + g]
        wv = lw["wv_b"].reshape(-1, h_all, dv)[:, g0:g0 + g]
        wo = lw["wo"].reshape(h_all, dv, -1)[g0:g0 + g]
        k_nope = self.mm("tc,chn->thn", c_kv, wk)
        v = self.mm("tc,chv->thv", c_kv, wv)
        scale = softmax_scale(cfg)

        def rows(c_q, sel, cos, sin):
            q = self.mm("tq,qhe->the", c_q, wq)
            q_nope = q[..., :dn]
            q_rope = _rope_interleaved(q[..., dn:], cos, sin)
            s = (self.mm("thn,shn->hts", q_nope, k_nope)
                 + self.mm("thr,sr->hts", q_rope, k_r)) * scale
            s = jnp.where(sel[None], s, NEG)
            p = jax.nn.softmax(s, -1)
            o = self.mm("hts,shv->thv", p, v)
            return self.mm("thv,hvd->td", o, wo)
        return _keep(x + _blocks(rows, t, c_q, selected, cos, sin),
                     self.precision)

    # -- feed-forward

    def _swiglu(self, h, wg, wu, wd):
        a = jax.nn.silu(self.mm("td,df->tf", h, wg)) * self.mm(
            "td,df->tf", h, wu)
        return self.mm("tf,fd->td", a, wd)

    def _dense(self, x, norm, wg, wu, wd):
        """x + SwiGLU(norm(x)): the dense layer, and the shared expert."""
        return _keep(x + _blocks(
            lambda x: self._swiglu(_rms(x, norm, self.eps), wg, wu, wd),
            x.shape[0], x), self.precision)

    def _routed(self, x, norm, router, bias):
        """-> (expert ids [T, k], weights [T, k]); float32 always."""
        def rows(x):
            h = _rms(x, norm, self.eps)
            s = jax.nn.sigmoid(jnp.dot(h, router.astype(jnp.float32),
                                       precision=jax.lax.Precision.HIGHEST))
            return route(self.cfg, s, bias)
        return _blocks(rows, x.shape[0], x)

    def _expert(self, y, x, norm, rows, w_rows, wg, wu, wd, e):
        """y with ``w_rows * expert_e(norm(x[rows]))`` added at ``rows`` (the
        tokens routed to expert e, padded to whole blocks with weight 0).
        The stacked weights are indexed here, so one program serves every
        held expert."""
        def block(x, w):
            return w[:, None] * self._swiglu(
                _rms(x, norm, self.eps), wg[e], wu[e], wd[e])
        return y.at[rows].add(_blocks(block, rows.shape[0], x[rows], w_rows))

    def _head(self, w, x):
        return self.mm("td,vd->tv", _rms(x, w["norm_f"], self.eps),
                       w["head"])  # untied; stored [V, D] like the embedding

    # -- the whole

    def hidden(self, w: Weights, ids: jax.Array) -> Tuple[jax.Array, Dict]:
        """ids [T] -> residual stream after the last layer [T, D], and what
        was chosen on the way (per layer: the selection mask, the routed
        expert ids and weights)."""
        cfg = self.cfg
        chosen: Dict[str, Any] = {"selected": [], "experts": [],
                                  "expert_weights": []}
        t = ids.shape[0]
        if t > BLOCK and t % BLOCK:
            # whole blocks: zeros behind the sequence (causal: they cannot
            # reach an earlier position), cut off again below
            ids = jnp.pad(ids, (0, -t % BLOCK))
        x = _keep(w["embed"][ids].astype(jnp.float32), self.precision)
        for i in range(cfg["n_layers"]):
            lw = w[f"layer_{i}"]
            c_q, c_kv, k_r, k_i, w_i, cos, sin = self.pre(lw, x)
            sel = self.select(lw, c_q, k_i, w_i, cos, sin)
            for g0 in range(0, cfg["num_attention_heads"], HEAD_GROUP):
                x = self.attend(lw, x, c_q, c_kv, k_r, sel, cos, sin, g0=g0)
            chosen["selected"].append(sel[:t, :t])
            if i < cfg["first_k_dense_replace"]:
                x = self.dense(x, lw["mlp_norm"], lw["w_gate"], lw["w_up"],
                               lw["w_down"])
                chosen["experts"].append(None)
                chosen["expert_weights"].append(None)
                continue
            ids_e, w_e = self.routed(x, lw["mlp_norm"], lw["router"],
                                     lw["router_bias"])
            y = self.dense(x, lw["mlp_norm"], lw["shared_gate"],
                           lw["shared_up"], lw["shared_down"])
            off = int(cfg.get("expert_offset", 0))
            host_ids, host_w = jax.device_get((ids_e, w_e))
            for e in range(cfg["n_routed_experts_held"]):
                hit = host_ids == off + e                        # [T, k]
                rows = np.nonzero(hit.any(-1))[0]
                if rows.size == 0:
                    continue
                w_rows = (host_w * hit).sum(-1)[rows]
                pad = -rows.size % BLOCK     # whole blocks: few programs
                y = self.expert(
                    y, x, lw["mlp_norm"],
                    jnp.asarray(np.pad(rows, (0, pad)), jnp.int32),
                    jnp.asarray(np.pad(w_rows, (0, pad)), jnp.float32),
                    lw["experts_gate"], lw["experts_up"],
                    lw["experts_down"], jnp.int32(e))
            x = _keep(y, self.precision)
            chosen["experts"].append(ids_e[:t])
            chosen["expert_weights"].append(w_e[:t])
        return x[:t], chosen

    def __call__(self, w: Weights, ids, rows=None) -> jax.Array:
        """ids [T] -> logits [T, V] (or of ``rows`` only) in float32."""
        x, _ = self.hidden(w, jnp.asarray(ids))
        return self.head(w, x if rows is None else x[jnp.asarray(rows)])


def make_logits_fn(cfg: Dict[str, Any]):
    """``fwd(precision)`` -> ``(w, ids [T], rows=None) -> logits``; the
    pieces compile once a precision and sequence length."""
    @functools.lru_cache(maxsize=None)
    def fwd(precision: str) -> _Forward:
        return _Forward(cfg, precision)
    return fwd


def logits(w: Weights, cfg: Dict[str, Any], ids, precision: str = "float32"
           ) -> jax.Array:
    return make_logits_fn(cfg)(precision)(w, ids)


# ----------------------------------------------------------------- serving

def served_gaps(w: Weights, cfg: Dict[str, Any], ids: np.ndarray,
                prompt_len: int, precision_pick: Optional[str] = None,
                fwd=None) -> np.ndarray:
    """For one request, ``ids`` = prompt followed by the tokens served for
    it (greedy): at each served position, how far the served token's logit
    lies below the reference's best, in the reference's own float32 logits;
    0 where the served token is the reference's pick. One full forward over
    prompt + served tokens.

    With ``precision_pick`` the token compared at each position is the one
    a forward pass in that lower precision puts first (the control)."""
    n = len(ids) - prompt_len
    # zeros behind the sequence up to a whole number of BUCKET positions
    # (causal: they reach no earlier position): few distinct lengths, so
    # the pieces compile a handful of times, not once a request
    buf = np.zeros((-(-len(ids) // BUCKET) * BUCKET
                    if len(ids) > BUCKET else len(ids),), np.int32)
    buf[:len(ids)] = ids
    if fwd is None:
        fwd = make_logits_fn(cfg)
    served = np.arange(prompt_len - 1, prompt_len - 1 + n)
    rows = fwd("float32")(w, buf, served)                      # [n, V]
    if precision_pick is None:
        picked = jnp.asarray(ids[prompt_len:prompt_len + n])
    else:
        picked = jnp.argmax(fwd(precision_pick)(w, buf, served), axis=-1)
    gap = rows.max(-1) - jnp.take_along_axis(
        rows, picked[:, None], axis=-1)[:, 0]
    return np.asarray(jax.device_get(gap), np.float64)
