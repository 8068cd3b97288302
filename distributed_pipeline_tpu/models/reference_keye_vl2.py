"""Plain reference for the Keye-VL-2.0 language-model configurations: float32
``jax.numpy``, matmuls at ``highest`` precision, no kernel, no cache, no sort,
no batching, one sequence at a time.

It imports nothing of the program and takes nothing the program has made. It
makes its own weights from the seed (``make_weights``; the driver hands the
same arrays to the program, in the configuration's ``param_dtype``) and
computes the forward pass of the layers the configuration file states: every
width as published, the router over all ``num_experts`` and every one of the
experts, a Python loop over them, each over the rows routed to it: every
token's eight experts are computed an expert at a time.

A layer, for a sequence ``x`` [T, D] (all norms RMSNorm, eps
``rms_norm_eps``; pre-norm residual blocks; no bias anywhere):

* attention, ``h = norm(x)``: ``q = h W_q`` -> ``num_attention_heads`` heads
  of ``head_dim``, ``k = h W_k`` and ``v = h W_v`` -> ``num_key_value_heads``
  heads; a per-head RMSNorm over ``head_dim`` on ``q`` and on ``k`` (the
  file's ``assumed``); plain RoPE at ``rope_theta`` over the whole head in
  SPLIT HALVES (``rotate_half``; text positions: the three ``mrope`` streams
  are equal, so ``mrope_section`` picks among equal angle tables); query
  head ``j`` reads key head ``j // (heads / kv_heads)``; scores
  ``q . k * head_dim**-0.5``;
* the lightning indexer (``sa_config``): ``q_I = h W_Iq`` ->
  ``indexer_num_heads`` heads of ``indexer_head_dim``, ``k_I = LayerNorm(h
  W_Ik)`` (eps 1e-6; ONE key head), RoPE on all of ``indexer_head_dim`` in
  split halves at ``rope_theta``, ``w = h W_Iw``; ``I[t, s] = sum_j w[t, j]
  relu(q_I[t, j] . k_I[s])`` for ``s <= t``, as a dense [T, T] score; query
  ``t`` attends only to its ``topk`` highest (``lax.top_k``: of equal scores
  the earlier position first), to all while ``t < topk``; one selection a
  token, shared by all heads. No Hadamard rotation, no FP8, no positive
  scalar on ``w`` (the file's ``assumed``);
* experts, ``h = norm(x)``: ``p = softmax(h W_r)`` over all ``num_experts``
  (float32); the ``num_experts_per_tok`` largest are taken (of equal values
  the lower index first); weights ``p_e / sum_taken p`` (``norm_topk_prob``);
  ``x + sum_taken w_e W_d^e(silu(W_g^e h) * W_u^e h)``. No shared expert.

Everything is computed in blocks of rows (``BLOCK``) and a key head at a
time, each piece its own small jitted function called from Python, so that a
sequence of 17 k positions fits beside the bfloat16 weights (8.7 GB) on one
chip: only the pieces in use are ever cast to float32, an expert at a time.

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
# what attention's first piece reads (``wo`` goes to the heads' piece)
ATTENTION_WEIGHTS = (
    "attn_norm", "wq", "wk", "wv", "q_norm", "k_norm", "idx_wq", "idx_wk",
    "idx_k_norm_g", "idx_k_norm_b", "idx_w")
BLOCK = 256          # rows a block (queries, tokens through an expert)
# served_gaps pads a request to one of these lengths (doubling, then whole
# steps of the first): few distinct lengths, few compilations
BUCKETS = (4096, 8192, 16384)
NEG = -1e30


# ------------------------------------------------------------------ weights

def param_shapes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    h, g = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    sa = cfg["sa_config"]
    j, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    e, f = cfg["num_experts"], cfg["moe_intermediate_size"]
    layer = {
        "attn_norm": (d,), "wq": (d, h * dh), "wk": (d, g * dh),
        "wv": (d, g * dh), "q_norm": (dh,), "k_norm": (dh,),
        "wo": (h * dh, d),
        "idx_wq": (d, j * di), "idx_wk": (d, di), "idx_k_norm_g": (di,),
        "idx_k_norm_b": (di,), "idx_w": (d, j),
        "mlp_norm": (d,), "router": (d, cfg["num_experts"]),
        "experts_gate": (e, d, f), "experts_up": (e, d, f),
        "experts_down": (e, f, d)}
    out: Dict[str, Any] = {"embed": (cfg["vocab_size"], d),
                           "head": (cfg["vocab_size"], d), "norm_f": (d,)}
    for i in range(cfg["n_layers"]):
        out[f"layer_{i}"] = dict(layer)
    return out


def _leaves(shapes: Dict[str, Any]):
    for name, v in shapes.items():
        if isinstance(v, dict):
            for sub, shape in v.items():
                yield (name, sub), shape
        else:
            yield (name,), v


def param_count(cfg: Dict[str, Any]) -> int:
    """The parameters HELD HERE (the layers that run, embedding and head)."""
    return int(sum(math.prod(s) for _, s in _leaves(param_shapes(cfg))))


def seed_arg(seed: int) -> np.ndarray:
    """``--seed`` as the uint32 ``make_weights`` takes; pass it as an
    ARGUMENT of the jitted call (a seed in a closure is a new program)."""
    return np.uint32(int(seed) % (2 ** 32))


def make_weights(cfg: Dict[str, Any], seed) -> Weights:
    """Weights from the seed (``seed_arg``; may be traced) in the
    configuration's ``param_dtype``, drawn in that type (no float32 copy of
    an 8.7 GB tree): normal(0, initializer_range) for every matrix but the
    embedding, which is normal(0, embedding_initializer_range) where the
    file gives one (with a small embedding the context's mean, not the
    token's own row, leads the residual stream and the router sends whole
    sequences to a few experts; a trained router spreads its load); norm
    scales 1, the indexer's LayerNorm bias 0."""
    std = float(cfg.get("initializer_range", 0.02))
    stds = {"embed": float(cfg.get("embedding_initializer_range", std))}
    dtype = jnp.dtype(cfg.get("param_dtype", "bfloat16"))
    key = jax.random.PRNGKey(seed)
    out: Weights = {}
    for i, (path, shape) in enumerate(_leaves(param_shapes(cfg))):
        name = path[-1]
        if name.endswith(("norm", "norm_g", "norm_f")):
            leaf = jnp.ones(shape, dtype)
        elif name.endswith("norm_b"):
            leaf = jnp.zeros(shape, dtype)
        else:
            leaf = (jax.random.normal(jax.random.fold_in(key, i), shape,
                                      dtype)
                    * jnp.asarray(stds.get(name, std), dtype))
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


def inv_freq(theta: float, dim: int) -> np.ndarray:
    """Plain RoPE (``rope_type`` default) over ``dim`` numbers."""
    freqs = float(theta) ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    return (1.0 / freqs).astype(np.float32)


def _rope_halves(x, cos, sin):
    """x [T, (H,) R], cos/sin [T, R/2]: (x[i], x[i + R/2])."""
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    if a.ndim == 3:
        cos, sin = cos[:, None], sin[:, None]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def route(cfg: Dict[str, Any], probs: jax.Array
          ) -> Tuple[jax.Array, jax.Array]:
    """probs [T, E] (softmax, float32) -> (expert ids [T, k], weights
    [T, k]): the k largest (of equal values the lower index first), their
    probabilities normalised to sum 1 (``norm_topk_prob``)."""
    ids = jnp.argsort(-probs, -1, stable=True)[
        :, :cfg["num_experts_per_tok"]]
    w = jnp.take_along_axis(probs, ids, axis=1)
    return ids, w / w.sum(-1, keepdims=True)


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
        if not cfg.get("norm_topk_prob", True):
            raise ValueError("norm_topk_prob false is not this model's")
        self.cfg, self.precision = cfg, precision
        self.eps = float(cfg.get("rms_norm_eps", 1e-6))
        self.pre = jax.jit(self._pre)
        self.attend = jax.jit(self._attend)
        self.select = jax.jit(self._select)
        self.routed = jax.jit(self._routed)
        self.expert = jax.jit(self._expert)
        self.head = jax.jit(self._head)

    def mm(self, eq, a, b):
        return _mm(eq, a, b, self.precision)

    # -- attention

    def _pre(self, lw, x):
        """x [T, D] -> what attention and the indexer need of every row:
        q [T, H, dh] and k [T, G, dh] normed and roped, v [T, G, dh], the
        indexer's q [T, J, di] and k [T, di] roped, its head weights
        [T, J]."""
        cfg, sa, t = self.cfg, self.cfg["sa_config"], x.shape[0]
        dh, g = cfg["head_dim"], cfg["num_key_value_heads"]
        di = sa["indexer_head_dim"]
        at = jnp.arange(t, dtype=jnp.float32)[:, None]

        def angles(dim):
            ang = at * jnp.asarray(inv_freq(cfg["rope_theta"], dim))[None]
            return jnp.cos(ang), jnp.sin(ang)
        (cos, sin), (cos_i, sin_i) = angles(dh), angles(di)

        def rows(x, cos, sin, cos_i, sin_i):
            n = x.shape[0]
            h = _rms(x, lw["attn_norm"], self.eps)
            q = _rope_halves(_rms(
                self.mm("td,de->te", h, lw["wq"]).reshape(n, -1, dh),
                lw["q_norm"], self.eps), cos, sin)
            k = _rope_halves(_rms(
                self.mm("td,de->te", h, lw["wk"]).reshape(n, g, dh),
                lw["k_norm"], self.eps), cos, sin)
            v = self.mm("td,de->te", h, lw["wv"]).reshape(n, g, dh)
            q_i = _rope_halves(
                self.mm("td,de->te", h, lw["idx_wq"]).reshape(n, -1, di),
                cos_i, sin_i)
            k_i = _rope_halves(_layer_norm(
                self.mm("td,dk->tk", h, lw["idx_wk"]), lw["idx_k_norm_g"],
                lw["idx_k_norm_b"], 1e-6), cos_i, sin_i)
            w_i = self.mm("td,dj->tj", h, lw["idx_w"])
            return q, k, v, q_i, k_i, w_i
        return _blocks(rows, t, x, cos, sin, cos_i, sin_i)

    def _select(self, q_i, k_i, w_i):
        """The dense [T, T] index score and its explicit top-k mask."""
        t = q_i.shape[0]
        k = min(self.cfg["sa_config"]["topk"], t)
        key_pos = jnp.arange(t)

        def rows(q_i, w_i, q_pos):
            s = self.mm("tjd,sd->tjs", q_i, k_i)
            score = jnp.sum(jax.nn.relu(s) * w_i[:, :, None], 1)    # [t, T]
            score = jnp.where(key_pos[None, :] <= q_pos[:, None], score, NEG)
            top, idx = jax.lax.top_k(score, k)
            return jnp.zeros(score.shape, bool).at[
                jnp.arange(score.shape[0])[:, None], idx].set(top > NEG)
        return _blocks(rows, t, q_i, w_i, key_pos)

    def _attend(self, wo, x, q, k, v, selected, g0):
        """The query heads that read key head ``g0`` (an argument: one
        program serves every key head): their attention output where
        ``selected`` [T, T] says, through their rows of W_o, added to x."""
        cfg = self.cfg
        dh, h_all = cfg["head_dim"], cfg["num_attention_heads"]
        rep = h_all // cfg["num_key_value_heads"]
        t = x.shape[0]
        k_g = jax.lax.dynamic_index_in_dim(k, g0, 1, keepdims=False)
        v_g = jax.lax.dynamic_index_in_dim(v, g0, 1, keepdims=False)
        q_g = jax.lax.dynamic_slice_in_dim(q, g0 * rep, rep, 1)
        wo_g = jax.lax.dynamic_slice_in_dim(
            wo.reshape(h_all, dh, -1), g0 * rep, rep, 0)
        scale = dh ** -0.5

        def rows(q, sel):
            s = self.mm("trd,sd->rts", q, k_g) * scale
            s = jnp.where(sel[None], s, NEG)
            p = jax.nn.softmax(s, -1)
            o = self.mm("rts,sd->rtd", p, v_g)
            return self.mm("rtd,rde->te", o, wo_g)
        return _keep(x + _blocks(rows, t, q_g, selected), self.precision)

    # -- experts

    def _routed(self, x, norm, router):
        """-> (expert ids [T, k], weights [T, k]); float32 always."""
        def rows(x):
            h = _rms(x, norm, self.eps)
            p = jax.nn.softmax(jnp.dot(
                h, router.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST), -1)
            return route(self.cfg, p)
        return _blocks(rows, x.shape[0], x)

    def _expert(self, x_rows, norm, w_rows, wg, wu, wd, e):
        """``w_rows * expert_e(norm(x_rows))`` for the rows routed to expert
        e (padded to whole blocks with weight 0). The stacked weights are
        indexed here and the rows are taken out by the caller, so one
        program serves every expert, layer and sequence length."""
        def block(x, w):
            h = _rms(x, norm, self.eps)
            a = jax.nn.silu(self.mm("td,df->tf", h, wg[e])) * self.mm(
                "td,df->tf", h, wu[e])
            return w[:, None] * self.mm("tf,fd->td", a, wd[e])
        return _blocks(block, x_rows.shape[0], x_rows, w_rows)

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
            # attention's pieces take attention's weights only
            aw = {k: lw[k] for k in ATTENTION_WEIGHTS}
            q, k, v, q_i, k_i, w_i = self.pre(aw, x)
            sel = self.select(q_i, k_i, w_i)
            for g0 in range(cfg["num_key_value_heads"]):
                x = self.attend(lw["wo"], x, q, k, v, sel, jnp.int32(g0))
            chosen["selected"].append(sel[:t, :t])
            ids_e, w_e = self.routed(x, lw["mlp_norm"], lw["router"])
            y = x
            host_ids, host_w = jax.device_get((ids_e, w_e))
            for e in range(cfg["num_experts"]):
                hit = host_ids == e                              # [T, k]
                rows = np.nonzero(hit.any(-1))[0]
                if rows.size == 0:
                    continue
                w_rows = (host_w * hit).sum(-1)[rows]
                # whole blocks, doubling: few programs
                pad = BLOCK * 2 ** math.ceil(math.log2(
                    max(rows.size / BLOCK, 1))) - rows.size
                at = jnp.asarray(np.pad(rows, (0, pad)), jnp.int32)
                y = y.at[at].add(self.expert(
                    x[at], lw["mlp_norm"],
                    jnp.asarray(np.pad(w_rows, (0, pad)), jnp.float32),
                    lw["experts_gate"], lw["experts_up"],
                    lw["experts_down"], jnp.int32(e)))
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

def padded_len(n: int) -> int:
    """The length a request of ``n`` positions is computed at: the first of
    ``BUCKETS`` that holds it, whole steps of ``BUCKETS[0]`` past the last;
    a sequence of one block (the tests' sizes) stays as it is."""
    if n <= BLOCK:
        return n
    for b in BUCKETS:
        if n <= b:
            return b
    return -(-n // BUCKETS[0]) * BUCKETS[0]


def served_gaps(w: Weights, cfg: Dict[str, Any], ids: np.ndarray,
                prompt_len: int, precision_pick: Optional[str] = None,
                fwd=None) -> np.ndarray:
    """For one request, ``ids`` = prompt followed by the tokens served for
    it (greedy): at each served position, how far the served token's logit
    lies below the reference's best, in the reference's own float32 logits;
    0 where the served token is the reference's pick. One full forward over
    prompt + served tokens, logits at the served positions only.

    With ``precision_pick`` the token compared at each position is the one
    a forward pass in that lower precision puts first (the control)."""
    n = len(ids) - prompt_len
    # zeros behind the sequence (causal: they reach no earlier position)
    buf = np.zeros((padded_len(len(ids)),), np.int32)
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
