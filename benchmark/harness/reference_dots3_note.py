"""Plain reference for the dots3-note-prev configurations: float32
``jax.numpy``, matmuls at ``highest`` precision, no kernel, no cache, no
absorbed projection, one sequence at a time.

It imports nothing of the program and takes nothing the program has made. It
makes its own weights from the seed (``make_weights``; the driver hands the
same arrays to the program, in the configuration's ``param_dtype``) and
computes the forward pass of ONE CHIP'S SHARE of the deployment the
configuration file states: every width as published, the router over all
``n_routed_experts``, and of the routed experts only the
``n_routed_experts_held`` from ``expert_offset`` (a Python loop over them,
each over the rows routed to it); what the absent experts would add is left
out, as in the program. Logits are over the vocabulary slice.

A layer, for a sequence ``x`` [T, D] (all norms RMSNorm, eps
``rms_norm_eps``; ``h = norm(x)``), is of the kind ``layer_types[i]`` names
(``kind_of``):

* ``full_attention``: DeepSeek-V3.2's MLA with the lightning indexer at
  ``num_attention_heads`` / ``q_lora_rank`` / ``kv_lora_rank`` /
  ``qk_nope_head_dim`` / ``qk_rope_head_dim`` / ``v_head_dim`` /
  ``rope_theta``. Un-absorbed: ``c_q = norm(h W_qa)``, ``q = c_q W_qb`` ->
  heads of (nope | rope); ``[c_kv | k_r] = h W_kva``, ``c_kv = norm(c_kv)``;
  ``k_nope = c_kv W_kb``, ``v = c_kv W_vb`` a head; plain RoPE
  (``rope_scaling`` null; INTERLEAVED pairs rotated in place) on ``q_rope``
  and on the one shared ``k_r``; scores ``(q_nope k_nope + q_rope k_r) *
  qk_head_dim**-0.5``. Indexer: ``q_I = c_q W_Iq`` -> ``index_n_heads``
  heads, ``k_I = LayerNorm(h W_Ik)`` (eps 1e-6), rope on the first
  ``qk_rope_head_dim`` dims of both in SPLIT HALVES, ``w = h W_Iw *
  index_n_heads**-0.5 * index_head_dim**-0.5``; ``I[t, s] = sum_j w[t, j]
  relu(q_I[t, j] . k_I[s])`` for ``s <= t``, as a dense [T, T] score; query
  ``t`` attends only to its ``index_topk`` highest (``lax.top_k``: of equal
  scores the earlier position first), to all while ``t < index_topk``. No
  Hadamard rotation, no FP8 (the file's ``assumed``);
* ``sliding_attention``: the same MLA equations at the ``swa_*`` sizes and
  ``swa_rope_theta``; query ``t`` sees keys ``s`` with ``0 <= t - s <
  sliding_window_size``; no indexer. Computed a block of queries against the
  slice of keys its band can reach;
* both (``attention_gate_type`` / ``swa_attention_gate_type`` "headwise"):
  head ``j``'s attention output is multiplied by ``sigmoid(h W_g)[t, j]``
  before ``W_o``; with ``apply_mla_qkv_lora_rescale`` (the file's
  ``assumed``) ``c_q`` and ``c_kv`` are multiplied, after their norms, by
  ``sqrt(hidden / q_lora_rank)`` and ``sqrt(hidden / kv_lora_rank)``, each
  kind with its own ranks (so the indexer's query sees it too);
* feed-forward: SwiGLU, dense in the leading ``first_k_dense_replace``
  layers; after them the shared expert plus the held routed experts: ``s =
  sigmoid(h W_r)``; the ``num_experts_per_tok`` best of ALL experts on ``s
  + bias`` are taken (``noaux_tc`` with no group keys: one group); weights
  ``s_e / sum_taken s * routed_scaling_factor``.

Everything is computed in blocks of rows (``BLOCK``) and a few heads at a
time, each piece its own small jitted function called from Python, so that
a sequence of 16,768 positions fits beside the bfloat16 weights (9.2 GB) on
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
ATTENTION_WEIGHTS = (
    "attn_norm", "wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm", "wk_b", "wv_b",
    "wo", "idx_wq_b", "idx_wk", "idx_k_norm_g", "idx_k_norm_b", "idx_w",
    "wo_gate")
BLOCK = 256          # rows a block (queries, tokens through an MLP)
HEAD_GROUP = 32      # heads whose keys and values are live at once
BUCKET = 4096        # served_gaps pads a request to a multiple of this
NEG = -1e30


# ------------------------------------------------------------------ weights

def kind_of(cfg: Dict[str, Any], i: int) -> Dict[str, Any]:
    """Layer ``i``'s attention sizes under kind-neutral names."""
    sliding = cfg["layer_types"][i] == "sliding_attention"
    pre = "swa_" if sliding else ""
    out = {k: cfg[pre + k] for k in (
        "num_attention_heads", "q_lora_rank", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "rope_theta")}
    rescale = bool(cfg.get("apply_mla_qkv_lora_rescale", False))
    d = cfg["hidden_size"]
    out.update(
        name="sliding" if sliding else "full",
        window=cfg["sliding_window_size"] if sliding else 0,
        gate=cfg.get(pre + "attention_gate_type") == "headwise",
        q_rescale=math.sqrt(d / out["q_lora_rank"]) if rescale else 1.0,
        kv_rescale=math.sqrt(d / out["kv_lora_rank"]) if rescale else 1.0)
    return out


def kinds(cfg: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    """The distinct kinds among the layers that run, by name."""
    return {k["name"]: k for k in (
        kind_of(cfg, i) for i in range(cfg["n_layers"]))}


def param_shapes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    d = cfg["hidden_size"]

    def attn(kd: Dict[str, Any]) -> Dict[str, Any]:
        h, q, c = (kd["num_attention_heads"], kd["q_lora_rank"],
                   kd["kv_lora_rank"])
        out = {
            "attn_norm": (d,), "wq_a": (d, q), "q_norm": (q,),
            "wq_b": (q, h * (kd["qk_nope_head_dim"]
                             + kd["qk_rope_head_dim"])),
            "wkv_a": (d, c + kd["qk_rope_head_dim"]), "kv_norm": (c,),
            "wk_b": (c, h * kd["qk_nope_head_dim"]),
            "wv_b": (c, h * kd["v_head_dim"]),
            "wo": (h * kd["v_head_dim"], d)}
        if not kd["window"]:
            out.update({
                "idx_wq_b": (q, cfg["index_n_heads"]
                             * cfg["index_head_dim"]),
                "idx_wk": (d, cfg["index_head_dim"]),
                "idx_k_norm_g": (cfg["index_head_dim"],),
                "idx_k_norm_b": (cfg["index_head_dim"],),
                "idx_w": (d, cfg["index_n_heads"])})
        if kd["gate"]:
            out["wo_gate"] = (d, h)
        out["mlp_norm"] = (d,)
        return out
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
            **attn(kind_of(cfg, i)),
            **(dense if i < cfg["first_k_dense_replace"] else moe)}
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
    a 9 GB tree): normal(0, initializer_range) for every matrix but the
    embedding, which is normal(0, embedding_initializer_range) where the
    file gives one (with every matrix at 0.006 the context's mean, not the
    token's own row, leads the residual stream, every token of a sequence
    is routed to the same few experts and a share holds a hot expert or
    none by the seed; a trained router spreads its load); norm scales 1,
    the indexer's LayerNorm bias and the router's correction bias 0 (the
    latter float32, as the source keeps it)."""
    std = float(cfg.get("initializer_range", 0.006))
    stds = {"embed": float(cfg.get("embedding_initializer_range", std))}
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


def inv_freq(kd: Dict[str, Any]) -> np.ndarray:
    """Plain RoPE (``rope_scaling`` null) at the kind's base."""
    dim = kd["qk_rope_head_dim"]
    freqs = float(kd["rope_theta"]) ** (
        np.arange(0, dim, 2, dtype=np.float64) / dim)
    return (1.0 / freqs).astype(np.float32)


def softmax_scale(kd: Dict[str, Any]) -> float:
    return (kd["qk_nope_head_dim"] + kd["qk_rope_head_dim"]) ** -0.5


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
    [T, k]): the k best of all experts on ``scores + bias`` (of equal values
    the lower index first), weights from the unbiased scores."""
    ids = jnp.argsort(-(scores + bias[None, :]), -1, stable=True)[
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
    """The pieces of one configuration in one precision, each jitted once
    (attention's compile once a kind of layer: ``kind`` is static)."""

    def __init__(self, cfg: Dict[str, Any], precision: str) -> None:
        if precision not in PRECISIONS:
            raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
        self.cfg, self.precision = cfg, precision
        self.eps = float(cfg.get("rms_norm_eps", 1e-5))
        self.kinds = kinds(cfg)
        self.pre = jax.jit(self._pre, static_argnames=("kind",))
        self.attend = jax.jit(self._attend, static_argnames=("kind",))
        self.select = jax.jit(self._select)
        self.dense = jax.jit(self._dense)
        self.routed = jax.jit(self._routed)
        self.expert = jax.jit(self._expert)
        self.head = jax.jit(self._head)

    def mm(self, eq, a, b):
        return _mm(eq, a, b, self.precision)

    # -- attention

    def _pre(self, lw, x, *, kind: str):
        """x [T, D] -> what attention (and, in a full layer, the indexer)
        needs of every row; ``k_i`` / ``w_i`` None in a sliding layer,
        ``gate`` None without one."""
        cfg, kd, t = self.cfg, self.kinds[kind], x.shape[0]
        ang = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.asarray(
            inv_freq(kd))[None]
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        r, c = kd["qk_rope_head_dim"], kd["kv_lora_rank"]

        def rows(x, cos, sin):
            h = _rms(x, lw["attn_norm"], self.eps)
            c_q = _rms(self.mm("td,dq->tq", h, lw["wq_a"]), lw["q_norm"],
                       self.eps) * kd["q_rescale"]
            kv = self.mm("td,dc->tc", h, lw["wkv_a"])
            c_kv = _rms(kv[:, :c], lw["kv_norm"], self.eps) \
                * kd["kv_rescale"]
            k_r = _rope_interleaved(kv[:, c:], cos, sin)
            gate = jax.nn.sigmoid(self.mm("td,dh->th", h, lw["wo_gate"])) \
                if kd["gate"] else None
            if kd["window"]:
                return c_q, c_kv, k_r, None, None, gate
            k_i = _layer_norm(self.mm("td,dk->tk", h, lw["idx_wk"]),
                              lw["idx_k_norm_g"], lw["idx_k_norm_b"], 1e-6)
            k_i = jnp.concatenate(
                [_rope_halves(k_i[:, :r], cos, sin), k_i[:, r:]], -1)
            w_i = self.mm("td,dj->tj", h, lw["idx_w"]) * (
                cfg["index_n_heads"] ** -0.5 * cfg["index_head_dim"] ** -0.5)
            return c_q, c_kv, k_r, k_i, w_i, gate
        c_q, c_kv, k_r, k_i, w_i, gate = _blocks(rows, t, x, cos, sin)
        return c_q, c_kv, k_r, k_i, w_i, gate, cos, sin

    def _select(self, lw, c_q, k_i, w_i, cos, sin):
        """A full layer's dense [T, T] index score and its explicit top-k
        mask (the rope dims are the full kind's)."""
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

    def _attend(self, lw, x, c_q, c_kv, k_r, selected, gate, cos, sin, g0,
                *, kind: str):
        """Heads [g0, g0 + HEAD_GROUP) (``g0`` an argument: one program
        serves every group): their share of the attention output
        (each head times its gate) through their rows of W_o, added to x.
        A full layer attends where ``selected`` [T, T] says; a sliding layer
        (``selected`` None) a block of queries against the keys its band can
        reach: the ``window - 1`` before the block's first row, rounded up
        to whole blocks, and the block itself."""
        kd = self.kinds[kind]
        h_all = kd["num_attention_heads"]
        g = min(HEAD_GROUP, h_all)
        if h_all % g:
            raise ValueError(f"{h_all} heads are no whole groups of {g}")
        dn, dr, dv = (kd["qk_nope_head_dim"], kd["qk_rope_head_dim"],
                      kd["v_head_dim"])
        t = x.shape[0]

        def group(a, axis):
            return jax.lax.dynamic_slice_in_dim(a, g0, g, axis)
        wq = group(lw["wq_b"].reshape(-1, h_all, dn + dr), 1)
        wk = group(lw["wk_b"].reshape(-1, h_all, dn), 1)
        wv = group(lw["wv_b"].reshape(-1, h_all, dv), 1)
        wo = group(lw["wo"].reshape(h_all, dv, -1), 0)
        k_nope = self.mm("tc,chn->thn", c_kv, wk)
        v = self.mm("tc,chv->thv", c_kv, wv)
        scale = softmax_scale(kd)
        window = kd["window"]
        behind = -(-(window - 1) // BLOCK) * BLOCK if window else 0
        if window:      # rows before position 0: never inside a band
            k_nope, v, k_r = (jnp.pad(a, ((behind, 0),) + ((0, 0),) * (
                a.ndim - 1)) for a in (k_nope, v, k_r))

        def rows(c_q, cos, sin, q_pos, sel=None, gate=None):
            q = self.mm("tq,qhe->the", c_q, wq)
            q_nope = q[..., :dn]
            q_rope = _rope_interleaved(q[..., dn:], cos, sin)
            kn, vv, kr = k_nope, v, k_r
            if window:
                n_keys = behind + c_q.shape[0]
                kn, vv, kr = (jax.lax.dynamic_slice_in_dim(
                    a, q_pos[0], n_keys, 0) for a in (k_nope, v, k_r))
                key_pos = q_pos[0] - behind + jnp.arange(n_keys)
                gap = q_pos[:, None] - key_pos[None, :]
                sel = (gap >= 0) & (gap < window) & (key_pos >= 0)[None, :]
            s = (self.mm("thn,shn->hts", q_nope, kn)
                 + self.mm("thr,sr->hts", q_rope, kr)) * scale
            s = jnp.where(sel[None], s, NEG)
            p = jax.nn.softmax(s, -1)
            o = self.mm("hts,shv->thv", p, vv)
            if gate is not None:
                o = o * group(gate, 1)[:, :, None]
            return self.mm("thv,hvd->td", o, wo)
        extra = {k: a for k, a in (("sel", selected), ("gate", gate))
                 if a is not None}
        out = _blocks(lambda c_q, cos, sin, q_pos, *more: rows(
            c_q, cos, sin, q_pos, **dict(zip(extra, more))),
            t, c_q, cos, sin, jnp.arange(t), *extra.values())
        return _keep(x + out, self.precision)

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

    def _expert(self, x_rows, norm, w_rows, wg, wu, wd, e):
        """``w_rows * expert_e(norm(x_rows))`` for the rows routed to expert
        e (padded to whole blocks with weight 0). The stacked weights are
        indexed here and the rows are taken out by the caller, so one
        program serves every held expert, layer and sequence length."""
        def block(x, w):
            return w[:, None] * self._swiglu(
                _rms(x, norm, self.eps), wg[e], wu[e], wd[e])
        return _blocks(block, x_rows.shape[0], x_rows, w_rows)

    def _head(self, w, x):
        return self.mm("td,vd->tv", _rms(x, w["norm_f"], self.eps),
                       w["head"])  # untied; stored [V, D] like the embedding

    # -- the whole

    def hidden(self, w: Weights, ids: jax.Array) -> Tuple[jax.Array, Dict]:
        """ids [T] -> residual stream after the last layer [T, D], and what
        was chosen on the way (per layer: the selection mask, None in a
        sliding layer; the routed expert ids and weights, None in a dense
        layer)."""
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
            lw, kd = w[f"layer_{i}"], kind_of(cfg, i)
            # attention's pieces take attention's weights only: one
            # program a kind of layer, whatever feed-forward follows
            aw = {k: lw[k] for k in ATTENTION_WEIGHTS if k in lw}
            c_q, c_kv, k_r, k_i, w_i, gate, cos, sin = self.pre(
                aw, x, kind=kd["name"])
            sel = None if kd["window"] else self.select(
                aw, c_q, k_i, w_i, cos, sin)
            for g0 in range(0, kd["num_attention_heads"], HEAD_GROUP):
                x = self.attend(aw, x, c_q, c_kv, k_r, sel, gate, cos, sin,
                                jnp.int32(g0), kind=kd["name"])
            chosen["selected"].append(None if sel is None else sel[:t, :t])
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
    # (causal: they reach no earlier position): five distinct lengths up to
    # 16,768 positions, so the pieces compile a handful of times over ALL
    # runs (on the chip a length costs 45 s of compilation and 16,768
    # positions 9 s of arithmetic: PERF.md, PR 33); a sequence of one block
    # (the tests' sizes) stays as it is
    buf = np.zeros((-(-len(ids) // BUCKET) * BUCKET
                    if len(ids) > BLOCK else len(ids),), np.int32)
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
