"""Plain reference for the GPT-2 configurations: float32 ``jax.numpy``, matmuls
at ``highest`` precision, no kernel, no cache, no batching tricks.

It imports nothing of the program and takes nothing the program has made. It
makes its own weights from the seed (``make_weights``; the drivers hand the
same arrays to the program), computes the forward pass, the masked next-token
loss, its gradients (in blocks of rows, so that it fits beside nothing else on
a chip) and AdamW, and reads a served token's gap to the reference's best.

The architecture is the published GPT-2 (pre-LayerNorm blocks, fused qkv,
tanh-approximated GELU, 4x MLP, learned positions, tied output head) with the
departures the configuration files list: the linear layers carry no bias, as
the program under test builds them.

``precision`` selects the arithmetic of every matmul operand:
``float32`` (the reference), and the lower precisions that serve as the
control of "How correct is decided": ``bfloat16`` (what the configuration
states), ``fp8`` (e4m3, per-tensor scale) and ``int8`` (per-tensor scale).
The lower precisions round the operands of every matmul, forward and backward
(the 8-bit forms through their grid, e5m2 for an fp8 cotangent, then to
bfloat16), multiply with float32 accumulation, and keep every matmul's result
and the residual stream in bfloat16, as a bfloat16 program does.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Weights = Dict[str, jax.Array]
LAYER_KEYS = ("ln1.g", "ln1.b", "attn.wqkv", "attn.wo",
              "ln2.g", "ln2.b", "mlp.wi", "mlp.wo")
PRECISIONS = ("float32", "bfloat16", "fp8", "int8")


# ------------------------------------------------------------------ weights

def param_shapes(cfg: Dict[str, Any]) -> Dict[str, Tuple[int, ...]]:
    d, h = cfg["n_embd"], cfg["n_head"]
    dh = d // h
    shapes: Dict[str, Tuple[int, ...]] = {
        "wte": (cfg["vocab_size"], d), "wpe": (cfg["n_positions"], d)}
    for i in range(cfg["n_layer"]):
        shapes.update({
            f"h{i}.ln1.g": (d,), f"h{i}.ln1.b": (d,),
            f"h{i}.attn.wqkv": (d, 3, h, dh), f"h{i}.attn.wo": (h, dh, d),
            f"h{i}.ln2.g": (d,), f"h{i}.ln2.b": (d,),
            f"h{i}.mlp.wi": (d, 4 * d), f"h{i}.mlp.wo": (4 * d, d)})
    shapes.update({"lnf.g": (d,), "lnf.b": (d,)})
    return shapes


def param_count(cfg: Dict[str, Any]) -> int:
    return int(sum(math.prod(s) for s in param_shapes(cfg).values()))


def seed_arg(seed: int) -> np.ndarray:
    """``--seed`` (any whole number up to a little over 2**31) as the uint32
    that ``make_weights`` takes. Pass it as an ARGUMENT of the jitted call,
    not through a closure: a seed baked into the program is a new program,
    and a new compile, for every seed."""
    return np.uint32(int(seed) % (2 ** 32))


def make_weights(cfg: Dict[str, Any], seed) -> Weights:
    """float32 weights from the seed (``seed_arg``; may be traced), GPT-2's
    published initialisation: normal(0, initializer_range) for every matrix,
    the two projections that write into the residual stream scaled by
    1/sqrt(2 * n_layer); LayerNorm scale 1, bias 0. Pure ``jax.random``: jit
    it to make them on the device in one call."""
    std = float(cfg.get("initializer_range", 0.02))
    resid = std / math.sqrt(2.0 * cfg["n_layer"])
    key = jax.random.PRNGKey(seed)
    out: Weights = {}
    for i, (name, shape) in enumerate(param_shapes(cfg).items()):
        if name.endswith(".g"):
            out[name] = jnp.ones(shape, jnp.float32)
        elif name.endswith(".b"):
            out[name] = jnp.zeros(shape, jnp.float32)
        else:
            s = resid if name.endswith(("attn.wo", "mlp.wo")) else std
            out[name] = s * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32)
    return out


# ----------------------------------------------------------------- forward

def _round_to(x: jax.Array, precision: str, cotangent: bool = False
              ) -> jax.Array:
    """Round a matmul operand to the precision's grid, then to bfloat16.
    The 8-bit forms use a per-tensor absmax scale; in ``fp8`` a forward
    operand takes e4m3 and a cotangent e5m2, as fp8 training does."""
    if precision == "bfloat16":
        return x.astype(jnp.bfloat16)
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    if precision == "fp8":
        grid, top = ((jnp.float8_e5m2, 57344.0) if cotangent
                     else (jnp.float8_e4m3fn, 448.0))
        scale = amax / top
        q = (x / scale).astype(grid).astype(jnp.float32) * scale
    else:
        scale = amax / 127.0
        q = jnp.round(x / scale) * scale
    return q.astype(jnp.bfloat16)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 3))
def _low_mm(eq: str, a: jax.Array, b: jax.Array, precision: str
            ) -> jax.Array:
    return jnp.einsum(eq, _round_to(a, precision), _round_to(b, precision),
                      preferred_element_type=jnp.float32)


def _low_mm_fwd(eq, a, b, precision):
    aq, bq = _round_to(a, precision), _round_to(b, precision)
    out = jnp.einsum(eq, aq, bq, preferred_element_type=jnp.float32)
    return out, (aq, bq)


def _low_mm_bwd(eq, precision, saved, g):
    """The backward matmuls of a low-precision program: the rounded
    operands against the cotangent, itself rounded to the precision."""
    aq, bq = saved
    gq = _round_to(g, precision, cotangent=True)
    _, vjp = jax.vjp(lambda x, y: jnp.einsum(
        eq, x, y, preferred_element_type=jnp.float32), aq, bq)
    da, db = vjp(gq.astype(jnp.float32))
    return da.astype(jnp.float32), db.astype(jnp.float32)


_low_mm.defvjp(_low_mm_fwd, _low_mm_bwd)


def _mm(eq: str, a: jax.Array, b: jax.Array, precision: str) -> jax.Array:
    if precision == "float32":
        return jnp.einsum(eq, a, b, precision=jax.lax.Precision.HIGHEST)
    return _keep(_low_mm(eq, a, b, precision), precision)


def _keep(x: jax.Array, precision: str) -> jax.Array:
    """An activation as the precision keeps it: bfloat16 below float32."""
    if precision == "float32":
        return x
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _layer_norm(x: jax.Array, g: jax.Array, b: jax.Array, eps: float
                ) -> jax.Array:
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def _gelu_new(x: jax.Array) -> jax.Array:
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(x: jax.Array, lw: Dict[str, jax.Array], key_mask: jax.Array,
           eps: float, precision: str) -> jax.Array:
    L = x.shape[1]
    dh = lw["attn.wqkv"].shape[-1]
    h = _layer_norm(x, lw["ln1.g"], lw["ln1.b"], eps)
    qkv = _mm("bld,dthk->tbhlk", h, lw["attn.wqkv"], precision)
    q, k, v = qkv[0], qkv[1], qkv[2]
    s = _mm("bhqk,bhjk->bhqj", q, k, precision) / math.sqrt(dh)
    allowed = (jnp.tril(jnp.ones((L, L), bool))[None, None]
               & (key_mask[:, None, None, :] > 0))
    s = jnp.where(allowed, s, jnp.finfo(jnp.float32).min)
    p = jax.nn.softmax(s, axis=-1)
    o = _mm("bhqj,bhjk->bhqk", p, v, precision)
    x = _keep(x + _mm("bhlk,hkd->bld", o, lw["attn.wo"], precision),
              precision)
    h = _layer_norm(x, lw["ln2.g"], lw["ln2.b"], eps)
    h = _gelu_new(_mm("bld,dm->blm", h, lw["mlp.wi"], precision))
    return _keep(x + _mm("blm,md->bld", h, lw["mlp.wo"], precision),
                 precision)


def hidden_states(w: Weights, cfg: Dict[str, Any], ids: jax.Array,
                  key_mask: Optional[jax.Array] = None,
                  precision: str = "float32") -> jax.Array:
    """ids [B, L] -> final hidden states [B, L, D] (after the last
    LayerNorm). The layers run as a scan over their stacked weights with
    each block rematerialised in the backward pass: same mathematics, less
    memory and a shorter compile."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    B, L = ids.shape
    eps = float(cfg.get("layer_norm_epsilon", 1e-5))
    if key_mask is None:
        key_mask = jnp.ones((B, L), jnp.int32)
    x = _keep(w["wte"][ids] + w["wpe"][:L][None], precision)
    stacked = {k: jnp.stack([w[f"h{i}.{k}"] for i in range(cfg["n_layer"])])
               for k in LAYER_KEYS}
    block = jax.checkpoint(
        lambda x, lw: _block(x, lw, key_mask, eps, precision))
    x, _ = jax.lax.scan(lambda x, lw: (block(x, lw), None), x, stacked)
    return _layer_norm(x, w["lnf.g"], w["lnf.b"], eps)


def logits(w: Weights, cfg: Dict[str, Any], ids: jax.Array,
           key_mask: Optional[jax.Array] = None,
           precision: str = "float32") -> jax.Array:
    h = hidden_states(w, cfg, ids, key_mask, precision)
    return _mm("bld,vd->blv", h, w["wte"], precision)


# ---------------------------------------------------------------- training

def masked_nll_sum(w: Weights, cfg: Dict[str, Any], batch: Dict[str, Any],
                   precision: str = "float32") -> jax.Array:
    """Sum over the loss span of the next-token negative log-likelihood:
    position t predicts ids[t + 1]; the span is input_mask * pad_mask."""
    ids = batch["input_ids"]
    lg = logits(w, cfg, ids, batch["pad_mask"], precision)[:, :-1]
    tgt = ids[:, 1:]
    mask = (batch["input_mask"] * batch["pad_mask"])[:, 1:].astype(
        jnp.float32)
    lse = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, tgt[..., None], axis=-1)[..., 0]
    return ((lse - picked) * mask).sum()


def loss_and_grads(w: Weights, cfg: Dict[str, Any],
                   batch: Dict[str, np.ndarray], *, rows_per_block: int,
                   precision: str = "float32",
                   grad_fn=None) -> Tuple[jax.Array, Weights]:
    """Masked mean loss over the whole batch and its gradients, accumulated
    over blocks of rows so that the activations of one block are all that is
    live. (Every row of the benchmark's corpus has the same span length, so
    this equals the mean of per-microbatch means that gradient accumulation
    takes.)"""
    n = batch["input_ids"].shape[0]
    denom = float((batch["input_mask"] * batch["pad_mask"])[:, 1:].sum())
    if grad_fn is None:
        grad_fn = make_grad_fn(cfg, precision)
    total = jnp.zeros((), jnp.float32)
    grads = None
    for r in range(0, n, rows_per_block):
        blk = {k: jnp.asarray(v[r:r + rows_per_block])
               for k, v in batch.items()}
        s, g = grad_fn(w, blk)
        total = total + s
        grads = g if grads is None else jax.tree_util.tree_map(
            jnp.add, grads, g)
    scale = 1.0 / max(denom, 1.0)
    return total * scale, jax.tree_util.tree_map(lambda x: x * scale, grads)


def make_grad_fn(cfg: Dict[str, Any], precision: str = "float32"):
    return jax.jit(jax.value_and_grad(
        lambda w, b: masked_nll_sum(w, cfg, b, precision)))


def lr_at(count: int, hp: Dict[str, Any]) -> float:
    """Linear anneal lr * (1 - count / learning_steps), count = updates
    already made; a constant where learning_steps is 0."""
    lr = float(hp["lr"])
    if hp.get("learning_steps", 0) > 0:
        lr *= max(0.0, 1.0 - count / hp["learning_steps"])
    return lr


@functools.partial(jax.jit, static_argnames=("b1", "b2", "eps", "wd"))
def _adamw(w, m, v, g, lr, t, *, b1, b2, eps, wd):
    def leaf(p, m_, v_, g_):
        m_ = b1 * m_ + (1.0 - b1) * g_
        v_ = b2 * v_ + (1.0 - b2) * g_ * g_
        mhat = m_ / (1.0 - b1 ** t)
        vhat = v_ / (1.0 - b2 ** t)
        p = p - lr * (mhat / (jnp.sqrt(vhat) + eps) + wd * p)
        return p, m_, v_
    out = {k: leaf(w[k], m[k], v[k], g[k]) for k in w}
    return ({k: o[0] for k, o in out.items()},
            {k: o[1] for k, o in out.items()},
            {k: o[2] for k, o in out.items()})


def leaf_norms(tree: Weights) -> Dict[str, float]:
    got = jax.device_get(jax.jit(lambda t: {
        k: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
        for k, x in t.items()})(tree))
    return {k: float(x) for k, x in got.items()}


def train_steps(w0: Weights, cfg: Dict[str, Any],
                batches: Sequence[Dict[str, np.ndarray]],
                hp: Dict[str, Any], *, rows_per_block: int,
                precision: str = "float32") -> Dict[str, Any]:
    """Follow ``len(batches)`` optimizer steps from ``w0``. Returns each
    step's loss (before its update), the first gradient's norm by leaf and
    the norm by leaf of the parameters' change over all the steps."""
    b1, b2 = float(hp.get("b1", 0.9)), float(hp.get("b2", 0.999))
    eps, wd = float(hp.get("eps", 1e-8)), float(hp.get("weight_decay", 0.0))
    grad_fn = make_grad_fn(cfg, precision)
    w = w0
    m = jax.tree_util.tree_map(jnp.zeros_like, w0)
    v = jax.tree_util.tree_map(jnp.zeros_like, w0)
    losses: List[float] = []
    grad_norms: Dict[str, float] = {}
    for t, batch in enumerate(batches, start=1):
        loss, g = loss_and_grads(w, cfg, batch, rows_per_block=rows_per_block,
                                 precision=precision, grad_fn=grad_fn)
        losses.append(float(loss))
        if t == 1:
            grad_norms = leaf_norms(g)
        w, m, v = _adamw(w, m, v, g, jnp.float32(lr_at(t - 1, hp)),
                         jnp.float32(t), b1=b1, b2=b2, eps=eps, wd=wd)
        del g
    delta = leaf_norms(jax.jit(lambda a, b: {k: a[k] - b[k] for k in a})(
        w, w0))
    return {"losses": losses, "grad_norms": grad_norms, "delta_norms": delta}


# ----------------------------------------------------------------- serving

def served_gaps(w: Weights, cfg: Dict[str, Any], ids: np.ndarray,
                prompt_len: int, precision_pick: Optional[str] = None,
                fwd=None) -> np.ndarray:
    """For one request, ``ids`` = prompt followed by the tokens served for
    it (greedy): at each served position, how far the served token's logit
    lies below the reference's best, in the reference's own float32 logits.
    0 where the served token is the reference's pick.

    With ``precision_pick`` the served tokens are ignored beyond giving the
    context: the token compared at each position is the one a forward pass
    in that lower precision puts first (the control: it need not decode)."""
    n = len(ids) - prompt_len
    pad_to = cfg["n_positions"]
    buf = np.zeros((1, pad_to), np.int32)
    buf[0, :len(ids)] = ids
    if fwd is None:
        fwd = make_logits_fn(cfg)
    lg = fwd("float32")(w, jnp.asarray(buf))[0]
    rows = lg[prompt_len - 1:prompt_len - 1 + n]            # [n, V]
    if precision_pick is None:
        picked = jnp.asarray(ids[prompt_len:prompt_len + n])
    else:
        low = fwd(precision_pick)(w, jnp.asarray(buf))[0]
        picked = jnp.argmax(low[prompt_len - 1:prompt_len - 1 + n], axis=-1)
    gap = rows.max(-1) - jnp.take_along_axis(
        rows, picked[:, None], axis=-1)[:, 0]
    return np.asarray(jax.device_get(gap), np.float64)


def make_logits_fn(cfg: Dict[str, Any]):
    """``fwd(precision)`` -> jitted ``(w, ids[1, n_positions]) -> logits``;
    one compiled program a precision (trailing zero padding cannot reach an
    earlier position through causal attention)."""
    @functools.lru_cache(maxsize=None)
    def fwd(precision: str):
        return jax.jit(lambda w, ids: logits(w, cfg, ids, None, precision))
    return fwd
