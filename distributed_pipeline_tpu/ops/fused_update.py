"""Fused AdamW + EMA weight update: one pass over every state copy.

The trainer's XLA update (utils/trainer.py ``train_step``) chains
``optax.adamw`` -> ``apply_updates`` -> one ``update_ema`` tree-map per EMA
rate. On TPU each stage is its own fusion island, so a param leaf is read
back from HBM once per state copy: params' re-read for every EMA rate, the
Adam moments round-tripping between scale_by_adam and the weight-decay /
schedule stages. This kernel does the whole update in ONE pass per leaf:
read param/grad/mu/nu plus every EMA copy once, write param'/mu'/nu' plus
every EMA copy once — ``(4 + R)`` reads and ``(3 + R)`` writes of leaf
bytes, versus the staged path's re-reads (R = number of EMA rates).

Bit-parity contract: the kernel body replays optax's exact op sequence —
``mu' = (1-b1)*g + b1*mu``; ``nu' = (1-b2)*g^2 + b2*nu``;
``u = (mu'/bc1) / (sqrt(nu'/bc2) + eps)``; ``u += wd*p``;
``u *= -lr``; ``p' = p + u``; ``e' = e*rate + p'*(1-rate)`` — with the
per-step scalars (``-lr``, the ``1 - beta**count_inc`` bias corrections)
computed OUTSIDE the kernel by the same expressions optax uses and fed in
as data, so no recompile tracks the schedule. Losses under the fused path
are bit-identical to the optax path (tests/test_kernels.py); the optimizer
state keeps optax's exact pytree structure (ScaleByAdamState counts
increment identically), so checkpoints, ZeRO-1 shardings and restore are
oblivious to which path wrote them.

ZeRO-1 composition: the caller (trainer) runs this inside the jitted train
step with mu/nu/EMA in the zshard layout (parallel/partition
``zero1_shardings``) and out_shardings pinned. GSPMD cannot partition a
Mosaic kernel, so on a mesh of more than one device each leaf's kernel is
wrapped in ``shard_map`` on that layout (:func:`_leaf_update_on_mesh`) and
every shard touches only its own slice. Where that layout is finer than the
param's own (ZeRO-1 on ``data > 1``) the kernel hands back the update and
``p + u`` follows the gather, as in the optax chain — which keeps the two
arms' params bit-equal there too.

Off-TPU the kernel runs in Pallas interpreter mode (real kernel logic on
CPU, tier-1 testable). HBM accounting for the bench leg:
:func:`update_hbm_bytes` is the kernel's exact per-step traffic from the
read/write census above — interpreter-mode emulation can't be
cost-analyzed faithfully (see ops/flash_decode.py) — and the XLA twin is
measured by cost analysis of the staged update compiled standalone.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

_VMEM = pltpu.VMEM

__all__ = ["fused_adamw_ema", "update_hbm_bytes", "resolve_fused_update"]

_TRUE = ("true", "on", "yes", "1")
_FALSE = ("false", "off", "no", "0")


def resolve_fused_update(val: Any) -> bool:
    """Resolve the tri-state ``--fused_update`` flag to a concrete bool.

    ``"auto"`` (the default since ISSUE 20) means "fused on TPU, staged
    optax elsewhere": off-TPU interpreter mode is pure overhead; on TPU
    the one-pass kernel against the optax chain is not measured. Bools
    and the usual true/false spellings still parse so existing argv and
    call sites keep working.
    """
    if isinstance(val, bool):
        return val
    s = str(val).strip().lower()
    if s == "auto":
        return jax.default_backend() == "tpu"
    if s in _TRUE:
        return True
    if s in _FALSE:
        return False
    raise ValueError(f"fused_update must be auto/true/false, got {val!r}")

KERNEL_NAME = "fused_adamw_ema"  # stable: traces and HLO text find it
LANES = 128
_BLOCK_ROWS = 256  # rows per grid step: 256x128 f32 = 128 KiB per operand


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _update_kernel(steps_ref, scal_ref, p_ref, g_ref, mu_ref, nu_ref,
                   *rest, b1: float, b2: float, eps: float, wd: float,
                   rates: Tuple[float, ...], emit_update: bool = False):
    """optax.adamw's elementwise tail + every EMA lerp, one block pass.
    ``rest`` is (ema_in..., p_out, mu_out, nu_out, ema_out...). With
    ``emit_update`` the first output is the update ``u`` and not ``p + u``
    (:func:`_leaf_update_on_mesh`: the caller adds it after the gather)."""
    del steps_ref  # prefetch slot unused: no routing, blocks stream in order
    n_r = len(rates)
    e_in = rest[:n_r]
    p_out, mu_out, nu_out = rest[n_r], rest[n_r + 1], rest[n_r + 2]
    e_out = rest[n_r + 3:]
    step_size = scal_ref[0, 0]   # -lr (already schedule-evaluated)
    bc1 = scal_ref[1, 0]         # 1 - b1**count_inc
    bc2 = scal_ref[2, 0]
    p = p_ref[...]
    g = g_ref[...]
    # Exact optax op order (module docstring) — reassociating any of these
    # breaks the bit-parity contract.
    mu = (1 - b1) * g + b1 * mu_ref[...]
    nu = (1 - b2) * (g * g) + b2 * nu_ref[...]
    u = (mu / bc1) / (jnp.sqrt(nu / bc2) + eps)
    u = u + wd * p
    u = step_size * u
    pn = p + u
    p_out[...] = (u if emit_update else pn).astype(p_out.dtype)
    mu_out[...] = mu
    nu_out[...] = nu
    for i, r in enumerate(rates):
        e_out[i][...] = e_in[i][...] * r + pn * (1.0 - r)


def _leaf_update(p, g, mu, nu, emas: List[jnp.ndarray], scalars,
                 b1: float, b2: float, eps: float, wd: float,
                 rates: Tuple[float, ...], emit_update: bool = False):
    """Run one leaf through the kernel: flatten -> [rows, LANES] blocks."""
    shape, dt = p.shape, p.dtype
    n = p.size
    rows = -(-n // LANES)
    br = min(_BLOCK_ROWS, max(8, rows))
    rows_p = -(-rows // br) * br

    def to2d(x):
        flat = jnp.pad(x.reshape(-1), (0, rows_p * LANES - n))
        return flat.reshape(rows_p, LANES)

    ins = [to2d(x) for x in (p, g, mu, nu, *emas)]
    svec = jnp.broadcast_to(scalars[:, None], (scalars.shape[0], LANES))
    n_out = 3 + len(emas)
    blk = pl.BlockSpec((br, LANES), lambda i, s: (i, 0), memory_space=_VMEM)
    sblk = pl.BlockSpec(svec.shape, lambda i, s: (0, 0), memory_space=_VMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(rows_p // br,),
        in_specs=[sblk] + [blk] * len(ins),
        out_specs=[blk] * n_out,
        scratch_shapes=[])
    outs = pl.pallas_call(
        functools.partial(_update_kernel, b1=b1, b2=b2, eps=eps, wd=wd,
                          rates=rates, emit_update=emit_update),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((rows_p, LANES), dt)] * n_out,
        name=KERNEL_NAME, interpret=_interpret())(
            jnp.zeros((1, 1), jnp.int32), svec, *ins)

    def back(x):
        return x.reshape(-1)[:n].reshape(shape)

    return back(outs[0]), back(outs[1]), back(outs[2]), \
        [back(o) for o in outs[3:]]


def _leaf_update_on_mesh(mesh, spec, pspec, p, g, mu, nu, emas, scalars,
                         b1, b2, eps, wd, rates):
    """:func:`_leaf_update` on a mesh of more than one device. Mosaic
    kernels cannot be partitioned by GSPMD ("wrap the call in a
    shard_map"), so this is that shard_map: the kernel is elementwise, so
    every operand and result of the leaf takes the weight-update layout
    ``spec`` and each device updates its shard; the scalars are replicated.

    Where that layout is finer than the param's own ``pspec`` (ZeRO-1) the
    kernel hands back the update ``u`` and ``p + u`` is taken outside, on
    the param layout — where the optax chain takes it: the gather lies
    between ``u`` and the add there, so the add rounds on its own, while
    an add inside the kernel may contract with ``step_size * u`` into one
    rounding (XLA:CPU does) and the two param trees part by an ulp. The
    EMA copies still lerp toward the kernel's own ``p + u``, so under
    ZeRO-1 they may sit an ulp from the optax arm's on such a backend."""
    from jax.sharding import PartitionSpec as P

    from ..utils.jax_compat import shard_map

    n_r = len(emas)
    gathered = _norm_spec(spec) != _norm_spec(pspec)

    def body(scal, p_, g_, mu_, nu_, *es):
        a, m, v, eo = _leaf_update(p_, g_, mu_, nu_, list(es), scal,
                                   b1, b2, eps, wd, rates,
                                   emit_update=gathered)
        return (a, m, v, *eo)

    outs = shard_map(body, mesh=mesh, in_specs=(P(),) + (spec,) * (4 + n_r),
                     out_specs=(spec,) * (3 + n_r),
                     check_vma=False)(scalars, p, g, mu, nu, *emas)
    new_p = p + outs[0] if gathered else outs[0]
    return new_p, outs[1], outs[2], list(outs[3:])


def _norm_spec(spec) -> Tuple:
    """A PartitionSpec as a comparable tuple: ``"x"`` and ``("x",)`` are
    one axis set, trailing ``None``s say nothing."""
    dims = [() if d is None else (d,) if isinstance(d, str) else tuple(d)
            for d in spec]
    while dims and not dims[-1]:
        dims.pop()
    return tuple(dims)


def fused_adamw_ema(params: Any, grads: Any, opt_state: Any,
                    ema: Dict[str, Any], *, lr_fn, b1: float = 0.9,
                    b2: float = 0.999, eps: float = 1e-8,
                    weight_decay: float = 0.0, mesh=None,
                    specs: Any = None, param_specs: Any = None
                    ) -> Tuple[Any, Any, Dict]:
    """Drop-in replacement for the trainer's staged update:
    ``opt.update -> apply_updates -> update_ema per rate`` in one kernel
    pass per leaf.

    ``opt_state`` must be the state of ``optax.adamw`` (ScaleByAdamState
    first, optional ScaleByScheduleState last — exactly what the trainer's
    ``_make_optimizer`` builds); it is returned with the same structure and
    identically-incremented counts. ``lr_fn`` maps the (pre-increment) step
    count to the learning rate — the trainer passes ``_lr_at`` or a
    constant, matching what it handed optax. ``ema`` maps rate strings to
    params-shaped trees.

    On a ``mesh`` of more than one device pass ``specs``, a params-shaped
    tree of PartitionSpecs — the layout of the weight-update state — and,
    when that is the finer ZeRO-1 layout, ``param_specs``, the params' own
    (default: the same). Each leaf's kernel then runs under ``shard_map``
    on its ``specs`` entry, params and grads are sliced down to it on the
    way in, and where the two layouts differ the update is gathered and
    added on the param layout (:func:`_leaf_update_on_mesh`)."""
    on_mesh = mesh is not None and mesh.size > 1
    if on_mesh and specs is None:
        raise ValueError(
            "fused_adamw_ema on a mesh of more than one device needs the "
            "leaves' PartitionSpecs (specs=): a Mosaic kernel cannot be "
            "partitioned automatically")
    adam = opt_state[0]
    count_inc = optax.safe_int32_increment(adam.count)
    # The same expressions optax evaluates per step (bias_correction /
    # scale_by_schedule), hoisted out of the per-leaf kernels as data.
    bc1 = 1 - b1 ** count_inc
    bc2 = 1 - b2 ** count_inc
    step_size = -lr_fn(adam.count)
    scalars = jnp.stack([jnp.asarray(step_size, jnp.float32),
                         bc1.astype(jnp.float32), bc2.astype(jnp.float32)])
    rate_keys = list(ema.keys())
    rates = tuple(float(r) for r in rate_keys)

    leaves_p, tdef = jax.tree_util.tree_flatten(params)
    leaves_g = jax.tree_util.tree_leaves(grads)
    leaves_mu = jax.tree_util.tree_leaves(adam.mu)
    leaves_nu = jax.tree_util.tree_leaves(adam.nu)
    leaves_e = [jax.tree_util.tree_leaves(ema[r]) for r in rate_keys]
    leaves_s = tdef.flatten_up_to(specs) if on_mesh else None
    leaves_ps = (leaves_s if param_specs is None or not on_mesh
                 else tdef.flatten_up_to(param_specs))
    pn: List[jnp.ndarray] = []
    mun: List[jnp.ndarray] = []
    nun: List[jnp.ndarray] = []
    en: List[List[jnp.ndarray]] = [[] for _ in rate_keys]
    for i in range(len(leaves_p)):
        leaf = (leaves_p[i], leaves_g[i], leaves_mu[i], leaves_nu[i],
                [leaves_e[j][i] for j in range(len(rate_keys))],
                scalars, b1, b2, eps, weight_decay, rates)
        a, m, v, es = (_leaf_update_on_mesh(mesh, leaves_s[i], leaves_ps[i],
                                            *leaf)
                       if on_mesh else _leaf_update(*leaf))
        pn.append(a)
        mun.append(m)
        nun.append(v)
        for j in range(len(rate_keys)):
            en[j].append(es[j])

    unflatten = functools.partial(jax.tree_util.tree_unflatten, tdef)
    new_adam = adam._replace(count=count_inc, mu=unflatten(mun),
                             nu=unflatten(nun))
    rest = [
        s._replace(count=optax.safe_int32_increment(s.count))
        if "count" in getattr(s, "_fields", ()) else s
        for s in opt_state[1:]
    ]
    new_ema = {r: unflatten(en[j]) for j, r in enumerate(rate_keys)}
    return unflatten(pn), (new_adam, *rest), new_ema


def update_hbm_bytes(params: Any, n_ema_rates: int,
                     dtype_bytes: int = 4) -> int:
    """Exact HBM bytes one fused update step moves: ``(4 + R)`` reads and
    ``(3 + R)`` writes of every leaf, plus the per-leaf scalar row. The
    kernel-arm number for the ``diffuseq-base-seq128-fusedupd`` bench leg
    (module docstring: why not cost analysis off-TPU)."""
    leaves = jax.tree_util.tree_leaves(params)
    total = 0
    for leaf in leaves:
        n = int(np_size(leaf))
        total += (4 + n_ema_rates + 3 + n_ema_rates) * n * dtype_bytes
        total += 3 * 4 * LANES  # broadcast scalar row per kernel launch
    return int(total)


def np_size(leaf) -> int:
    size = getattr(leaf, "size", None)
    if size is not None:
        return int(size)
    shape = getattr(leaf, "shape", ())
    out = 1
    for d in shape:
        out *= int(d)
    return out
