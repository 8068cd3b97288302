"""FlashAttention forward + backward kernels in Pallas for TPU.

Blocked online-softmax attention: for each query block the kernel streams key/
value blocks through VMEM, keeping running max/normalizer/accumulator scratch,
so the [L, L] score matrix never exists in HBM — O(L) memory instead of the
XLA path's O(L^2) logits. This is the framework's long-context kernel (the
reference has no native kernels at all, SURVEY.md §2.1; its GPU equivalent
would be a fused cuDNN/triton attention).

Grid layout (round 5): the kernels iterate a **compressed step table** fed via
``pltpu.PrefetchScalarGridSpec`` — a static [n_steps, 5] int32 array of
``(iq, ik, first, last, diag)`` rows covering only the *live* (query-block,
key-block) pairs. Under causal masking that skips every block strictly above
the diagonal entirely: no grid step, no DMA, no predicated no-op — at L=4096
with 1024-wide blocks, 6 of 16 block pairs vanish from the schedule instead
of being `pl.when`-skipped after their operands were already copied in.
``diag`` marks diagonal-straddling blocks so only they pay the iota/compare
triangle mask; interior blocks run unmasked.

The backward is a **single fused kernel** (FlashAttention-2 math): the forward
emits the per-row log-sum-exp (LSE), and the backward recomputes each
probability block from (q, k, LSE) once, then derives all three gradients
from it — dv += pᵀ·dO, ds = p·(dp − delta), dk += dsᵀ·q, dq_partial = ds·k.
That is 5 MXU passes per block pair versus 7 for the classic two-kernel
split (separate dq and dk/dv kernels each recompute s and dp). The grid runs
column-major so dk/dv accumulate in VMEM scratch across a key-block's column;
dq cannot accumulate in the same order, so each step writes its dq block to a
per-key-block f32 partial buffer that XLA masked-sums over the key axis
afterwards — the dead (above-diagonal) partials are never written and are
excluded by a static mask, so uninitialized memory never reaches the sum.
The partial buffer is capped at ~1 GiB: longer sequences run the backward
as several column passes over sliced k/v, keeping training memory O(L).

Layout choices per the TPU tiling rules (/opt/skills/guides/pallas_guide.md):
last dim padded to a multiple of 128 lanes, block sizes clamped to multiples
of the 8-row sublane tile, in-VMEM running stats (max/normalizer) kept as
[block_q, 128] lane-replicated tiles, scores accumulated in f32 on the MXU
via ``preferred_element_type``. The HBM-resident per-row stats (LSE, delta)
are COMPACT [bh, nq, block_q] whenever block_q is lane-aligned — one small
transpose per block beats writing (and re-reading, once per live step) a
128x lane-replicated copy; tiny/odd block sizes fall back to replication.

Masking: entries whose score was pushed to ``NEG_INF`` (padded keys, causal
future) are excluded by an exact ``where``, so fully-masked query rows
produce true zeros in the forward and zero gradients in the backward. When
there is no pad mask and no key padding, the mask input (and its per-step
VPU add) is dropped entirely.

On non-TPU backends the kernels run in Pallas interpreter mode, so CPU tests
exercise the real kernel logic.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

_VMEM = pltpu.VMEM

__all__ = ["flash_attention", "flash_attention_lse"]

# stable kernel names: traces and compiled-HLO text find them by these
FWD_KERNEL_NAME = "flash_attention_fwd"
BWD_KERNEL_NAME = "flash_attention_bwd"

NEG_INF = -1e9
LANES = 128  # TPU lane width: last-dim tiles and stat buffers align to this
# Cap on the backward's dq partial buffer; beyond it the backward chunks
# into column passes (tests shrink this to force the multi-pass path).
DQ_PARTIAL_BUDGET_BYTES = 1 << 30
# Largest [Lq, D] f32 dq accumulator kept resident in VMEM scratch (the
# fast path: no HBM partials at all). 2 MiB covers L=4096 at Dh'<=128 —
# measured the v5e limit: the 4 MiB L=8192 plane pushes the kernel's
# scoped-VMEM footprint to 19.5M > the 16M cap. Longer sequences fall
# back to the column-pass partial buffer.
DQ_SCRATCH_MAX_BYTES = 2 << 20


@functools.lru_cache(maxsize=None)
def _plan_steps(nq: int, nk: int, block_q: int, block_k: int,
                causal: bool, order: str, col0: int = 0,
                col1: Optional[int] = None):
    """Static step table for the compressed grid.

    Returns (steps [n_steps, 6] int32, live [ncols, nq] bool). Each step row
    is ``(iq, ik_local, first, last, diag, ik_global)``: ``ik_local`` indexes
    blocks of the (possibly column-sliced) operands the kernel sees,
    ``ik_global`` is the key block's position in the FULL sequence (the
    causal iota math needs global column offsets). first/last flag the
    boundary of the accumulation run the kernel owns: for ``order='row'``
    (forward) a run is one query-block row (o/l/m accumulate over its live
    key blocks); for ``order='col'`` (backward) a run is one key-block
    column (dk/dv accumulate over its live query blocks). ``diag`` marks
    blocks straddling the causal diagonal — only those apply the triangle
    mask. ``col0``/``col1`` restrict the table to a half-open range of key
    columns (the backward's memory-bounded column passes).
    """
    if col1 is None:
        col1 = nk

    def is_live(iq, ik):
        return (not causal) or (ik * block_k < (iq + 1) * block_q)

    def is_interior(iq, ik):
        return causal and ((ik + 1) * block_k <= iq * block_q)

    cols = range(col0, col1)
    steps = []
    if order == "row":
        for iq in range(nq):
            ks = [ik for ik in cols if is_live(iq, ik)]
            for ik in ks:
                steps.append((iq, ik - col0, int(ik == ks[0]),
                              int(ik == ks[-1]),
                              int(causal and not is_interior(iq, ik)), ik))
    elif order == "col":
        for ik in cols:
            qs = [iq for iq in range(nq) if is_live(iq, ik)]
            for iq in qs:
                steps.append((iq, ik - col0, int(iq == qs[0]),
                              int(iq == qs[-1]),
                              int(causal and not is_interior(iq, ik)), ik))
    else:  # pragma: no cover
        raise ValueError(order)
    live = np.zeros((col1 - col0, nq), bool)
    for iq, ikl, *_ in steps:
        live[ikl, iq] = True
    return np.asarray(steps, np.int32), live


def _scores(q, k, mask_row, sm_scale, apply_causal, iq, ik, block_q, block_k):
    """Score block [bq, bk] in f32 with key-pad / causal masking applied,
    plus the boolean map of live (unmasked) entries — or None when nothing
    is masked (no pad mask, block fully below the diagonal), so callers can
    skip the exactness ``where``. ``iq``/``ik`` are traced scalars read from
    the step table."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * sm_scale
    if mask_row is not None:
        s = s + (1.0 - mask_row.astype(jnp.float32))[None, :] * NEG_INF
    if apply_causal:
        rows = iq * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        cols = ik * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(rows >= cols, s, NEG_INF)
    # Real scores are O(10); anything at NEG_INF scale is a masked entry.
    live = (s > NEG_INF / 2) if (mask_row is not None or apply_causal) else None
    return s, live


def _masked_exp(s, live, shift):
    """exp(s - shift), exactly zero where masked: without the where, a
    fully-masked row's p would be the softmax over the RAW scores."""
    p = jnp.exp(s - shift)
    return p if live is None else jnp.where(live, p, 0.0)


def _diag_dispatch(causal, diag, body):
    """Run ``body(apply_causal)``: non-causal kernels never mask; causal
    kernels branch on the step table's diag flag so only diagonal-straddling
    blocks pay the iota/compare/where triangle work (interior blocks are
    fully live — the per-element mask is pure VPU waste there; blocks above
    the diagonal are not in the step table at all)."""
    if not causal:
        body(False)
        return

    @pl.when(diag == 0)
    def _interior():
        body(False)

    @pl.when(diag == 1)
    def _diagonal():
        body(True)


def _fwd_kernel(steps_ref, *refs, sm_scale: float, causal: bool,
                block_q: int, block_k: int, has_mask: bool,
                compact_stats: bool):
    if has_mask:
        (mask_ref, q_ref, k_ref, v_ref,
         o_ref, lse_ref, acc_ref, m_ref, l_ref) = refs
    else:
        mask_ref = None
        q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
    t = pl.program_id(1)
    iq = steps_ref[t, 0]
    ik = steps_ref[t, 5]  # global column position (causal iota math)

    @pl.when(steps_ref[t, 2] == 1)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def _compute(apply_causal):
        q = q_ref[0]                       # [block_q, D]
        k = k_ref[0]                       # [block_k, D]
        v = v_ref[0]                       # [block_k, D]
        mask_row = mask_ref[0, 0] if has_mask else None
        s, live = _scores(q, k, mask_row, sm_scale,
                          apply_causal, iq, ik, block_q, block_k)
        m_prev = m_ref[:, :1]                             # [bq, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)        # [bq, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)                   # [bq, 1]
        p = _masked_exp(s, live, m_new)                   # [bq, bk]
        l_ref[:] = alpha * l_ref[:] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = alpha * acc_ref[:] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)

    _diag_dispatch(causal, steps_ref[t, 4], _compute)

    @pl.when(steps_ref[t, 3] == 1)
    def _finalize():
        # Fully-masked query rows have l == 0 exactly; emit zeros, not NaNs.
        l = l_ref[:, :1]
        o_ref[0] = (acc_ref[:] / jnp.maximum(l, 1e-20)).astype(o_ref.dtype)
        lse = m_ref[:, :1] + jnp.log(jnp.maximum(l, 1e-20))
        if compact_stats:
            # stats live COMPACT in HBM ([bh, nq, block_q]; the whole
            # plane is one VMEM-resident block per bh): one small
            # transpose per row block instead of a 128x lane-replicated
            # write (and the backward's matching fat reads)
            lse_ref[0, pl.ds(iq, 1), :] = jnp.transpose(lse, (1, 0))
        else:
            lse_ref[0] = jnp.broadcast_to(lse, lse_ref.shape[1:])


def _bwd_kernel(steps_ref, *refs, sm_scale: float, causal: bool,
                block_q: int, block_k: int, has_mask: bool,
                dq_scratch: bool):
    """Fused backward: one probability recompute feeds dv, dk (VMEM scratch
    accumulation down the key-block's column) AND the step's dq
    contribution. ``dq_scratch=True`` (the fast path) accumulates dq in a
    VMEM-resident [Lq, D] f32 plane, written out once per bh — no HBM
    partials; False writes per-step partials summed outside (huge-L
    fallback)."""
    if has_mask:
        (mask_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dk_ref, dv_ref, dk_acc, dv_acc, *dq_pl) = refs
    else:
        mask_ref = None
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dk_ref, dv_ref, dk_acc, dv_acc, *dq_pl) = refs
    t = pl.program_id(1)
    n_steps = pl.num_programs(1)

    if dq_scratch:
        dq_plane = dq_pl[0]

        @pl.when(t == 0)
        def _zero_plane():
            dq_plane[:] = jnp.zeros_like(dq_plane)
    iq = steps_ref[t, 0]
    ik = steps_ref[t, 5]  # global column position (causal iota math)

    def _stat_col(ref):
        """This row block's per-row stat as a [block_q, 1] column."""
        return ref[0][:, :1]

    @pl.when(steps_ref[t, 2] == 1)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def _compute(apply_causal):
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]                                    # [bq, D]
        mask_row = mask_ref[0, 0] if has_mask else None
        s, live = _scores(q, k, mask_row, sm_scale,
                          apply_causal, iq, ik, block_q, block_k)
        lse = _stat_col(lse_ref)                          # [bq, 1]
        p = _masked_exp(s, live, lse)                     # [bq, bk] f32
        dv_acc[:] += jax.lax.dot_general(                 # p^T dO [bk, D]
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(                         # dO V^T [bq, bk]
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        delta = _stat_col(delta_ref)                      # rowsum(dO*O) [bq,1]
        ds = p * (dp - delta) * sm_scale                  # [bq, bk]
        dk_acc[:] += jax.lax.dot_general(                 # ds^T Q [bk, D]
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dq_blk = jax.lax.dot_general(                     # ds K [bq, D]
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        if dq_scratch:
            row0 = steps_ref[t, 0] * block_q
            dq_plane[pl.ds(row0, block_q), :] += dq_blk
        else:
            dq_ref[0, 0] = dq_blk.astype(dq_ref.dtype)

    _diag_dispatch(causal, steps_ref[t, 4], _compute)

    @pl.when(steps_ref[t, 3] == 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)

    if dq_scratch:
        @pl.when(t == n_steps - 1)
        def _emit_dq():
            dq_ref[0] = dq_plane[:].astype(dq_ref.dtype)


def _pad_to(x: jnp.ndarray, axis: int, multiple: int) -> jnp.ndarray:
    size = x.shape[axis]
    rem = (-size) % multiple
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, rem)
    return jnp.pad(x, pad)


def _block_sizes(L: int, block_q: int, block_k: int):
    """Clamp block sizes to the sequence length, rounded UP to the tile
    floor for the dimension each one feeds: block_q is a sublane dim
    (8-row tile), block_k is the LANE dim of the score/mask tiles (128),
    so explicit small/odd L still lowers on TPU."""
    ceil8 = ((L + 7) // 8) * 8
    ceil_lanes = ((L + LANES - 1) // LANES) * LANES
    return (max(8, min(block_q, ceil8)),
            max(LANES, min(block_k, ceil_lanes)))


def _prep(q, k, v, pad_mask, block_q, block_k):
    """Shared padding/reshape for forward and backward: [B, H, L, Dh] ->
    [B*H, Lq|Lk, D] plus the 8-sublane key-side mask. The mask is None when
    nothing needs key-side masking (no pad mask, no key padding) — the
    kernels then skip the mask input and its per-step add entirely."""
    B, H, L, Dh = q.shape
    qp = _pad_to(_pad_to(q, 3, LANES), 2, block_q)
    kp = _pad_to(_pad_to(k, 3, LANES), 2, block_k)
    vp = _pad_to(_pad_to(v, 3, LANES), 2, block_k)
    Lq, Lk, D = qp.shape[2], kp.shape[2], qp.shape[3]
    if pad_mask is None and Lk != L:
        pad_mask = jnp.ones((B, L), jnp.int32)  # zero-pad keys must mask
    if pad_mask is not None:
        maskp = _pad_to(pad_mask, 1, block_k)  # padded keys -> 0
        mask8 = jnp.broadcast_to(maskp[:, None, :], (B, 8, Lk))
    else:
        mask8 = None
    bh = B * H
    return (qp.reshape(bh, Lq, D), kp.reshape(bh, Lk, D),
            vp.reshape(bh, Lk, D), mask8, Lq, Lk, D)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _grid_call(name, kernel, steps, grid, in_specs, out_specs, out_shape,
               scratch_shapes, inputs):
    """pallas_call through a scalar-prefetch grid spec: the step table rides
    in SMEM ahead of the grid so index maps can route each step's blocks."""
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch_shapes,
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec, out_shape=out_shape, name=name,
        interpret=_interpret())(steps, *inputs)


def _flash_forward(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   pad_mask: Optional[jnp.ndarray], causal: bool,
                   block_q: int, block_k: int):
    """Returns (out [B, H, L, Dh], lse [B*H, Lq, LANES] f32)."""
    B, H, L, Dh = q.shape
    sm_scale = Dh ** -0.5  # scale by the REAL head dim; zero-padding Dh
    # leaves q·k unchanged
    block_q, block_k = _block_sizes(L, block_q, block_k)
    qp, kp, vp, mask8, Lq, Lk, D = _prep(q, k, v, pad_mask, block_q, block_k)
    has_mask = mask8 is not None
    bh = B * H
    nq = Lq // block_q
    steps_np, _ = _plan_steps(nq, Lk // block_k,
                              block_q, block_k, causal, "row")
    grid = (bh, steps_np.shape[0])
    compact = block_q % LANES == 0

    def _iq(b, t, s):
        return (b, s[t, 0], 0)

    def _ik(b, t, s):
        return (b, s[t, 1], 0)

    in_specs = []
    inputs = []
    if has_mask:
        in_specs.append(pl.BlockSpec((1, 8, block_k),
                                     lambda b, t, s: (b // H, 0, s[t, 1]),
                                     memory_space=_VMEM))
        inputs.append(mask8)
    in_specs += [
        pl.BlockSpec((1, block_q, D), _iq, memory_space=_VMEM),
        pl.BlockSpec((1, block_k, D), _ik, memory_space=_VMEM),
        pl.BlockSpec((1, block_k, D), _ik, memory_space=_VMEM),
    ]
    inputs += [qp, kp, vp]

    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, has_mask=has_mask,
        compact_stats=compact)
    lse_spec = (pl.BlockSpec((1, nq, block_q),
                             lambda b, t, s: (b, 0, 0), memory_space=_VMEM)
                if compact else
                pl.BlockSpec((1, block_q, LANES), _iq, memory_space=_VMEM))
    lse_shape = ((bh, nq, block_q) if compact else (bh, Lq, LANES))
    out, lse = _grid_call(
        FWD_KERNEL_NAME, kernel, jnp.asarray(steps_np), grid, in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, D), _iq, memory_space=_VMEM),
            lse_spec,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, Lq, D), q.dtype),
            jax.ShapeDtypeStruct(lse_shape, jnp.float32),
        ],
        scratch_shapes=[
            _VMEM((block_q, D), jnp.float32),       # acc
            _VMEM((block_q, LANES), jnp.float32),   # running max (replicated)
            _VMEM((block_q, LANES), jnp.float32),   # running normalizer
        ],
        inputs=inputs)
    # The LSE persists as a VJP residual for the whole fwd->bwd lifetime in
    # the COMPACT [bh, Lq] form (when block_q is lane-aligned it is written
    # compact by the kernel; tiny/odd blocks write the lane-replicated
    # fallback and compact here).
    lse = lse.reshape(bh, Lq) if compact else lse[:, :, 0]
    return out.reshape(B, H, Lq, D)[:, :, :L, :Dh], lse


def _flash_backward(q, k, v, pad_mask, o, lse, g, causal, block_q, block_k,
                    g_lse=None):
    """Fused blocked dq/dk/dv — each probability block is recomputed from
    (q, k, lse) exactly once and feeds all three gradients; nothing
    [L, L]-shaped touches HBM (FlashAttention-2 backward, single kernel).

    ``g_lse`` (optional, [bh, Lq] f32) is the cotangent of the emitted LSE
    (ring attention differentiates through its cross-hop fold weights):
    d lse_i/d s_ij = p_ij, so the contribution folds into the existing
    softmax-jacobian term as ds = p*(dp - (delta - g_lse)) — the kernel
    runs unchanged on an adjusted delta."""
    B, H, L, Dh = q.shape
    sm_scale = Dh ** -0.5
    block_q, block_k = _block_sizes(L, block_q, block_k)
    qp, kp, vp, mask8, Lq, Lk, D = _prep(q, k, v, pad_mask, block_q, block_k)
    has_mask = mask8 is not None
    bh = B * H
    nq, nk = Lq // block_q, Lk // block_k
    gp = _pad_to(_pad_to(g, 3, LANES), 2, block_q).reshape(bh, Lq, D)
    op = _pad_to(_pad_to(o, 3, LANES), 2, block_q).reshape(bh, Lq, D)
    # delta = rowsum(dO * O) (the softmax-jacobian correction); both stats
    # are expanded to lane-replicated [*, Lq, LANES] tiles here, just-in-time
    # for the kernel (the compact [bh, Lq] form is what persists).
    delta = jnp.sum(gp.astype(jnp.float32) * op.astype(jnp.float32), axis=-1)
    if g_lse is not None:
        delta = delta - g_lse.astype(jnp.float32)
    # The backward reads stats LANE-REPLICATED ([*, Lq, LANES] blocks): the
    # compact layout was measured SLOWER here — its per-step dynamic-row
    # select + lane->sublane transpose (2 per live step) cost more than the
    # fat reads save (the forward, one transpose per ROW-run, keeps the
    # compact write).
    delta = jnp.broadcast_to(delta[..., None], (bh, Lq, LANES))
    lse = jnp.broadcast_to(lse[..., None], (bh, Lq, LANES))

    def _iq(b, t, s):
        return (b, s[t, 0], 0)

    def _ik(b, t, s):
        return (b, s[t, 1], 0)

    stat_spec = pl.BlockSpec((1, block_q, LANES), _iq, memory_space=_VMEM)
    q_spec = pl.BlockSpec((1, block_q, D), _iq, memory_space=_VMEM)
    k_spec = pl.BlockSpec((1, block_k, D), _ik, memory_space=_VMEM)

    # dq blocks revisit non-consecutively under the column-major grid, so
    # they cannot ride an output block's VMEM residency. Fast path: a
    # whole-[Lq, D] f32 accumulator plane in VMEM scratch, zeroed per bh
    # and emitted once — no HBM partials at all (fits to L≈4k at D=128,
    # see DQ_SCRATCH_MAX_BYTES). Fallback
    # for longer sequences: each step writes an f32 partial that XLA sums
    # over the pass's key-block axis afterwards, with the partial buffer
    # capped at ~1 GiB via several column passes over sliced k/v (dk/dv
    # concatenate; dq partial sums accumulate) — training memory stays
    # O(L) either way.
    use_scratch = Lq * D * 4 <= DQ_SCRATCH_MAX_BYTES
    kernel = functools.partial(
        _bwd_kernel, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, has_mask=has_mask,
        dq_scratch=use_scratch)
    per_col = bh * Lq * D * 4
    if use_scratch:
        cols_per_pass = nk
    else:
        cols_per_pass = max(1, min(nk, DQ_PARTIAL_BUDGET_BYTES
                                   // max(per_col, 1)))
    dq = jnp.zeros((bh, Lq, D), jnp.float32)
    dks, dvs = [], []
    for c0 in range(0, nk, cols_per_pass):
        c1 = min(nk, c0 + cols_per_pass)
        ncols = c1 - c0
        steps_np, live_np = _plan_steps(nq, nk, block_q, block_k, causal,
                                        "col", c0, c1)
        if steps_np.shape[0] == 0:  # pragma: no cover — defensive
            dks.append(jnp.zeros((bh, ncols * block_k, D), k.dtype))
            dvs.append(jnp.zeros((bh, ncols * block_k, D), v.dtype))
            continue
        sl = slice(c0 * block_k, c1 * block_k)
        in_specs = []
        inputs = []
        if has_mask:
            in_specs.append(pl.BlockSpec((1, 8, block_k),
                                         lambda b, t, s: (b // H, 0, s[t, 1]),
                                         memory_space=_VMEM))
            inputs.append(mask8[:, :, sl])
        in_specs += [q_spec, k_spec, k_spec, q_spec, stat_spec, stat_spec]
        inputs += [qp, kp[:, sl], vp[:, sl], gp, lse, delta]

        if use_scratch:
            dq_spec = pl.BlockSpec((1, Lq, D), lambda b, t, s: (b, 0, 0),
                                   memory_space=_VMEM)
            dq_shape = jax.ShapeDtypeStruct((bh, Lq, D), q.dtype)
            scratch = [_VMEM((block_k, D), jnp.float32),
                       _VMEM((block_k, D), jnp.float32),
                       _VMEM((Lq, D), jnp.float32)]
        else:
            dq_spec = pl.BlockSpec((1, 1, block_q, D),
                                   lambda b, t, s: (s[t, 1], b, s[t, 0], 0),
                                   memory_space=_VMEM)
            dq_shape = jax.ShapeDtypeStruct((ncols, bh, Lq, D), jnp.float32)
            scratch = [_VMEM((block_k, D), jnp.float32),
                       _VMEM((block_k, D), jnp.float32)]
        dq_part, dk_c, dv_c = _grid_call(
            BWD_KERNEL_NAME, kernel, jnp.asarray(steps_np), (bh, steps_np.shape[0]), in_specs,
            out_specs=[dq_spec, k_spec, k_spec],
            out_shape=[
                dq_shape,
                jax.ShapeDtypeStruct((bh, ncols * block_k, D), k.dtype),
                jax.ShapeDtypeStruct((bh, ncols * block_k, D), v.dtype),
            ],
            scratch_shapes=scratch,
            inputs=inputs)

        if use_scratch:
            dq = dq_part  # already the full [bh, Lq, D] accumulator
        # Masked sum over the key-block axis: dead (above-diagonal)
        # partials were never written — the where keeps their uninitialized
        # contents (possibly NaN bit patterns) out of the reduction. XLA
        # fuses the select into the reduce: one pass over the partials.
        elif bool(np.all(live_np)):
            dq = dq + jnp.sum(dq_part, axis=0)
        else:
            live = jnp.asarray(live_np)  # [ncols, nq]
            part5 = dq_part.reshape(ncols, bh, nq, block_q, D)
            part5 = jnp.where(live[:, None, :, None, None], part5, 0.0)
            dq = dq + jnp.sum(part5, axis=0).reshape(bh, Lq, D)
        dks.append(dk_c)
        dvs.append(dv_c)

    dk = dks[0] if len(dks) == 1 else jnp.concatenate(dks, axis=1)
    dv = dvs[0] if len(dvs) == 1 else jnp.concatenate(dvs, axis=1)

    def unpad(x):
        return x.reshape(B, H, -1, D)[:, :, :L, :Dh]

    return unpad(dq.astype(q.dtype)), unpad(dk), unpad(dv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    pad_mask: Optional[jnp.ndarray] = None,
                    causal: bool = False,
                    block_q: int = 1024, block_k: int = 1024) -> jnp.ndarray:
    """Blocked O(L)-memory attention on [B, H, L, Dh]; numerically matches
    ops.attention._xla_attention (see tests/test_ops.py) in both directions.

    Default 1024x1024 blocks are the measured v5e sweet spot (r4 sweep,
    gpt2-base shape L=4096 bh=48, dispatch-amortized chained timing; 2048-
    wide blocks exceed the 16M scoped-VMEM limit). Short/odd L clamps block
    sizes to the sequence (rounded to the 8-row sublane tile)."""
    out, _ = _flash_forward(q, k, v, pad_mask, causal, block_q, block_k)
    return out


def _fwd(q, k, v, pad_mask, causal, block_q, block_k):
    out, lse = _flash_forward(q, k, v, pad_mask, causal, block_q, block_k)
    return out, (q, k, v, pad_mask, out, lse)


def _bwd(causal, block_q, block_k, res, g):
    q, k, v, pad_mask, o, lse = res
    dq, dk, dv = _flash_backward(q, k, v, pad_mask, o, lse, g, causal,
                                 block_q, block_k)
    return dq, dk, dv, None


flash_attention.defvjp(_fwd, _bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def flash_attention_lse(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        pad_mask: Optional[jnp.ndarray] = None,
                        causal: bool = False,
                        block_q: int = 1024, block_k: int = 1024):
    """Like :func:`flash_attention` but also returns the per-row
    log-sum-exp ([B, H, L] f32). Ring attention (parallel/ring.py) composes
    per-hop flash results with exactly-softmax cross-hop folding using the
    LSE; its gradient flows through BOTH outputs (the fold weights are
    functions of the LSE), which the VJP folds into the delta term.

    Fully-masked query rows emit out == 0 and lse == NEG_INF-ish, which the
    ring fold maps to weight 0 — so masked hops contribute nothing."""
    B, H, L, _ = q.shape
    out, lse = _flash_forward(q, k, v, pad_mask, causal, block_q, block_k)
    return out, lse[:, :L].reshape(B, H, L)


def _fwd_lse(q, k, v, pad_mask, causal, block_q, block_k):
    B, H, L, _ = q.shape
    out, lse = _flash_forward(q, k, v, pad_mask, causal, block_q, block_k)
    return (out, lse[:, :L].reshape(B, H, L)), (q, k, v, pad_mask, out, lse)


def _bwd_lse(causal, block_q, block_k, res, cotangents):
    q, k, v, pad_mask, o, lse = res
    g_out, g_lse = cotangents
    B, H, L, _ = q.shape
    Lq = lse.shape[1]  # padded query length the kernel iterates over
    g_lse_p = jnp.zeros((B * H, Lq), jnp.float32)
    g_lse_p = g_lse_p.at[:, :L].set(
        g_lse.reshape(B * H, L).astype(jnp.float32))
    dq, dk, dv = _flash_backward(q, k, v, pad_mask, o, lse, g_out, causal,
                                 block_q, block_k, g_lse=g_lse_p)
    return dq, dk, dv, None


flash_attention_lse.defvjp(_fwd_lse, _bwd_lse)
