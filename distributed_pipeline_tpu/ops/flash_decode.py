"""Flash-decode: single-query attention straight out of the paged KV pool.

The serving decode step (models/backbone.py ``_paged_attention``, single-
token branch) is pure XLA today: ``gather_kv`` materializes a dense
``[B, H, pages_per_slot * page_size, Dh]`` copy of every slot's pages in HBM
— dead tail pages included — then masked softmax attention re-reads it. Per
generated token that is ~3x the live K/V bytes (pool read + copy write +
copy read), and it scales with the slot's page RESERVATION, not its live
length. This kernel removes the copy: each grid step DMAs ONE live page
``[page_size, H * Dh]`` directly from the pool through the slot's block
table, splits its heads and folds it into online-softmax scratch in VMEM,
and writes only the ``[B, H, Dh]`` output. Dead pages and inactive slots
never enter the schedule (the compressed-step-table trick from
ops/flash_attention.py).

Step table (computed ON DEVICE inside the jitted decode step — positions
and block tables are data, so the table costs no recompile and no host
sync): a static worst-case ``[7, B * pages_per_slot]`` int32 array, one
column of ``(slot, page_id, first, last, needs_mask, page_base, pos)`` per
step (steps along the minor dimension: SMEM pads that one to 128 words).
Live steps cover exactly each slot's ``pos // page_size + 1`` live pages in
slot-major order (a contiguous accumulation run per slot); dead steps are
packed at the tail and route to the trash page and a zero query row, so on
TPU consecutive dead steps re-DMA nothing (identical index-map output) and
the run's first/last flags make them self-contained no-ops. ``needs_mask``
is set only on a slot's LAST live page — the one place the within-page
``position <= pos`` compare is not vacuous (interior pages are fully live).

Page-layout contract (what TP layouts and int8 pages must keep to ride
this kernel later):

* pool is ``[num_pages, page_size, H * Dh]`` per layer, K and V separate:
  a token's heads side by side in one lane-dense row (serving/paged_kv.py
  says why: with ``Dh`` alone in the lanes the TPU stored the pool
  page-minor and every program relaid it). Nothing reshapes the POOL;
  the XLA arm splits the heads of the gathered view, this kernel of the
  page block in VMEM. Page 0 is the trash page — the kernel never reads
  it through a live step, dead steps may;
* a block-table row lists a slot's pages head-first; entries past the live
  prefix may be anything (trash, stale, shared) — the schedule never
  visits them;
* positions are absolute token indices; the row at ``pos % page_size`` of
  page ``pos // page_size`` must already hold the current token's K/V
  (the caller writes via ``write_token_kv`` BEFORE attending);
* page sharing (serving/paged_kv.py ``PrefixCache``) is invisible here:
  two slots listing the same page id just schedule two DMAs of it;
* on real TPU the kernel's ``(H, Dh)`` view of a page block (a reshape of
  the ``[page_size, H * Dh]`` block in VMEM) must tile the ``(8, 128)``
  f32 layout: Mosaic takes the split at ``Dh % 128 == 0`` and refuses it
  at ``Dh`` 64 ("unsupported shape cast"); models that don't tile
  dispatch to the XLA path under ``impl="auto"`` — see
  :func:`resolve_decode_impl`, which reads ``H`` and ``Dh`` from its
  caller (the stored pool no longer shows them);
* int8 pools (serving/paged_kv.py ``write_*_kv_q8``) ride the SAME schedule:
  each page's fp32 scale is bitcast to int32 and appended to its step
  (fields 7..8, K and V scales), so the scale arrives with the scalar
  prefetch and the kernel dequantizes the DMA'd page in VMEM
  (``page.astype(f32) * scale``) before the products — no second gather, no
  extra HBM traffic beyond the 8-byte-per-page scale pair. On real TPU
  int8 page blocks want ``(32, 128)`` tiles; small-model pools again fall
  back to the XLA arm, which dequantizes after ``gather_kv``.

Dispatch: ``impl="auto"`` -> this kernel on TPU (layout permitting), the
XLA gather path elsewhere; ``"pallas"`` forces the kernel (interpreter
mode off-TPU — CPU tests exercise the real kernel logic); ``"xla"`` forces
the gather path. Numerics: the kernel's online softmax reassociates the
sum, so outputs match the XLA path to float tolerance, not bitwise — the
serving contract is greedy-token identity (tests/test_kernels.py).

Chip status (PR 22): the kernel, its int8 form and the span form COMPILE
for a described v5e (tests/test_chip_compile.py) after three repairs that
interpret mode could not ask for — the two head-batched products moved
from ``dot_general`` (no free lhs dimension: refused by Mosaic) to
multiply-and-reduce on the VPU, the int8 scale's bitcast works on a
splatted vector, and the step table turned field-major. It has RUN on a
chip once, for correctness (``H16 / Dh128``, bf16 and int8, decode and
4-link span: within 0.008 of the XLA arm on the same inputs — builder's
run, PR 22). No shipped preset selects it (``Dh = 64`` resolves to the XLA
arm). Speed against that arm: not measured.

HBM accounting: :func:`decode_hbm_bytes` reproduces the schedule's DMA
traffic exactly (blocks x steps, consecutive-identical reuse deducted) —
this is the kernel-arm number the ``gpt2-serve-decode-kernel`` bench leg
lands next to the XLA twin's cost-analysis bytes, because interpreter-mode
emulation (scan + full-array updates) does not share the kernel's memory
profile and cannot be cost-analyzed faithfully off-TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

_VMEM = pltpu.VMEM

__all__ = ["flash_decode", "paged_decode_attention", "paged_span_attention",
           "resolve_decode_impl", "decode_hbm_bytes", "xla_paged_decode",
           "xla_paged_span_decode"]

KERNEL_NAME = "flash_decode"  # stable: traces and HLO text find it
NEG_INF = -1e9
LANES = 128
TRASH_PAGE = 0  # mirrors serving/paged_kv.py (leaf module, no import cycle)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def resolve_decode_impl(impl: str, page_shape=None) -> str:
    """``auto`` -> "pallas" on TPU when the page layout tiles, else "xla".

    ``page_shape`` is the pool's geometry ``(P, page_size, H, Dh)`` as the
    caller knows it — ``H`` and ``Dh`` come from the model or the query,
    the stored pool being ``[P, page_size, H * Dh]`` (optional: auto on TPU
    without it assumes tileable). Forced values pass through."""
    if impl in ("pallas", "xla"):
        return impl
    if impl != "auto":
        raise ValueError(f"decode impl must be auto|pallas|xla, got {impl!r}")
    if jax.default_backend() != "tpu":
        return "xla"
    if page_shape is not None:
        _, _, h, dh = page_shape
        if h % 8 != 0 or dh % LANES != 0:  # pragma: no cover — TPU-only
            return "xla"  # layout contract: (H, Dh) must tile (8, 128)
    return "pallas"  # pragma: no cover — TPU-only


def _build_steps(block_table: jnp.ndarray, positions: jnp.ndarray,
                 page_size: int, n_slots: int, scales_k=None,
                 scales_v=None) -> jnp.ndarray:
    """Traced ``[7, B * n_pages]`` step table (module docstring), one
    COLUMN per step — SMEM pads the minor dimension to 128 words, so the
    steps have to lie along it (step-major, a 4-link span over 8 slots of
    64 pages asked for the whole 1 MiB of SMEM and was refused): live
    steps packed first, slot-major; dead steps route to (slot=B, trash
    page, pos=-1) so they mask to zero and re-DMA nothing on TPU. With
    int8 scales the table grows to 9 fields: each step carries its page's
    K and V scales as bitcast int32, gathered through the block table."""
    B, n = block_table.shape
    pos = positions.astype(jnp.int32)
    n_live = jnp.minimum(pos // page_size + 1, n)              # [B]
    j = jnp.arange(n, dtype=jnp.int32)
    live = j[None, :] < n_live[:, None]                        # [B, n]
    slot = jnp.broadcast_to(jnp.arange(B, dtype=jnp.int32)[:, None], (B, n))
    first = (j[None, :] == 0) & live
    last = (j[None, :] == n_live[:, None] - 1) & live
    base = jnp.broadcast_to((j * page_size)[None, :], (B, n))
    posb = jnp.broadcast_to(pos[:, None], (B, n))
    dead = (~live).reshape(-1).astype(jnp.int32)
    order = jnp.argsort(dead, stable=True)  # stable: keeps slot-major order
    dsel = dead[order]

    def pack(x, fill):
        return jnp.where(dsel == 1, fill,
                         x.reshape(-1)[order]).astype(jnp.int32)

    cols = [
        pack(slot, n_slots), pack(block_table, TRASH_PAGE),
        pack(first.astype(jnp.int32), 1), pack(last.astype(jnp.int32), 1),
        # needs_mask == last: only a slot's final page is partially live
        pack(last.astype(jnp.int32), 1),
        pack(base, 0), pack(posb, -1)]
    if scales_k is not None:
        for sc in (scales_k, scales_v):
            bits = jax.lax.bitcast_convert_type(
                sc.astype(jnp.float32), jnp.int32)[block_table]   # [B, n]
            cols.append(pack(bits, 0))  # dead rows: scale 0 -> dequant to 0
    return jnp.stack(cols, axis=0)


def _decode_kernel(steps_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, scale: float, quant: bool):
    t = pl.program_id(0)

    @pl.when(steps_ref[2, t] == 1)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)        # [H, Dh]
    # the page block arrives lane-dense, [page_size, H * Dh]: split the
    # heads here, in VMEM (the pool in HBM is never reshaped)
    split = (k_ref.shape[1],) + q.shape     # [page_size, H, Dh]
    k = k_ref[0].reshape(split).astype(jnp.float32)
    v = v_ref[0].reshape(split).astype(jnp.float32)
    if quant:  # int8 page + per-page scale riding the step table (bitcast)
        # (bitcast wants a vector on the chip: splat the SMEM word first)
        def scale_of(col):
            word = jnp.full((8, LANES), steps_ref[col, t], jnp.int32)
            return jax.lax.bitcast_convert_type(word, jnp.float32)[:1, :1]

        k = k * scale_of(7)
        v = v * scale_of(8)
    # s[t, h] = q[h, :] . k[t, h, :] — one query row per head has no free
    # lhs dimension, and Mosaic takes no dot_general without one ("failed
    # to parse TPU_DotDimensionNumbersAttr parameter
    # 'lhs_non_contracting_dims'"). So both products are VPU work in the
    # page's own [page_size, H, Dh] layout: multiply, then reduce over the
    # lanes (scores) or over the page rows (output). A decode step reads
    # every K/V byte once for one multiply-add each — it is bound by that
    # read, not by the arithmetic the MXU would have saved.
    s = jnp.sum(k * q[None], axis=-1, keepdims=True) * scale  # [ps, H, 1]

    def _fold(apply_mask):
        sl = s
        if apply_mask:
            tglob = steps_ref[5, t] + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            sl = jnp.where(tglob <= steps_ref[6, t], sl, NEG_INF)
        m_prev = m_ref[:, :1]                            # [H, 1]
        m_new = jnp.maximum(m_prev, jnp.max(sl, axis=0))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(sl - m_new[None])                    # [ps, H, 1]
        if apply_mask:  # exact zeros for masked entries (fully-dead rows
            # would otherwise softmax over the raw trash scores)
            p = jnp.where(sl > NEG_INF / 2, p, 0.0)
        l_ref[:] = alpha * l_ref[:] + jnp.sum(p, axis=0)
        acc_ref[:] = alpha * acc_ref[:] + jnp.sum(p * v, axis=0)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(steps_ref[4, t] == 0)
    def _interior():  # fully-live page: skip the iota/compare mask
        _fold(False)

    @pl.when(steps_ref[4, t] == 1)
    def _boundary():
        _fold(True)

    @pl.when(steps_ref[3, t] == 1)
    def _finalize():
        # Dead runs have l == 0 exactly; emit zeros, not NaNs.
        l = l_ref[:, :1]
        o_ref[0] = (acc_ref[:] / jnp.maximum(l, 1e-20)).astype(o_ref.dtype)


def flash_decode(q: jnp.ndarray, pages_k: jnp.ndarray, pages_v: jnp.ndarray,
                 block_table: jnp.ndarray, positions: jnp.ndarray,
                 scales_k=None, scales_v=None) -> jnp.ndarray:
    """Paged single-query attention: ``q`` [B, H, Dh], pool
    ``[P, page_size, H * Dh]``, ``block_table`` [B, n_pages], ``positions``
    [B] -> [B, H, Dh]. Attends positions ``0..positions[b]`` of each slot
    through its block table; everything later is skipped at schedule level.
    ``scales_k``/``scales_v`` ([P] fp32) flag an int8 pool: the kernel
    dequantizes each DMA'd page with its scale from the step table."""
    B, H, Dh = q.shape
    page_size = pages_k.shape[1]
    quant = scales_k is not None
    steps = _build_steps(block_table, positions, page_size, B,
                         scales_k, scales_v)
    # Row B is the dead-step sink: zero query in, garbage-free zeros out.
    qp = jnp.concatenate([q, jnp.zeros((1, H, Dh), q.dtype)], axis=0)
    n_steps = steps.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_steps,),
        in_specs=[
            pl.BlockSpec((1, H, Dh), lambda t, s: (s[0, t], 0, 0),
                         memory_space=_VMEM),
            pl.BlockSpec((1, page_size, H * Dh),
                         lambda t, s: (s[1, t], 0, 0), memory_space=_VMEM),
            pl.BlockSpec((1, page_size, H * Dh),
                         lambda t, s: (s[1, t], 0, 0), memory_space=_VMEM),
        ],
        out_specs=pl.BlockSpec((1, H, Dh), lambda t, s: (s[0, t], 0, 0),
                               memory_space=_VMEM),
        scratch_shapes=[
            _VMEM((H, Dh), jnp.float32),      # acc
            _VMEM((H, LANES), jnp.float32),   # running max (lane-replicated)
            _VMEM((H, LANES), jnp.float32),   # running normalizer
        ])
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=Dh ** -0.5, quant=quant),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B + 1, H, Dh), q.dtype),
        name=KERNEL_NAME,
        interpret=_interpret())(steps, qp, pages_k, pages_v)
    return out[:B]


def xla_paged_decode(q: jnp.ndarray, pages_k: jnp.ndarray,
                     pages_v: jnp.ndarray, block_table: jnp.ndarray,
                     positions: jnp.ndarray, scales_k=None,
                     scales_v=None) -> jnp.ndarray:
    """The gather-path twin ([B, H, Dh] in/out), kept callable standalone so
    the bench leg can cost-analyze the seam it replaces. int8 pools
    (``scales_*`` given) are dequantized right after the gather."""
    from ..serving.paged_kv import dequant_gathered, gather_kv
    from .attention import dot_product_attention
    h = q.shape[1]
    ks = gather_kv(pages_k, block_table, h)     # [B, H, n*page_size, Dh]
    vs = gather_kv(pages_v, block_table, h)
    if scales_k is not None:
        ps = pages_k.shape[1]
        ks = dequant_gathered(ks, scales_k, block_table, ps, q.dtype)
        vs = dequant_gathered(vs, scales_v, block_table, ps, q.dtype)
    live = (jnp.arange(ks.shape[2])[None, :]
            <= positions[:, None]).astype(jnp.int32)
    o = dot_product_attention(q[:, :, None], ks, vs, live, causal=False,
                              impl="xla")
    return o[:, :, 0]


def paged_decode_attention(q, pages_k, pages_v, block_table, positions,
                           impl: str = "auto", scales_k=None,
                           scales_v=None) -> jnp.ndarray:
    """The decode-step seam: dispatch one generated token's attention.

    ``q`` [B, H, Dh]; returns [B, H, Dh]. The caller has already written
    the token's K/V into the pool (page-layout contract); for int8 pools it
    passes the [P] scale sidecars and both arms dequantize."""
    _, H, Dh = q.shape
    if resolve_decode_impl(impl, pages_k.shape[:2] + (H, Dh)) == "pallas":
        return flash_decode(q, pages_k, pages_v, block_table, positions,
                            scales_k, scales_v)
    return xla_paged_decode(q, pages_k, pages_v, block_table, positions,
                            scales_k, scales_v)


def xla_paged_span_decode(q: jnp.ndarray, pages_k: jnp.ndarray,
                          pages_v: jnp.ndarray, block_table: jnp.ndarray,
                          positions: jnp.ndarray, scales_k=None,
                          scales_v=None) -> jnp.ndarray:
    """Span (speculative-verify) twin of :func:`xla_paged_decode`.

    ``q`` [B, H, L, Dh] holds each slot's L chain links; ``positions``
    [B, L] their per-link depths. Gathers each slot's dense view ONCE —
    the pseudo-slot formulation (L repeated block-table rows through the
    single-token path) re-gathers the same pages L times, and on the XLA
    arm that gather traffic dominated the verify dispatch. Per link the
    math mirrors xla_paged_decode's exactly (same einsum contractions,
    same NEG_INF additive bias in the logits dtype, same f32 softmax), so
    a span link's output is bitwise the single-token output at the same
    position — the spec-decode identity contract rides on this."""
    from ..serving.paged_kv import dequant_gathered, gather_kv
    h, dh = q.shape[1], q.shape[-1]
    ks = gather_kv(pages_k, block_table, h)     # [B, H, n*page_size, Dh]
    vs = gather_kv(pages_v, block_table, h)
    if scales_k is not None:
        ps = pages_k.shape[1]
        ks = dequant_gathered(ks, scales_k, block_table, ps, q.dtype)
        vs = dequant_gathered(vs, scales_v, block_table, ps, q.dtype)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, ks) * jnp.asarray(
        dh ** -0.5, q.dtype)
    live = (jnp.arange(ks.shape[2])[None, None, :]
            <= positions[:, :, None]).astype(jnp.int32)   # [B, L, Lmax]
    logits = logits + (1 - live[:, None]).astype(logits.dtype) * NEG_INF
    probs = jax.nn.softmax(logits.astype(jnp.float32),
                           axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, vs)


def paged_span_attention(q, pages_k, pages_v, block_table, positions,
                         impl: str = "auto", scales_k=None,
                         scales_v=None) -> jnp.ndarray:
    """The speculative-verify span seam: one dispatch attends a whole
    draft chain. ``q`` [B, H, L, Dh], ``positions`` [B, L]; returns
    [B, H, L, Dh]. The caller has already written every link's K/V into
    the pool. The pallas arm runs the flash decode kernel over B*L
    pseudo-slots (each link repeats its slot's block-table row); the XLA
    arm gathers each slot once and masks per link."""
    B, H, L, Dh = q.shape
    if resolve_decode_impl(impl, pages_k.shape[:2] + (H, Dh)) == "pallas":
        qf = q.transpose(0, 2, 1, 3).reshape(B * L, H, Dh)
        bt = jnp.repeat(block_table, L, axis=0)
        o = flash_decode(qf, pages_k, pages_v, bt, positions.reshape(-1),
                         scales_k, scales_v)
        return o.reshape(B, L, H, Dh).transpose(0, 2, 1, 3)
    return xla_paged_span_decode(q, pages_k, pages_v, block_table,
                                 positions, scales_k, scales_v)


def decode_hbm_bytes(block_table: np.ndarray, positions: np.ndarray,
                     page_size: int, n_heads: int, head_dim: int,
                     dtype_bytes: int = 4, kv_dtype_bytes=None,
                     quantized: bool = False) -> int:
    """Exact HBM bytes one kernel invocation DMAs, from its own schedule.

    Counts each DISTINCT live page's K and V blocks once across the whole
    schedule — the schedule visits pages slot-major, so a page shared by
    many slots (PrefixCache) or revisited consecutively is fetched once;
    dedup is by page-id set, which also zero-rates the packed dead tail.
    (The pre-r22 census deduped only consecutive-identical visits, which
    under-credited the kernel on shared-prefix workloads where the same
    prefix pages appear in every slot's run.) Adds one q read and one
    output write per slot and the SMEM step table. ``kv_dtype_bytes``
    prices the pool separately from q/out (int8 pools: 1 vs 4);
    ``quantized`` widens the table to 9 columns — the per-page scale pair
    rides it, so it costs table bytes, not extra page traffic."""
    bt = np.asarray(block_table)
    pos = np.asarray(positions)
    B, n = bt.shape
    if kv_dtype_bytes is None:
        kv_dtype_bytes = 1 if quantized else dtype_bytes
    page_bytes = page_size * n_heads * head_dim * kv_dtype_bytes
    qo_bytes = n_heads * head_dim * dtype_bytes
    n_live = np.minimum(pos // page_size + 1, n)
    total = 0
    seen: set = set()
    for b in range(B):
        for j in range(int(n_live[b])):
            page = int(bt[b, j])
            if page not in seen:
                total += 2 * page_bytes            # K and V blocks
                seen.add(page)
        total += 2 * qo_bytes                      # q read + out write
    total += (B * n) * (9 if quantized else 7) * 4  # step table (SMEM)
    return int(total)
