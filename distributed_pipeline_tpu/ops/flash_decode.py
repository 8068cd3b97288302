"""Flash-decode: single-query attention straight out of the paged KV pool.

The XLA arm of the serving decode step (``xla_paged_decode`` ->
serving/paged_kv.py ``gather_kv``) materializes a dense copy of every
slot's page RESERVATION in HBM — dead tail pages included — splits its
heads (at ``Dh`` 64, half a lane tile, a relayout that moves every byte)
and runs masked softmax attention over all of it: its cost follows slots x
reservation, not live tokens (PERF.md PR 28, PR 30). This kernel removes
the copy and the split: each grid step attends a BLOCK of ``G`` consecutive
pages ``[page_size, H * Dh]`` of a slot, copied straight from the pool
through the slot's block table — the pages that hold a live position and
no other — folds it into online-softmax scratch in VMEM, and writes only a
``[1, H * Dh]`` output row a slot. Dead pages never enter the schedule, and
the dead entries of a slot's last block are never copied.

No head is split, of the pool or of the query. A slot's query row
``[1, H * Dh]`` is spread once into a block-diagonal matrix
``Qd [heads, H * Dh]`` (row ``h`` keeps head ``h``'s ``Dh`` lanes, zeros
elsewhere), so ``Qd @ K^T`` over the block's rows AS THEY LIE IN THE POOL
is the per-head scores ``[heads, rows]`` — heads on the sublanes,
positions on the lanes, flash attention's own layout. The softmax runs
there in float32; ``P @ V`` gives ``[heads, H * Dh]``, of which row ``h``
is only wanted on head ``h``'s own lanes: that diagonal is taken once, at
the slot's last block. Both products have free dimensions on both sides
(MXU, operands in the pool's own type, float32 accumulation: what the XLA
arm's einsums do), every array is two-dimensional with whole lane tiles,
``H`` need not divide by 8 and ``Dh`` need not be 128. The price is
``heads`` (``H`` padded to 16) times the arithmetic a per-head product
would need, on an MXU a decode step otherwise leaves idle — and no row of
the pool is converted, split or multiplied on the VPU (computing the
scores as ``(k * q) @ S`` with a 0/1 head-sum matrix needs float32
products to stay exact and took 3.7 x the kernel time: PERF.md PR 30).

Step table (computed ON DEVICE inside the jitted decode step — positions
and block tables are data, so the table costs no recompile and no host
sync): a static worst-case ``[6 + G, B * ceil(n / G)]`` int32 array, one
column of ``(slot, first, last, base, pos, live)`` + ``G`` page ids per
step (steps along the minor dimension: SMEM pads that one to 128 words).
``G`` comes from ``page_size`` alone (a block of ``BLOCK_ROWS`` positions:
16 pages of 16) and the pages a slot has. The columns cover each slot's
``ceil((pos // page_size + 1) / G)`` live blocks in slot-major order (a
contiguous accumulation run per slot, found by comparing the column's
index with the running sum of the slots' block counts: no sort); their
number is the kernel's GRID, a traced scalar, so no dead step runs (a
static grid of ``B * ceil(n / G)`` steps spent half the kernel's time on
its dead tail). ``live`` is the count of the column's entries that hold a
live position, ``min(G, n_live - blk * G)``: ``G`` but in a slot's last
block. A slot with no live position (``pos`` < 0) still gets one block,
``live`` 0, all masked, and reads zeros.

The copies are the kernel's own (PR 38) where a step's ``2 G`` pages are
more than ``HIDDEN_STEP_BYTES`` (GPT-2-large's bfloat16 pool: 1.3 MB).
The pools stay in HBM (``memory_space=pl.ANY``) and a step starts one copy
a pool for each LIVE entry of a column, each page straight to its
``page_size`` rows of the column's buffer ``[G, page_size, H * Dh]`` (a
ring of ``MAX_RING`` buffers a pool where ``RING_BYTES`` hold them, else
of two), for the column ``ring - 1`` steps ahead of the one it computes,
across slot boundaries; then it waits for its own column's. The rows of an
entry past the live ones hold what an earlier step left there (the V
buffers start a call zeroed, so it is finite) and are masked by
``position <= pos`` — the mask is vacuous elsewhere and applied
everywhere: one form. Before, the pools were ``2 G`` pipeline operands of
one page each, a dead entry named the trash page, and the pipeline copied
all ``2 G`` at every step, whatever was live and whatever an operand had
held the step before (naming the same page again does not stop it): a
grid step was its 1.3 MB at four fifths of the HBM peak, 2.1-2.3 us, with
the arithmetic hidden under it (the chip, PERF.md PR 38).

Where the copies already hide, they stay those pipeline operands, a dead
entry naming the slot's last live page (read again under the mask), not
the trash page. A step's own arithmetic is about 0.9 us, and the kernel's
own copies are started and awaited by the scalar core in loops that
arithmetic cannot overlap, about 37 ns a page, so they win where the bytes
bound the step and lose where they do not (the chip, PERF.md PR 38, at
the dense serve cell's depth): an int8 pool (0.66 MB a step: 30.8 us a
call as pipeline operands, 42.8 with the kernel's own copies, where the
bfloat16 pool went 61.5 -> 44.4); GPT-2-base's bfloat16 pool (0.79 MB:
47.8 against 48.5); and the span form at any width, whose pseudo-slots
list the same pages ``L`` times in a row — the chip reads a page again
within microseconds far under the HBM's price (a 4-link span at
GPT-2-large's width: 139.9 us as pipeline operands, 233.6 with the
kernel's own copies), so ``paged_span_attention`` says ``revisits``.

Page-layout contract (what TP layouts must keep to ride this kernel):

* pool is ``[num_pages, page_size, H * Dh]`` per layer, K and V separate:
  a token's heads side by side in one lane-dense row (serving/paged_kv.py
  says why: with ``Dh`` alone in the lanes the TPU stored the pool
  page-minor and every program relaid it). Nothing reshapes the POOL; the
  XLA arm splits the heads of its gathered view, this kernel splits
  nothing. Page 0 is the trash page — this kernel does not read it (a
  slot whose table lists it under a live position, a released slot with a
  stale position, reads it as that slot's page);
* a block-table row lists a slot's pages head-first; entries past the live
  prefix may be anything (trash, stale, shared) — the schedule never
  copies them;
* positions are absolute token indices; the row at ``pos % page_size`` of
  page ``pos // page_size`` must already hold the current token's K/V
  (the caller writes via ``write_token_kv`` BEFORE attending);
* page sharing (serving/paged_kv.py ``PrefixCache``) is invisible here:
  two slots listing the same page id just start two copies of it;
* on real TPU a row must be whole lane tiles, ``(H * Dh) % 128 == 0``, and
  a page block whole sublane tiles of the pool's type (``page_size`` a
  multiple of 16; of 8 for a float32 pool); other shapes dispatch to the
  XLA path under ``impl="auto"`` — see :func:`resolve_decode_impl`, which
  reads ``H``, ``Dh``, ``page_size`` and the type from its caller;
* int8 pools (serving/paged_kv.py ``write_*_kv_q8``) ride the SAME
  schedule: each page's fp32 scales are bitcast to int32 and appended to
  its step's column (``2 G`` more rows), so they arrive with the scalar
  prefetch; the page is cast to the query's type (exact) and its scale
  multiplies the page's score columns (K) and weight columns (V) — no
  second gather, no extra HBM traffic beyond 8 bytes a page (an entry
  past the live ones carries the slot's last live page's pair: finite,
  under a zero weight). The chip's compiler takes a 16-row int8 page
  (half its (32, 128) tile) as a copy's destination.

Dispatch: ``impl="auto"`` -> this kernel on TPU (layout permitting), the
XLA gather path elsewhere; ``"pallas"`` forces the kernel (interpreter
mode off-TPU — CPU tests exercise the real kernel logic, also on rows
that are not whole lane tiles); ``"xla"`` forces the gather path.
Numerics: the kernel's online softmax reassociates the sum and keeps its
scores in float32 where the XLA arm rounds them to the activation type, so
outputs match the XLA path to float tolerance, not bitwise — the serving
contract is greedy-token identity (tests/test_kernels.py).

Chip status (PR 30, PR 38): 'auto' selects the kernel for GPT-2-large
(``H20 / Dh64``), GPT-2-base (``H12 / Dh64``) and ``H16 / Dh128``, bf16 and
int8 pools; it compiles for a described v5e at those shapes, decode and
4-link span (tests/test_chip_compile.py), and RUNS on the chip inside the
served model (``gpt2-large.serve.closed16``, PERF.md PR 30, PR 38) and
alone against the XLA arm (``python chip_smoke.py --only decode``: both
geometries, bf16 and int8, decode and span, with each arm's time a call
and the schedule's page census).
The span form runs B * L pseudo-slots through the kernel and so reads a
slot's pages L times (the XLA span arm gathers them once).

HBM accounting: :func:`decode_hbm_bytes` prices the schedule's traffic
(the distinct pages that hold a live position — the trash page is not
among them — the step table, a q and an output row a slot), because
interpreter-mode emulation (scan + full-array updates) does not share the
kernel's memory profile and cannot be cost-analyzed faithfully off-TPU;
:func:`decode_page_census` is its page count beside the live pages'.
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
           "resolve_decode_impl", "decode_hbm_bytes", "decode_page_census",
           "xla_paged_decode",
           "xla_paged_span_decode"]

KERNEL_NAME = "flash_decode"  # stable: traces and HLO text find it
NEG_INF = -1e9
LANES = 128
BLOCK_ROWS = 256        # positions a grid step attends: G pages of page_size
HIDDEN_STEP_BYTES = 1 << 20  # a step's 2 G pages up to this many bytes hide
#                              under its arithmetic as pipeline operands
MAX_RING = 4            # buffers a pool for the kernel's own copies (the step
RING_BYTES = 8 << 20    # computed + 3 ahead) where this much VMEM holds them
# step-table rows (one column a grid step); G page ids follow, then for an
# int8 pool G K-scale words and G V-scale words
_SLOT, _FIRST, _LAST, _BASE, _POS, _LIVE, _PAGE0 = range(7)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def resolve_decode_impl(impl: str, page_shape=None, kv_dtype=None) -> str:
    """``auto`` -> "pallas" on TPU when the page layout tiles, else "xla".

    ``page_shape`` is the pool's geometry ``(P, page_size, H, Dh)`` as the
    caller knows it — ``H`` and ``Dh`` come from the model or the query,
    the stored pool being ``[P, page_size, H * Dh]`` — and ``kv_dtype`` the
    pool's type (optional, both: auto on TPU without them assumes a bf16
    pool that tiles). The rule is on shapes and the type alone: a row of
    whole lane tiles, and a page block ``(page_size, H * Dh)`` of whole
    sublane tiles of the pool's type. Forced values pass through."""
    if impl in ("pallas", "xla"):
        return impl
    if impl != "auto":
        raise ValueError(f"decode impl must be auto|pallas|xla, got {impl!r}")
    if jax.default_backend() != "tpu":
        return "xla"
    if page_shape is not None:
        _, page_size, h, dh = page_shape
        # float32 tiles hold 8 rows, bfloat16 16; an int8 tile holds 32,
        # but the chip's compiler takes a 16-row int8 block (half a tile:
        # tests/test_chip_compile.py) and the chip ran it (chip_smoke.py)
        rows = 8 if jnp.dtype(kv_dtype or jnp.bfloat16).itemsize == 4 else 16
        if (h * dh) % LANES != 0 or page_size % rows != 0:
            return "xla"
    return "pallas"


def _pages_per_block(page_size: int, n_pages: int) -> int:
    """``G``: pages a grid step attends — a block of about ``BLOCK_ROWS``
    positions (16 pages of 16), never more pages than a slot has."""
    return max(1, min(BLOCK_ROWS // page_size, n_pages))


def _live_counts(positions, page_size: int, n_pages: int, g: int, xp):
    """Live pages and live blocks a slot ([B] each; ``xp`` is jnp for the
    traced table, numpy for the byte census). An empty slot still gets one
    block, all masked, so that its output row is written (zeros)."""
    n_live = xp.clip(positions // page_size + 1, 0, n_pages)
    return n_live, xp.maximum(-(-n_live // g), 1)


def _schedule(block_table, positions, page_size: int, g: int, xp):
    """The kernel's schedule ([T] = every column of the step table; ``xp``
    as in :func:`_live_counts`): each column's slot, its block of the slot,
    the slots' live-block counts, the grid (the columns that are steps),
    the LIVE entries a column — ``min(G, n_live - blk * G)``, the pages the
    kernel copies — and the ``[T, G]`` page ids. Step ``t`` belongs to the
    slot whose run of live blocks holds it (a compare against the running
    sum of the slots' live-block counts: no sort). An entry past the live
    ones repeats the slot's last live page: it is never copied, and names a
    page only so that an int8 pool's scale words there are a live page's."""
    B, n = block_table.shape
    nb = -(-n // g)
    n_live, nb_live = _live_counts(positions, page_size, n, g, xp)
    ends = xp.cumsum(nb_live)
    t = xp.arange(B * nb, dtype=xp.int32)
    slot = xp.minimum(
        xp.sum(t[:, None] >= ends[None, :], axis=1), B - 1).astype(xp.int32)
    blk = t - (ends - nb_live)[slot]
    live = xp.clip(n_live[slot] - blk * g, 0, g).astype(xp.int32)
    j = xp.minimum(blk[:, None] * g + xp.arange(g, dtype=xp.int32)[None, :],
                   xp.maximum(n_live[slot] - 1, 0)[:, None])        # [T, G]
    pages = block_table[slot[:, None], j].astype(xp.int32)
    return slot, blk, nb_live, ends[-1], live, pages


def _build_steps(block_table: jnp.ndarray, positions: jnp.ndarray,
                 page_size: int, g: int, scales_k=None, scales_v=None):
    """Traced step table ``[6 + G, B * ceil(n / G)]`` (module docstring)
    and the number of its columns that are steps — the kernel's grid, a
    traced scalar. One COLUMN per step — SMEM pads the minor dimension to
    128 words, so the steps have to lie along it; the columns past the
    last slot's run are never visited. With int8 scales the table grows by
    ``2 G`` rows: each named page's K and V scale as bitcast int32,
    gathered through the page ids."""
    pos = positions.astype(jnp.int32)
    slot, blk, nb_live, n_steps, live, pages = _schedule(
        block_table, pos, page_size, g, jnp)
    rows = [slot, blk == 0, blk == nb_live[slot] - 1,
            blk * (g * page_size), pos[slot], live]
    rows += list(pages.T)
    if scales_k is not None:
        for sc in (scales_k, scales_v):
            bits = jax.lax.bitcast_convert_type(
                sc.astype(jnp.float32), jnp.int32)[pages]         # [T, G]
            rows += list(bits.T)
    return jnp.stack([r.astype(jnp.int32) for r in rows], axis=0), n_steps


def _fetch_live_pages(steps_ref, k_hbm, v_hbm, kbuf, vbuf, sems):
    """The kernel's own copies (a step of over ``HIDDEN_STEP_BYTES``):
    start the LIVE entries of the column ``ring - 1`` steps ahead, a page
    of each pool straight to its rows of that column's buffers — no other
    page leaves HBM — then wait for this step's own. The copies of later
    steps fly while this one is computed, across slot boundaries."""
    t = pl.program_id(0)
    n_steps = pl.num_programs(0)           # the traced grid
    ring = kbuf.shape[0]

    def copies(col, i):
        page, to = steps_ref[_PAGE0 + i, col], col % ring
        return (pltpu.make_async_copy(k_hbm.at[page], kbuf.at[to, i],
                                      sems.at[to, 0]),
                pltpu.make_async_copy(v_hbm.at[page], vbuf.at[to, i],
                                      sems.at[to, 1]))

    def start(col):
        def one(i, carry):
            for copy in copies(col, i):
                copy.start()
            return carry
        jax.lax.fori_loop(0, steps_ref[_LIVE, col], one, 0)

    @pl.when(t == 0)
    def _prologue():
        # rows of entries never copied lie under a zero weight and have to
        # be finite: what a buffer held before this call need not be
        vbuf[:] = jnp.zeros_like(vbuf)
        for col in range(ring - 1):
            pl.when(col < n_steps)(functools.partial(start, col))

    pl.when(t + ring - 1 < n_steps)(functools.partial(start, t + ring - 1))

    def land(i, carry):
        for copy in copies(t, i):
            copy.wait()
        return carry
    jax.lax.fori_loop(0, steps_ref[_LIVE, t], land, 0)


def _decode_kernel(steps_ref, q_ref, *refs, scale: float, g: int,
                   head_dim: int, quant: bool, own_copies: bool):
    t = pl.program_id(0)
    if own_copies:
        k_hbm, v_hbm, o_ref, kbuf, vbuf, sems = refs[:6]
        _fetch_live_pages(steps_ref, k_hbm, v_hbm, kbuf, vbuf, sems)
        here = t % kbuf.shape[0]           # the buffers this step reads
        k_pages = [kbuf.at[here, i] for i in range(g)]
        v_pages = [vbuf.at[here, i] for i in range(g)]
        refs = refs[6:]
    else:
        # 2 G pipeline operands of a page, copied at every step
        k_pages = [ref.at[0] for ref in refs[:g]]
        v_pages = [ref.at[0] for ref in refs[g:2 * g]]
        o_ref, refs = refs[2 * g], refs[2 * g + 1:]
    qd_ref, acc_ref, m_ref, l_ref = refs
    heads, width = qd_ref.shape            # heads: H padded to 16 rows
    page_size = k_pages[0].shape[0]
    n_rows = g * page_size
    dtype = qd_ref.dtype                   # the products' operand type
    exact = None if dtype == jnp.bfloat16 else jax.lax.Precision.HIGHEST

    def own_lanes():
        """[heads, H * Dh] mask: row h owns head h's Dh lanes of a row."""
        lane = jax.lax.broadcasted_iota(jnp.int32, (heads, width), 1)
        lo = jax.lax.broadcasted_iota(
            jnp.int32, (heads, width), 0) * head_dim
        return (lane >= lo) & (lane < lo + head_dim)

    @pl.when(steps_ref[_FIRST, t] == 1)
    def _init():
        # No head is split, of the pool or of the query. The slot's query
        # row becomes a block-diagonal [heads, H * Dh] matrix (row h keeps
        # head h's lanes, zeros elsewhere), so that Qd @ K^T over the
        # pool's own lane-dense rows IS the per-head scores.
        qd_ref[:] = jnp.where(own_lanes(), q_ref[0].astype(jnp.float32),
                              0.0).astype(dtype)
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def block(page_refs):
        """The step's G pages as one [G * page_size, H * Dh] block, rows in
        position order, as they lie in the pool (int8: cast, exact)."""
        pages = [ref[...].astype(dtype) for ref in page_refs]
        return pages[0] if g == 1 else jnp.concatenate(pages, axis=0)

    def page_scales(row):
        """int8 pools: the G pages' scales from the step table, a lane a
        block row ([1, G * page_size]). The words are selected as int32
        and bitcast as one vector (the chip takes no scalar bitcast)."""
        lane = jax.lax.broadcasted_iota(jnp.int32, (8, n_rows), 1)
        words = jnp.zeros((8, n_rows), jnp.int32)
        for i in range(g):
            words = jnp.where(lane >= i * page_size, steps_ref[row + i, t],
                              words)
        return jax.lax.bitcast_convert_type(words, jnp.float32)[:1]

    # scores [heads, rows]: heads along the sublanes, the block's
    # positions along the lanes — flash attention's own layout, with
    # free dimensions on both sides of both products (MXU).
    s = jax.lax.dot_general(
        qd_ref[:], block(k_pages), (((1,), (1,)), ((), ())),
        precision=exact, preferred_element_type=jnp.float32) * scale
    if quant:
        s = s * page_scales(_PAGE0 + g)
    at = steps_ref[_BASE, t] + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 1)
    live = at <= steps_ref[_POS, t]    # vacuous but on a slot's last
    s = jnp.where(live, s, NEG_INF)    # block; one form, no branch
    m_prev = m_ref[:]                  # [heads, 128], lane-replicated
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    # exact zeros for masked positions (a block with no live position
    # would otherwise softmax over whatever its rows hold)
    p = jnp.where(live, jnp.exp(s - m_new[:, :1]), 0.0)
    l_new = alpha * l_ref[:] + jnp.sum(p, axis=1, keepdims=True)
    m_ref[:] = m_new
    l_ref[:] = l_new
    if quant:
        p = p * page_scales(_PAGE0 + 2 * g)
    # [heads, H * Dh]: row h is head h's weights over EVERY head's
    # lanes; only its own Dh lanes are kept, at the slot's last block
    acc = alpha[:, :1] * acc_ref[:] + jnp.dot(
        p.astype(dtype), block(v_pages), precision=exact,
        preferred_element_type=jnp.float32)
    acc_ref[:] = acc

    @pl.when(steps_ref[_LAST, t] == 1)
    def _finalize():
        # a slot with no live position has l == 0: zeros, not NaNs
        own = jnp.where(own_lanes(),
                        acc / jnp.maximum(l_new[:, :1], 1e-20), 0.0)
        o_ref[0] = jnp.sum(own, axis=0, keepdims=True).astype(
            o_ref.dtype)


def flash_decode(q: jnp.ndarray, pages_k: jnp.ndarray, pages_v: jnp.ndarray,
                 block_table: jnp.ndarray, positions: jnp.ndarray,
                 scales_k=None, scales_v=None,
                 revisits: bool = False) -> jnp.ndarray:
    """Paged single-query attention: ``q`` [B, H, Dh], pool
    ``[P, page_size, H * Dh]``, ``block_table`` [B, n_pages], ``positions``
    [B] -> [B, H, Dh]. Attends positions ``0..positions[b]`` of each slot
    through its block table; everything later is skipped at schedule level.
    ``scales_k``/``scales_v`` ([P] fp32) flag an int8 pool: the kernel
    scales each page's scores and weights with its pair from the step
    table. ``revisits``: consecutive slots list the same pages (the span
    form's pseudo-slots). The kernel copies the live pages itself where a
    step's pages are more bytes than hide under its arithmetic and are not
    such revisits, which the chip reads again cheaply (module docstring)."""
    page_size, width = pages_k.shape[1:]
    g = _pages_per_block(page_size, block_table.shape[1])
    step_bytes = 2 * g * page_size * width * pages_k.dtype.itemsize
    return _flash_decode(
        q, pages_k, pages_v, block_table, positions, scales_k, scales_v,
        interpret=_interpret(),
        own_copies=not revisits and step_bytes > HIDDEN_STEP_BYTES)


# jitted, so that a model's layers share ONE trace of the kernel and its
# table (traced a layer, 36 layers of 33 block specs cost the serve cell
# 12 s of set-up); where it runs is part of the cache's key
@functools.partial(jax.jit, static_argnames=("interpret", "own_copies"))
def _flash_decode(q, pages_k, pages_v, block_table, positions, scales_k,
                  scales_v, *, interpret: bool, own_copies: bool):
    B, H, Dh = q.shape
    page_size, width = pages_k.shape[1:]
    g = _pages_per_block(page_size, block_table.shape[1])
    quant = scales_k is not None
    dtype = q.dtype if quant else jnp.promote_types(q.dtype, pages_k.dtype)
    steps, n_steps = _build_steps(block_table, positions, page_size, g,
                                  scales_k, scales_v)

    row_spec = pl.BlockSpec((1, 1, width), lambda t, s: (s[_SLOT, t], 0, 0),
                            memory_space=_VMEM)
    heads = -(-H // 16) * 16        # whole sublane tiles of either type
    scratch = [
        _VMEM((heads, width), dtype),         # block-diagonal query
        _VMEM((heads, width), jnp.float32),   # acc
        _VMEM((heads, LANES), jnp.float32),   # running max
        _VMEM((heads, LANES), jnp.float32),   # running normaliser
    ]
    if own_copies:
        # the pools stay in HBM and the kernel copies the live pages
        # itself, into a ring of buffers: the one computed and the steps
        # ahead of it (a power of two: the step's buffer is t % ring)
        block_bytes = g * page_size * width * pages_k.dtype.itemsize
        ring = MAX_RING if 2 * MAX_RING * block_bytes <= RING_BYTES else 2
        pools = [pl.BlockSpec(memory_space=pl.ANY)] * 2
        operands = [pages_k, pages_v]
        scratch = [_VMEM((ring, g, page_size, width), pages_k.dtype),
                   _VMEM((ring, g, page_size, width), pages_v.dtype),
                   pltpu.SemaphoreType.DMA((ring, 2))] + scratch
    else:
        # copies that hide under the step's arithmetic as they are, where
        # the kernel's own would not (module docstring). The SAME pool
        # operand is named G times; spec i reads page id i of the step's
        # column (the pipeline's own DMAs)
        pools = [pl.BlockSpec((1, page_size, width),
                              lambda t, s, i=i: (s[_PAGE0 + i, t], 0, 0),
                              memory_space=_VMEM) for i in range(g)] * 2
        operands = [pages_k] * g + [pages_v] * g
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_steps,),   # traced: the live blocks, no dead step runs
        in_specs=[row_spec] + pools,
        out_specs=row_spec,
        scratch_shapes=scratch)
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=Dh ** -0.5, g=g,
                          head_dim=Dh, quant=quant, own_copies=own_copies),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, 1, width), q.dtype),
        name=KERNEL_NAME,
        interpret=interpret)(steps, q.reshape(B, 1, width), *operands)
    return out.reshape(B, H, Dh)


def xla_paged_decode(q: jnp.ndarray, pages_k: jnp.ndarray,
                     pages_v: jnp.ndarray, block_table: jnp.ndarray,
                     positions: jnp.ndarray, scales_k=None,
                     scales_v=None) -> jnp.ndarray:
    """The gather-path twin ([B, H, Dh] in/out), callable standalone: the
    tests' reference and the arm ``chip_smoke.py --only decode`` times the
    kernel against. int8 pools (``scales_*`` given) are dequantized right
    after the gather."""
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
    if resolve_decode_impl(impl, pages_k.shape[:2] + (H, Dh),
                           pages_k.dtype) == "pallas":
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
    if resolve_decode_impl(impl, pages_k.shape[:2] + (H, Dh),
                           pages_k.dtype) == "pallas":
        qf = q.transpose(0, 2, 1, 3).reshape(B * L, H, Dh)
        bt = jnp.repeat(block_table, L, axis=0)
        o = flash_decode(qf, pages_k, pages_v, bt, positions.reshape(-1),
                         scales_k, scales_v, revisits=True)
        return o.reshape(B, L, H, Dh).transpose(0, 2, 1, 3)
    return xla_paged_span_decode(q, pages_k, pages_v, block_table,
                                 positions, scales_k, scales_v)


def decode_page_census(block_table: np.ndarray, positions: np.ndarray,
                       page_size: int):
    """``(pages_live, pages_copied)`` of one kernel invocation a pool, both
    distinct page ids: the pages that hold a live position of some slot,
    by the block table and the positions alone, and the pages the kernel
    starts a copy for — the live entries of its schedule's steps
    (:func:`_schedule` itself, in numpy). They are equal. The mechanism's
    counter — ``chip_smoke.py --only decode`` prints it; the serving tick
    does not compute it."""
    bt = np.asarray(block_table)
    pos = np.asarray(positions).astype(np.int32)
    n = bt.shape[1]
    g = _pages_per_block(page_size, n)
    n_live, _ = _live_counts(pos, page_size, n, g, np)
    holds = {int(p) for b, k in enumerate(n_live) for p in bt[b, :int(k)]}
    *_, n_steps, live, pages = _schedule(bt, pos, page_size, g, np)
    copied = np.arange(g)[None, :] < live[:int(n_steps), None]
    return len(holds), len({int(p) for p in pages[:int(n_steps)][copied]})


def decode_hbm_bytes(block_table: np.ndarray, positions: np.ndarray,
                     page_size: int, n_heads: int, head_dim: int,
                     dtype_bytes: int = 4, kv_dtype_bytes=None,
                     quantized: bool = False) -> int:
    """HBM bytes one kernel invocation has to move, from its own schedule.

    Counts each DISTINCT page's K and V blocks once across the whole
    schedule (:func:`decode_page_census`'s ``pages_copied``: the live
    pages and no other — no copy is started for the entries of a slot's
    last block past its live ones; a page shared by many slots
    (PrefixCache) is priced once, dedup is by page-id set; the table's
    columns past the last live block are not steps: the grid ends there).
    Adds one q read and one output write per slot and the SMEM step table,
    ``[6 + G, B * ceil(n / G)]`` words with ``G`` the kernel's own
    (:func:`_pages_per_block`). ``kv_dtype_bytes`` prices the pool
    separately from q/out (int8 pools: 1 vs 4); ``quantized`` adds the
    table's ``2 G`` scale rows — the per-page scale pair rides it, so it
    costs table bytes, not extra page traffic."""
    B, n = np.asarray(block_table).shape
    if kv_dtype_bytes is None:
        kv_dtype_bytes = 1 if quantized else dtype_bytes
    width = n_heads * head_dim
    g = _pages_per_block(page_size, n)
    nb = -(-n // g)
    _, copied = decode_page_census(block_table, positions, page_size)
    total = copied * 2 * page_size * width * kv_dtype_bytes  # K and V
    total += B * 2 * width * dtype_bytes           # q read + out write
    total += (B * nb) * (_PAGE0 + (3 if quantized else 1) * g) * 4  # table
    return int(total)
