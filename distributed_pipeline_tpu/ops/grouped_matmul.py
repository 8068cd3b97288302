"""The expert layer's three products as ONE Pallas kernel over rows that are
sorted by expert and laid out in TILE-ALIGNED groups: every row tile belongs
to exactly one expert, so a grid step is one plain ``[tile, D] x [D, F]``
SwiGLU against that expert's matrices — no mask over experts, no ``cond`` an
expert — and an expert's 9.4 MB of matrices are fetched once for all the
tiles that hold its rows (consecutive tiles of one expert map to the same
weight block, which the pipeline does not fetch again).

The rows are NOT laid out in HBM on the way in. The kernel takes the
tokens' own ``[T, D]`` block whole (one block with a constant index map:
fetched once a call, 4 MB for a chunk) and a ROW TABLE, the token each padded
row holds; a grid step makes its ``[tile, D]`` operand in VMEM as ``(iota ==
token) . h`` on the matrix unit. One non-zero product a row, accumulated in
float32 and cast back: each operand row is the token's row to the bit (a
``-0`` comes out ``+0``). A padded copy of the rows would be 24,448 rows a
layer-chunk, 100 MB written and 71 MB read back (PERF.md, PR 36).

At 64 rows an expert (a 1,024-token chunk over 128 experts, 8 a token) the
layer is bound by the read of its matrices, not by the matrix unit: XLA's own
grouped product (``lax.ragged_dot``) takes row tiles of 512 there, multiplies
each tile whole for EVERY expert that has a row in it, and took 4.5 ms a
layer for the 1.5 ms its bytes need (PERF.md, PR 35). Here the caller picks
the tile (:func:`row_tile`: 128 rows for a chunk, 16 for a decode step's
handful) and pads each expert's group to whole tiles
(:func:`aligned_layout`); tiles behind the last used one are skipped (their
blocks are clamped to the last used tile's, so they move no bytes).

* ``h``        [T, D]  the tokens' rows (``dtype``)
* ``token``    [rows_padded] int32  the row of ``h`` each padded row holds
               (``aligned_layout``'s ``source`` over the assignments a token)
* ``wg, wu``   [E, D, F], ``wd`` [E, F, D]  the held experts' matrices
* ``tile_expert`` [tiles] int32, ``tiles_used`` [1] int32: scalar-prefetched
* result       [rows_padded, D] float32: ``silu(x wg) * (x wu)`` through
               ``wd`` for ``x = h[token]``, tile by tile; rows of unused
               tiles are NOT written

:func:`grouped_swiglu_xla` takes the same arguments, gathers ``h[token]``
itself and runs ``lax.ragged_dot`` over the same layout (the CPU arm, the
arm of a sequence too long to stay resident, and what the kernel is tested
against)."""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["grouped_swiglu", "grouped_swiglu_xla", "aligned_layout",
           "row_tile", "padded_rows", "rows_stay_resident", "KERNEL_NAME",
           "RESIDENT_ROWS_BYTES"]

KERNEL_NAME = "grouped_expert_matmul"
# the tokens' block the kernel keeps in VMEM for a whole call (twice: the
# pipeline's two buffers), beside an expert's matrices twice (19 MB): a
# prefill chunk's 1,024 rows of 2,048 bfloat16. A tile's one-hot operand is
# as wide as the block has rows, so past this the gather costs less
RESIDENT_ROWS_BYTES = 4 * 1024 * 1024


def row_tile(rows: int) -> int:
    """Rows of a tile for ``rows`` assignments: 128 (the matrix unit's
    height) where experts see dozens of rows, 16 (one bfloat16 sublane
    tile) for a decode step's handful, where a tile's rows are mostly
    padding and its cost is its expert's matrices."""
    return 128 if rows >= 1024 else 16


def rows_stay_resident(tokens: int, width: int, dtype) -> bool:
    """Whether ``tokens`` rows of ``width`` numbers are the kernel's to hold
    (a prefill chunk, a decode step), or a whole sequence's, which the XLA
    arm gathers."""
    return tokens * width * jnp.dtype(dtype).itemsize <= RESIDENT_ROWS_BYTES


def padded_rows(rows: int, experts: int, tile: int) -> int:
    """Rows that hold ``rows`` assignments in tile-aligned groups whatever
    the routing: every expert may leave a tile all but one row short."""
    return -(-(rows + experts * (tile - 1)) // tile) * tile


def aligned_layout(key: jnp.ndarray, experts: int, tile: int
                   ) -> Dict[str, jnp.ndarray]:
    """``key`` [M] int32: each assignment's held expert, ``experts`` for one
    that leaves the sort. Groups in expert order, each padded to whole
    tiles of ``tile`` rows. Returns: ``sizes`` [E] real rows an expert,
    ``tile_expert`` [tiles] the expert of each tile (of the last used tile
    behind it), ``tiles_used`` [1], ``source`` [rows_padded] the assignment
    (index into ``key``) each padded row holds (0 where it holds none),
    ``dest`` [M] the padded row of each assignment (0 for one that left the
    sort).

    Made of sorts, compares against the ``experts`` and ONE scatter of the
    ``M`` assignments, not of gathers a padded row: on the chip a gather or
    scatter of int32 scalars costs 5-8 ns an element whatever it reads
    (24,448 padded rows: 0.19 ms), a sort of 8,192 keys 9 us (PERF.md,
    PR 36)."""
    m = key.shape[0]
    n_rows = padded_rows(m, experts, tile)
    n_tiles = n_rows // tile
    held = jnp.arange(experts, dtype=jnp.int32)
    at = jnp.arange(m, dtype=jnp.int32)
    # by expert, an expert's assignments in their own order
    sorted_key, order = jax.lax.sort((key, at), num_keys=2)
    sizes = jnp.sum(key[:, None] == held[None, :], 0, dtype=jnp.int32)
    tiles = (sizes + tile - 1) // tile
    tile_end = jnp.cumsum(tiles)
    used = tile_end[-1]
    # a tile's expert: as many experts' groups end at or before it
    tile_at = jnp.minimum(jnp.arange(n_tiles, dtype=jnp.int32),
                          jnp.maximum(used - 1, 0))
    expert_of = jnp.minimum(jnp.sum(
        tile_end[None, :] <= tile_at[:, None], 1, dtype=jnp.int32),
        experts - 1)
    # sorted position -> padded row: behind the padding of every expert
    # before its own
    pad = tiles * tile - sizes
    row = at + jnp.sum(jnp.where(sorted_key[:, None] > held[None, :],
                                 pad[None, :], 0), 1, dtype=jnp.int32)
    in_sort = sorted_key < experts
    source = jnp.zeros((n_rows,), jnp.int32).at[
        jnp.where(in_sort, row, n_rows + at)].set(
            order, mode="drop", indices_are_sorted=True, unique_indices=True)
    # assignment -> its padded row: `row` back in the assignments' order
    _, dest = jax.lax.sort((order, jnp.where(in_sort, row, 0)), num_keys=1)
    return {"sizes": sizes, "tile_expert": expert_of,
            "tiles_used": used.reshape(1).astype(jnp.int32),
            "source": source, "dest": dest}


def grouped_swiglu_xla(h, token, wg, wu, wd, tile_expert, tiles_used, *,
                       tile: int):
    """The same products by ``lax.ragged_dot`` over the aligned layout, the
    rows gathered first: group sizes are the experts' tiles times ``tile``.
    (The CPU's grouped product multiplies no bfloat16: there the operands go
    in as float32 — the same sums.)"""
    experts = wg.shape[0]
    x = h[token]                                   # [rows padded, D]
    live = jnp.arange(tile_expert.shape[0]) < tiles_used[0]
    sizes = tile * jnp.bincount(jnp.where(live, tile_expert, experts),
                                length=experts + 1)[:experts].astype(
                                    jnp.int32)
    wide = x.dtype if jax.default_backend() == "tpu" else jnp.float32

    def grouped(a, m):
        return jax.lax.ragged_dot(a.astype(wide), m.astype(wide), sizes,
                                  preferred_element_type=jnp.float32)
    a = jax.nn.silu(grouped(x, wg)) * grouped(x, wu)
    return grouped(a.astype(x.dtype), wd)


def _rows_of(h, token):
    """``h[token]`` on the matrix unit: ``h`` [T, D], ``token`` [n, 1] int32
    -> [n, D] in ``h``'s type. One non-zero product a row, so the float32
    sum is that row."""
    pick = jax.lax.broadcasted_iota(
        jnp.int32, (token.shape[0], h.shape[0]), 1) == token
    return jnp.dot(pick.astype(h.dtype), h,
                   preferred_element_type=jnp.float32).astype(h.dtype)


def _kernel(expert_ref, used_ref, token_ref, h_ref, wg_ref, wu_ref, wd_ref,
            out_ref):
    del expert_ref

    @pl.when(pl.program_id(0) < used_ref[0])
    def _tile():
        x = _rows_of(h_ref[...], token_ref[...])
        gate = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
        up = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
        a = (gate * jax.nn.sigmoid(gate) * up).astype(x.dtype)
        out_ref[...] = jnp.dot(a, wd_ref[0],
                               preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def grouped_swiglu(h, token, wg, wu, wd, tile_expert, tiles_used, *,
                   tile: int, interpret: bool = False):
    t, d = h.shape
    _, _, f = wg.shape
    rows = token.shape[0]
    n_tiles = rows // tile

    def last_used(i, used):
        return jnp.minimum(i, jnp.maximum(used[0] - 1, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((tile, 1), lambda i, e, u: (last_used(i, u), 0)),
            pl.BlockSpec((t, d), lambda i, e, u: (0, 0)),
            pl.BlockSpec((1, d, f), lambda i, e, u: (e[i], 0, 0)),
            pl.BlockSpec((1, d, f), lambda i, e, u: (e[i], 0, 0)),
            pl.BlockSpec((1, f, d), lambda i, e, u: (e[i], 0, 0))],
        out_specs=pl.BlockSpec((tile, d),
                               lambda i, e, u: (last_used(i, u), 0)))
    return pl.pallas_call(
        _kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 * 1024 * 1024),
        name=KERNEL_NAME, interpret=interpret,
    )(tile_expert, tiles_used, token.reshape(rows, 1), h, wg, wu, wd)
