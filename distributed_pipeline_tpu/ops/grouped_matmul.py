"""The expert layer's three products as ONE Pallas kernel over rows that are
sorted by expert and laid out in TILE-ALIGNED groups: every row tile belongs
to exactly one expert, so a grid step is one plain ``[tile, D] x [D, F]``
SwiGLU against that expert's matrices — no mask, no one-hot operand, no
``cond`` an expert — and an expert's 9.4 MB of matrices are fetched once for
all the tiles that hold its rows (consecutive tiles of one expert map to the
same weight block, which the pipeline does not fetch again).

At 64 rows an expert (a 1,024-token chunk over 128 experts, 8 a token) the
layer is bound by the read of its matrices, not by the matrix unit: XLA's own
grouped product (``lax.ragged_dot``) takes row tiles of 512 there, multiplies
each tile whole for EVERY expert that has a row in it, and took 4.5 ms a
layer for the 1.5 ms its bytes need (PERF.md, PR 35). Here the caller picks
the tile (:func:`row_tile`: 128 rows for a chunk, 16 for a decode step's
handful) and pads each expert's group to whole tiles
(:func:`aligned_layout`); tiles behind the last used one are skipped (their
blocks are clamped to the last used tile's, so they move no bytes).

* ``x``        [rows_padded, D]  rows in the aligned layout (``dtype``)
* ``wg, wu``   [E, D, F], ``wd`` [E, F, D]  the held experts' matrices
* ``tile_expert`` [tiles] int32, ``tiles_used`` [1] int32: scalar-prefetched
* result       [rows_padded, D] float32: ``silu(x wg) * (x wu)`` through
               ``wd``, tile by tile; rows of unused tiles are NOT written

:func:`grouped_swiglu_xla` is the same on ``lax.ragged_dot`` over the same
layout (the CPU arm, and what the kernel is tested against)."""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["grouped_swiglu", "grouped_swiglu_xla", "aligned_layout",
           "row_tile", "padded_rows", "KERNEL_NAME"]

KERNEL_NAME = "grouped_expert_matmul"


def row_tile(rows: int) -> int:
    """Rows of a tile for ``rows`` assignments: 128 (the matrix unit's
    height) where experts see dozens of rows, 16 (one bfloat16 sublane
    tile) for a decode step's handful, where a tile's rows are mostly
    padding and its cost is its expert's matrices."""
    return 128 if rows >= 1024 else 16


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
    (index into ``key``) each padded row holds (any valid index where it
    holds none), ``dest`` [M] the padded row of each assignment (0 for one
    that left the sort)."""
    m = key.shape[0]
    n_tiles = padded_rows(m, experts, tile) // tile
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    sizes = jnp.bincount(key, length=experts + 1)[:experts].astype(jnp.int32)
    start = jnp.cumsum(sizes) - sizes              # first sorted position
    tiles = (sizes + tile - 1) // tile
    tile_end = jnp.cumsum(tiles)
    tile_start = tile_end - tiles
    used = tile_end[-1]
    at = jnp.arange(n_tiles, dtype=jnp.int32)
    expert_of = jnp.searchsorted(tile_end, jnp.minimum(
        at, jnp.maximum(used - 1, 0)), side="right").astype(jnp.int32)
    expert_of = jnp.minimum(expert_of, experts - 1)
    # padded row -> the sorted position it holds
    row = jnp.arange(n_tiles * tile, dtype=jnp.int32)
    e_row = expert_of[row // tile]
    rank = row - tile_start[e_row] * tile
    source = order[jnp.clip(start[e_row] + rank, 0, m - 1)]
    # assignment -> its padded row
    back = jnp.zeros((m,), jnp.int32).at[order].set(
        jnp.arange(m, dtype=jnp.int32))
    e_key = jnp.minimum(key, experts - 1)
    dest = jnp.where(key < experts,
                     tile_start[e_key] * tile + back - start[e_key], 0)
    return {"sizes": sizes, "tile_expert": expert_of,
            "tiles_used": used.reshape(1).astype(jnp.int32),
            "source": source, "dest": dest.astype(jnp.int32)}


def grouped_swiglu_xla(x, wg, wu, wd, tile_expert, tiles_used, *,
                       tile: int):
    """The same products by ``lax.ragged_dot`` over the aligned layout:
    group sizes are the experts' tiles times ``tile``. (The CPU's grouped
    product multiplies no bfloat16: there the operands go in as float32 —
    the same sums.)"""
    experts = wg.shape[0]
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


def _kernel(expert_ref, used_ref, x_ref, wg_ref, wu_ref, wd_ref, out_ref):
    del expert_ref

    @pl.when(pl.program_id(0) < used_ref[0])
    def _tile():
        x = x_ref[...]
        gate = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
        up = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
        a = (gate * jax.nn.sigmoid(gate) * up).astype(x.dtype)
        out_ref[...] = jnp.dot(a, wd_ref[0],
                               preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def grouped_swiglu(x, wg, wu, wd, tile_expert, tiles_used, *, tile: int,
                   interpret: bool = False):
    rows, d = x.shape
    _, _, f = wg.shape
    n_tiles = rows // tile

    def last_used(i, used):
        return jnp.minimum(i, jnp.maximum(used[0] - 1, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((tile, d), lambda i, e, u: (last_used(i, u), 0)),
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
    )(tile_expert, tiles_used, x, wg, wu, wd)
