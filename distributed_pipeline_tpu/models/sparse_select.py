"""The lightning indexer's selection during a chunked prefill, for every
family that has one (models/deepseek_v32.py's latent layers,
models/keye_vl2.py's grouped-query layers): score the live context block by
block, find each query's ``k``-th largest score exactly, break ties towards
the earlier position. One implementation; a family brings only how a block's
indexer keys come out of its pool.

Everything is TRANSPOSED, keys on the rows and queries on the lanes
(``[keys, queries]``): a context block is whole rows of the score buffer, and
the attention kernel (ops/mla_attention.py) wants its mask that way. The
walks have a dynamic trip count (``n_blocks``): work follows the live length,
not the compiled maximum.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from ..ops import mla_attention

__all__ = ["NEG", "on_chip", "index_scores", "block_attend", "sortable",
           "score_context", "select_top_k"]

NEG = mla_attention.NEG   # "masked" in float32 score space (finite)


def on_chip(kernel_impl: str, *sizes: int) -> str:
    """Which arm a Pallas-backed piece takes: the kernel on the chip where
    the sizes are whole tiles, plain XLA elsewhere (the CPU, a tiny test
    shape); a ``kernel_impl`` other than "auto" forces one."""
    if kernel_impl != "auto":
        return kernel_impl
    aligned = all(n % 128 == 0 for n in sizes)
    return "pallas" if (aligned and jax.default_backend() == "tpu"
                        ) else "xla"


def index_scores(kernel_impl: str, qi_t, wi_t, ki_rows) -> jnp.ndarray:
    """I[s, t] = sum_j w[t, j] relu(q[t, j] . k[s]) for one block of keys,
    TRANSPOSED: ``qi_t`` [J, di, T], ``wi_t`` [J, 1, T], ``ki_rows``
    [S, di] -> [S, T] float32."""
    impl = on_chip(kernel_impl, qi_t.shape[2], ki_rows.shape[0])
    if impl == "xla":
        return mla_attention.index_scores_xla(qi_t, wi_t, ki_rows)
    return mla_attention.index_scores(qi_t, wi_t, ki_rows,
                                      interpret=impl == "interpret")


def block_attend(kernel_impl: str, q_t, k, v_t, bias, carry, scale: float):
    """One context block of the prefill's attention
    (ops/mla_attention.py)."""
    impl = on_chip(kernel_impl, q_t.shape[2], k.shape[1])
    if impl == "xla":
        return mla_attention.block_attend_xla(
            q_t, k, v_t, bias, carry, scale=scale)
    return mla_attention.block_attend(
        q_t, k, v_t, bias, carry, scale=scale,
        interpret=impl == "interpret")


def sortable(x: jnp.ndarray) -> jnp.ndarray:
    """float32 -> uint32 with the same order."""
    u = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    return jnp.where(u >> 31 == 1, ~u, u | jnp.uint32(0x80000000))


def score_context(kernel_impl: str, qi_t, wi_t,
                  block_keys: Callable[[jnp.ndarray], jnp.ndarray],
                  pos: jnp.ndarray, kb: int, n_blocks, l_max: int
                  ) -> jnp.ndarray:
    """Index scores of the live context, block by block: ``block_keys(b)``
    [kb, di] the indexer keys of context block ``b``, ``pos`` [n] the
    queries' positions. Returns [l_max, n] float32, ``NEG`` where a key lies
    behind its query or in a block that is not live."""
    n = pos.shape[0]

    def score_block(b, buf):
        s = index_scores(kernel_impl, qi_t, wi_t, block_keys(b))
        key_pos = b * kb + jnp.arange(kb, dtype=jnp.int32)
        s = jnp.where(key_pos[:, None] <= pos[None, :], s, NEG)
        return jax.lax.dynamic_update_slice(buf, s, (b * kb, 0))
    return jax.lax.fori_loop(
        0, n_blocks, score_block, jnp.full((l_max, n), NEG, jnp.float32))


def select_top_k(scores: jnp.ndarray, live_len, k_sel: int, kb: int,
                 n_blocks) -> jnp.ndarray:
    """``scores`` [l_max, n] (``NEG`` = not a candidate) -> which (key,
    query) pairs are among the query's ``k_sel`` largest, [l_max, n] bool;
    of equal scores at the boundary the earlier positions, as
    ``lax.top_k`` (decode) takes them. With no more live rows than
    ``k_sel`` every candidate is selected."""
    n = scores.shape[1]

    # each query's k-th largest score, exactly: the sortable keys' digits
    # from the top, four bits a pass (a radix select over the live blocks:
    # 8 passes, 15 counts each, one read a block)
    def kth_key(keys):
        def digit(j, prefix):
            shift = (28 - 4 * j).astype(jnp.uint32)
            cands = prefix[None, :] | (
                jnp.arange(1, 16, dtype=jnp.uint32)[:, None] << shift)

            def count(b, acc):
                blk = jax.lax.dynamic_slice(keys, (b * kb, 0), (kb, n))
                return acc + jnp.sum(
                    blk[None] >= cands[:, None, :], 1, dtype=jnp.int32)
            cnt = jax.lax.fori_loop(
                0, n_blocks, count, jnp.zeros((15, n), jnp.int32))
            # counts fall as the digit rises: as many digits reach k as
            # the largest that does
            best = jnp.sum(cnt >= k_sel, 0).astype(jnp.uint32)
            return prefix | (best << shift)
        return jax.lax.fori_loop(0, 8, digit, jnp.zeros((n,), jnp.uint32))
    keys = sortable(scores)
    threshold = jax.lax.cond(
        live_len > k_sel, kth_key,
        lambda keys: jnp.zeros((n,), jnp.uint32), keys)
    selected = (keys >= threshold[None, :]) & (scores > NEG)

    def break_ties(selected):
        """Equal scores at the threshold: the earliest positions take the
        places left."""
        above = (keys > threshold[None, :]) & (scores > NEG)
        equal = selected & ~above
        left = k_sel - jnp.sum(above, 0, dtype=jnp.int32)
        rank = jnp.cumsum(equal, 0, dtype=jnp.int32)
        return above | (equal & (rank <= left[None, :]))
    return jax.lax.cond(
        jnp.max(jnp.sum(selected, 0, dtype=jnp.int32)) > k_sel,
        break_ties, lambda sel: sel, selected)
