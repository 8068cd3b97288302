"""The two score matrices of the chunked MLA prefill as Pallas kernels: one
block of masked attention, and one block of the lightning indexer's scores.
In both, the scores of a query chunk against ONE block of context never leave
on-chip memory.

``models/deepseek_v32.py`` walks the live context block by block (a dynamic
trip count) and carries an online softmax through the walk. In plain XLA every
block's ``[heads, queries, keys]`` float32 scores go to HBM and come back
three or four times (scale and mask, row maximum, exponential and sum, the
value product): at 640 FLOPs a score that traffic, not the matrix unit, set
the time (PERF.md, PR 29: 1.1 ms a block of 256 keys against 0.1 ms of
matmul). Here a grid step holds one head's scores for a tile of queries in
VMEM, updates the running maximum, sum and accumulator, and only they travel.

Everything is laid out TRANSPOSED, keys on the sublanes and queries on the
lanes, so that the per-query statistics are lane-major rows ``[1, queries]``
and no operand is transposed inside the kernel:

* ``q_t``   [H, Dq, C]   queries (nope | rope), ``Dq`` on the sublanes
* ``k``     [H, K, Dq]   the block's keys a head (nope | the shared rope key)
* ``v_t``   [H, Dv, K]   the block's values a head, transposed
* ``bias``  [K, C]       0 where query c may attend key k, ``NEG`` elsewhere
                         (the indexer's selection and causality; one mask for
                         all heads)
* carry     ``m`` / ``l`` [H, 1, C], ``acc`` [H, Dv, C]: float32, updated in
            place (``input_output_aliases``)

The grid is (query tiles, heads, key tiles); the key axis is innermost and
sequential, the carry's block stays resident across it. :func:`block_attend_xla`
is the same update in ``jax.numpy`` (the CPU arm, and what the kernel is
tested against)."""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["block_attend", "block_attend_xla", "index_scores",
           "index_scores_xla", "KERNEL_NAME", "INDEX_KERNEL_NAME", "NEG"]

KERNEL_NAME = "mla_block_attend"
INDEX_KERNEL_NAME = "lightning_index_scores"
NEG = -1e30

Carry = Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]


def block_attend_xla(q_t, k, v_t, bias, carry: Carry, *, scale: float
                     ) -> Carry:
    m, l, acc = carry
    s = jnp.einsum("hkd,hdc->hkc", k, q_t,
                   preferred_element_type=jnp.float32) * scale + bias[None]
    m_new = jnp.maximum(m, jnp.max(s, 1, keepdims=True))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new)
    l = alpha * l + jnp.sum(p, 1, keepdims=True)
    acc = alpha * acc + jnp.einsum("hvk,hkc->hvc", v_t, p.astype(v_t.dtype),
                                   preferred_element_type=jnp.float32)
    return m_new, l, acc


def _kernel(q_ref, k_ref, v_ref, bias_ref, m_in, l_in, acc_in,
            m_out, l_out, acc_out, *, scale: float):
    @pl.when(pl.program_id(2) == 0)
    def _load_carry():
        m_out[...] = m_in[...]
        l_out[...] = l_in[...]
        acc_out[...] = acc_in[...]

    s = jnp.dot(k_ref[0], q_ref[0],
                preferred_element_type=jnp.float32) * scale + bias_ref[...]
    m_old = m_out[0]                                           # [1, tq]
    m_new = jnp.maximum(m_old, jnp.max(s, axis=0, keepdims=True))
    alpha = jnp.exp(m_old - m_new)
    p = jnp.exp(s - m_new)                                     # [tk, tq]
    l_out[0] = alpha * l_out[0] + jnp.sum(p, axis=0, keepdims=True)
    acc_out[0] = alpha * acc_out[0] + jnp.dot(
        v_ref[0], p.astype(v_ref.dtype), preferred_element_type=jnp.float32)
    m_out[0] = m_new


def _tile(n: int, want: int) -> int:
    """The largest multiple of 128 that divides ``n`` and is at most
    ``want``; ``n`` itself when it has none (a small test shape)."""
    best = 0
    for t in range(128, min(n, want) + 1, 128):
        if n % t == 0:
            best = t
    return best or n


@functools.partial(jax.jit, static_argnames=("scale", "block_q", "block_k",
                                             "interpret"))
def block_attend(q_t, k, v_t, bias, carry: Carry, *, scale: float,
                 block_q: int = 1024, block_k: int = 512,
                 interpret: bool = False) -> Carry:
    h, dq, c = q_t.shape
    _, n_keys, _ = k.shape
    dv = v_t.shape[1]
    tq, tk = _tile(c, block_q), _tile(n_keys, block_k)
    m, l, acc = carry
    # query tiles outermost: the mask's block is then the same for every
    # head and is fetched once a query tile, not once a grid step
    stat = pl.BlockSpec((1, 1, tq), lambda j, i, t: (i, 0, j))
    acc_spec = pl.BlockSpec((1, dv, tq), lambda j, i, t: (i, 0, j))
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale),
        grid=(c // tq, h, n_keys // tk),
        in_specs=[
            pl.BlockSpec((1, dq, tq), lambda j, i, t: (i, 0, j)),
            pl.BlockSpec((1, tk, dq), lambda j, i, t: (i, t, 0)),
            pl.BlockSpec((1, dv, tk), lambda j, i, t: (i, 0, t)),
            pl.BlockSpec((tk, tq), lambda j, i, t: (t, j)),
            stat, stat, acc_spec],
        out_specs=[stat, stat, acc_spec],
        out_shape=[jax.ShapeDtypeStruct(m.shape, jnp.float32),
                   jax.ShapeDtypeStruct(l.shape, jnp.float32),
                   jax.ShapeDtypeStruct(acc.shape, jnp.float32)],
        input_output_aliases={4: 0, 5: 1, 6: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        name=KERNEL_NAME, interpret=interpret,
    )(q_t, k, v_t, bias, m, l, acc)


# ------------------------------------------------ the indexer's scores

def index_scores_xla(q_t, w_t, k):
    """``q_t`` [J, di, C], ``w_t`` [J, 1, C] float32, ``k`` [K, di] ->
    I[k, c] = sum_j w[j, c] relu(k[k] . q[j, :, c]), float32 [K, C]."""
    s = jnp.einsum("kd,jdc->jkc", k, q_t, preferred_element_type=jnp.float32)
    return jnp.sum(jax.nn.relu(s) * w_t, axis=0)


def _index_kernel(q_ref, k_ref, w_ref, out_ref):
    @pl.when(pl.program_id(2) == 0)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    s = jnp.dot(k_ref[...], q_ref[0], preferred_element_type=jnp.float32)
    out_ref[...] += jnp.maximum(s, 0.0) * w_ref[0]


@functools.partial(jax.jit, static_argnames=("block_q", "block_k",
                                             "interpret"))
def index_scores(q_t, w_t, k, *, block_q: int = 1024, block_k: int = 512,
                 interpret: bool = False):
    """The same, one indexer head a grid step: a head's [keys, queries]
    scores stay in VMEM, only their weighted sum over the heads leaves."""
    j, di, c = q_t.shape
    n_keys = k.shape[0]
    tq, tk = _tile(c, block_q), _tile(n_keys, block_k)
    return pl.pallas_call(
        _index_kernel,
        grid=(c // tq, n_keys // tk, j),
        in_specs=[pl.BlockSpec((1, di, tq), lambda a, b, h: (h, 0, a)),
                  pl.BlockSpec((tk, di), lambda a, b, h: (b, 0)),
                  pl.BlockSpec((1, 1, tq), lambda a, b, h: (h, 0, a))],
        out_specs=pl.BlockSpec((tk, tq), lambda a, b, h: (b, a)),
        out_shape=jax.ShapeDtypeStruct((n_keys, c), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name=INDEX_KERNEL_NAME, interpret=interpret,
    )(q_t, k, w_t)
