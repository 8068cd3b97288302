"""Keye-VL-2.0's language model (``model_family="keye_vl2"``) as ONE STAGE of
a pipeline-parallel deployment, every layer whole on the chip: grouped-query
attention through the lightning indexer over a paged K/V pool, and an expert
layer that HOLDS its experts and passes over them sorted and grouped.

What the serving stack runs beside GPT-2 and the latent-attention families
(models/deepseek_v32.py): RMSNorm, ``num_attention_heads`` query heads on
``num_key_value_heads`` key/value heads of ``head_dim`` with a per-head RMS
norm on queries and keys and rotary positions over the whole head (split
halves; text positions, so the three ``mrope`` streams are one), the lightning
indexer of ``sa_config`` (``indexer_num_heads`` heads on ONE indexer key
head; every query attends only to the ``topk`` positions it scores highest,
one selection a token for all heads), a softmax router over ``num_experts``
that takes ``num_experts_per_tok`` and renormalises, no shared expert, no
dense layer, an untied head.

Plain functions over a plain parameter tree, as ``LatentMoEModel``; the
engine's chunked-prefill seam calls two of them on a paged cache that holds,
a layer, K/V rows ``[pages, page_size, 2 * kv_heads * head_dim]`` (the keys
of the key heads, normed and rotated, then the values: whole lane tiles) and
indexer-key rows ``[pages, page_size, 128]`` (``indexer_head_dim`` numbers
and zeros up to a whole lane tile: a 64-wide row is padded to 128 lanes on
the chip anyway), both through the ONE page table a slot:

* :meth:`SparseGQAMoEModel.prefill_chunk` — one chunk of one prompt: writes
  the chunk's rows, scores the live context and finds each query's top-k
  (models/sparse_select.py, shared with the latent families), and walks the
  live blocks with selection and causality as the bias
  (ops/mla_attention.py's ``block_attend``, a key head's block repeated for
  the query heads that read it);
* :meth:`SparseGQAMoEModel.decode_step` — one token for every slot:
  ``top_k`` over each slot's live scores, a gather of the selected K/V ROWS
  out of the pool through the block table, grouped-query softmax attention
  over them in plain XLA.

The expert layer (:func:`grouped_experts`) is dropless and follows the
assignments: the ``[T, k]`` assignments are flattened and sorted by expert
into tile-aligned groups, the three products run as one Pallas kernel a row
tile against that tile's expert (ops/grouped_matmul.py:
``grouped_expert_matmul``, which takes the tokens' ``[T, D]`` block and the
table of the token each padded row holds, and makes a tile's rows in VMEM:
no padded copy of the rows is written on the way in; ``lax.ragged_dot`` over
the same layout, behind a gather of the rows, is its XLA arm and what a
whole sequence takes), and each token takes its ``k`` weighted rows back
through the rows' destinations. No one-hot operand over experts, no ``cond``
an expert; an expert without a row is not read. Every expert is held: the
layer's matrices are ``[num_experts, ...]`` and there is no cut to state.

:meth:`SparseGQAMoEModel.apply` is the prefill chunk over a private one-slot
cache, so there is one set of layer equations. Arithmetic as the latent
families': the residual stream, every norm, the router, the index scores,
the selection and the softmax are float32; matmul operands are ``dtype``
(bfloat16 as served) with float32 accumulation; cache rows are ``dtype``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import grouped_matmul
from . import sparse_select
from .deepseek_v32 import (COUNTERS, TRASH_PAGE, _angles, init_params,
                           last_valid_logits, layer_norm, rms_norm,
                           rope_halves, route)
from .sparse_select import NEG

__all__ = ["KeyeVL2Config", "SparseGQAMoEModel", "GROUPED_COUNTERS",
           "grouped_experts"]

# behind deepseek_v32.COUNTERS: rows the grouped products multiplied (whole
# row tiles, the padding of each expert's last tile included; summed over
# layers, the three matrices' common row count once)
GROUPED_COUNTERS = ("expert_rows_computed",)
KV_BLOCK = 512       # rows of context the chunked prefill reads a step
LANES = 128

@dataclasses.dataclass(frozen=True)
class KeyeVL2Config:
    """The source's ``config.json`` keys of the language model (same names;
    ``sa_config`` and ``rope_scaling`` flattened), the cut (``n_layers``),
    and the one thing a deployment states of its host (``dispatch_lag``)."""

    vocab_size: int = 151936
    hidden_size: int = 2048
    n_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    num_experts: int = 128
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 768
    norm_topk_prob: bool = True
    indexer_num_heads: int = 16
    indexer_head_dim: int = 64
    indexer_num_kv_heads: int = 1
    topk: int = 2048
    q_chunk_size: int = 512      # the source's indexer kernel's tiles:
    kv_chunk_size: int = 512     # they change no value
    mrope_section: Tuple[int, ...] = (16, 24, 24)
    rope_theta: float = 10000000.0
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 262144
    initializer_range: float = 0.02
    embedding_initializer_range: float = 1.0
    # the deployment's, not the source's: decode dispatches the server keeps
    # in flight before it fetches tokens (DecodeServer's default is one). A
    # host that stalls for longer than a decode step idles the chip unless
    # that many steps are queued; each costs a first token one tick
    dispatch_lag: int = 1

    # what `route` reads of a router without groups, bias or scale
    n_group = 1
    routed_scaling_factor = 1.0

    @classmethod
    def from_arch(cls, arch: Dict[str, Any], **over: Any) -> "KeyeVL2Config":
        """From a dict of the source's keys (a benchmark configuration file,
        ``training_args.json``'s ``arch``); keys this class does not know
        are ignored."""
        flat = dict(arch)
        flat.update(flat.pop("sa_config", None) or {})
        sections = (flat.pop("rope_scaling", None) or {}).get(
            "mrope_section", flat.get("mrope_section"))
        if sections is not None:
            flat["mrope_section"] = tuple(sections)
        flat.update({k: v for k, v in over.items() if v})
        names = {f.name for f in dataclasses.fields(cls)}
        cfg = cls(**{k: v for k, v in flat.items() if k in names})
        if cfg.num_attention_heads % cfg.num_key_value_heads:
            raise ValueError("query heads must divide by key/value heads")
        if cfg.indexer_num_kv_heads != 1:
            raise ValueError("the lightning indexer has ONE key head")
        if not cfg.norm_topk_prob:
            raise ValueError("norm_topk_prob false is not this model's")
        if 2 * sum(cfg.mrope_section) != cfg.head_dim:
            raise ValueError(
                f"mrope_section {cfg.mrope_section} does not cover half of "
                f"head_dim {cfg.head_dim}")
        return cfg

    def _inv_freq(self, dim: int) -> Tuple[float, ...]:
        """Plain RoPE (``rope_type`` default) over ``dim`` numbers, as
        float32 holds it. Text positions: the three ``mrope`` streams are
        equal, so ``mrope_section`` picks among equal angle tables."""
        freqs = float(self.rope_theta) ** (
            np.arange(0, dim, 2, dtype=np.float64) / dim)
        return tuple(map(float, (1.0 / freqs).astype(np.float32)))

    @property
    def inv_freq(self) -> Tuple[float, ...]:
        return self._inv_freq(self.head_dim)

    @property
    def indexer_inv_freq(self) -> Tuple[float, ...]:
        return self._inv_freq(self.indexer_head_dim)

    @property
    def kv_row(self) -> int:
        """One cached K/V row: the key heads, then the value heads."""
        return 2 * self.num_key_value_heads * self.head_dim

    @property
    def index_row(self) -> int:
        """The indexer key as the pool stores it: whole lane tiles."""
        return -(-self.indexer_head_dim // LANES) * LANES


# ------------------------------------------------------- the expert layer

def grouped_experts(h: jnp.ndarray, ids: jnp.ndarray, w: jnp.ndarray,
                    live: jnp.ndarray, wg: jnp.ndarray, wu: jnp.ndarray,
                    wd: jnp.ndarray, *, dtype: Any,
                    kernel_impl: str = "auto"
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """A routed layer over all of its experts, sorted and grouped.

    ``h`` [T, D] the normalised rows, ``ids`` / ``w`` [T, k] each token's
    experts and weights, ``live`` [T] the tokens that count; ``wg`` / ``wu``
    [E, D, F] and ``wd`` [E, F, D] every expert's matrices. An assignment of
    a token that does not count leaves the sort (no row holds it and
    nothing is multiplied for it). The assignments are sorted by expert into
    tile-aligned groups (ops/grouped_matmul.py), the three products run tile
    by tile against each tile's expert on ``h`` and the table of the token
    each padded row holds (the kernel where ``h`` is a chunk's or a decode
    step's and stays in VMEM; the XLA arm, which gathers the rows, for a
    whole sequence), and every token takes its ``k`` rows back, weighted.
    Returns (sum over a token's experts of ``w_e * expert_e(h)`` [T, D]
    float32, [assignments computed, experts that saw a row, rows the
    products multiplied (whole tiles)] int32)."""
    t, k = ids.shape
    e, d, f = wg.shape
    ok = jnp.broadcast_to(live[:, None], ids.shape)
    tile = grouped_matmul.row_tile(t * k)
    with jax.named_scope("experts.sort"):
        lay = grouped_matmul.aligned_layout(
            jnp.where(ok, ids, e).reshape(-1), e, tile)
        token = lay["source"] // k       # the token each padded row holds
    with jax.named_scope("experts.grouped"):
        impl = sparse_select.on_chip(kernel_impl, d, f)
        if kernel_impl == "auto" and not grouped_matmul.rows_stay_resident(
                t, d, dtype):
            impl = "xla"        # a whole sequence (`apply`): gather its rows
        args = (h.astype(dtype), token, wg.astype(dtype), wu.astype(dtype),
                wd.astype(dtype), lay["tile_expert"], lay["tiles_used"])
        if impl == "xla":
            out = grouped_matmul.grouped_swiglu_xla(*args, tile=tile)
        else:
            out = grouped_matmul.grouped_swiglu(
                *args, tile=tile, interpret=impl == "interpret")
    # (`where`, not a zero weight: the row of an assignment that left the
    # sort is whatever lies at row 0)
    y = jnp.sum(jnp.where(
        ok[:, :, None],
        out[lay["dest"]].reshape(t, k, d) * w[:, :, None], 0.0), 1)
    sizes = lay["sizes"]
    stats = jnp.stack([jnp.sum(sizes), jnp.sum(sizes > 0),
                       tile * lay["tiles_used"][0]]).astype(jnp.int32)
    return y, stats


# -------------------------------------------------------------- the model

@dataclasses.dataclass(frozen=True)
class SparseGQAMoEModel:
    """The functions of one :class:`KeyeVL2Config`. ``dtype`` is the type
    of the weights, the cache rows and the matmul operands."""

    cfg: KeyeVL2Config
    seq_len: int
    dtype: Any = jnp.bfloat16
    # the tests' hook: "xla" or "interpret" forces that arm of the prefill's
    # two Pallas-backed pieces; "auto" is what every caller runs
    kernel_impl: str = "auto"

    chunked_prefill = True   # what DecodeEngine asks a model
    counters = COUNTERS + GROUPED_COUNTERS   # behind a program's tokens

    @property
    def dispatch_lag(self) -> int:
        """What DecodeServer asks where its caller names no lag."""
        return self.cfg.dispatch_lag

    @property
    def vocab_size(self) -> int:
        return self.cfg.vocab_size

    # ---------------------------------------------------------- parameters

    def param_shapes(self) -> Dict[str, Any]:
        c = self.cfg
        d, dh = c.hidden_size, c.head_dim
        h, g = c.num_attention_heads, c.num_key_value_heads
        j, di = c.indexer_num_heads, c.indexer_head_dim
        e, f = c.num_experts, c.moe_intermediate_size
        layer = {
            "attn_norm": (d,), "wq": (d, h * dh), "wk": (d, g * dh),
            "wv": (d, g * dh), "q_norm": (dh,), "k_norm": (dh,),
            "wo": (h * dh, d),
            "idx_wq": (d, j * di), "idx_wk": (d, di), "idx_k_norm_g": (di,),
            "idx_k_norm_b": (di,), "idx_w": (d, j),
            "mlp_norm": (d,), "router": (d, c.num_experts),
            "experts_gate": (e, d, f), "experts_up": (e, d, f),
            "experts_down": (e, f, d)}
        out: Dict[str, Any] = {"embed": (c.vocab_size, d),
                               "head": (c.vocab_size, d), "norm_f": (d,)}
        for i in range(c.n_layers):
            out[f"layer_{i}"] = dict(layer)
        return out

    def init(self, rng: jax.Array, *_example: Any) -> Dict[str, Any]:
        """``{"params": tree}``: normal(0, initializer_range) matrices (the
        embedding at its own range), unit norm scales, zero biases."""
        return {"params": init_params(
            self.param_shapes(), rng, self.cfg.initializer_range, self.dtype,
            embed_std=self.cfg.embedding_initializer_range)}

    # --------------------------------------------------------------- cache

    def cache_shapes(self, max_pages: int, page_size: int,
                     window_pages: int = 0) -> Dict[str, Any]:
        """Two pools a layer through the one page table: K/V rows and
        indexer keys (nothing here is a ring: ``window_pages`` is the
        seam's, unused)."""
        del window_pages
        c = self.cfg

        def rows(width: int) -> jax.ShapeDtypeStruct:
            return jax.ShapeDtypeStruct((max_pages, page_size, width),
                                        self.dtype)
        return {f"layer_{i}": {"kv": rows(c.kv_row),
                               "index_k": rows(c.index_row)}
                for i in range(c.n_layers)}

    # ----------------------------------------------------------- the maths

    def _mm(self, a: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
        return jnp.dot(a.astype(self.dtype), w.astype(self.dtype),
                       preferred_element_type=jnp.float32)

    def _logits(self, p, hidden: jnp.ndarray) -> jnp.ndarray:
        """The untied head, stored [V, D] like the embedding."""
        return jnp.einsum("td,vd->tv", hidden.astype(self.dtype), p["head"],
                          preferred_element_type=jnp.float32)

    def _angle_tables(self, positions):
        """((cos, sin) over the attention head, (cos, sin) over the
        indexer's head) at ``positions``."""
        return (_angles(self.cfg.inv_freq, positions),
                _angles(self.cfg.indexer_inv_freq, positions))

    def _queries(self, lp, h, angles):
        """Normalised layer input -> (q [T, H, dh] normed and roped, indexer
        q [T, J, di] roped, indexer head weights [T, J]), float32."""
        c = self.cfg
        (cos, sin), (cos_i, sin_i) = angles
        t = h.shape[0]
        q = rms_norm(self._mm(h, lp["wq"]).reshape(
            t, c.num_attention_heads, c.head_dim), lp["q_norm"],
            c.rms_norm_eps)
        qi = self._mm(h, lp["idx_wq"]).reshape(
            t, c.indexer_num_heads, c.indexer_head_dim)
        # the heads' weights in float32, as the router's scores are: 16
        # columns, and part of the index score's head sum
        wi = jnp.dot(h, lp["idx_w"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
        return rope_halves(q, cos, sin), rope_halves(qi, cos_i, sin_i), wi

    def _rows(self, lp, h, angles):
        """What a token leaves in the cache: the K/V row (the key heads
        normed and roped, then the value heads) and the indexer key (zeros
        up to whole lane tiles), in the cache's type."""
        c = self.cfg
        (cos, sin), (cos_i, sin_i) = angles
        t, g = h.shape[0], c.num_key_value_heads
        k = rope_halves(rms_norm(
            self._mm(h, lp["wk"]).reshape(t, g, c.head_dim), lp["k_norm"],
            c.rms_norm_eps), cos, sin)
        kv = jnp.concatenate([k.reshape(t, g * c.head_dim),
                              self._mm(h, lp["wv"])], -1)
        ki = rope_halves(layer_norm(
            self._mm(h, lp["idx_wk"]), lp["idx_k_norm_g"],
            lp["idx_k_norm_b"], 1e-6), cos_i, sin_i)
        fill = jnp.zeros((t, c.index_row - c.indexer_head_dim), jnp.float32)
        return (kv.astype(self.dtype),
                jnp.concatenate([ki, fill], -1).astype(self.dtype))

    def _experts(self, lp, h: jnp.ndarray, live: jnp.ndarray):
        """The expert layer on normalised ``h`` [T, D]. Returns (output
        float32, [assignments, experts touched, rows computed] int32,
        routed expert ids [T, k])."""
        c = self.cfg
        probs = jax.nn.softmax(jnp.dot(
            h, lp["router"].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST), -1)
        ids, w = route(c, probs, jnp.zeros((c.num_experts,), jnp.float32))
        y, stats = grouped_experts(
            h, ids, w, live, lp["experts_gate"], lp["experts_up"],
            lp["experts_down"], dtype=self.dtype,
            kernel_impl=self.kernel_impl)
        return y, stats, ids

    def _layers(self, p, cache, x, attend, live, collect: bool):
        """The residual stream through every layer: attention by the
        caller's function (``(lp, lc, h)`` -> heads' outputs [T, H, dh], the
        layer's cache, what was selected, rows counted by counter name),
        the output projection, the expert layer. Returns (x, cache,
        counters int32 in ``self.counters``' order, aux)."""
        c = self.cfg
        zero = jnp.zeros((), jnp.int32)
        counters = jnp.zeros((len(self.counters),), jnp.int32)
        aux: Dict[str, Any] = {"selected": [], "experts": []}
        for i in range(c.n_layers):
            lp = p[f"layer_{i}"]
            h = rms_norm(x, lp["attn_norm"], c.rms_norm_eps)
            o, lc, selected, counted = attend(lp, cache[f"layer_{i}"], h)
            cache = {**cache, f"layer_{i}": lc}
            x = x + self._mm(o.reshape(o.shape[0], -1), lp["wo"])
            h = rms_norm(x, lp["mlp_norm"], c.rms_norm_eps)
            y, stats, expert_ids = self._experts(lp, h, live)
            x = x + y
            counted.update(expert_assignments_held=stats[0],
                           experts_touched=stats[1],
                           expert_rows_computed=stats[2])
            counters = counters + jnp.stack(
                [counted.get(name, zero) for name in self.counters])
            if collect:
                aux["selected"].append(selected)
                aux["experts"].append(expert_ids)
        return x, cache, counters, aux

    # ------------------------------------------------ prefill (and forward)

    def _chunk_hidden(self, p, cache, ids, start, n_valid, table_row,
                      collect: bool = False):
        """One chunk of one sequence through every layer.

        ``ids`` [C] the chunk's tokens (zero-padded past ``n_valid``), at
        positions ``start ..``; ``table_row`` [n_pages] the sequence's
        pages. Writes the chunk's rows first, then reads the live context
        (the chunk's own rows among it) back from the pools, so prefill
        sees exactly the rows decode will. Returns (final-normed hidden
        [C, D] float32, cache, counters int32, aux)."""
        c = self.cfg
        n = ids.shape[0]
        ps = jax.tree_util.tree_leaves(cache)[0].shape[1]
        kb = max(ps, KV_BLOCK // ps * ps)       # rows a context block
        pb = kb // ps
        n_blocks_max = -(-(table_row.shape[0] * ps) // kb)
        pad_pages = n_blocks_max * pb - table_row.shape[0]
        table = jnp.concatenate(
            [table_row, jnp.full((pad_pages,), TRASH_PAGE, jnp.int32)])
        l_max = n_blocks_max * kb
        pos = start + jnp.arange(n, dtype=jnp.int32)
        valid = jnp.arange(n) < n_valid
        live_len = start + n_valid
        n_blocks = (live_len + kb - 1) // kb
        angles = self._angle_tables(pos)
        # where the chunk's rows go (padded tail -> the trash page)
        page = jnp.where(valid, table[jnp.minimum(pos // ps,
                                                  table.shape[0] - 1)],
                         TRASH_PAGE)
        off = pos % ps
        k_sel = min(c.topk, l_max)
        g, dh = c.num_key_value_heads, c.head_dim
        rep = c.num_attention_heads // g
        x = p["embed"][ids].astype(jnp.float32)
        n_live = jnp.sum(jnp.where(valid, pos + 1, 0), dtype=jnp.int32)

        def block_pages(b):
            return jax.lax.dynamic_slice(table, (b * pb,), (pb,))

        def attend(lp, lc, h):
            q, qi, wi = self._queries(lp, h, angles)
            kv_rows, idx_rows = self._rows(lp, h, angles)
            kv = lc["kv"].at[page, off].set(kv_rows)
            idx = lc["index_k"].at[page, off].set(idx_rows)
            with jax.named_scope("attend.select"):
                scores = sparse_select.score_context(
                    self.kernel_impl,
                    qi.astype(self.dtype).transpose(1, 2, 0),
                    wi.T[:, None, :],
                    lambda b: idx[block_pages(b)].reshape(kb, -1)[
                        :, :c.indexer_head_dim], pos, kb, n_blocks, l_max)
                selected = sparse_select.select_top_k(
                    scores, live_len, k_sel, kb, n_blocks)
            # the walk over the live blocks, an online softmax carried
            # through it; transposed layout (keys on the rows, queries on
            # the lanes), a key head's block repeated for its query heads
            q_t = q.astype(self.dtype).transpose(1, 2, 0)        # [H, dh, n]
            key_head = jnp.arange(c.num_attention_heads) // rep

            def attend_block(b, carry):
                m, l, acc, n_att = carry
                rows = kv[block_pages(b)].reshape(kb, 2, g, dh)
                keys = rows[:, 0].transpose(1, 0, 2)[key_head]
                v_t = rows[:, 1].transpose(1, 2, 0)[key_head]
                sel = jax.lax.dynamic_slice(selected, (b * kb, 0), (kb, n))
                m, l, acc = sparse_select.block_attend(
                    self.kernel_impl, q_t, keys, v_t,
                    jnp.where(sel, 0.0, NEG), (m, l, acc), dh ** -0.5)
                return m, l, acc, n_att + jnp.sum(sel & valid[None, :],
                                                  dtype=jnp.int32)
            heads = c.num_attention_heads
            _, l, acc, n_att = jax.lax.fori_loop(
                0, n_blocks, attend_block,
                (jnp.full((heads, 1, n), NEG, jnp.float32),
                 jnp.zeros((heads, 1, n), jnp.float32),
                 jnp.zeros((heads, dh, n), jnp.float32),
                 jnp.zeros((), jnp.int32)))
            return ((acc / l).transpose(2, 0, 1),
                    {"kv": kv, "index_k": idx}, selected.T,
                    {"index_rows_scored": n_live, "kv_rows_attended": n_att,
                     "kv_rows_live": n_live})

        x, cache, counters, aux = self._layers(p, cache, x, attend, valid,
                                               collect)
        return (rms_norm(x, p["norm_f"], c.rms_norm_eps), cache, counters,
                aux)

    def prefill_chunk(self, p, cache, ids, start, n_valid, table_row):
        """-> (cache, logits [V] float32 of the chunk's last valid token,
        counters int32)."""
        hidden, cache, counters, _ = self._chunk_hidden(
            p, cache, ids, start, n_valid, table_row)
        return cache, last_valid_logits(
            hidden, n_valid, lambda tile: self._logits(p, tile)), counters

    def apply(self, variables, ids, pad_mask=None, *, collect: bool = False):
        """Cache-free forward: ``ids`` [B, T] -> logits [B, T, V] float32
        (the prefill chunk over a private one-slot cache, a sequence at a
        time). ``pad_mask`` is accepted for the factory's calling
        convention and must be all ones. With ``collect`` also the
        per-layer selection masks and routed expert ids."""
        del pad_mask
        p = variables["params"]
        t = ids.shape[1]
        ps = min(16, t)
        n_pages = -(-t // ps)
        cache0 = jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype),
            self.cache_shapes(n_pages + 1, ps))
        table = jnp.arange(1, n_pages + 1, dtype=jnp.int32)

        def one(row):
            hidden, _, _, aux = self._chunk_hidden(
                p, cache0, row, jnp.int32(0), jnp.int32(t), table,
                collect=collect)
            return self._logits(p, hidden), aux
        # (`lax.map`, not `vmap`: a grouped product batches over nothing
        # but its rows)
        logits, aux = jax.lax.map(one, ids)
        return (logits, aux) if collect else logits

    # -------------------------------------------------------------- decode

    def decode_step(self, p, cache, tokens, positions, block_table, active,
                    collect: bool = False):
        """One token for every slot. ``tokens`` / ``positions`` [S]: the
        token in each slot's state and the index it is written at;
        ``block_table`` [S, n_pages]; ``active`` [S]. Returns (cache,
        logits [S, V] float32, counters int32, aux)."""
        c = self.cfg
        s_n = tokens.shape[0]
        ps = jax.tree_util.tree_leaves(cache)[0].shape[1]
        l_max = block_table.shape[1] * ps
        k_sel = min(c.topk, l_max)
        angles = self._angle_tables(positions)
        page = jnp.take_along_axis(
            block_table, jnp.minimum(positions // ps,
                                     block_table.shape[1] - 1)[:, None],
            axis=1)[:, 0]
        off = positions % ps
        live = active > 0
        causal = jnp.arange(l_max, dtype=jnp.int32)[None, :] \
            <= positions[:, None]                                # [S, L]
        g, dh = c.num_key_value_heads, c.head_dim
        rep = c.num_attention_heads // g
        x = p["embed"][tokens].astype(jnp.float32)
        n_live = jnp.sum(jnp.where(live, positions + 1, 0), dtype=jnp.int32)

        def attend(lp, lc, h):
            q, qi, wi = self._queries(lp, h, angles)
            kv_rows, idx_rows = self._rows(lp, h, angles)
            kv = lc["kv"].at[page, off].set(kv_rows)
            idx = lc["index_k"].at[page, off].set(idx_rows)
            with jax.named_scope("attend.select"):
                # score every live row of every slot, take the k best
                ki = idx[block_table].reshape(s_n, l_max, -1)
                qi = jnp.pad(qi, ((0, 0), (0, 0),
                                  (0, c.index_row - c.indexer_head_dim)))
                sc = jnp.einsum("sjd,sld->sjl", qi.astype(self.dtype), ki,
                                preferred_element_type=jnp.float32)
                sc = jnp.sum(jax.nn.relu(sc) * wi[:, :, None], axis=1)
                sc = jnp.where(causal, sc, NEG)
                top, sel = jax.lax.top_k(sc, k_sel)              # [S, K]
                ok = top > NEG
            with jax.named_scope("attend.gather"):
                phys = jnp.take_along_axis(
                    block_table, sel // ps, axis=1) * ps + sel % ps
                rows = kv.reshape(-1, kv.shape[-1])[phys].reshape(
                    s_n, k_sel, 2, g, dh)
            # grouped-query attention over the gathered rows: `rep` query
            # heads on each key head
            s = jnp.einsum("sgrd,skgd->sgrk",
                           q.astype(self.dtype).reshape(s_n, g, rep, dh),
                           rows[:, :, 0], preferred_element_type=jnp.float32
                           ) * dh ** -0.5
            pr = jax.nn.softmax(jnp.where(ok[:, None, None, :], s, NEG), -1)
            o = jnp.einsum("sgrk,skgd->sgrd", pr.astype(self.dtype),
                           rows[:, :, 1], preferred_element_type=jnp.float32)
            n_att = jnp.sum(ok & live[:, None], dtype=jnp.int32)
            return (o.reshape(s_n, g * rep, dh), {"kv": kv, "index_k": idx},
                    jnp.where(ok, sel, -1),
                    {"index_rows_scored": n_live, "kv_rows_attended": n_att,
                     "kv_rows_live": n_live})

        x, cache, counters, aux = self._layers(p, cache, x, attend, live,
                                               collect)
        logits = self._logits(p, rms_norm(x, p["norm_f"], c.rms_norm_eps))
        return cache, logits, counters, aux
