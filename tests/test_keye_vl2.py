"""Keye-VL-2.0's language model as one pipeline stage (models/keye_vl2.py)
against its plain reference (models/reference_keye_vl2.py) at a tiny size on
the CPU, on seeded weights: the cache-free forward, chunked prefill then
decode through the paged K/V and indexer pools (prompts shorter than ``topk``,
longer than it, a chunk boundary inside the selection), the family through
``DecodeServer`` with short and long requests in one queue, the discrete
choices compared as sets, the grouped expert layer against the reference's
expert-at-a-time sum (even, skewed, empty-expert routing), the share test,
the grouped pass's layout (by hand and against a plain loop), its kernel on
the tokens' block and the row table, its counters by hand, the guard that no
padded copy of the rows is made in front of the kernel, softmax routing with
one group by hand,
the two copies of the reference and the configuration file."""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_pipeline_tpu.models import create_model_from_config
from distributed_pipeline_tpu.models import deepseek_v32 as latent
from distributed_pipeline_tpu.models import keye_vl2 as prog
from distributed_pipeline_tpu.models import reference_keye_vl2 as ref
from distributed_pipeline_tpu.models.keye_vl2 import KeyeVL2Config
from distributed_pipeline_tpu.ops import grouped_matmul
from distributed_pipeline_tpu.serving import DecodeServer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB, POSITIONS, TOPK = 211, 96, 12
CONFIG_FILE = os.path.join(ROOT, "benchmark", "configs",
                           "keye-vl-2.0-30b-a3b-pp8.json")

# the source's keys at a tiny size: three layers, 8 query heads on 2 key
# heads of 16, the indexer at 4 heads of 16 on one key head with topk far
# below the lengths used, 16 experts of which 4 a token, all held
TINY = {
    "hidden_size": 64, "n_layers": 3, "num_attention_heads": 8,
    "num_key_value_heads": 2, "head_dim": 16,
    "num_experts": 16, "num_experts_per_tok": 4, "moe_intermediate_size": 32,
    "norm_topk_prob": True,
    "sa_config": {"indexer_head_dim": 16, "indexer_num_heads": 4,
                  "indexer_num_kv_heads": 1, "topk": TOPK,
                  "q_chunk_size": 512, "kv_chunk_size": 512},
    "rope_scaling": {"mrope_section": [2, 3, 3], "rope_type": "default",
                     "type": "default"},
    "rope_theta": 10000000, "rms_norm_eps": 1e-6,
    "vocab_size": VOCAB, "max_position_embeddings": POSITIONS,
    "initializer_range": 0.1, "embedding_initializer_range": 1.0,
    "param_dtype": "float32", "dtype": "float32"}
LAYERS = TINY["n_layers"]
PER_TOKEN = TINY["num_experts_per_tok"]


def arch_of(cfg):
    return {k: v for k, v in cfg.items()
            if k not in ("vocab_size", "dtype", "param_dtype")}


def build(cfg, seed=7):
    """(workload, reference weights, program tree): the SAME arrays on both
    sides, as the benchmark's driver hands them over."""
    wl = create_model_from_config(
        model_family="keye_vl2", vocab_size=cfg["vocab_size"],
        seq_len=cfg["max_position_embeddings"], dtype=cfg["dtype"],
        arch=arch_of(cfg))
    w = jax.jit(lambda s: ref.make_weights(cfg, s))(ref.seed_arg(seed))
    return wl, w, {"params": w}


@pytest.fixture(scope="module")
def tiny():
    return build(TINY)


@pytest.fixture
def small_blocks(monkeypatch):
    """Context blocks of 8 rows, so that the prefill's block walk and the
    radix select cross blocks."""
    monkeypatch.setattr(prog, "KV_BLOCK", 8)


def ids_of(n, seed=0, vocab=VOCAB):
    return np.random.default_rng(seed).integers(4, vocab, (n,)).astype(
        np.int32)


# ------------------------------------------------- (a) the whole forward

def test_forward_equals_reference_float32(tiny, small_blocks):
    """float32 on both sides: what is left is the order of summation
    (blocks, the grouped products against an expert at a time): 1e-4 on
    logits of order 3 is a hundred times the 1e-6 read, and far below what
    the nearest lower precision (bfloat16, below) reads."""
    wl, w, tree = tiny
    m = wl.model
    assert m.chunked_prefill and not hasattr(m, "window_rows")
    assert tree["params"]["layer_0"]["wk"].shape == (64, 2 * 16)
    assert tree["params"]["layer_0"]["experts_down"].shape == (16, 32, 64)
    ids = ids_of(80)
    got = jax.jit(m.apply)(tree, ids[None])[0]
    want = ref.logits(w, TINY, ids)
    assert got.shape == want.shape == (80, VOCAB)
    assert float(jnp.max(jnp.abs(want))) > 1.0
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


BF16 = dict(TINY, dtype="bfloat16", param_dtype="bfloat16")
# bfloat16 operands with float32 accumulation and a float32 residual stream,
# against the float32 reference on the same (bfloat16) weights. As for the
# latent families, the two discontinuities (router top-k, indexer top-k) flip
# on some tokens in any precision, so the lower quartile of the per-token
# largest error is compared: the program reads 0.007-0.032 on three seeds
# (the reference in bfloat16 0.016), the reference with fp8 operands (the
# nearest lower precision) 0.25-0.36. 0.09 keeps a factor of 2.8 to both.
BF16_QUARTILE = 0.09


def test_forward_bfloat16_within_its_tolerance_and_fp8_outside():
    wl, w, tree = build(BF16)
    ids = ids_of(80, seed=1)
    want = np.asarray(ref.logits(w, BF16, ids))
    got = np.asarray(jax.jit(wl.model.apply)(tree, ids[None])[0])
    low = np.asarray(ref.logits(w, BF16, ids, precision="fp8"))

    def quartile(x):
        return np.percentile(np.abs(x - want).max(1), 25)
    assert quartile(got) < BF16_QUARTILE < quartile(low)


# ---------- (b) chunked prefill, then decode: the K/V and indexer pools

@pytest.mark.parametrize("prompt", [5, 29, 41],
                         ids=["shorter_than_topk", "longer_than_topk",
                              "chunk_boundary_in_selection"])
def test_chunked_prefill_then_decode_equals_reference(tiny, small_blocks,
                                                      prompt):
    """Chunks of 12, pages of 4: a prompt of 5 never reaches topk (12), one
    of 29 crosses it in its second chunk, and one of 41 ends four rows into
    a chunk whose queries select rows of three earlier chunks; every logit
    against the reference's ONE full forward, and the counters by hand."""
    wl, w, tree = tiny
    m, p = wl.model, tree["params"]
    ids = ids_of(60, seed=2)
    want = np.asarray(ref.logits(w, TINY, ids))
    ps, n_pages, chunk = 4, 16, 12
    shapes = m.cache_shapes(1 + n_pages, ps)
    assert shapes["layer_1"]["kv"].shape == (17, 4, 2 * 2 * 16)
    assert shapes["layer_1"]["index_k"].shape == (17, 4, 128)
    assert set(shapes["layer_1"]) == {"kv", "index_k"}
    cache = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), shapes)
    table = jnp.arange(1, n_pages + 1, dtype=jnp.int32)
    step = jax.jit(m.prefill_chunk)
    for start in range(0, prompt, chunk):
        n = min(chunk, prompt - start)
        buf = np.zeros((chunk,), np.int32)
        buf[:n] = ids[start:start + n]
        cache, logits, counted = step(
            p, cache, jnp.asarray(buf), jnp.int32(start), jnp.int32(n),
            table)
        np.testing.assert_allclose(np.asarray(logits), want[start + n - 1],
                                   atol=1e-4)
        counted = dict(zip(m.counters, np.asarray(counted)))
        live = sum(range(start + 1, start + n + 1))
        assert counted["kv_rows_live"] == counted["index_rows_scored"] \
            == live * LAYERS
        assert counted["kv_rows_attended"] == LAYERS * sum(
            min(t + 1, TOPK) for t in range(start, start + n))
        assert counted["expert_assignments_held"] == n * PER_TOKEN * LAYERS
    # slot 1 stays inactive (an all-trash table): it must disturb nothing
    tables = jnp.stack([table, jnp.zeros_like(table)])
    decode = jax.jit(m.decode_step)
    for t in range(prompt, 60):
        cache, logits, counted, _ = decode(
            p, cache, jnp.asarray([ids[t], 0]), jnp.asarray([t, 0]), tables,
            jnp.asarray([1, 0]))
        np.testing.assert_allclose(np.asarray(logits[0]), want[t], atol=1e-4)
        counted = dict(zip(m.counters, np.asarray(counted)))
        assert counted["kv_rows_live"] == (t + 1) * LAYERS
        assert counted["kv_rows_attended"] == min(t + 1, TOPK) * LAYERS
        assert counted["expert_assignments_held"] == PER_TOKEN * LAYERS
        assert 1 <= counted["experts_touched"] <= PER_TOKEN * LAYERS


def test_served_through_decode_server_equals_reference(tiny, small_blocks):
    """The normal path: DecodeServer with pages of 2 and the chunk the
    engine derives from max_prompt_len (64 / 16 = 4), two slots, short and
    long requests in ONE queue (3 to 60 prompt tokens; slots are reused
    after release, over pages other requests wrote). Every served token is
    the reference's pick at its position (float32: a gap above 1e-4 would
    be a wrong row, not rounding); pages return to the free list; the
    six counters come back with the tokens."""
    wl, w, tree = tiny
    server = DecodeServer(wl, tree, decode_slots=2, page_size=2,
                          max_prompt_len=64, max_len=POSITIONS)
    eng = server.engine
    assert eng.chunked and eng.prefill_chunk == 4
    assert eng.window_pages_per_slot == 0 and server.window_mgr is None
    assert eng.cache["layer_1"]["kv"].shape == (1 + 2 * 48, 2, 64)
    assert eng.weights["leaves_cast"] == 0
    shapes = [(29, 9), (3, 2), (60, 30), (13, 14), (37, 6), (5, 1), (24, 11)]
    reqs = [server.submit(ids_of(n, seed=10 + i), g)
            for i, (n, g) in enumerate(shapes)]
    server.drain()
    assert all(r.finished and len(r.tokens) == g
               for r, (_, g) in zip(reqs, shapes))
    for r in reqs:
        seq = np.concatenate([r.prompt, np.asarray(r.tokens, np.int32)])
        gaps = ref.served_gaps(w, TINY, seq, r.prompt_len)
        assert gaps.shape == (len(r.tokens),) and gaps.max() <= 1e-4
    assert server.mgr.free_pages == server.mgr.capacity == 96
    assert all(s is None for s in server.slots) and not server.busy
    assert server.prompt_tokens_prefilled == sum(n for n, _ in shapes)
    assert server.tokens_fetched == sum(g for _, g in shapes)
    for program in ("prefill", "decode"):
        c = server.counted[program]
        assert tuple(c) == latent.COUNTERS + prog.GROUPED_COUNTERS
        assert 0 < c["kv_rows_attended"] < c["kv_rows_live"]
        assert c["expert_rows_computed"] >= c["expert_assignments_held"] > 0
    pre, dec = server.counted["prefill"], server.counted["decode"]
    assert pre["expert_assignments_held"] == PER_TOKEN * LAYERS * sum(
        n for n, _ in shapes)
    assert eng.kv_pool_bytes() == sum(
        leaf.size * leaf.dtype.itemsize
        for leaf in jax.tree_util.tree_leaves(eng.cache))


@pytest.mark.parametrize("stated, asked, held", [
    (None, None, 1), (8, None, 8), (8, 2, 2)],
    ids=["no_lag_stated", "the_deployments_lag", "the_callers_lag_wins"])
def test_server_keeps_the_deployments_dispatches_in_flight(
        tiny, small_blocks, stated, asked, held):
    """DecodeServer, told no ``dispatch_lag``, asks the model what its
    deployment states (one where it states none; the caller's wins). With
    8 dispatches in flight the ring holds up to 8 entries behind every busy
    tick, a first token arrives that many entries after its dispatch, and
    the tokens are those of a server that fetches one dispatch behind: the
    budget is spent by count at dispatch, so the lag moves no token."""
    wl, w, tree = tiny
    if stated is not None:
        wl, _, _ = build({**TINY, "dispatch_lag": stated})
    shapes = [(29, 9), (3, 2), (40, 17), (13, 14), (5, 1)]

    def serve(**kw):
        server = DecodeServer(wl, tree, decode_slots=2, page_size=2,
                              max_prompt_len=64, max_len=POSITIONS, **kw)
        reqs = [server.submit(ids_of(n, seed=30 + i), g)
                for i, (n, g) in enumerate(shapes)]
        deepest = 0
        while server.busy and server.step():
            deepest = max(deepest, len(server._ring))
        server.drain()
        assert server.mgr.free_pages == server.mgr.capacity
        return server, deepest, [list(r.tokens) for r in reqs]

    server, deepest, tokens = serve(
        **({} if asked is None else {"dispatch_lag": asked}))
    assert server.dispatch_lag == deepest == held
    assert [len(t) for t in tokens] == [g for _, g in shapes]
    if held != 1:
        assert tokens == serve(dispatch_lag=1)[2]


# ------------------------------- (c) the discrete choices, compared as sets

def test_selected_rows_and_routed_experts_equal_the_reference(tiny,
                                                              small_blocks):
    """For every token of a seeded sequence every layer's selected
    positions and routed experts are the reference's, in the prefill form
    (a mask) and in the decode form (``lax.top_k`` indices)."""
    wl, w, tree = tiny
    m, p = wl.model, tree["params"]
    ids = ids_of(64, seed=3)
    _, chosen = ref.make_logits_fn(TINY)("float32").hidden(
        w, jnp.asarray(ids))
    _, aux = jax.jit(lambda v, i: m.apply(v, i, collect=True))(
        tree, ids[None])
    for layer in range(LAYERS):
        want = np.asarray(chosen["selected"][layer])
        got = np.asarray(aux["selected"][layer])[0][:64, :64]
        assert (got == want).all()
        assert want.sum(1).tolist() == [min(t + 1, TOPK) for t in range(64)]
        assert (np.sort(np.asarray(aux["experts"][layer])[0], -1)
                == np.sort(np.asarray(chosen["experts"][layer]), -1)).all()
    ps, n_pages = 8, 8
    cache = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        m.cache_shapes(1 + n_pages, ps))
    table = jnp.arange(1, n_pages + 1, dtype=jnp.int32)
    cache, _, _ = jax.jit(m.prefill_chunk)(
        p, cache, jnp.asarray(ids[:40]), jnp.int32(0), jnp.int32(40), table)
    decode = jax.jit(lambda *a: m.decode_step(*a, collect=True))
    for t in range(40, 64):
        cache, _, _, aux = decode(p, cache, jnp.asarray(ids[t:t + 1]),
                                  jnp.asarray([t]), table[None],
                                  jnp.asarray([1]))
        for layer in range(LAYERS):
            got = set(np.asarray(aux["selected"][layer])[0].tolist()) - {-1}
            want = set(np.nonzero(np.asarray(
                chosen["selected"][layer])[t])[0].tolist())
            assert got == want, (t, layer)
            assert set(np.asarray(aux["experts"][layer])[0].tolist()) \
                == set(np.asarray(chosen["experts"][layer])[t].tolist())


# ----------------------------------------- (d) the grouped expert layer

def expert_at_a_time(lw, x, ids_e, w_e, experts, cfg=TINY):
    """The reference's sum: ``experts`` one after the other, each over every
    row with its routed weight (0 where it was not taken), less ``x``."""
    f = ref.make_logits_fn(cfg)("float32")
    want = jnp.zeros_like(x)
    for e in experts:
        want = want + f.expert(
            x, lw["mlp_norm"], jnp.sum(jnp.where(ids_e == e, w_e, 0.0), -1),
            lw["experts_gate"], lw["experts_up"], lw["experts_down"],
            jnp.int32(e))
    return np.asarray(want)


def routing(kind, t, rng):
    """[T, 4] expert ids without a repeat in a row, and weights."""
    if kind == "even":          # every expert the same number of rows
        ids = (np.arange(t)[:, None] * 4 + np.arange(4)[None, :]) % 16
    elif kind == "skewed":      # every token to expert 5; 11 never taken
        ids = np.stack([rng.permutation(
            [e for e in range(16) if e not in (5, 11)])[:3]
            for _ in range(t)])
        ids = np.concatenate([np.full((t, 1), 5), ids], 1)
    else:                       # experts 0-3 and 12-15 see no row at all
        ids = np.stack([rng.permutation(np.arange(4, 12))[:4]
                        for _ in range(t)])
    w = rng.random((t, 4)).astype(np.float32) + 0.1
    return ids.astype(np.int32), w / w.sum(-1, keepdims=True)


@pytest.mark.parametrize("kind", ["even", "skewed", "empty_experts"])
def test_grouped_layer_equals_the_expert_at_a_time_sum(tiny, kind):
    """Sorted and grouped against an expert at a time, under routing a
    trained router gives, routing that sends every token to one expert, and
    routing that leaves half the experts without a row: nothing dropped,
    and the three counts by hand (160 assignments take row tiles of 16: an
    expert's rows are multiplied as whole tiles)."""
    _, w, _ = tiny
    lw = w["layer_1"]
    t = 40
    rng = np.random.default_rng(5)
    x = jax.random.normal(jax.random.PRNGKey(4), (t, 64))
    ids_e, w_e = routing(kind, t, rng)
    live = np.ones((t,), bool)
    live[-3:] = False           # a chunk's padded tail counts for nothing
    want = expert_at_a_time(
        lw, x, jnp.asarray(np.where(live[:, None], ids_e, -1)),
        jnp.asarray(w_e), range(16))
    h = latent.rms_norm(x, lw["mlp_norm"], TINY["rms_norm_eps"])
    y, stats = jax.jit(lambda h, i, w, live: prog.grouped_experts(
        h, i, w, live, lw["experts_gate"], lw["experts_up"],
        lw["experts_down"], dtype=jnp.float32))(
            h, jnp.asarray(ids_e), jnp.asarray(w_e), jnp.asarray(live))
    np.testing.assert_allclose(np.asarray(y), want, atol=2e-5)
    assert grouped_matmul.row_tile(t * 4) == 16
    sizes = np.bincount(ids_e[live].reshape(-1), minlength=16)
    assert np.asarray(stats).tolist() == [
        37 * 4, int((sizes > 0).sum()), 16 * int((-(-sizes // 16)).sum())]
    assert stats[2] >= stats[0]
    if kind == "skewed":
        assert sizes[5] == 37 and sizes[11] == 0
    if kind == "empty_experts":
        assert int((sizes > 0).sum()) == 8


def test_aligned_layout_by_hand():
    """Seven assignments over three held experts (3 = left the sort), tiles
    of 4 rows: expert 0 has 5 rows (two tiles), expert 1 none, expert 2 one;
    rows are laid out expert by expert, each group padded to whole tiles;
    the tiles behind the last used one repeat its expert."""
    key = jnp.asarray([2, 0, 3, 0, 0, 0, 0], jnp.int32)
    lay = jax.tree_util.tree_map(
        np.asarray, grouped_matmul.aligned_layout(key, 3, 4))
    assert grouped_matmul.padded_rows(7, 3, 4) == 16
    assert lay["sizes"].tolist() == [5, 0, 1]
    assert lay["tiles_used"].tolist() == [3]
    assert lay["tile_expert"].tolist() == [0, 0, 2, 2]
    # expert 0's rows hold assignments 1, 3, 4, 5, 6; expert 2's tile holds 0
    assert lay["source"][:5].tolist() == [1, 3, 4, 5, 6]
    assert lay["source"][8] == 0
    assert lay["dest"].tolist() == [8, 0, 0, 1, 2, 3, 4]


def layout_by_loop(key, experts, tile):
    """The layout, an expert and a row at a time."""
    rows = grouped_matmul.padded_rows(len(key), experts, tile)
    source, dest = np.zeros(rows, int), np.zeros(len(key), int)
    tile_expert, sizes, at = [], [], 0
    for e in range(experts):
        mine = np.nonzero(key == e)[0]          # in the assignments' order
        sizes.append(len(mine))
        source[at:at + len(mine)] = mine
        dest[mine] = at + np.arange(len(mine))
        n_tiles = -(-len(mine) // tile)
        tile_expert += [e] * n_tiles
        at += n_tiles * tile
    used = len(tile_expert)
    last = tile_expert[-1] if used else experts - 1
    return {"sizes": sizes, "tiles_used": [used], "source": source,
            "dest": dest,
            "tile_expert": tile_expert + [last] * (rows // tile - used)}


@pytest.mark.parametrize("m, experts, tile", [(160, 16, 16), (128, 128, 16),
                                              (1024, 8, 128)],
                         ids=["m160_e16_t16", "m128_e128_t16",
                              "m1024_e8_t128"])
@pytest.mark.parametrize("kind", ["mixed", "all_left", "one_expert",
                                  "first_and_last"])
def test_aligned_layout_equals_a_plain_loop(m, experts, tile, kind):
    """``aligned_layout`` (sorts, compares and one scatter) against a loop
    over experts and rows: every output, under routing with assignments
    that left the sort, with none that stayed, with one expert taking all
    and with only the first and the last expert taken; a row that holds
    nothing names assignment 0."""
    rng = np.random.default_rng(m + len(kind))
    key = {"mixed": rng.integers(0, experts + 1, m),
           "all_left": np.full(m, experts),
           "one_expert": np.full(m, experts // 2),
           "first_and_last": rng.choice([0, experts - 1, experts], m)}[kind]
    got = jax.tree_util.tree_map(np.asarray, jax.jit(
        lambda k: grouped_matmul.aligned_layout(k, experts, tile))(
            jnp.asarray(key, jnp.int32)))
    want = layout_by_loop(key, experts, tile)
    assert sorted(got) == sorted(want)
    for name, value in want.items():
        assert got[name].dtype == np.int32, name
        assert got[name].tolist() == list(value), name


KERNEL_EXPERTS = 4


def kernel_routing(kind, t, rng):
    """[T, 2] held experts of ``KERNEL_EXPERTS`` (that number itself: an
    assignment that left the sort, as one of a token that does not count)."""
    e = KERNEL_EXPERTS
    if kind == "skewed":        # PR 35's case: 0 holds most, 1 none, some left
        return rng.choice([0, 0, 0, 0, 2, 3, e], (t, 2))
    if kind == "no_row":        # expert 1 sees no row at all
        return np.stack([rng.permutation([0, 2, 3])[:2] for _ in range(t)])
    if kind == "past_one_tile":  # expert 0 holds a row of EVERY token
        return np.stack([np.zeros(t, int), rng.integers(1, e, t)], 1)
    if kind == "dead_tokens":   # `live` false: a third of them, the tail too
        ids = np.stack([rng.permutation(e)[:2] for _ in range(t)])
        dead = rng.random(t) < 0.3
        dead[-5:] = True
        return np.where(dead[:, None], e, ids)
    assert kind == "one_expert"  # every token on expert 2 and nowhere else
    return np.stack([np.full(t, 2), np.full(t, e)], 1)


@pytest.mark.parametrize("kind", ["skewed", "no_row", "past_one_tile",
                                  "dead_tokens", "one_expert"])
@pytest.mark.parametrize("tile, t", [(16, 40), (128, 200)],
                         ids=["tile16", "tile128"])
def test_grouped_kernel_with_its_row_table(tile, t, kind):
    """ops/grouped_matmul.py's kernel, interpreted, on the tokens' own
    ``[T, D]`` block and the row table (bfloat16, widths scaled down), both
    tiles the program takes: against ``ragged_dot`` behind a gather over the
    same layout, and against a plain loop, a token and a dense expert at a
    time; the rows the kernel's one-hot product makes are ``h[source // k]``
    to the bit; tiles behind the last used one are skipped."""
    e, d, f, k = KERNEL_EXPERTS, 128, 128, 2
    ids = kernel_routing(kind, t, np.random.default_rng(tile + len(kind)))
    lay = grouped_matmul.aligned_layout(
        jnp.asarray(ids.reshape(-1), jnp.int32), e, tile)
    token = lay["source"] // k
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    h = jax.random.normal(ks[0], (t, d), jnp.bfloat16)
    wg, wu = (0.1 * jax.random.normal(key, (e, d, f), jnp.bfloat16)
              for key in ks[1:3])
    wd = 0.1 * jax.random.normal(ks[3], (e, f, d), jnp.bfloat16)

    def bits(a):
        return np.asarray(jax.lax.bitcast_convert_type(a, jnp.uint16))
    assert (bits(grouped_matmul._rows_of(h, token[:, None]))
            == bits(h[token])).all()

    args = (h, token, wg, wu, wd, lay["tile_expert"], lay["tiles_used"])
    want = grouped_matmul.grouped_swiglu_xla(*args, tile=tile)
    got = np.asarray(grouped_matmul.grouped_swiglu(
        *args, tile=tile, interpret=True))
    used = int(lay["tiles_used"][0]) * tile
    assert 0 < used < token.shape[0]
    np.testing.assert_allclose(got[:used], np.asarray(want)[:used],
                               atol=2e-3)
    sizes = np.bincount(ids.reshape(-1), minlength=e + 1)[:e]
    assert used == tile * int((-(-sizes // tile)).sum())
    if kind in ("skewed", "past_one_tile"):
        assert sizes[0] > tile                  # an expert past one tile
    if kind in ("skewed", "no_row"):
        assert sizes[1] == 0

    f32 = jnp.float32
    dest = np.asarray(lay["dest"]).reshape(t, k)
    for row in range(t):
        x = h[row].astype(f32)
        for j in range(k):
            if ids[row, j] == e:
                continue
            g, u, dn = (m[ids[row, j]].astype(f32) for m in (wg, wu, wd))
            a = (jax.nn.silu(x @ g) * (x @ u)).astype(jnp.bfloat16)
            np.testing.assert_allclose(
                got[dest[row, j]], np.asarray(a.astype(f32) @ dn),
                atol=3e-3)
    assert np.abs(got[:used]).max() > 0.05


def chunk_jaxpr(t, e=128, d=2048, f=768, k=8):
    """``grouped_experts`` traced (nothing runs) as the chip would take it
    at Keye-VL-2.0's widths: -> (every value made outside a kernel's body
    as (shape, dtype), the kernels' names)."""
    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype)
    jaxpr = jax.make_jaxpr(lambda *a: prog.grouped_experts(
        *a, dtype=jnp.bfloat16))(
            sds((t, d), jnp.float32), sds((t, k), jnp.int32),
            sds((t, k), jnp.float32), sds((t,), bool), sds((e, d, f)),
            sds((e, d, f)), sds((e, f, d)))
    values, kernels = [], []

    def walk(jp):
        for eqn in jp.eqns:
            values.extend((v.aval.shape, v.aval.dtype) for v in eqn.outvars)
            if eqn.primitive.name == "pallas_call":
                kernels.append(eqn.params["name"])
                continue
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
    walk(jaxpr.jaxpr)
    return values, kernels


@pytest.mark.parametrize("t, kernel", [(1024, True), (16, True),
                                       (16896, False)],
                         ids=["chunk", "decode_step", "whole_sequence"])
def test_no_padded_copy_of_the_rows_in_front_of_the_kernel(monkeypatch, t,
                                                           kernel):
    """On the chip a prefill chunk (4 MB of rows) and a decode step take
    the kernel, and nothing of ``[padded rows, D]`` in the operands' type is
    made outside it: the rows go in as ``h`` and the row table. A whole
    sequence (``apply``: past the budget stated in ops/grouped_matmul.py)
    takes the XLA arm, which gathers them."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    d, k, e = 2048, 8, 128
    assert grouped_matmul.rows_stay_resident(t, d, jnp.bfloat16) == kernel
    values, kernels = chunk_jaxpr(t)
    padded = grouped_matmul.padded_rows(
        t * k, e, grouped_matmul.row_tile(t * k))
    copies = [v for v in values if v == ((padded, d), jnp.bfloat16)]
    if kernel:
        assert kernels == [grouped_matmul.KERNEL_NAME] and not copies
        assert ((padded, d), jnp.float32) in values     # the kernel's out
    else:
        assert not kernels and copies


def test_shares_add_up_to_the_uncut_layer(tiny):
    """The model's expert layer is the reference's expert-at-a-time sum, and
    four shares of it sum to it: each share keeps the assignments that fall
    on one quarter of the experts (the others' tokens do not count there:
    an assignment at a time, ``k`` 1) and the shares' assignments add up to
    all of them (dropless)."""
    wl, w, _ = tiny
    lw = w["layer_2"]
    x = jax.random.normal(jax.random.PRNGKey(6), (40, 64))
    f = ref.make_logits_fn(TINY)("float32")
    ids_e, w_e = f.routed(x, lw["mlp_norm"], lw["router"])
    want = expert_at_a_time(lw, x, ids_e, w_e, range(16))
    h = latent.rms_norm(x, lw["mlp_norm"], TINY["rms_norm_eps"])
    live = jnp.ones((40,), bool)
    whole, stats, ids_p = jax.jit(wl.model._experts)(lw, h, live)
    assert (np.sort(np.asarray(ids_p), -1)
            == np.sort(np.asarray(ids_e), -1)).all()
    np.testing.assert_allclose(np.asarray(whole), want, atol=2e-5)
    assert int(stats[0]) == 40 * PER_TOKEN
    share = jax.jit(lambda i, w, live: prog.grouped_experts(
        h, i, w, live, lw["experts_gate"], lw["experts_up"],
        lw["experts_down"], dtype=jnp.float32))
    total, computed = np.zeros_like(want), 0
    for rank in range(4):
        for j in range(PER_TOKEN):
            here = ids_e[:, j] // 4 == rank
            y, stats = share(ids_e[:, j:j + 1], w_e[:, j:j + 1], here)
            total += np.asarray(y)
            computed += int(stats[0])
            assert int(stats[0]) == int(here.sum()) and int(stats[1]) <= 4
    assert computed == 40 * PER_TOKEN
    np.testing.assert_allclose(total, want, atol=2e-5)


# ------------------------------------------- (e) routing, worked by hand

@pytest.mark.parametrize("route", [
    lambda p: latent.route(KeyeVL2Config.from_arch(dict(
        n_layers=1, num_experts=8, num_experts_per_tok=3)), p,
        jnp.zeros((8,))),
    lambda p: ref.route({"num_experts_per_tok": 3}, p)],
    ids=["program", "reference"])
def test_softmax_routing_with_one_group_by_hand(route):
    """8 experts, 3 taken, no bias, group or scale: ``route`` IS softmax
    top-k with renormalised weights. Logits (2, 0, 1, 1, -1, 0.5, 1.5, -2):
    the largest probabilities are experts 0, 6 and then 2 (1 = 1: of equal
    values the lower index), weights e^2, e^1.5, e^1 over their sum."""
    logits = jnp.asarray([[2.0, 0.0, 1.0, 1.0, -1.0, 0.5, 1.5, -2.0]])
    ids, w = route(jax.nn.softmax(logits, -1))
    assert np.asarray(ids)[0].tolist() == [0, 6, 2]
    e = np.exp([2.0, 1.5, 1.0])
    np.testing.assert_allclose(np.asarray(w)[0], e / e.sum(), rtol=1e-6)


def test_sizes_of_the_source():
    """The published sizes as the program reads them: 32 query heads on 4
    key heads of 128, a cached row of 1,024 numbers (keys then values) and
    an indexer key of 64 stored as a whole lane tile, rotary frequencies
    over the whole head at base 1e7, every expert's matrices in the tree."""
    cfg = KeyeVL2Config()
    assert (cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim,
            cfg.kv_row, cfg.indexer_head_dim, cfg.index_row, cfg.topk) == (
                32, 4, 128, 1024, 64, 128, 2048)
    assert cfg.num_experts == 128 and cfg.num_experts_per_tok == 8
    assert (cfg.n_group, cfg.routed_scaling_factor) == (1, 1.0)
    np.testing.assert_allclose(
        cfg.inv_freq, 1.0 / 1e7 ** (np.arange(0, 128, 2) / 128), rtol=1e-6)
    np.testing.assert_allclose(
        cfg.indexer_inv_freq, ref.inv_freq(1e7, 64), rtol=1e-7)
    file_cfg = KeyeVL2Config.from_arch(json.load(open(CONFIG_FILE)))
    assert cfg.dispatch_lag == 1     # the class states no deployment
    assert file_cfg == dataclasses.replace(cfg, n_layers=6,
                                           max_position_embeddings=16896,
                                           dispatch_lag=8)
    with pytest.raises(ValueError, match="mrope_section"):
        KeyeVL2Config.from_arch({"rope_scaling": {"mrope_section": [8, 8]}})


# ------------------------------------------------ the copies, the files

def test_the_two_reference_copies_give_the_same_logits(tiny):
    """benchmark/harness/ keeps its own copy (the benchmark imports nothing
    of the program); the files are the same bytes, and one test holds what
    they compute together."""
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    try:
        from harness import reference_keye_vl2 as bench_ref
    finally:
        sys.path.pop(0)
    assert open(bench_ref.__file__, "rb").read() \
        == open(ref.__file__, "rb").read()
    _, w, _ = tiny
    ids = ids_of(50, seed=8)
    w2 = jax.jit(lambda s: bench_ref.make_weights(TINY, s))(
        bench_ref.seed_arg(7))
    assert all(np.array_equal(a, b) for a, b in zip(
        jax.tree_util.tree_leaves(w), jax.tree_util.tree_leaves(w2)))
    assert ref.param_count(TINY) == bench_ref.param_count(TINY) \
        == sum(x.size for x in jax.tree_util.tree_leaves(w))
    for precision in ("float32", "fp8"):
        np.testing.assert_array_equal(
            np.asarray(ref.logits(w, TINY, ids, precision)),
            np.asarray(bench_ref.logits(w, TINY, ids, precision)))
    assert [ref.padded_len(n) for n in (50, 257, 4096, 4097, 16385, 17152,
                                        20481)] == [
        50, 4096, 4096, 8192, 20480, 20480, 24576]


def test_configuration_file_states_its_cut():
    cfg = json.load(open(CONFIG_FILE))
    # six whole layers, embedding and head: 4.375 B parameters, 8.749 GB in
    # bfloat16
    assert ref.param_count(cfg) == 4_374_622_464
    assert cfg["num_experts"] == 128 and cfg["num_experts_per_tok"] == 8
    assert cfg["n_layers"] == 6 >= 4 and cfg["vocab_size"] == 151936
    assert cfg["sa_config"]["topk"] == 2048
    for key in cfg["reduced"]:
        assert key in cfg and key in cfg["reduced_note"]
    assert cfg["published"]["num_hidden_layers"] \
        == cfg["num_hidden_layers"] == 48
    for key in ("head_norms", "rope_layout", "text_positions", "indexer",
                "chunk_sizes", "router", "precision", "weights", "ties"):
        assert cfg["assumed"][key]
    assert "8-stage pipeline" in cfg["deployment"]
    # the deployment keeps 8 dispatches in flight, and says why
    assert cfg["dispatch_lag"] == 8 and "110 ms" in cfg["dispatch_lag_note"]
    # the program reads the file as the benchmark's adapter hands it over
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    try:
        from harness import family_keye_vl2 as fam
    finally:
        sys.path.pop(0)
    model = create_model_from_config(
        seq_len=fam.dims(cfg)["positions"], **fam.program_flags(cfg)).model
    assert model.param_shapes() == ref.param_shapes(cfg)
    assert model.dispatch_lag == 8
    shapes = model.cache_shapes(1 + 16 * 264, 64)
    assert shapes["layer_5"]["kv"].shape == (4225, 64, 1024)
    assert shapes["layer_5"]["index_k"].shape == (4225, 64, 128)
    # the embedding alone is drawn at the file's embedding_initializer_range
    assert cfg["embedding_initializer_range"] == 1.0
    w = ref.make_weights(TINY, ref.seed_arg(3))
    assert 0.9 < float(jnp.std(w["embed"])) < 1.1
    assert 0.09 < float(jnp.std(w["head"])) < 0.11
    assert 0.09 < float(jnp.std(w["layer_1"]["router"])) < 0.11


def test_family_through_run_serve(tmp_path):
    """The serving entry point: a run directory (``training_args.json`` +
    a checkpoint) of the family, served by ``run.serve``'s single-replica
    path with the ordinary flags."""
    from distributed_pipeline_tpu.run import serve as serve_cli
    from distributed_pipeline_tpu.utils import checkpoint as ckpt_lib

    wl, _, tree = build(TINY)
    run_dir = str(tmp_path / "run")
    os.makedirs(run_dir)
    with open(os.path.join(run_dir, "training_args.json"), "w") as f:
        json.dump({"model_family": "keye_vl2", "vocab_size": VOCAB,
                   "seq_len": POSITIONS, "dtype": "float32",
                   "arch": arch_of(TINY)}, f)
    ckpt_lib.save_checkpoint(run_dir, 1, tree)
    ns = serve_cli.create_parser().parse_args([
        "--checkpoint_path", run_dir, "--decode_slots", "2",
        "--page_size", "4", "--max_prompt_len", "40", "--max_len", "64",
        "--max_new_tokens", "6", "--synthetic_requests", "3",
        "--synthetic_prompt_len", "21", "--decode_span", "2",
        "--sanitize", "true"])
    summary = serve_cli.main(ns)
    assert summary["requests"] == 3 and summary["recompile_count"] == 0
    assert summary["prefill_steps"] == 3 * 6     # 21 tokens in chunks of 4


def test_prefill_with_the_kernels_equals_its_xla_arm(monkeypatch):
    """The prefill chunk with ops/mla_attention.py's kernels interpreted in
    the attention's and the indexer's place (8 query heads on 2 repeated
    key heads; the indexer at 4 heads of 16), at a tile-aligned tiny size
    (blocks of 128 rows), against the XLA arm."""
    monkeypatch.setattr(prog, "KV_BLOCK", 128)
    cfg = dict(TINY, max_position_embeddings=512)
    cfg["sa_config"] = dict(TINY["sa_config"], topk=150)
    wl, w, tree = build(cfg)
    ids = ids_of(384, seed=9)
    ps, n_pages = 16, 24
    table = jnp.arange(1, n_pages + 1, dtype=jnp.int32)
    outs = []
    for impl in ("xla", "interpret"):
        m = dataclasses.replace(wl.model, kernel_impl=impl)
        cache = jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype),
            m.cache_shapes(1 + n_pages, ps))
        step = jax.jit(m.prefill_chunk)
        for start in (0, 128, 256):
            cache, logits, _ = step(
                tree["params"], cache, jnp.asarray(ids[start:start + 128]),
                jnp.int32(start), jnp.int32(128), table)
        outs.append(np.asarray(logits))
    np.testing.assert_allclose(outs[1], outs[0], atol=1e-4)
