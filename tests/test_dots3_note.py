"""The dots3-note-prev share (models/dots3_note.py: a configuration of
models/deepseek_v32.py's one set of layer equations) against its plain
reference (models/reference_dots3_note.py) at a tiny size on the CPU, on
seeded weights: both kinds of layer (full with the indexer, sliding with a
window the sequences cross), the cache-free forward, chunked prefill then
decode through the paged pool AND the window cache's rings (prompts shorter
than the window, longer than it, longer than the ring), the family through
``DecodeServer`` with short and long requests in one queue, the window
cache's accounting, the discrete choices compared as sets, the share test,
routing with one group by hand, the two copies of the reference and the
configuration file."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_pipeline_tpu.models import create_model_from_config
from distributed_pipeline_tpu.models import deepseek_v32 as prog
from distributed_pipeline_tpu.models import reference_dots3_note as ref
from distributed_pipeline_tpu.models.dots3_note import Dots3NoteConfig
from distributed_pipeline_tpu.serving import DecodeServer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB, POSITIONS, WINDOW = 211, 96, 7
FULL, SLIDING = "full_attention", "sliding_attention"

# the source's keys at a tiny size: five layers of both kinds (the leading
# dense one full, as published), the two kinds with different head counts,
# ranks, head sizes and rope bases, a window of 7 that every sequence here
# crosses, index_topk far below the lengths used, more experts than are held
TINY = {
    "hidden_size": 64, "n_layers": 5, "first_k_dense_replace": 1,
    "layer_types": [FULL, FULL, SLIDING, SLIDING, FULL],
    "sliding_window_size": WINDOW,
    "num_attention_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 16,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "rope_theta": 80000000,
    "swa_num_attention_heads": 2, "swa_q_lora_rank": 32,
    "swa_kv_lora_rank": 32, "swa_qk_nope_head_dim": 24,
    "swa_qk_rope_head_dim": 8, "swa_v_head_dim": 16, "swa_rope_theta": 50000,
    "attention_gate_type": "headwise", "swa_attention_gate_type": "headwise",
    "apply_mla_qkv_lora_rescale": True,
    "index_n_heads": 8, "index_head_dim": 16, "index_topk": 12,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "n_routed_experts": 32, "n_routed_experts_held": 4, "expert_offset": 0,
    "n_shared_experts": 1, "num_experts_per_tok": 4,
    "routed_scaling_factor": 1.0, "rms_norm_eps": 1e-5,
    "vocab_size": VOCAB, "max_position_embeddings": POSITIONS,
    "initializer_range": 0.1, "param_dtype": "float32", "dtype": "float32"}
N_FULL = TINY["layer_types"].count(FULL)
N_SLIDING = TINY["layer_types"].count(SLIDING)


def arch_of(cfg):
    arch = {k: v for k, v in cfg.items()
            if k not in ("vocab_size", "dtype", "param_dtype")}
    arch["n_dense_layers"] = cfg["first_k_dense_replace"]
    return arch


def build(cfg, seed=7):
    """(workload, reference weights, program tree): the SAME arrays on both
    sides, as the benchmark's driver hands them over."""
    wl = create_model_from_config(
        model_family="dots3_note", vocab_size=cfg["vocab_size"],
        seq_len=cfg["max_position_embeddings"], dtype=cfg["dtype"],
        arch=arch_of(cfg))
    w = jax.jit(lambda s: ref.make_weights(cfg, s))(ref.seed_arg(seed))
    return wl, w, {"params": w}


@pytest.fixture(scope="module")
def tiny():
    return build(TINY)


@pytest.fixture
def small_blocks(monkeypatch):
    """Context blocks of 8 rows, so that the prefill's block walk crosses
    blocks, starts behind the window and skips what lies before it."""
    monkeypatch.setattr(prog, "KV_BLOCK", 8)


def ids_of(n, seed=0, vocab=VOCAB):
    return np.random.default_rng(seed).integers(4, vocab, (n,)).astype(
        np.int32)


def in_window(t):
    """Rows query ``t`` reads in one sliding layer."""
    return min(t + 1, WINDOW)


# ------------------------------------------------- (a) the whole forward

def test_forward_equals_reference_float32(tiny, small_blocks):
    """float32 on both sides: what is left is the order of summation
    (blocks, absorbed against un-absorbed products): 1e-4 on logits of
    order 3 is thirty times the 3e-6 read, and far below what the nearest
    lower precision (bfloat16, below) reads."""
    wl, w, tree = tiny
    assert [k.window for k in wl.model.kinds] == [0, 0, WINDOW, WINDOW, 0]
    assert tree["params"]["layer_2"]["wo_gate"].shape == (64, 2)
    assert "idx_wk" not in tree["params"]["layer_2"]
    ids = ids_of(80)
    got = jax.jit(wl.model.apply)(tree, ids[None])[0]
    want = ref.logits(w, TINY, ids)
    assert got.shape == want.shape == (80, VOCAB)
    assert float(jnp.max(jnp.abs(want))) > 1.0
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


def test_reference_band_by_blocks_equals_one_block(tiny, monkeypatch):
    """The reference computes a sliding layer a block of queries against
    the slice of keys its band reaches; with blocks of 8 rows (a window of 7
    then reaches one block back) it gives what one block over the whole
    sequence gives."""
    _, w, _ = tiny
    ids = ids_of(64, seed=4)
    whole = np.asarray(ref.logits(w, TINY, ids))
    monkeypatch.setattr(ref, "BLOCK", 8)
    np.testing.assert_allclose(np.asarray(ref.logits(w, TINY, ids)), whole,
                               atol=1e-5)


BF16 = dict(TINY, dtype="bfloat16", param_dtype="bfloat16")
# bfloat16 operands with float32 accumulation and a float32 residual stream,
# against the float32 reference on the same (bfloat16) weights. As for the
# DeepSeek share, the two discontinuities (router top-k, indexer top-k) flip
# on some tokens in any precision, so the lower quartile of the per-token
# largest error is compared: the program reads 0.012-0.016 on three seeds,
# the reference with fp8 operands (the nearest lower precision) 0.33-0.39.
# 0.07 keeps a factor of four to the first and five to the second.
BF16_QUARTILE = 0.07


def test_forward_bfloat16_within_its_tolerance_and_fp8_outside():
    wl, w, tree = build(BF16)
    ids = ids_of(80, seed=1)
    want = np.asarray(ref.logits(w, BF16, ids))
    got = np.asarray(jax.jit(wl.model.apply)(tree, ids[None])[0])
    low = np.asarray(ref.logits(w, BF16, ids, precision="fp8"))

    def quartile(x):
        return np.percentile(np.abs(x - want).max(1), 25)
    assert quartile(got) < BF16_QUARTILE < quartile(low)


# ------------- (b) chunked prefill, then decode: paged pool and window rings

@pytest.mark.parametrize("prompt", [5, 29, 41],
                         ids=["shorter_than_window", "longer_than_ring",
                              "chunk_boundary_in_window"])
def test_chunked_prefill_then_decode_equals_reference(tiny, small_blocks,
                                                      prompt):
    """Chunks of 12 (a chunk boundary inside every later query's window),
    pages of 4, a ring of 5 pages = 20 rows (window 7 + chunk 12 = 19): a
    prompt of 5 never fills its window, one of 29 wraps the ring in its
    second chunk, and all 60 positions wrap it three times; every logit
    against the reference's ONE full forward, and the counters by hand."""
    wl, w, tree = tiny
    m, p = wl.model, tree["params"]
    ids = ids_of(60, seed=2)
    want = np.asarray(ref.logits(w, TINY, ids))
    ps, n_pages, chunk = 4, 16, 12
    ring = -(-m.window_rows(chunk) // ps)
    assert m.window_rows(chunk) == WINDOW + chunk and ring == 5
    shapes = m.cache_shapes(1 + n_pages, ps, 1 + ring)
    assert shapes["layer_1"]["latent"].shape == (17, 4, 128)
    assert shapes["layer_2"]["window"].shape == (6, 4, 128)
    assert set(shapes["layer_2"]) == {"window"}
    cache = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), shapes)
    table = jnp.arange(1, n_pages + 1, dtype=jnp.int32)
    wtable = jnp.arange(1, ring + 1, dtype=jnp.int32)
    step = jax.jit(m.prefill_chunk)
    for start in range(0, prompt, chunk):
        n = min(chunk, prompt - start)
        buf = np.zeros((chunk,), np.int32)
        buf[:n] = ids[start:start + n]
        cache, logits, counted = step(
            p, cache, jnp.asarray(buf), jnp.int32(start), jnp.int32(n),
            table, wtable)
        np.testing.assert_allclose(np.asarray(logits), want[start + n - 1],
                                   atol=1e-4)
        counted = dict(zip(m.counters, np.asarray(counted)))
        live = sum(range(start + 1, start + n + 1))
        assert counted["kv_rows_live"] == counted["index_rows_scored"] \
            == live * N_FULL
        assert counted["kv_rows_attended"] == N_FULL * sum(
            min(t + 1, TINY["index_topk"]) for t in range(start, start + n))
        assert counted["window_rows_live"] == live * N_SLIDING
        assert counted["window_rows_attended"] == N_SLIDING * sum(
            in_window(t) for t in range(start, start + n))
    # slot 1 stays inactive (all-trash tables): it must disturb nothing
    tables = jnp.stack([table, jnp.zeros_like(table)])
    wtables = jnp.stack([wtable, jnp.zeros_like(wtable)])
    decode = jax.jit(m.decode_step)
    for t in range(prompt, 60):
        cache, logits, counted, _ = decode(
            p, cache, jnp.asarray([ids[t], 0]), jnp.asarray([t, 0]), tables,
            jnp.asarray([1, 0]), wtables)
        np.testing.assert_allclose(np.asarray(logits[0]), want[t], atol=1e-4)
        counted = dict(zip(m.counters, np.asarray(counted)))
        assert counted["kv_rows_live"] == (t + 1) * N_FULL
        assert counted["kv_rows_attended"] == \
            min(t + 1, TINY["index_topk"]) * N_FULL
        assert counted["window_rows_live"] == (t + 1) * N_SLIDING
        assert counted["window_rows_attended"] == in_window(t) * N_SLIDING


def test_served_through_decode_server_equals_reference(tiny, small_blocks):
    """The normal path: DecodeServer with pages of 2 and the chunk the
    engine derives from max_prompt_len (64 / 16 = 4), two slots, short and
    long requests in ONE queue (3 to 60 prompt tokens; slots are reused
    after release, over ring pages other requests wrote). Every served
    token is the reference's pick at its position (float32: a gap above
    1e-4 would be a wrong row, not rounding). The window cache's
    accounting: a slot's ring is window + chunk rows whatever the request's
    length, a short request takes fewer pages, and pages of both kinds
    return to their free lists."""
    wl, w, tree = tiny
    server = DecodeServer(wl, tree, decode_slots=2, page_size=2,
                          max_prompt_len=64, max_len=POSITIONS)
    eng = server.engine
    assert eng.chunked and eng.prefill_chunk == 4
    # 7 + 4 = 11 rows -> 6 pages of 2 a slot, against 48 for a whole slot
    assert eng.window_pages_per_slot == 6 and eng.pages_per_slot == 48
    assert eng.max_window_pages == 1 + 2 * 6
    assert eng.cache["layer_2"]["window"].shape[0] == 13
    assert eng.cache["layer_1"]["latent"].shape[0] == 1 + 2 * 48
    shapes = [(29, 9), (3, 2), (60, 30), (13, 14), (37, 6), (5, 1), (24, 11)]
    reqs = [server.submit(ids_of(n, seed=10 + i), g)
            for i, (n, g) in enumerate(shapes)]
    most_window_pages = 0
    while server.busy:
        server.step()
        for st in server.slots:
            if st is not None:
                want_pages = min(6, -(-(st.req.prompt_len + st.req.g_max)
                                      // 2))
                assert len(st.window_pages) == want_pages
                most_window_pages = max(most_window_pages, want_pages)
        held = sum(len(st.window_pages) for st in server.slots
                   if st is not None)
        assert server.window_mgr.free_pages \
            == server.window_mgr.capacity - held
        # resident sliding-layer rows never exceed window + chunk a slot
        assert held * 2 <= 2 * (WINDOW + eng.prefill_chunk + 1)
    assert most_window_pages == 6
    assert all(r.finished and len(r.tokens) == g
               for r, (_, g) in zip(reqs, shapes))
    for r in reqs:
        seq = np.concatenate([r.prompt, np.asarray(r.tokens, np.int32)])
        gaps = ref.served_gaps(w, TINY, seq, r.prompt_len)
        assert gaps.shape == (len(r.tokens),) and gaps.max() <= 1e-4
    # nothing leaks, of either kind
    assert server.mgr.free_pages == server.mgr.capacity == 96
    assert server.window_mgr.free_pages == server.window_mgr.capacity == 12
    assert (server.window_tables == 0).all()
    assert all(s is None for s in server.slots) and not server.busy
    assert server.prompt_tokens_prefilled == sum(n for n, _ in shapes)
    assert server.tokens_fetched == sum(g for _, g in shapes)
    for program in ("prefill", "decode"):
        c = server.counted[program]
        assert set(c) == set(prog.COUNTERS + prog.WINDOW_COUNTERS)
        assert 0 < c["kv_rows_attended"] < c["kv_rows_live"]
        assert 0 < c["window_rows_attended"] < c["window_rows_live"]
        assert c["kv_rows_live"] * N_SLIDING \
            == c["window_rows_live"] * N_FULL
    assert eng.kv_pool_bytes() == sum(
        leaf.size * leaf.dtype.itemsize
        for leaf in jax.tree_util.tree_leaves(eng.cache))


def test_admission_books_pages_by_kind(tiny, tmp_path):
    """``serve.admit`` carries the pages reserved by kind (spans follow the
    profiler: a session is on); a window pool too small for a ring a slot
    makes admission wait (all or nothing, both kinds) instead of stranding
    a request."""
    from distributed_pipeline_tpu.obs import trace
    from distributed_pipeline_tpu.serving.paged_kv import PageManager
    wl, _, tree = tiny
    server = DecodeServer(wl, tree, decode_slots=2, page_size=2,
                          max_prompt_len=64, max_len=POSITIONS)
    server.window_mgr = PageManager(1 + 8, 2)      # not two rings of 6
    trace.clear_recorded()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        a = server.submit(ids_of(30, seed=1), 4)
        b = server.submit(ids_of(30, seed=2), 4)
        server.step()
        assert server.free_slots == 1 and len(server.queue) == 1
        assert server.mgr.free_pages == server.mgr.capacity - 17
        server.drain()
    finally:
        jax.profiler.stop_trace()
    assert a.finished and b.finished
    assert server.mgr.free_pages == server.mgr.capacity
    assert server.window_mgr.free_pages == 8
    admits = [e["args"] for e in trace.recorded()
              if e.get("name") == "serve.admit" and (e.get("args") or {}
                                                     ).get("n")]
    trace.clear_recorded()
    assert [(x["pages_full"], x["pages_window"]) for x in admits[-2:]] \
        == [(17, 6), (17, 6)]


def test_a_family_without_window_layers_has_no_window_pool():
    """DeepSeek-V3.2-Exp is the case "every layer full": no ring, no second
    allocator, the five counters it had."""
    wl = create_model_from_config(
        model_family="deepseek_v32", vocab_size=VOCAB, seq_len=POSITIONS,
        dtype="float32", arch={
            k: v for k, v in arch_of(TINY).items()
            if not k.startswith("swa_") and k != "layer_types"})
    assert wl.model.window_rows(4) == 0
    assert wl.model.counters == prog.COUNTERS
    tree = wl.model.init(jax.random.PRNGKey(0))
    server = DecodeServer(wl, tree, decode_slots=2, page_size=2,
                          max_prompt_len=64, max_len=POSITIONS)
    assert server.window_mgr is None
    assert server.engine.window_pages_per_slot == 0
    assert all("window" not in layer for layer in
               server.engine.cache.values())
    with pytest.raises(ValueError, match="no indexer"):
        prog.LayerKind(heads=2, q_lora_rank=8, kv_lora_rank=8,
                       qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
                       inv_freq=(1.0,) * 4, softmax_scale=0.25, window=5)


# ------------------------------- (c) the discrete choices, compared as sets

def test_selected_rows_and_routed_experts_equal_the_reference(tiny,
                                                              small_blocks):
    """For every token of a seeded sequence the full layers' selected
    positions and every expert layer's routed experts are the reference's,
    in the prefill form (a mask) and in the decode form (``lax.top_k``
    indices); a sliding layer selects nothing (None on both sides)."""
    wl, w, tree = tiny
    m, p = wl.model, tree["params"]
    ids = ids_of(64, seed=3)
    _, chosen = ref.make_logits_fn(TINY)("float32").hidden(
        w, jnp.asarray(ids))
    _, aux = jax.jit(lambda v, i: m.apply(v, i, collect=True))(
        tree, ids[None])
    for layer, kind in enumerate(TINY["layer_types"]):
        if kind == SLIDING:
            assert chosen["selected"][layer] is None
            assert aux["selected"][layer] is None
        else:
            want = np.asarray(chosen["selected"][layer])
            got = np.asarray(aux["selected"][layer])[0][:64, :64]
            assert (got == want).all()
            assert want.sum(1).tolist() == [
                min(t + 1, TINY["index_topk"]) for t in range(64)]
        if chosen["experts"][layer] is not None:
            assert (np.sort(np.asarray(aux["experts"][layer])[0], -1)
                    == np.sort(np.asarray(chosen["experts"][layer]), -1)
                    ).all()
    ps, n_pages = 8, 8
    cache = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        m.cache_shapes(1 + n_pages, ps, 1 + n_pages))
    table = jnp.arange(1, n_pages + 1, dtype=jnp.int32)
    cache, _, _ = jax.jit(m.prefill_chunk)(
        p, cache, jnp.asarray(ids[:40]), jnp.int32(0), jnp.int32(40), table,
        table)
    decode = jax.jit(lambda *a: m.decode_step(*a, collect=True))
    for t in range(40, 64):
        cache, _, _, aux = decode(p, cache, jnp.asarray(ids[t:t + 1]),
                                  jnp.asarray([t]), table[None],
                                  jnp.asarray([1]), table[None])
        for layer, kind in enumerate(TINY["layer_types"]):
            if kind == FULL:
                got = set(np.asarray(
                    aux["selected"][layer])[0].tolist()) - {-1}
                want = set(np.nonzero(np.asarray(
                    chosen["selected"][layer])[t])[0].tolist())
                assert got == want, (t, layer)
            if chosen["experts"][layer] is not None:
                assert set(np.asarray(aux["experts"][layer])[0].tolist()) \
                    == set(np.asarray(chosen["experts"][layer])[t].tolist())


# ------------------------------------------------------ (d) the share test

@pytest.mark.parametrize("decode", [False, True], ids=["chunk", "decode"])
def test_shares_add_up_to_the_uncut_layer(decode):
    """What ties the cut to the model: the routed parts of all 8 shares (4
    experts each) plus the shared expert counted ONCE equal the uncut
    reference's expert layer (all 32 experts held by one, one group of 32,
    the 4 best taken), in the form a prefill chunk runs and in the form a
    decode step runs."""
    whole = dict(TINY, n_routed_experts_held=32)
    w = jax.jit(lambda s: ref.make_weights(whole, s))(ref.seed_arg(11))
    lw = w["layer_2"]
    x = jax.random.normal(jax.random.PRNGKey(4), (40, TINY["hidden_size"]))
    f = ref.make_logits_fn(whole)("float32")
    ids_e, w_e = f.routed(x, lw["mlp_norm"], lw["router"], lw["router_bias"])
    want = f.dense(x, lw["mlp_norm"], lw["shared_gate"], lw["shared_up"],
                   lw["shared_down"])
    for e in range(32):
        want = want + f.expert(x, lw["mlp_norm"],
                               jnp.sum(jnp.where(ids_e == e, w_e, 0.0), -1),
                               lw["experts_gate"], lw["experts_up"],
                               lw["experts_down"], jnp.int32(e))
    want = np.asarray(want - x)
    h = prog.rms_norm(x, lw["mlp_norm"], TINY["rms_norm_eps"])
    live = jnp.ones((40,), bool)
    shared = None
    total = np.zeros_like(want)
    held_sum = 0
    for rank in range(8):
        cfg = dict(TINY, expert_offset=4 * rank)
        model = create_model_from_config(
            model_family="dots3_note", vocab_size=VOCAB, seq_len=POSITIONS,
            dtype="float32", arch=arch_of(cfg)).model
        lp = dict(lw, **{k: lw[k][4 * rank:4 * rank + 4] for k in (
            "experts_gate", "experts_up", "experts_down")})
        no_experts = dict(lp, router_bias=jnp.full((32,), 0.0).at[
            4 * rank:4 * rank + 4].set(-jnp.inf))
        y, stats, _ = jax.jit(
            lambda lp, h: model._ffn(lp, 2, h, live, decode=decode))(lp, h)
        if shared is None:
            # the shared expert alone: the same layer with this share's
            # experts made unreachable
            shared, none, _ = model._ffn(no_experts, 2, h, live,
                                         decode=decode)
            assert int(none[0]) == 0
        total += np.asarray(y - shared)
        held_sum += int(stats[0])
    assert held_sum == 40 * TINY["num_experts_per_tok"]   # dropless
    np.testing.assert_allclose(total + np.asarray(shared), want, atol=2e-5)


# ------------------------------------------- (e) routing, worked by hand

@pytest.mark.parametrize("route", [
    lambda s, b: prog.route(Dots3NoteConfig.from_arch(dict(
        n_layers=1, layer_types=[FULL], n_routed_experts=8,
        n_routed_experts_held=8, num_experts_per_tok=3,
        routed_scaling_factor=1.0)), s, b),
    lambda s, b: ref.route({"num_experts_per_tok": 3,
                            "routed_scaling_factor": 1.0}, s, b)],
    ids=["program", "reference"])
def test_routing_with_one_group_by_hand(route):
    """8 experts, no groups, 3 taken. The scores that the group-limited
    test of the DeepSeek share uses: with groups of 2 expert 4's 0.6 fell
    out with its group; with ONE group the best of all are taken: 7
    (0.35 + 0.7 bias = 1.05), 0 (0.9), 4 (0.6). Expert 7 wins only through
    its bias: its WEIGHT is from its unbiased 0.35: weights (0.35, 0.9,
    0.6) / 1.85 * 1."""
    scores = jnp.asarray([[0.9, 0.1, 0.5, 0.45, 0.6, 0.3, 0.2, 0.35]])
    bias = jnp.asarray([0, 0, 0, 0, 0, 0, 0, 0.7], jnp.float32)
    ids, w = route(scores, bias)
    assert np.asarray(ids)[0].tolist() == [7, 0, 4]
    np.testing.assert_allclose(
        np.asarray(w)[0], np.array([0.35, 0.9, 0.6]) / 1.85, rtol=1e-6)
    ids, w = route(scores, jnp.zeros((8,)))
    assert np.asarray(ids)[0].tolist() == [0, 4, 2]
    np.testing.assert_allclose(np.asarray(w)[0].sum(), 1.0, rtol=1e-6)


def test_layer_kinds_of_the_source():
    """The published sizes as the shared equations read them: 128 heads of
    (128 | 64) over a 512-wide latent at base 8e7 with the indexer, 64
    heads of (192 | 64) over a 1,024-wide latent at base 5e4 inside a
    window of 513; gates on both; the latent rescale sqrt(5120 / rank);
    rows stored as whole lane tiles (640 and 1,152 wide)."""
    cfg = Dots3NoteConfig()
    assert cfg.layer_types.count(FULL) == 13 and len(cfg.layer_types) == 46
    full, sliding = cfg.layer(0), cfg.layer(2)
    assert (full.heads, full.qk_head_dim, full.latent_width, full.latent_row,
            full.window, full.indexer, full.gate) == (
                128, 192, 576, 640, 0, True, True)
    assert (sliding.heads, sliding.qk_head_dim, sliding.latent_width,
            sliding.latent_row, sliding.window, sliding.indexer,
            sliding.gate) == (64, 256, 1088, 1152, 513, False, True)
    assert abs(full.softmax_scale - 192 ** -0.5) < 1e-12
    assert abs(sliding.softmax_scale - 256 ** -0.5) < 1e-12
    assert abs(full.q_rescale - 5 ** 0.5) < 1e-12 \
        and abs(full.kv_rescale - 10 ** 0.5) < 1e-12 \
        and abs(sliding.kv_rescale - 5 ** 0.5) < 1e-12
    np.testing.assert_allclose(
        full.inv_freq, 1.0 / 8e7 ** (np.arange(0, 64, 2) / 64), rtol=1e-6)
    np.testing.assert_allclose(
        sliding.inv_freq, ref.inv_freq(ref.kind_of(json.load(open(
            os.path.join(ROOT, "benchmark", "configs",
                         "dots3-note-prev-ep16.json"))), 2)), rtol=1e-7)
    with pytest.raises(ValueError, match="layer_types"):
        Dots3NoteConfig.from_arch({"n_layers": 3, "layer_types": [FULL]})


# ------------------------------------------------ the copies, the files

def test_the_two_reference_copies_give_the_same_logits(tiny):
    """benchmark/harness/ keeps its own copy (the benchmark imports nothing
    of the program); the files are the same bytes, and one test holds what
    they compute together."""
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    try:
        from harness import reference_dots3_note as bench_ref
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


def test_configuration_file_states_its_cut():
    cfg = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "dots3-note-prev-ep16.json")))
    # one chip's share: 4.603 B parameters, 9.21 GB in bfloat16
    assert ref.param_count(cfg) == 4_603_365_632
    assert cfg["n_routed_experts"] == 256 and cfg["num_experts_per_tok"] == 8
    assert cfg["n_layers"] - cfg["first_k_dense_replace"] >= 4
    # the leading dense layer and two whole periods (full, sliding x 3)
    assert cfg["layer_types"] == [FULL] + [FULL, SLIDING, SLIDING,
                                           SLIDING] * 2
    assert len(cfg["layer_types"]) == cfg["n_layers"] == 9
    assert cfg["n_routed_experts_held"] >= 8
    assert cfg["vocab_size"] * 8 >= cfg["published"]["vocab_size"]
    assert cfg["sliding_window_size"] == 513 and cfg["rope_scaling"] is None
    for key in cfg["reduced"]:
        assert key in cfg and key in cfg["reduced_note"]
    assert cfg["published"]["num_hidden_layers"] \
        == cfg["num_hidden_layers"] == 46
    for key in ("latent_rescale", "gate", "window", "indexer", "router"):
        assert cfg["assumed"][key]
    assert cfg["deployment"]
    # the program reads the file as the benchmark's adapter hands it over
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    try:
        from harness import family_dots3_note as fam
    finally:
        sys.path.pop(0)
    model = create_model_from_config(
        seq_len=fam.dims(cfg)["positions"], **fam.program_flags(cfg)).model
    assert model.param_shapes() == ref.param_shapes(cfg)
    # 513 + 1,024 -> 25 pages of 64 = 1,600 rows a slot a sliding layer
    assert -(-model.window_rows(1024) // 64) == 25
    # the embedding alone is drawn at the file's embedding_initializer_range
    assert cfg["embedding_initializer_range"] == 1.0 and cfg["assumed"][
        "weights"]
    w = ref.make_weights(dict(TINY, embedding_initializer_range=1.0),
                         ref.seed_arg(3))
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
        json.dump({"model_family": "dots3_note", "vocab_size": VOCAB,
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


def test_window_walk_with_the_kernel_equals_its_xla_arm(monkeypatch):
    """The prefill chunk of both layer kinds with ops/mla_attention.py's
    kernels interpreted in the attention's and the indexer's place, at a
    tile-aligned tiny size (blocks of 128 rows, a window of 150 that
    reaches two blocks back), against the XLA arm."""
    import dataclasses
    monkeypatch.setattr(prog, "KV_BLOCK", 128)
    cfg = dict(TINY, sliding_window_size=150, max_position_embeddings=512)
    wl, w, tree = build(cfg)
    ids = ids_of(384, seed=9)
    ps, n_pages = 16, 24
    table = jnp.arange(1, n_pages + 1, dtype=jnp.int32)
    outs = []
    for impl in ("xla", "interpret"):
        m = dataclasses.replace(wl.model, kernel_impl=impl)
        cache = jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype),
            m.cache_shapes(1 + n_pages, ps, 1 + n_pages))
        step = jax.jit(m.prefill_chunk)
        for start in (0, 128, 256):
            cache, logits, _ = step(
                tree["params"], cache, jnp.asarray(ids[start:start + 128]),
                jnp.int32(start), jnp.int32(128), table, table)
        outs.append(np.asarray(logits))
    np.testing.assert_allclose(outs[0], outs[1], atol=1e-4)
    np.testing.assert_allclose(
        outs[0], np.asarray(ref.logits(w, cfg, ids))[-1], atol=1e-4)
