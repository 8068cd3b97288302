"""The DeepSeek-V3.2-Exp share (models/deepseek_v32.py) against its plain
reference (models/reference_deepseek_v32.py) at a tiny size on the CPU, on
seeded weights: the cache-free forward, chunked prefill then decode through
the paged latent and indexer pools, the discrete choices (indexer selection,
routed experts) compared as sets, the share test, routing by hand, the two
copies of the reference, and the family on the serving entry points."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_pipeline_tpu.models import create_model_from_config
from distributed_pipeline_tpu.models import deepseek_v32 as prog
from distributed_pipeline_tpu.models import reference_deepseek_v32 as ref
from distributed_pipeline_tpu.serving import DecodeServer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB, POSITIONS = 211, 96

# the source's keys at a tiny size: 2 groups more than are kept, more
# experts than are held, index_topk far below the lengths used (selection
# bites from position 12 on), 8 indexer heads (with fewer, relu leaves
# whole rows of exact zeros and the top-k boundary is all ties)
TINY = {
    "hidden_size": 64, "n_layers": 3, "first_k_dense_replace": 1,
    "num_attention_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 16,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "index_n_heads": 8, "index_head_dim": 16, "index_topk": 12,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "n_routed_experts": 32, "n_routed_experts_held": 4, "expert_offset": 0,
    "n_shared_experts": 1, "num_experts_per_tok": 4, "n_group": 4,
    "topk_group": 2, "routed_scaling_factor": 2.5, "rms_norm_eps": 1e-6,
    "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 24, "type": "yarn"},
    "vocab_size": VOCAB, "max_position_embeddings": POSITIONS,
    "initializer_range": 0.1, "param_dtype": "float32", "dtype": "float32"}


def arch_of(cfg):
    arch = {k: v for k, v in cfg.items()
            if k not in ("vocab_size", "dtype", "param_dtype")}
    arch["n_dense_layers"] = cfg["first_k_dense_replace"]
    return arch


def build(cfg, seed=7):
    """(workload, reference weights, program tree): the SAME arrays on both
    sides, as the benchmark's driver hands them over."""
    wl = create_model_from_config(
        model_family="deepseek_v32", vocab_size=cfg["vocab_size"],
        seq_len=cfg["max_position_embeddings"], dtype=cfg["dtype"],
        arch=arch_of(cfg))
    w = jax.jit(lambda s: ref.make_weights(cfg, s))(ref.seed_arg(seed))
    return wl, w, {"params": w}


@pytest.fixture(scope="module")
def tiny():
    return build(TINY)


def ids_of(n, seed=0, vocab=VOCAB):
    return np.random.default_rng(seed).integers(4, vocab, (n,)).astype(
        np.int32)


# ------------------------------------------------- (a) the whole forward

def test_forward_equals_reference_float32(tiny):
    """float32 on both sides: what is left is the order of summation
    (blocks, absorbed against un-absorbed products): 1e-4 on logits of
    order 3 is a hundred times the 2e-6 read, and a thousandth of what the
    nearest lower precision (bfloat16, below) reads."""
    wl, w, tree = tiny
    ids = ids_of(80)
    got = jax.jit(wl.model.apply)(tree, ids[None])[0]
    want = ref.logits(w, TINY, ids)
    assert got.shape == want.shape == (80, VOCAB)
    assert float(jnp.max(jnp.abs(want))) > 1.0
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


BF16 = dict(TINY, dtype="bfloat16", param_dtype="bfloat16")
# bfloat16 operands with float32 accumulation and a float32 residual
# stream, against the float32 reference on the same (bfloat16) weights.
# The model has two discontinuities (router top-k, indexer top-k): at this
# tiny width rounding flips one of them on about half the tokens, and a
# flipped token's logits move by order 1 in ANY precision, so the maximum
# says nothing. The lower quartile of the per-token largest error counts
# the tokens no flip reached: the program reads 0.013-0.018 on three seeds,
# the reference with fp8 operands (the nearest lower precision) 0.47-0.50.
# 0.08 keeps a factor of four to the first and five to the second.
BF16_QUARTILE = 0.08


def test_forward_bfloat16_within_its_tolerance_and_fp8_outside():
    """The program in the precision the configuration states stays inside
    the written tolerance; the same mathematics with operands of the
    nearest lower precision (fp8) does not: a program computed below the
    stated precision fails."""
    wl, w, tree = build(BF16)
    ids = ids_of(80, seed=1)
    want = np.asarray(ref.logits(w, BF16, ids))
    got = np.asarray(jax.jit(wl.model.apply)(tree, ids[None])[0])
    low = np.asarray(ref.logits(w, BF16, ids, precision="fp8"))

    def quartile(x):
        return np.percentile(np.abs(x - want).max(1), 25)
    assert quartile(got) < BF16_QUARTILE < quartile(low)


# --------------------------- (b) chunked prefill, then decode, paged pools

def test_chunked_prefill_then_decode_equals_reference(tiny):
    """Three chunks of 12 (the last one short and ending mid-page: 29 = 7
    pages of 4 and one row) and every decode step after them, logits
    against the reference's ONE full forward; lengths run far past
    index_topk = 12."""
    wl, w, tree = tiny
    m, p = wl.model, tree["params"]
    ids = ids_of(60, seed=2)
    want = np.asarray(ref.logits(w, TINY, ids))
    ps, n_pages, chunk, prompt = 4, 16, 12, 29
    cache = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), m.cache_shapes(1 + n_pages, ps))
    table = jnp.arange(1, n_pages + 1, dtype=jnp.int32)
    step = jax.jit(m.prefill_chunk)
    for start in range(0, prompt, chunk):
        n = min(chunk, prompt - start)
        buf = np.zeros((chunk,), np.int32)
        buf[:n] = ids[start:start + n]
        cache, logits, counted = step(p, cache, jnp.asarray(buf),
                                      jnp.int32(start), jnp.int32(n), table)
        np.testing.assert_allclose(np.asarray(logits), want[start + n - 1],
                                   atol=1e-4)
        counted = dict(zip(prog.COUNTERS, np.asarray(counted)))
        live = sum(range(start + 1, start + n + 1)) * TINY["n_layers"]
        assert counted["kv_rows_live"] == counted["index_rows_scored"] == live
        assert counted["kv_rows_attended"] == TINY["n_layers"] * sum(
            min(t + 1, TINY["index_topk"]) for t in range(start, start + n))
    # slot 1 stays inactive (all-trash table): it must disturb nothing
    tables = jnp.stack([table, jnp.zeros_like(table)])
    decode = jax.jit(m.decode_step)
    for t in range(prompt, 60):
        cache, logits, counted, _ = decode(
            p, cache, jnp.asarray([ids[t], 0]), jnp.asarray([t, 0]), tables,
            jnp.asarray([1, 0]))
        np.testing.assert_allclose(np.asarray(logits[0]), want[t], atol=1e-4)
        counted = dict(zip(prog.COUNTERS, np.asarray(counted)))
        assert counted["kv_rows_live"] == (t + 1) * TINY["n_layers"]
        assert counted["kv_rows_attended"] == \
            TINY["index_topk"] * TINY["n_layers"]
        assert counted["experts_touched"] <= \
            counted["expert_assignments_held"] <= 2 * 4


def test_served_through_decode_server_equals_reference(tiny):
    """The normal path: DecodeServer with pages of 2 and the chunk the
    engine derives from max_prompt_len (64 / 16 = 4: two pages), two slots,
    five requests (so slots are reused after release, over pages other
    requests wrote), prompts that end mid-page and mid-chunk. Every served
    token is the reference's pick at its position (float32: a gap above
    1e-4 would be a wrong row, not rounding)."""
    wl, w, tree = tiny
    server = DecodeServer(wl, tree, decode_slots=2, page_size=2,
                          max_prompt_len=64, max_len=POSITIONS)
    assert server.engine.chunked and server.engine.prefill_chunk == 4
    shapes = [(29, 9), (13, 14), (37, 6), (24, 11), (5, 1)]
    reqs = [server.submit(ids_of(n, seed=10 + i), g)
            for i, (n, g) in enumerate(shapes)]
    server.drain()
    assert all(r.finished and len(r.tokens) == g
               for r, (_, g) in zip(reqs, shapes))
    for r in reqs:
        seq = np.concatenate([r.prompt, np.asarray(r.tokens, np.int32)])
        gaps = ref.served_gaps(w, TINY, seq, r.prompt_len)
        assert gaps.shape == (len(r.tokens),) and gaps.max() <= 1e-4
    # nothing leaks, and the harness's counters mean what they did
    assert server.mgr.free_pages == server.mgr.capacity
    assert all(s is None for s in server.slots) and not server.busy
    assert server.prompt_tokens_prefilled == sum(n for n, _ in shapes)
    assert server.prefill_steps == sum(-(-n // 4) for n, _ in shapes)
    assert server.prefill_token_slots == 4 * server.prefill_steps
    assert server.tokens_fetched == sum(g for _, g in shapes)
    for program in ("prefill", "decode"):
        c = server.counted[program]
        assert set(c) == set(prog.COUNTERS)
        assert 0 < c["kv_rows_attended"] < c["kv_rows_live"]
        assert c["experts_touched"] <= c["expert_assignments_held"]


def test_engine_asks_for_a_capability_not_a_family():
    from distributed_pipeline_tpu.serving.engine import DecodeEngine
    wl = create_model_from_config(model_family="diffuseq", vocab_size=64,
                                  seq_len=16, hidden_size=32, num_layers=1,
                                  num_heads=2, dtype="float32")
    with pytest.raises(ValueError, match="paged cache"):
        DecodeEngine(wl, None, decode_slots=2, page_size=4, max_pages=9,
                     max_prompt_len=8)
    wl, _, tree = build(TINY)
    with pytest.raises(NotImplementedError, match="prefix cache"):
        DecodeServer(wl, tree, decode_slots=2, page_size=4,
                     max_prompt_len=16, max_len=32, prefix_cache=True)


# ------------------------------- (c) the discrete choices, compared as sets

def test_selected_rows_and_routed_experts_equal_the_reference(tiny):
    """The two discontinuities a logit tolerance could hide: for every
    token of a seeded sequence the indexer's selected positions and the
    router's experts are the reference's, in the prefill form (a mask) and
    in the decode form (``lax.top_k`` indices)."""
    wl, w, tree = tiny
    m, p = wl.model, tree["params"]
    ids = ids_of(64, seed=3)
    _, chosen = ref.make_logits_fn(TINY)("float32").hidden(
        w, jnp.asarray(ids))
    _, aux = jax.jit(lambda v, i: m.apply(v, i, collect=True))(
        tree, ids[None])
    for layer in range(TINY["n_layers"]):
        want = np.asarray(chosen["selected"][layer])
        got = np.asarray(aux["selected"][layer])[0][:64, :64]
        assert (got == want).all()
        assert want.sum(1).tolist() == [
            min(t + 1, TINY["index_topk"]) for t in range(64)]
        if chosen["experts"][layer] is not None:
            assert (np.sort(np.asarray(aux["experts"][layer])[0], -1)
                    == np.sort(np.asarray(chosen["experts"][layer]), -1)
                    ).all()
    # decode: prefill 40 rows in one chunk, then step by step
    ps, n_pages = 8, 8
    cache = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), m.cache_shapes(1 + n_pages, ps))
    table = jnp.arange(1, n_pages + 1, dtype=jnp.int32)
    cache, _, _ = jax.jit(m.prefill_chunk)(
        p, cache, jnp.asarray(ids[:40]), jnp.int32(0), jnp.int32(40), table)
    decode = jax.jit(lambda *a: m.decode_step(*a, collect=True))
    for t in range(40, 64):
        cache, _, _, aux = decode(p, cache, jnp.asarray(ids[t:t + 1]),
                                  jnp.asarray([t]), table[None],
                                  jnp.asarray([1]))
        for layer in range(TINY["n_layers"]):
            got = set(np.asarray(aux["selected"][layer])[0].tolist()) - {-1}
            want = set(np.nonzero(
                np.asarray(chosen["selected"][layer])[t])[0].tolist())
            assert got == want, (t, layer)
            if chosen["experts"][layer] is not None:
                assert set(np.asarray(aux["experts"][layer])[0].tolist()) \
                    == set(np.asarray(chosen["experts"][layer])[t].tolist())


# ------------------------------------------------------ (d) the share test

@pytest.mark.parametrize("decode", [False, True], ids=["chunk", "decode"])
def test_shares_add_up_to_the_uncut_layer(decode):
    """What ties the cut to the model: the routed parts of all 8 shares (4
    experts each) plus the shared expert counted ONCE equal the uncut
    reference's expert layer (all 32 experts held by one), in the form a
    prefill chunk runs and in the form a decode step runs."""
    whole = dict(TINY, n_routed_experts_held=32)
    w = jax.jit(lambda s: ref.make_weights(whole, s))(ref.seed_arg(11))
    lw = w["layer_1"]
    x = jax.random.normal(jax.random.PRNGKey(4), (40, TINY["hidden_size"]))
    f = ref.make_logits_fn(whole)("float32")
    ids_e, w_e = f.routed(x, lw["mlp_norm"], lw["router"], lw["router_bias"])
    want = f.dense(x, lw["mlp_norm"], lw["shared_gate"], lw["shared_up"],
                   lw["shared_down"])
    every = jnp.arange(40, dtype=jnp.int32)
    for e in range(32):
        want = f.expert(want, x, lw["mlp_norm"], every,
                        jnp.sum(jnp.where(ids_e == e, w_e, 0.0), -1),
                        lw["experts_gate"], lw["experts_up"],
                        lw["experts_down"], jnp.int32(e))
    want = np.asarray(want - x)
    h = prog.rms_norm(x, lw["mlp_norm"], 1e-6)
    live = jnp.ones((40,), bool)
    shared = None
    total = np.zeros_like(want)
    held_sum = 0
    for rank in range(8):
        cfg = dict(TINY, expert_offset=4 * rank)
        model = create_model_from_config(
            model_family="deepseek_v32", vocab_size=VOCAB, seq_len=POSITIONS,
            dtype="float32", arch=arch_of(cfg)).model
        lp = dict(lw, **{k: lw[k][4 * rank:4 * rank + 4] for k in (
            "experts_gate", "experts_up", "experts_down")})
        no_experts = dict(lp, router_bias=jnp.full((32,), 0.0).at[
            4 * rank:4 * rank + 4].set(-jnp.inf))
        y, stats, _ = jax.jit(
            lambda lp, h: model._ffn(lp, 1, h, live, decode=decode))(lp, h)
        if shared is None:
            # the shared expert alone: the same layer with this share's
            # experts made unreachable
            shared, none, _ = model._ffn(no_experts, 1, h, live,
                                         decode=decode)
            assert int(none[0]) == 0
        total += np.asarray(y - shared)
        held_sum += int(stats[0])
    assert held_sum == 40 * TINY["num_experts_per_tok"]   # dropless
    np.testing.assert_allclose(total + np.asarray(shared), want, atol=2e-5)


def test_hot_expert_takes_the_dense_branch_and_drops_nothing():
    """Every token routed to ONE held expert (far past its slice of rows):
    the layer computes all of them (no capacity)."""
    cfg = dict(TINY)
    wl, w, tree = build(cfg, seed=5)
    lw = dict(w["layer_1"])
    # bias that makes expert 0 (and 3 others of its group) everyone's pick
    lw["router_bias"] = jnp.zeros((32,)).at[0].set(10.0)
    x = jax.random.normal(jax.random.PRNGKey(6), (96, TINY["hidden_size"]))
    h = prog.rms_norm(x, lw["mlp_norm"], 1e-6)
    y, stats, ids_e = jax.jit(
        lambda lp, h: wl.model._ffn(lp, 1, h, jnp.ones((96,), bool)))(lw, h)
    assert (np.asarray(ids_e) == 0).any(1).all()
    f = ref.make_logits_fn(cfg)("float32")
    r_ids, r_w = f.routed(x, lw["mlp_norm"], lw["router"], lw["router_bias"])
    want = f.dense(x, lw["mlp_norm"], lw["shared_gate"], lw["shared_up"],
                   lw["shared_down"])
    for e in range(4):
        want = f.expert(want, x, lw["mlp_norm"],
                        jnp.arange(96, dtype=jnp.int32),
                        jnp.sum(jnp.where(r_ids == e, r_w, 0.0), -1),
                        lw["experts_gate"], lw["experts_up"],
                        lw["experts_down"], jnp.int32(e))
    assert int(stats[0]) >= 96
    np.testing.assert_allclose(np.asarray(y), np.asarray(want - x),
                               atol=2e-5)


# ------------------------------------------- (e) routing, worked by hand

@pytest.mark.parametrize("route", [
    lambda s, b: prog.route(prog.DeepseekV32Config.from_arch(dict(
        n_routed_experts=8, n_routed_experts_held=8, n_group=4,
        topk_group=2, num_experts_per_tok=3, routed_scaling_factor=2.5)),
        s, b),
    lambda s, b: ref.route({"n_group": 4, "topk_group": 2,
                            "num_experts_per_tok": 3,
                            "routed_scaling_factor": 2.5}, s, b)],
    ids=["program", "reference"])
def test_group_limited_routing_by_hand(route):
    """8 experts in 4 groups of 2, 2 groups kept, 3 experts taken.
    Scores + bias: groups (0.9, 0.1) (0.5, 0.45) (0.6, 0.3) (0.2, 0.95+):
    group sums 1.0, 0.95, 0.9, 1.25 -> groups 3 and 0 stay, though expert
    4's 0.6 beats expert 1; inside them 7 (1.05), 0 (0.9), 6 (0.2) are
    taken. Expert 7 wins only through its bias (+0.7): its WEIGHT is from
    its unbiased 0.35: weights (0.35, 0.9, 0.2) / 1.45 * 2.5."""
    scores = jnp.asarray([[0.9, 0.1, 0.5, 0.45, 0.6, 0.3, 0.2, 0.35]])
    bias = jnp.asarray([0, 0, 0, 0, 0, 0, 0, 0.7], jnp.float32)
    ids, w = route(scores, bias)
    assert np.asarray(ids)[0].tolist() == [7, 0, 6]
    np.testing.assert_allclose(
        np.asarray(w)[0], np.array([0.35, 0.9, 0.2]) / 1.45 * 2.5, rtol=1e-6)
    # without the bias group 3 (0.2 + 0.35) falls out: groups 0 and 1 stay
    ids, w = route(scores, jnp.zeros((8,)))
    assert np.asarray(ids)[0].tolist() == [0, 2, 3]
    np.testing.assert_allclose(np.asarray(w)[0].sum(), 2.5, rtol=1e-6)


def test_yarn_constants_of_the_source():
    """factor 40 over 4096 original positions, beta 32/1, rope dim 64: the
    correction range is dims 10..23 of 32, the first frequencies are not
    scaled, the last are divided by 40; mscale = 0.1 ln 40 + 1 enters the
    softmax scale squared."""
    cfg = prog.DeepseekV32Config()
    inv = prog.yarn_inv_freq(cfg)
    base = 1.0 / 10000.0 ** (np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv[:11], base[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], base[23:] / 40, rtol=1e-6)
    assert (inv[11:23] < base[11:23]).all() \
        and (inv[11:23] > base[11:23] / 40).all()
    assert abs(cfg.softmax_scale - 192 ** -0.5 * 1.3689 ** 2) < 1e-4
    published = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "deepseek-v3.2-exp-ep16.json")))
    np.testing.assert_allclose(ref.yarn_inv_freq(published), inv, rtol=1e-6)
    assert abs(ref.softmax_scale(published) - cfg.softmax_scale) < 1e-9


# ------------------------------------------------ the copies, the files

def test_the_two_reference_copies_give_the_same_logits(tiny):
    """benchmark/harness/ keeps its own copy (the benchmark imports nothing
    of the program); one test holds the two together."""
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    try:
        from harness import reference_deepseek_v32 as bench_ref
    finally:
        sys.path.pop(0)
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
        ROOT, "benchmark", "configs", "deepseek-v3.2-exp-ep16.json")))
    # one chip's share: 5.587 B parameters, 11.17 GB in bfloat16
    assert ref.param_count(cfg) == 5_587_117_824
    assert cfg["n_routed_experts"] == 256 and cfg["num_experts_per_tok"] == 8
    assert cfg["n_layers"] - cfg["first_k_dense_replace"] >= 4
    assert cfg["n_routed_experts_held"] >= 8
    assert cfg["vocab_size"] * 8 >= cfg["published"]["vocab_size"]
    for key in cfg["reduced"]:
        assert key in cfg and key in cfg["reduced_note"]
    assert cfg["published"]["num_hidden_layers"] == 61
    for key in ("assumed", "deployment"):
        assert cfg[key]


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
        json.dump({"model_family": "deepseek_v32", "vocab_size": VOCAB,
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


def test_block_attend_kernel_equals_its_xla_arm():
    """ops/mla_attention.py in interpret mode against the jax.numpy update
    it replaces, two blocks in sequence (the carry goes through), at the
    published head widths; then the whole prefill chunk with the kernel in
    the attention's place, at a tile-aligned tiny size."""
    from distributed_pipeline_tpu.ops import mla_attention as ma
    h, dq, dv, c, k = 2, 192, 128, 256, 256
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q_t = jax.random.normal(keys[0], (h, dq, c)).astype(jnp.bfloat16)
    kk = jax.random.normal(keys[1], (2, h, k, dq)).astype(jnp.bfloat16)
    v_t = jax.random.normal(keys[2], (2, h, dv, k)).astype(jnp.bfloat16)
    bias = jnp.where(jax.random.uniform(keys[3], (2, k, c)) < 0.4, 0.0,
                     ma.NEG)
    a = b = (jnp.full((h, 1, c), ma.NEG), jnp.zeros((h, 1, c)),
             jnp.zeros((h, dv, c)))
    for i in range(2):
        a = ma.block_attend_xla(q_t, kk[i], v_t[i], bias[i], a, scale=0.07)
        b = ma.block_attend(q_t, kk[i], v_t[i], bias[i], b, scale=0.07,
                            block_q=128, block_k=128, interpret=True)
    out_a, out_b = a[2] / a[1], b[2] / b[1]
    # bfloat16 probabilities against a running maximum that differs by
    # tile order: 2e-2 on outputs of order 1 (float32 would read 1e-6)
    np.testing.assert_allclose(np.asarray(out_a), np.asarray(out_b),
                               atol=2e-2)
    import dataclasses
    wl, w, tree = build(TINY)
    ids = ids_of(128, seed=9)
    ps, n_pages = 16, 8
    outs = []
    for impl in ("xla", "interpret"):
        m = dataclasses.replace(wl.model, kernel_impl=impl)
        cache = jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype),
            m.cache_shapes(1 + n_pages, ps))
        outs.append(np.asarray(jax.jit(m.prefill_chunk)(
            tree["params"], cache, jnp.asarray(ids), jnp.int32(0),
            jnp.int32(128), jnp.arange(1, n_pages + 1, dtype=jnp.int32))[1]))
    np.testing.assert_allclose(outs[0], outs[1], atol=1e-4)
