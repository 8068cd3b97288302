"""Speculative decoding + int8 paged-KV suite (ISSUE 20).

Covers the whole tentpole surface: DecodeServer-level token identity of
the speculative path against the plain decode loop (greedy AND
stochastic, ngram AND early-exit model drafts, both ``decode_impl``
arms, mixed prompt/budget mixes, steady-state recompiles frozen at 0),
rejection/overshoot bookkeeping (no page or slot leaks, exact
positions, ``eos_id`` honored inside an accepted prefix), the span K/V
writers' bit-parity with sequential single-token writes plus the
budget-final overshoot clamp contract, the int8 page-pool's byte ratio
/ slot-doubling / quantization-error bounds, the serving-weight
round-trip guard, and the ``auto`` defaults flipped by this issue
(``--decode_impl``, ``--fused_update``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_pipeline_tpu.models import create_model_from_config
from distributed_pipeline_tpu.ops.flash_decode import xla_paged_span_decode
from distributed_pipeline_tpu.ops.fused_update import resolve_fused_update
from distributed_pipeline_tpu.serving import TRASH_PAGE, DecodeServer
from distributed_pipeline_tpu.serving.paged_kv import (
    Q8_MAX,
    dequant_gathered,
    gather_kv,
    write_prompt_kv,
    write_prompt_kv_q8,
    write_span_kv,
    write_span_kv_q8,
    write_token_kv,
)
from distributed_pipeline_tpu.serving.quantize import (
    QuantizationError,
    quantize_params,
)
from distributed_pipeline_tpu.serving.spec import ngram_propose

VOCAB, SEQ = 32, 16


@pytest.fixture(scope="module")
def wl_and_params():
    wl = create_model_from_config(
        model_family="gpt2", vocab_size=VOCAB, seq_len=SEQ, hidden_size=32,
        num_layers=2, num_heads=2, dtype="float32")
    return wl, wl.init_params(jax.random.PRNGKey(3))


def mixed_workload(n=10, seed=7):
    """Mixed-length prompts and budgets — slots churn through several
    admission generations so rollback interleaves with refill."""
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(4, VOCAB, (1 + i % 6,)).astype(np.int32)
               for i in range(n)]
    budgets = [2 + i % 7 for i in range(n)]
    return prompts, budgets


def serve(wl, params, prompts, budgets, eos_id=None, **kw):
    """Run a workload to completion and assert the post-drain invariants
    every configuration owes: all slots free, every pool page back in the
    allocator, every block-table row fully trash-routed."""
    cfg = dict(decode_slots=2, page_size=4, max_prompt_len=8, max_len=SEQ,
               seed=0, sanitize=True)
    cfg.update(kw)
    srv = DecodeServer(wl, params, **cfg)
    reqs = [srv.submit(p, b, eos_id=eos_id)
            for p, b in zip(prompts, budgets)]
    srv.drain()
    assert srv.free_slots == cfg["decode_slots"]
    assert srv.mgr.free_pages == srv.mgr.capacity
    assert np.all(srv.block_tables == TRASH_PAGE)
    return [list(r.tokens) for r in reqs], srv


# ------------------------------------------- token identity (tentpole a)


@pytest.fixture(scope="module")
def base_tokens(wl_and_params):
    """The non-speculative greedy stream every identity test compares
    against. decode_impl='auto' resolves to the XLA arm off-TPU, and the
    pallas arm is token-identical to it (test_kernels.py), so ONE base
    run serves both arms — one compile instead of one per test."""
    wl, params = wl_and_params
    prompts, budgets = mixed_workload()
    return serve(wl, params, prompts, budgets)[0]


@pytest.mark.parametrize("impl,k", [("xla", 1), ("xla", 2), ("pallas", 2)])
def test_spec_greedy_token_identical_both_arms(wl_and_params, base_tokens,
                                               impl, k):
    """Greedy speculative decode is token-for-token the non-speculative
    stream on BOTH decode_impl arms — acceptance is exact-match so a
    correct verify can never change the stream. (Deeper drafts K=3,5 ride
    the rejection-bookkeeping test.)"""
    wl, params = wl_and_params
    prompts, budgets = mixed_workload()
    got, srv = serve(wl, params, prompts, budgets, decode_impl=impl,
                     spec_tokens=k)
    assert got == base_tokens, f"impl={impl} K={k} diverged"
    assert srv.accept_rate >= 0.0  # gauge exists and is populated


def test_spec_model_draft_and_stochastic_identical(wl_and_params,
                                                   base_tokens):
    """The early-exit model draft and the stochastic sampler keep the
    identity too: the pick fold is per (slot, position), so WHAT proposed
    a token never reaches the accepted stream."""
    wl, params = wl_and_params
    prompts, budgets = mixed_workload()
    got, _ = serve(wl, params, prompts, budgets,
                   spec_tokens=2, spec_draft="model", draft_layers=1)
    assert got == base_tokens
    base_s, _ = serve(wl, params, prompts, budgets, temperature=0.8)
    got_s, _ = serve(wl, params, prompts, budgets, temperature=0.8,
                     spec_tokens=3)
    assert got_s == base_s


def test_spec_steady_state_recompiles_frozen(wl_and_params):
    """After the warmup request the speculative loop must never recompile:
    verify is one pinned-signature AOT executable, and slot churn /
    rejection depth only change VALUES, not shapes."""
    wl, params = wl_and_params
    prompts, budgets = mixed_workload()
    for impl in ("xla", "pallas"):
        srv = DecodeServer(wl, params, decode_slots=2, page_size=4,
                           max_prompt_len=8, max_len=SEQ, seed=0,
                           sanitize=True, decode_impl=impl, spec_tokens=2)
        srv.submit(prompts[0], budgets[0])
        srv.drain()
        warm = srv.recompile_count
        for p, b in zip(prompts[1:], budgets[1:]):
            srv.submit(p, b)
        srv.drain()
        assert srv.recompile_count == warm, \
            f"{impl} spec loop recompiled in steady state"


# ---------------------------- rejection / overshoot bookkeeping (sat 3)


def test_spec_rejection_bookkeeping_exact_positions(wl_and_params,
                                                    base_tokens):
    """Every request ends with EXACTLY its budget (or its eos truncation)
    regardless of how many draft links were rejected, and the drained
    server leaks nothing — rejected links only ever wrote rows past the
    live position inside pages reserved at admission."""
    wl, params = wl_and_params
    prompts, budgets = mixed_workload()
    for k in (3, 5):
        got, srv = serve(wl, params, prompts, budgets, spec_tokens=k)
        for toks, b in zip(got, budgets):
            assert len(toks) == b, "budget overshoot survived rollback"
        assert got == base_tokens
        # the walk really did reject: with K=5 on a tiny model some
        # proposals must miss, so accepted < proposed
        if k == 5:
            assert srv.accept_rate < 1.0


def test_spec_eos_honored_inside_accepted_prefix(wl_and_params,
                                                 base_tokens):
    """An eos_id landing INSIDE an accepted chain truncates the request
    right there — later links of the same verified span are discarded,
    matching the sequential stream's truncation point exactly."""
    wl, params = wl_and_params
    prompts, budgets = mixed_workload()
    # pick a token the greedy stream emits mid-request so eos truncation
    # actually triggers inside a span, not at a round boundary
    eos = next(t[1] for t in base_tokens if len(t) >= 3)
    base_e, _ = serve(wl, params, prompts, budgets, eos_id=eos)
    got_e, srv = serve(wl, params, prompts, budgets, eos_id=eos,
                       spec_tokens=3)
    assert got_e == base_e
    for toks in got_e:
        if eos in toks:
            assert toks.index(eos) == len(toks) - 1, \
                "tokens fetched past eos"


# ------------------------------------------------ span writers (tentpole)


def test_write_span_kv_matches_sequential_token_writes():
    """A span scatter is bitwise the L single-token scatters it replaces
    whenever no link overshoots — the identity the parallel verify leans
    on."""
    rng = np.random.default_rng(2)
    B, H, L, Dh, ps = 3, 2, 4, 8, 4
    pool = jnp.asarray(rng.standard_normal((1 + 3 * B, ps, H * Dh)),
                       jnp.float32)
    table = jnp.asarray(1 + np.arange(3 * B).reshape(B, 3), jnp.int32)
    kv = jnp.asarray(rng.standard_normal((B, H, L, Dh)), jnp.float32)
    start = jnp.asarray([0, 3, 7], jnp.int32)
    span = write_span_kv(pool, table, kv, start)
    seq = pool
    for j in range(L):
        seq = write_token_kv(seq, table, kv[:, :, j], start + j)
    np.testing.assert_array_equal(np.asarray(span), np.asarray(seq))


def test_write_span_kv_overshoot_clamps_not_wraps():
    """Budget-final overshoot: positions past the slot's reservation clamp
    to the LAST addressable cell instead of wrapping into live cells —
    ``pos // ps`` would clamp to the last table column while ``pos % ps``
    re-enters at offset 0, corrupting a live row."""
    rng = np.random.default_rng(3)
    H, Dh, ps = 2, 4, 4
    pool = jnp.asarray(rng.standard_normal((3, ps, H * Dh)), jnp.float32)
    table = jnp.asarray([[1, 2]], jnp.int32)       # addressable = 8
    kv = jnp.asarray(rng.standard_normal((1, H, 3, Dh)), jnp.float32)
    out = np.asarray(write_span_kv(pool, table, kv, jnp.asarray([7])))
    # positions 7, 8, 9 -> cells 7, 7, 7: last link wins the clamped cell
    np.testing.assert_array_equal(out[2, 3],
                                  np.asarray(kv[0, :, 2]).reshape(-1))
    # every other cell — notably page 2 offset 0, the wrap target — is
    # bitwise untouched
    ref = np.asarray(pool).copy()
    ref[2, 3] = np.asarray(kv[0, :, 2]).reshape(-1)
    np.testing.assert_array_equal(out, ref)


def test_write_span_kv_q8_bounded_and_leaves_cold_pages_alone():
    """The int8 span writer keeps the per-page quantization contract:
    dequantized rows land within scale/2 of the fp rows, scales only ever
    grow, and pages the span never touches stay bitwise identical."""
    rng = np.random.default_rng(4)
    B, H, L, Dh, ps = 2, 2, 3, 8, 4
    P = 1 + 2 * B
    pool = jnp.zeros((P, ps, H * Dh), jnp.int8)
    scales = jnp.zeros((P,), jnp.float32)
    table = jnp.asarray(1 + np.arange(2 * B).reshape(B, 2), jnp.int32)
    warm = jnp.asarray(rng.standard_normal((B, H, ps, Dh)), jnp.float32)
    valid = jnp.ones((B, ps), jnp.int32)
    pool, scales = write_prompt_kv_q8(pool, scales, table, warm, valid)
    # spans at start 4/5 land in each slot's SECOND page (2 and 4 here);
    # the prompt pages' scales don't grow, so the re-expression ratio is
    # exactly 1.0 and their int8 content must survive bitwise
    cold = np.asarray(pool[jnp.asarray([1, 3])]).copy()
    kv = jnp.asarray(4.0 * rng.standard_normal((B, H, L, Dh)), jnp.float32)
    out, s2 = write_span_kv_q8(pool, scales, table, kv,
                               jnp.asarray([4, 5], jnp.int32))
    assert np.all(np.asarray(s2) >= np.asarray(scales) - 1e-7)
    np.testing.assert_array_equal(np.asarray(out[jnp.asarray([1, 3])]),
                                  cold)
    dense = dequant_gathered(gather_kv(out, table, H), s2, table, ps,
                             jnp.float32)
    d = np.asarray(dense)
    sc = np.asarray(s2)[np.asarray(table)]         # [B, n_pages]
    for b in range(B):
        for j in range(L):
            pos = [4, 5][b] + j
            err = np.max(np.abs(d[b, :, pos] - np.asarray(kv[b, :, j])))
            assert err <= sc[b, pos // ps] / 2 + 1e-6


# ------------------------------------------- int8 pool economics (tentpole)


def test_int8_pool_bytes_and_slot_doubling(wl_and_params):
    """The page-pool ledger: int8 pages + fp32 scale sidecars land at
    <= 0.55x the fp pool at equal geometry, so DOUBLE the decode slots
    still fit the fp budget — and the doubled server actually serves."""
    wl, params = wl_and_params
    prompts, budgets = mixed_workload()
    fp = DecodeServer(wl, params, decode_slots=2, page_size=4,
                      max_prompt_len=8, max_len=SEQ, seed=0)
    q8 = DecodeServer(wl, params, decode_slots=2, page_size=4,
                      max_prompt_len=8, max_len=SEQ, seed=0,
                      kv_quant="int8")
    assert q8.engine.kv_pool_bytes() <= 0.55 * fp.engine.kv_pool_bytes()
    got, dbl = serve(wl, params, prompts, budgets, decode_slots=4,
                     kv_quant="int8")
    assert dbl.engine.kv_pool_bytes() <= fp.engine.kv_pool_bytes()
    assert all(len(t) == b for t, b in zip(got, budgets))


def test_int8_prompt_roundtrip_error_within_page_scale():
    """Prefill SET semantics: each touched page's dequantized content is
    within scale/2 = amax/(2*127) of the fp rows elementwise — the
    documented divergence floor everything downstream inherits."""
    rng = np.random.default_rng(5)
    B, H, Dh, ps = 2, 2, 8, 4
    pool = jnp.zeros((1 + 2 * B, ps, H * Dh), jnp.int8)
    scales = jnp.zeros((1 + 2 * B,), jnp.float32)
    table = jnp.asarray(1 + np.arange(2 * B).reshape(B, 2), jnp.int32)
    kv = jnp.asarray(rng.standard_normal((B, H, 2 * ps, Dh)), jnp.float32)
    valid = jnp.ones((B, 2 * ps), jnp.int32)
    pool, scales = write_prompt_kv_q8(pool, scales, table, kv, valid)
    dense = np.asarray(dequant_gathered(gather_kv(pool, table, H), scales,
                                        table, ps, jnp.float32))
    sc = np.asarray(scales)[np.asarray(table)]
    for b in range(B):
        for pg in range(2):
            rows = slice(pg * ps, (pg + 1) * ps)
            err = np.max(np.abs(dense[b, :, rows]
                                - np.asarray(kv[b, :, rows])))
            assert err <= sc[b, pg] / 2 + 1e-6, (b, pg, err)


def test_int8_span_attention_divergence_bounded():
    """End-to-end through the verify seam: span attention over the int8
    pool stays within a small absolute envelope of the fp pool — softmax
    averaging keeps output error at the order of the KV element error."""
    rng = np.random.default_rng(6)
    B, H, L, Dh, ps, n = 2, 2, 2, 8, 4, 3
    P = 1 + n * B
    fp_pool = jnp.zeros((P, ps, H * Dh), jnp.float32)
    q_pool = jnp.zeros((P, ps, H * Dh), jnp.int8)
    scales = jnp.zeros((P,), jnp.float32)
    table = jnp.asarray(1 + np.arange(n * B).reshape(B, n), jnp.int32)
    kv = jnp.asarray(rng.standard_normal((B, H, n * ps, Dh)), jnp.float32)
    valid = jnp.ones((B, n * ps), jnp.int32)
    fp_k = write_prompt_kv(fp_pool, table, kv, valid)
    fp_v = write_prompt_kv(fp_pool, table, 0.5 * kv, valid)
    q_k, s_k = write_prompt_kv_q8(q_pool, scales, table, kv, valid)
    q_v, s_v = write_prompt_kv_q8(q_pool, scales, table, 0.5 * kv, valid)
    q = jnp.asarray(rng.standard_normal((B, H, L, Dh)), jnp.float32)
    pos = jnp.asarray([[6, 7], [9, 10]], jnp.int32)
    ref = xla_paged_span_decode(q, fp_k, fp_v, table, pos)
    got = xla_paged_span_decode(q, q_k, q_v, table, pos,
                                scales_k=s_k, scales_v=s_v)
    assert float(jnp.max(jnp.abs(got - ref))) < 0.05


# -------------------------------------- serving-weight guard (tentpole c)


def test_quantize_params_roundtrip_and_nonfinite_guard():
    """Replica weight quantization: float leaves round-trip within the
    rel-err guard, int leaves ship verbatim, and a non-finite leaf aborts
    the swap loudly instead of serving garbage."""
    tree = {"w": jnp.asarray(np.random.default_rng(0).standard_normal(
        (8, 16)), jnp.float32), "idx": jnp.arange(4, dtype=jnp.int32)}
    out = quantize_params(tree)
    assert out["idx"] is tree["idx"]
    rel = float(jnp.max(jnp.abs(out["w"] - tree["w"]))
                / jnp.max(jnp.abs(tree["w"])))
    assert rel <= 0.02
    bad = {"w": jnp.asarray([[1.0, np.inf]], jnp.float32)}
    with pytest.raises(QuantizationError):
        quantize_params(bad)


# ---------------------------------------------- drafts / defaults (sat 2)


def test_ngram_propose_prompt_lookup_and_fallback():
    """Longest-suffix prompt lookup: a repeated bigram proposes its
    historical continuation; an unseen suffix repeats the current token."""
    hist = np.asarray([5, 6, 7, 8, 2, 3, 5, 6], np.int32)
    np.testing.assert_array_equal(ngram_propose(hist, 3), [7, 8, 2])
    np.testing.assert_array_equal(ngram_propose(
        np.asarray([1, 2, 9], np.int32), 2), [9, 9])


def test_auto_defaults():
    """ISSUE 20 flipped --decode_impl and --fused_update to 'auto'."""
    from distributed_pipeline_tpu.config.serve import ServeSettings
    from distributed_pipeline_tpu.config.train import TrainSettings

    assert ServeSettings.model_fields["decode_impl"].default == "auto"
    assert TrainSettings.model_fields["fused_update"].default == "auto"


def test_resolve_fused_update_tristate():
    assert resolve_fused_update(True) is True
    assert resolve_fused_update("false") is False
    # this suite runs under JAX_PLATFORMS=cpu: auto resolves to staged
    assert resolve_fused_update("auto") is (jax.default_backend() == "tpu")
    with pytest.raises(ValueError):
        resolve_fused_update("pallas")
