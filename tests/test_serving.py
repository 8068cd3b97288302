"""Serving-subsystem tests: paged-KV bit-identity against the dense cache,
page-allocator and scheduler invariants (no slot/page leaks, bounded
completion, late arrivals preempt nothing), DecodeServer CPU smoke with the
sanitizer's compile-exactly-once contract, and the run.sample / run.serve
entry wiring (ISSUE 7)."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_pipeline_tpu.data import load_data_from_args
from distributed_pipeline_tpu.models import create_model_from_config
from distributed_pipeline_tpu.models.sampling import gpt2_decode
from distributed_pipeline_tpu.serving import (
    TRASH_PAGE,
    DecodeServer,
    PageManager,
    gather_kv,
    one_shot_decode,
    write_prompt_kv,
    write_token_kv,
)

VOCAB = 32
SEQ = 16


def tiny_workload(**kw):
    cfg = dict(model_family="gpt2", vocab_size=VOCAB, seq_len=SEQ,
               hidden_size=32, num_layers=2, num_heads=2, dtype="float32")
    cfg.update(kw)
    return create_model_from_config(**cfg)


@pytest.fixture(scope="module")
def wl_and_params():
    wl = tiny_workload()
    return wl, wl.init_params(jax.random.PRNGKey(3))


def prompt_ids(batch=4, seed=0):
    return np.random.default_rng(seed).integers(
        4, VOCAB, (batch, SEQ)).astype(np.int32)


# ------------------------------------------------------------ paged_kv ops

def test_paged_write_gather_roundtrips_dense():
    """Pages + block table must reproduce the dense [B, H, L, Dh] layout
    bitwise: prompt scatter, per-slot token scatter, then gather."""
    rng = np.random.default_rng(1)
    B, H, L, Dh, ps = 3, 2, 8, 4, 2
    n_pages_per_slot = L // ps
    pages = jnp.zeros((1 + B * n_pages_per_slot, ps, H * Dh), jnp.float32)
    table = jnp.asarray(
        1 + np.arange(B * n_pages_per_slot).reshape(B, n_pages_per_slot),
        jnp.int32)
    kv = jnp.asarray(rng.standard_normal((B, H, L, Dh)), jnp.float32)
    lens = np.asarray([3, 8, 5])
    valid = jnp.asarray((np.arange(L)[None, :] < lens[:, None]).astype(
        np.int32))
    pages = write_prompt_kv(pages, table, kv, valid)
    # per-slot single-token writes at each slot's own position
    tok = jnp.asarray(rng.standard_normal((B, H, Dh)), jnp.float32)
    pos = jnp.asarray(lens, jnp.int32)  # append right after each prompt
    pages = write_token_kv(pages, table, tok, jnp.minimum(pos, L - 1))
    dense = np.asarray(gather_kv(pages, table, H))  # [B, H, L, Dh]
    ref = np.asarray(kv).copy()
    for b, n in enumerate(lens):
        ref[b, :, n:] = 0.0                      # invalid prompt tail unwritten
        ref[b, :, min(n, L - 1)] = np.asarray(tok)[b]
    np.testing.assert_array_equal(dense, ref)


def test_paged_invalid_writes_go_to_trash():
    B, H, L, Dh, ps = 2, 1, 4, 2, 2
    pages = jnp.zeros((1 + B * 2, ps, H * Dh), jnp.float32)
    table = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    kv = jnp.ones((B, H, L, Dh), jnp.float32)
    pages = write_prompt_kv(pages, table, kv, jnp.zeros((B, L), jnp.int32))
    # nothing valid: every real page stays zero (writes landed on page 0)
    assert float(jnp.abs(pages[1:]).sum()) == 0.0
    assert TRASH_PAGE == 0


def test_page_manager_invariants():
    mgr = PageManager(num_pages=6, page_size=4)
    assert mgr.capacity == 5 and mgr.free_pages == 5
    assert mgr.pages_for(1) == 1 and mgr.pages_for(4) == 1
    assert mgr.pages_for(5) == 2
    a = mgr.alloc(3)
    assert a is not None and TRASH_PAGE not in a.tolist()
    assert mgr.alloc(3) is None          # all-or-nothing
    b = mgr.alloc(2)
    assert mgr.free_pages == 0
    mgr.free(a)
    assert mgr.free_pages == 3
    with pytest.raises(ValueError):      # double free
        mgr.free(a)
    mgr.free(b)
    assert mgr.free_pages == 5
    with pytest.raises(ValueError):
        PageManager(num_pages=1, page_size=4)


# ------------------------------------------- paged vs dense bit-identity

def test_one_shot_decode_matches_gpt2_decode_greedy(wl_and_params):
    """The serving path (prefill/decode split + paged cache) must reproduce
    the monolithic dense-cache greedy decode token for token."""
    wl, params = wl_and_params
    ids = prompt_ids()
    jids = jnp.asarray(ids)
    for plen in (1, SEQ // 2, SEQ - 2):
        ref = np.asarray(gpt2_decode(wl, params, jids, plen, use_cache=True))
        got = one_shot_decode(wl, params, ids, plen, page_size=4)
        np.testing.assert_array_equal(ref, got, err_msg=f"plen={plen}")


def test_paged_geometry_is_bit_identical(wl_and_params):
    """Small pages vs a single max_len page: same padded KV length, so the
    outputs must match bitwise — greedy AND stochastic (same per-position
    fold_in), proving the paging indirection changes nothing numerically."""
    wl, params = wl_and_params
    ids = prompt_ids(seed=2)
    plen = SEQ // 2
    g2 = one_shot_decode(wl, params, ids, plen, page_size=2)
    g1 = one_shot_decode(wl, params, ids, plen, page_size=SEQ)
    np.testing.assert_array_equal(g2, g1)
    # same SEED, separately constructed keys (not one key object consumed
    # twice — graftlint GL001): identical sampling streams by construction
    s2 = one_shot_decode(wl, params, ids, plen, temperature=1.0,
                         rng=jax.random.PRNGKey(7), page_size=2)
    s1 = one_shot_decode(wl, params, ids, plen, temperature=1.0,
                         rng=jax.random.PRNGKey(7), page_size=SEQ)
    np.testing.assert_array_equal(s2, s1)
    assert not np.array_equal(s2, g2)  # temperature actually sampled


@pytest.mark.parametrize("kv_quant", ["fp", "int8"])
@pytest.mark.parametrize("heads,head_dim", [(4, 8), (5, 64)],
                         ids=["H4xDh8", "H5xDh64"])
def test_merged_head_axis_pool_serves_identically(heads, head_dim, kv_quant):
    """The pool is stored ``[pages, page_size, H * Dh]``; at head shapes
    the merged axis changes, (1) the engine accounts for exactly the bytes
    the geometry says, (2) tokens served through the paged pool are the
    dense-cache decode's, and (3) pages extracted from one engine and
    ingested by another reproduce the donor's tokens, the payload being
    rows of the stored shape.

    What "the dense decode's" can mean on the CPU backend: in float32,
    every token. In bfloat16 the dense loop and the served programs are
    different XLA:CPU programs and a near-tie argmax can flip after a few
    tokens (seen on the 4-D pool too: not the layout's), so a bf16 pool is
    held to the dense decode on the first token and, token for token, to
    the same pool with ONE page a slot — paging and the head merge then
    change nothing. An int8 pool keeps the prefill's logits, so the first
    token; its later ones carry the documented divergence, bounded at the
    seam in test_kernels."""
    from distributed_pipeline_tpu.mpmd.disagg import PrefillClient
    from distributed_pipeline_tpu.serving.engine import DecodeEngine

    def build(dtype):
        w = tiny_workload(hidden_size=heads * head_dim, num_heads=heads,
                          dtype=dtype)
        return w, w.init_params(jax.random.PRNGKey(3))

    wl, params = build("bfloat16")
    quant = kv_quant == "int8"
    ps, layers = 4, 2

    def server(page_size=ps, **kw):
        return DecodeServer(wl, params, seed=0, kv_quant=kv_quant,
                            page_size=page_size, **kw)

    # (1) bytes: K and V pools a layer, plus an int8 pool's [P] sidecars
    served = dict(decode_slots=4, max_prompt_len=8, max_len=SEQ)
    srv = server(**served)
    pages = srv.mgr.num_pages
    assert srv.engine.kv_pool_bytes() == 2 * layers * (
        pages * ps * heads * head_dim * (1 if quant else 2)
        + (pages * 4 if quant else 0))
    pools = [leaf for _, leaf in srv.engine._pool_leaves() if leaf.ndim > 1]
    assert {leaf.shape for leaf in pools} == {(pages, ps, heads * head_dim)}

    # (2) paged against dense, at the same padded length
    ids = prompt_ids(batch=3, seed=4)
    plen = SEQ // 2
    whole = dict(decode_slots=3, max_prompt_len=SEQ, max_len=SEQ,
                 prefill_batch=3)
    dense = np.asarray(gpt2_decode(wl, params, jnp.asarray(ids), plen,
                                   use_cache=True))
    paged = one_shot_decode(wl, params, ids, plen, server=server(**whole))
    np.testing.assert_array_equal(paged[:, :plen + 1], dense[:, :plen + 1])
    if not quant:
        one_page = one_shot_decode(wl, params, ids, plen, server=server(
            page_size=SEQ, **whole))
        np.testing.assert_array_equal(paged, one_page)
        wl32, params32 = build("float32")
        np.testing.assert_array_equal(
            one_shot_decode(wl32, params32, ids, plen, page_size=ps),
            np.asarray(gpt2_decode(wl32, params32, jnp.asarray(ids), plen,
                                   use_cache=True)))

    # (3) donor engine -> wire -> a second server. A slot a request and
    # fresh pools on both sides: an int8 decode write into a reserved page
    # takes max(the page's leftover scale, its own), so int8 tokens follow
    # a pool's history (PERF.md section 7), which this case keeps equal
    rng = np.random.default_rng(5)
    pairs = [(rng.integers(4, VOCAB, (1 + i % 6,)).astype(np.int32),
              2 + i % 4) for i in range(4)]
    reqs = [srv.submit(p, max_new_tokens=m) for p, m in pairs]
    srv.drain()
    donor = PrefillClient(wl, params, page_size=ps, max_prompt_len=8,
                          max_len=SEQ)
    e = donor.engine                       # the client's own is an fp engine
    donor.engine = DecodeEngine(
        wl, params, decode_slots=1, page_size=ps, max_pages=e.max_pages,
        max_prompt_len=8, max_len=SEQ, prefill_batch=1, kv_quant=kv_quant)
    recv = server(**served)
    got = []
    for prompt, budget in pairs:
        out = donor.prefill(prompt)
        assert {rows.shape[1:] for rows in out["kv"].values()
                if rows.ndim > 1} == {(ps, heads * head_dim)}
        got.append(recv.submit_prefilled(
            prompt, budget, first_token=out["first_token"],
            kv_pages=out["kv"]))
    recv.drain()
    assert [r.tokens for r in got] == [r.tokens for r in reqs]
    assert recv.mgr.free_pages == recv.mgr.capacity


def test_decode_span_is_equivalent(wl_and_params):
    """Multi-token decode dispatch (decode_span > 1: a lax.scan of steps
    inside one executable) must produce the same greedy tokens as
    step-per-dispatch serving, waste nothing visible (overshoot rows are
    discarded at fetch), and leak no slots/pages."""
    wl, params = wl_and_params
    rng = np.random.default_rng(5)
    prompts = [rng.integers(4, VOCAB, (1 + i % 6,)).astype(np.int32)
               for i in range(5)]
    outs = {}
    for span in (1, 3):
        srv = DecodeServer(wl, params, decode_slots=2, page_size=4,
                           max_prompt_len=8, max_len=SEQ, decode_span=span,
                           seed=0)
        reqs = [srv.submit(p, max_new_tokens=2 + i % 4)
                for i, p in enumerate(prompts)]
        srv.drain()
        outs[span] = [r.tokens for r in reqs]
        assert all(len(r.tokens) == min(r.max_new_tokens,
                                        SEQ - r.prompt_len) for r in reqs)
        assert srv.free_slots == 2
        assert srv.mgr.free_pages == srv.mgr.capacity
    assert outs[1] == outs[3]


# ------------------------------------------------- scheduler invariants

def make_server(wl, params, **kw):
    cfg = dict(decode_slots=2, page_size=4, max_prompt_len=8, max_len=SEQ,
               seed=0)
    cfg.update(kw)
    return DecodeServer(wl, params, **cfg)


def test_server_completes_all_and_leaks_nothing(wl_and_params):
    """More requests than slots, mixed lengths: every request finishes with
    exactly its budget, and afterwards every slot and every page is free."""
    wl, params = wl_and_params
    srv = make_server(wl, params)
    rng = np.random.default_rng(3)
    reqs = []
    for i in range(5):
        plen = int(rng.integers(1, 8))
        reqs.append(srv.submit(rng.integers(4, VOCAB, (plen,)).astype(
            np.int32), max_new_tokens=2 + i % 3))
    srv.drain()
    for r in reqs:
        g_max = min(r.max_new_tokens, SEQ - r.prompt_len)
        assert r.finished and len(r.tokens) == g_max, (r.id, r.tokens)
        assert r.ttft_s is not None and r.ttft_s >= 0.0
    assert srv.free_slots == 2
    assert srv.mgr.free_pages == srv.mgr.capacity
    assert (srv.block_tables == TRASH_PAGE).all()
    assert not srv.busy
    # bounded completion: one token per active slot per step, 2 slots ->
    # total decode steps can't exceed the total token budget
    total = sum(min(r.max_new_tokens, SEQ - r.prompt_len) for r in reqs)
    assert srv.decode_steps <= total


def test_page_pool_pressure_serializes_without_deadlock(wl_and_params):
    """A pool that fits only one request at a time admits head-of-line and
    completes everyone — reservation-at-admission means no mid-flight
    stranding, pool exhaustion just queues."""
    wl, params = wl_and_params
    # each request needs pages_for(4 + 4) = 2 pages; pool holds exactly 2
    srv = make_server(wl, params, decode_slots=4, max_pages=3)
    reqs = [srv.submit(np.arange(4, 8, dtype=np.int32), max_new_tokens=4)
            for _ in range(3)]
    srv.drain()
    assert all(len(r.tokens) == 4 for r in reqs)
    assert srv.mgr.free_pages == srv.mgr.capacity
    with pytest.raises(ValueError, match="pages"):
        srv.submit(np.arange(4, 12, dtype=np.int32), max_new_tokens=16)


def test_late_arrival_preempts_nothing(wl_and_params):
    """A request admitted mid-run must not change an in-flight request's
    output (greedy: token for token) — slots/pages only ever move from the
    free pool, never from a running request."""
    wl, params = wl_and_params
    p1 = np.arange(4, 10, dtype=np.int32)
    p2 = np.asarray([5, 9, 13, 17], np.int32)

    alone = make_server(wl, params)
    r_alone = alone.submit(p1, max_new_tokens=6)
    alone.drain()

    srv = make_server(wl, params)
    r1 = srv.submit(p1, max_new_tokens=6)
    srv.step()
    srv.step()
    r2 = srv.submit(p2, max_new_tokens=3)  # arrives while r1 decodes
    srv.drain()
    assert r1.tokens == r_alone.tokens
    assert len(r2.tokens) == 3
    assert srv.free_slots == 2 and srv.mgr.free_pages == srv.mgr.capacity


def test_prefix_cache_is_bit_identical_to_cold_prefill(wl_and_params):
    """ISSUE 11 satellite: a warm prefix-cache hit — the prompt's
    full-page K/V pages reused from an earlier request — produces
    token-for-token the same greedy output as a cold prefill, and the
    reused pages actually came out of the cache (hit + reuse gauges)."""
    wl, params = wl_and_params
    prompt = np.random.default_rng(0).integers(4, VOCAB, (8,)).astype(
        np.int32)

    cold = make_server(wl, params)
    ref = cold.submit(prompt, max_new_tokens=6)
    cold.drain()

    warm = make_server(wl, params, prefix_cache=True)
    first = warm.submit(prompt, max_new_tokens=6)
    warm.drain()
    second = warm.submit(prompt, max_new_tokens=6)  # hits the cache
    warm.drain()
    assert first.tokens == ref.tokens
    assert second.tokens == ref.tokens
    st = warm.prefix_stats()
    assert st["prefix_hits"] >= 1 and st["prefix_pages_reused"] >= 2
    # pool accounting: cache-resident pages are held, not leaked — the
    # free count plus residency is exactly the capacity
    assert warm.mgr.free_pages + st["prefix_resident_pages"] == \
        warm.mgr.capacity
    # a DIVERGENT prompt sharing only the first page reuses exactly that
    # page and still decodes like its own cold run
    div = prompt.copy()
    div[5] = (div[5] + 1) % VOCAB
    cold2 = make_server(wl, params)
    ref2 = cold2.submit(div, max_new_tokens=6)
    cold2.drain()
    got2 = warm.submit(div, max_new_tokens=6)
    warm.drain()
    assert got2.tokens == ref2.tokens


def test_prefix_cache_refcount_blocks_early_free(wl_and_params):
    """Replay/eviction can never free a shared page a live slot still
    reads: A and B share a prefix, A completes first (the shared pages
    must survive A's release), and pool-pressure eviction skips entries
    whose pages are slot-ref'd — B's output stays exact throughout."""
    wl, params = wl_and_params
    prompt = np.random.default_rng(1).integers(4, VOCAB, (8,)).astype(
        np.int32)
    cold = make_server(wl, params)
    ref = cold.submit(prompt, max_new_tokens=6)
    cold.drain()

    srv = make_server(wl, params, prefix_cache=True)
    a = srv.submit(prompt, max_new_tokens=2)   # finishes first, releases
    b = srv.submit(prompt, max_new_tokens=6)   # still reading the pages
    srv.drain()
    assert a.tokens == ref.tokens[:2]
    assert b.tokens == ref.tokens

    # the killer scenario: the PUBLISHER (a) completes while the sharer
    # (b) still decodes, and a third prompt's admission puts the pool
    # under eviction pressure mid-flight — the shared head pages must
    # survive (b holds slot refs) and c must WAIT, not steal them
    other = np.asarray([9, 13, 17, 21, 25, 29, 5, 7], np.int32)
    cold3 = make_server(wl, params)
    ref3 = cold3.submit(other, max_new_tokens=6)
    cold3.drain()
    # pool sized so c's 4 pages only fit once the 2 cached head pages
    # are evicted: capacity 5 = a(3) + b's fresh(2) at admission, and
    # 3 free after both complete — eviction must yield the last 2
    tight = make_server(wl, params, decode_slots=2, max_pages=6,
                        prefix_cache=True)
    a2 = tight.submit(prompt, max_new_tokens=2)   # publisher, done early
    b2 = tight.submit(prompt, max_new_tokens=6)   # sharer, long-lived
    tight.step()                                  # both admitted
    c2 = tight.submit(other, max_new_tokens=6)    # needs eviction to fit
    tight.drain()
    assert a2.tokens == ref.tokens[:2]
    assert b2.tokens == ref.tokens, \
        "sharer's pages were stolen mid-flight"
    assert c2.tokens == ref3.tokens
    assert tight.prefix_stats()["prefix_evicted_entries"] >= 1
    # ...and with the pool at rest, nothing leaked
    st = tight.prefix_stats()
    assert tight.mgr.free_pages + st["prefix_resident_pages"] == \
        tight.mgr.capacity


def test_prefix_cache_unit_refcounts():
    """PrefixCache bookkeeping in isolation: acquire refs, release frees
    only the private tail, and a page is freed exactly when it leaves
    both its last entry and its last slot ref — eviction may drop a
    slot-ref'd entry (orphaning its pages) but the pages come back only
    through release."""
    from distributed_pipeline_tpu.serving import PageManager, PrefixCache

    mgr = PageManager(num_pages=9, page_size=4)
    cache = PrefixCache(mgr)
    prompt = np.arange(10, dtype=np.int32)    # 2 full pages + tail
    assert cache.acquire(prompt) == ([], 0)   # miss
    pages = mgr.alloc(4)                      # 10 prompt + gen -> 4 pages
    cache.publish(prompt, pages)
    shared, covered = cache.acquire(prompt)
    assert covered == 8 and shared == [int(p) for p in pages[:2]]
    # release with one acquire outstanding: only the tail frees
    tail = cache.release(prompt, pages)
    assert tail.tolist() == [int(p) for p in pages[2:]]
    mgr.free(tail)
    # slot-ref'd from the second acquire: pool-pressure eviction drops
    # the entries but frees NOTHING — the live reader keeps its pages
    free_before = mgr.free_pages
    assert cache.evict_for(mgr.capacity + 1) == 0
    assert mgr.free_pages == free_before
    assert cache.stats()["prefix_entries"] == 0
    # ...and the orphaned pages come back with the LAST slot ref
    back = cache.release(prompt, np.asarray(shared, np.int32))
    assert sorted(back.tolist()) == sorted(int(p) for p in pages[:2])
    mgr.free(back)
    assert mgr.free_pages == mgr.capacity


def test_prefix_cache_eviction_never_deadlocks_shared_prefix_churn():
    """Regression (ISSUE 17, found by the autoscale bench leg): under a
    shared-prefix workload every cache entry's head pages are slot-ref'd
    by the request being admitted, and an eviction policy that skips
    such entries wholesale can free NOTHING — pool exhausted, admission
    waits forever, the worker spins with beacons ticking (so not even
    the watchdog fires). Churn many unique requests over one shared
    prefix through a tight pool: each admission must succeed because
    eviction drops cold entries and frees their unshared pages even
    while the hot shared head stays pinned."""
    from distributed_pipeline_tpu.serving import PageManager, PrefixCache

    # the bench shape: page 4, prompt 12 (3 full pages, 8 shared
    # tokens), gen 8 -> 5 pages/request, 2 slots -> 17-page pool
    mgr = PageManager(num_pages=17, page_size=4)
    cache = PrefixCache(mgr)
    shared8 = np.arange(100, 108, dtype=np.int32)

    def admit(i):
        prompt = np.concatenate(
            [shared8, np.asarray([i, i + 1, i + 2, i + 3], np.int32)])
        shared, covered = cache.acquire(prompt)
        need = 5 - len(shared)
        fresh = mgr.alloc(need)
        if fresh is None:                      # the scheduler's path
            cache.evict_for(need)
            fresh = mgr.alloc(need)
        assert fresh is not None, \
            f"admission {i} deadlocked: pool exhausted, nothing evicted"
        pages = np.concatenate(
            [np.asarray(shared, np.int32), fresh]) if shared else fresh
        cache.publish(prompt, pages, n_acquired=len(shared))
        return prompt, pages

    live = []
    for i in range(40):                        # >> pool capacity
        live.append(admit(i))
        if len(live) == 2:                     # 2 decode slots
            prompt, pages = live.pop(0)
            freeable = cache.release(prompt, pages)
            if freeable.size:
                mgr.free(freeable)
    for prompt, pages in live:
        freeable = cache.release(prompt, pages)
        if freeable.size:
            mgr.free(freeable)
    # invariant after the churn: every page is either free or resident
    # in the cache — nothing leaked, nothing double-freed
    assert mgr.free_pages + cache.resident_pages == mgr.capacity
    assert cache.stats()["prefix_hits"] >= 38  # the shared head stayed hot


def test_eos_finishes_early_and_frees_slot(wl_and_params):
    """EOS completion: learn the greedy continuation once, then re-serve
    with eos_id set to its second token — the request must stop there
    (observed one lagged step late) and release its resources."""
    wl, params = wl_and_params
    prompt = np.arange(4, 10, dtype=np.int32)
    probe = make_server(wl, params)
    r = probe.submit(prompt, max_new_tokens=8)
    probe.drain()
    assert len(r.tokens) == 8
    eos = r.tokens[1]

    srv = make_server(wl, params)
    r2 = srv.submit(prompt, max_new_tokens=8, eos_id=eos)
    srv.drain()
    # stops at the FIRST occurrence of eos (greedy may repeat tokens, so
    # that can be earlier than where it was sampled from)
    stop = r.tokens.index(eos) + 1
    assert r2.tokens == r.tokens[:stop]
    assert r2.finished
    assert srv.free_slots == 2 and srv.mgr.free_pages == srv.mgr.capacity


def test_server_smoke_sanitize_compiles_exactly_once(wl_and_params):
    """CPU smoke under the runtime sanitizer: the prefill and decode
    executables compile exactly once (warmup); a continuously-batched
    steady window adds ZERO compiles — the phase split's whole point."""
    wl, params = wl_and_params
    srv = make_server(wl, params, sanitize=True)
    try:
        warm = srv.submit(np.arange(4, 9, dtype=np.int32), max_new_tokens=3)
        srv.drain()
        assert warm.tokens and srv.compile_time_s > 0
        after_warm = srv.recompile_count
        assert after_warm >= 2  # at least prefill + decode compiled
        rng = np.random.default_rng(11)
        reqs = [srv.submit(rng.integers(4, VOCAB, (1 + i % 7,)).astype(
            np.int32), max_new_tokens=2 + i % 4) for i in range(6)]
        srv.drain()
        assert all(r.finished for r in reqs)
        assert srv.recompile_count == after_warm, \
            "steady-state serving recompiled — the AOT split regressed"
        assert len(srv.ttft) == 7
    finally:
        srv.stop_sanitizer()


# ------------------------------------- the prefill dispatch's token budget

LONG = 1024    # positions of the workload the budget's cases are built on


@pytest.fixture(scope="module")
def long_wl_and_params():
    """The tiny model with GPT-2's 1,024 positions, so that an engine can
    be built at the shapes a deployment has (its programs compile only at
    the first dispatch: building one costs an eval_shape and the pool)."""
    wl = tiny_workload(seq_len=LONG)
    return wl, wl.init_params(jax.random.PRNGKey(3))


@pytest.mark.parametrize("max_prompt_len,slots,asked,rows", [
    (512, 16, 0, 1),     # the serve cell, and GPT-2's default max_len // 2
    (256, 16, 0, 2),
    (64, 16, 0, 8),      # short prompts keep the rows they had
    (64, 4, 0, 4),       # never more rows than slots
    (1024, 16, 0, 1),    # a row over the budget: still one
    (512, 16, 3, 3),     # an explicit prefill_batch is honoured
    (8, 2, 0, 2),        # the tiny shapes of these tests
    (128, 16, 0, 4),
    (512, 1, 0, 1),
])
def test_prefill_rows_follow_the_token_budget(long_wl_and_params,
                                              max_prompt_len, slots, asked,
                                              rows):
    """``prefill_batch=0`` sizes the one compiled prefill shape by
    ``PREFILL_TOKENS`` positions a dispatch, not by eight rows: the rows
    are the budget over ``max_prompt_len``, at least one, at most
    ``min(decode_slots, 8)``; a caller's own number stands."""
    from distributed_pipeline_tpu.serving import engine as engine_lib

    assert engine_lib.PREFILL_TOKENS == 512
    wl, params = long_wl_and_params
    eng = engine_lib.DecodeEngine(
        wl, params, decode_slots=slots, page_size=max_prompt_len,
        max_pages=2, max_prompt_len=max_prompt_len, max_len=max_prompt_len,
        prefill_batch=asked)
    assert eng.prefill_batch == rows
    # still ONE prefill program an engine, built at its first dispatch
    assert set(eng.executables()) == {"prefill", "decode"}
    assert eng.executables()["prefill"].compiled is None


def burst(n=7, seed=21):
    """More requests than slots, every budget over one (a budget of one
    frees its pages at dispatch, which would order frees and allocations
    differently at one row and at eight)."""
    rng = np.random.default_rng(seed)
    return [(rng.integers(4, VOCAB, (int(rng.integers(3, 60)),)).astype(
        np.int32), 3 + i % 5) for i in range(n)]


@pytest.mark.parametrize("kv_quant", ["fp", "int8"])
def test_burst_at_one_row_serves_what_eight_rows_serve(long_wl_and_params,
                                                       kv_quant):
    """At ``max_prompt_len`` 512 the budget gives ONE row. A burst of more
    requests than slots is then admitted by several dispatches in one tick
    (four here, where eight rows made one) and serves, token for token,
    what an explicit ``prefill_batch=8`` serves: same order of admission,
    same pages, so an int8 pool's pages have equal histories on both
    sides (PERF.md section 7, 1d). The padding counter follows the shape
    that ran."""
    wl, params = long_wl_and_params
    lp, slots, work = 512, 4, burst()
    streams, servers = {}, {}
    for asked in (0, 8):
        srv = DecodeServer(wl, params, decode_slots=slots, page_size=16,
                           max_prompt_len=lp, max_len=lp + 16, seed=0,
                           prefill_batch=asked, kv_quant=kv_quant)
        reqs = [srv.submit(p, max_new_tokens=m) for p, m in work]
        srv.step()
        # the first tick filled every slot, whatever the rows
        assert srv.free_slots == 0 and len(srv.queue) == len(reqs) - slots
        first_tick = srv.prefill_steps
        srv.drain()
        assert all(r.finished and len(r.tokens) == r.max_new_tokens
                   for r in reqs)
        assert srv.free_slots == slots
        assert srv.mgr.free_pages == srv.mgr.capacity
        rows = srv.engine.prefill_batch
        assert srv.prefill_token_slots == srv.prefill_steps * rows * lp
        assert srv.prompt_tokens_prefilled == sum(
            r.prompt_len for r in reqs)
        streams[asked] = [r.tokens for r in reqs]
        servers[asked] = (rows, first_tick, srv.prefill_steps)
    assert servers[0][0] == 1 and servers[8][0] == 8
    assert servers[0][1] == slots and servers[8][1] == 1
    assert servers[0][2] == len(work)        # a dispatch a request
    assert streams[0] == streams[8]


def test_draft_engine_resolves_the_targets_rows(long_wl_and_params):
    """The speculative server mirrors every admission into its draft
    engine with the target's arrays, so both must resolve the same rows
    from the same arguments; a burst over several dispatches a tick keeps
    the greedy stream of the non-speculative server."""
    wl, params = long_wl_and_params
    kw = dict(decode_slots=4, page_size=16, max_prompt_len=256,
              max_len=288, seed=0)
    plain = DecodeServer(wl, params, **kw)
    spec = DecodeServer(wl, params, spec_tokens=2, spec_draft="model",
                        draft_layers=1, **kw)
    assert plain.engine.prefill_batch == 2       # 512 // 256, not min(4, 8)
    assert spec.engine.prefill_batch == 2
    assert spec._draft_engine.prefill_batch == 2
    got = {}
    for name, srv in (("plain", plain), ("spec", spec)):
        reqs = [srv.submit(p, max_new_tokens=m) for p, m in burst(n=6)]
        srv.drain()
        assert srv.prefill_steps >= 3            # six requests, two rows
        assert srv.prefill_token_slots == srv.prefill_steps * 2 * 256
        assert srv.mgr.free_pages == srv.mgr.capacity
        got[name] = [r.tokens for r in reqs]
    assert got["spec"] == got["plain"]


def test_engine_rejects_unsupported_models(wl_and_params):
    wl, params = wl_and_params
    scan_wl = tiny_workload(scan_layers=True)
    with pytest.raises(NotImplementedError, match="scan_layers"):
        DecodeServer(scan_wl, scan_wl.init_params(jax.random.PRNGKey(0)),
                     decode_slots=2, page_size=4, max_prompt_len=8)
    diff_wl = create_model_from_config(
        model_family="diffuseq", vocab_size=VOCAB, seq_len=SEQ,
        hidden_size=32, num_layers=2, num_heads=2, diffusion_steps=10,
        dtype="float32")
    # refused for what its model lacks, not for its family's name
    with pytest.raises(ValueError, match="paged cache"):
        DecodeServer(diff_wl, params, decode_slots=2, page_size=4,
                     max_prompt_len=8)
    with pytest.raises(ValueError, match="max_prompt_len"):
        DecodeServer(wl, params, decode_slots=2, page_size=4,
                     max_prompt_len=SEQ + 1)


# ------------------------- the serving form of the weights (ISSUE 34)

def _leaves(tree):
    return jax.tree_util.tree_leaves(tree)


def _by_path(tree):
    return {jax.tree_util.keystr(path): leaf for path, leaf in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


MATMUL_LEAVES = ("['attn']['qkv']", "['attn']['out']", "['mlp']['wi']",
                 "['mlp']['wo']", "['moe']['wi']", "['moe']['wo']")


class AsTrained:
    """The model with no serving form declared: an engine holds such a
    model's float32 tree untouched and every program casts it at each use,
    as every engine did before ISSUE 34. The twin the served tokens are
    held to."""

    def __init__(self, model):
        self._model = model

    def __getattr__(self, name):
        if name == "serving_variables":
            raise AttributeError(name)
        return getattr(self._model, name)


def as_trained(wl):
    import dataclasses
    return dataclasses.replace(wl, model=AsTrained(wl.model))


@pytest.fixture(scope="module", params=["dense", "moe"])
def bf16_wl_and_params(request):
    """bfloat16 compute over float32 masters, dense and with experts in
    every second block."""
    wl = tiny_workload(dtype="bfloat16",
                       moe_experts=4 if request.param == "moe" else 0)
    return wl, wl.init_params(jax.random.PRNGKey(3))


@pytest.mark.parametrize("shape", ["prefill", "decode"])
def test_serving_form_logits_are_bit_identical(bf16_wl_and_params, shape):
    """float32 -> bfloat16 is the same rounding outside a program as
    inside it: the logits of the serving form ARE the float32 tree's, for
    a whole-prompt call and for a one-token call over a cache."""
    wl, params = bf16_wl_and_params
    served = wl.model.serving_variables(params)
    dm = wl.model.clone(decode=True, moe_no_drop=True)
    ids = jnp.asarray(prompt_ids(seed=6))

    @jax.jit
    def prefill(p):
        logits, mvars = dm.apply(p, ids, None, mutable=["cache"])
        return logits, mvars["cache"]

    @jax.jit
    def decode(p, cache):
        return dm.apply({**p, "cache": cache}, ids[:, 5:6], None,
                        cache_index=jnp.asarray(5, jnp.int32),
                        mutable=["cache"])[0]

    want, cache = prefill(params)
    got, cache_s = prefill(served)
    if shape == "decode":
        want, got = decode(params, cache), decode(served, cache_s)
    assert want.dtype == jnp.bfloat16 and want.shape[-1] == VOCAB
    assert float(jnp.max(jnp.abs(want.astype(jnp.float32)))) > 0.1
    assert np.array_equal(np.asarray(want.astype(jnp.float32)),
                          np.asarray(got.astype(jnp.float32)))


@pytest.mark.parametrize("kw", [
    dict(), dict(kv_quant="int8"),
    dict(spec_tokens=2, spec_draft="model", draft_layers=1)],
    ids=["fp", "int8", "spec"])
def test_server_on_the_serving_form_serves_the_float32_trees_tokens(
        bf16_wl_and_params, kw):
    """A server casts once what each program cast at each use: token for
    token what the twin that still casts in its programs serves, from the
    fp pool, the int8 pool and through the speculative verify; and what
    the dense decode of the float32 tree picks (held on the first token,
    which the prefill's logits decide: test_merged_head_axis_pool's note
    on bfloat16 near-ties between different CPU programs)."""
    wl, params = bf16_wl_and_params
    geometry = dict(decode_slots=4, page_size=4, max_prompt_len=SEQ,
                    max_len=SEQ, prefill_batch=4, seed=0)
    ids = prompt_ids(seed=8)
    plen = SEQ // 2
    srv = DecodeServer(wl, params, **geometry, **kw)
    assert srv.engine.weights["leaves_cast"] > 0
    twin = DecodeServer(as_trained(wl), params, **geometry, **kw)
    assert twin.engine.weights["leaves_cast"] == 0
    assert all(a is b for a, b in zip(_leaves(twin.engine.params),
                                      _leaves(params)))
    got = one_shot_decode(wl, params, ids, plen, server=srv)
    want = one_shot_decode(wl, params, ids, plen, server=twin)
    np.testing.assert_array_equal(got, want)
    dense = np.asarray(gpt2_decode(wl, params, jnp.asarray(ids), plen,
                                   use_cache=True))
    np.testing.assert_array_equal(got[:, :plen + 1], dense[:, :plen + 1])
    if kw.get("spec_tokens"):
        assert srv.spec_rounds > 0 and srv.draft_proposed > 0


def test_engine_holds_matrices_in_the_compute_dtype(bf16_wl_and_params):
    """Every matrix a module casts before its one use is bfloat16 in
    ``engine.params``; LayerNorm leaves, the router and BOTH embedding
    tables stay float32 (the lookup's sum rounds once), the tied head
    reads a bfloat16 copy carried in a collection of its own, and the
    parameter count is the tree's."""
    from flax import linen as nn
    wl, params = bf16_wl_and_params
    srv = DecodeServer(wl, params, decode_slots=2, page_size=4,
                       max_prompt_len=8, spec_tokens=2, spec_draft="model",
                       draft_layers=1)
    held = srv.engine.params
    assert set(held) == {"params", "serving"}
    leaves = _by_path(nn.meta.unbox(held["params"]))
    given = _by_path(nn.meta.unbox(params["params"]))
    assert set(leaves) == set(given)
    cast = [k for k in leaves if k.endswith(MATMUL_LEAVES)]
    assert len(cast) == 2 * 4              # two blocks: qkv, out, wi, wo
    for key, leaf in leaves.items():
        assert leaf.shape == given[key].shape
        assert leaf.dtype == (jnp.bfloat16 if key in cast else jnp.float32)
    assert leaves["['word_emb']['embedding']"] is given[
        "['word_emb']['embedding']"]
    assert leaves["['pos_emb']"] is given["['pos_emb']"]
    head = held["serving"]["head"]
    assert head.dtype == jnp.bfloat16 and head.shape == (VOCAB, 32)
    assert wl.param_count(held) == wl.param_count(params)
    w = srv.engine.weights
    assert w["leaves_cast"] == len(cast) + 1          # and the head
    assert w["bytes_in"] == 4 * wl.param_count(params)
    n_cast = sum(leaves[k].size for k in cast)
    assert w["bytes_serving"] == w["bytes_in"] - 2 * n_cast + 2 * head.size
    # the draft's blocks and head ARE the target's: one copy for both
    draft = srv._draft_engine
    assert draft.weights["leaves_cast"] == 0
    mine = {id(x) for x in _leaves(held)}
    assert all(id(x) in mine for x in _leaves(draft.params))
    assert draft.params["serving"]["head"] is head


@pytest.mark.parametrize("case", ["float32_model", "served_tree",
                                  "described_tree", "chunked_family"])
def test_a_tree_that_is_right_is_never_copied(case):
    """A leaf already in its dtype is the caller's own array in
    ``engine.params``, whatever the tree's size (an 11 GB bfloat16 tree on
    a 16 GB chip cannot be copied); a described tree is re-described; a
    model that declares no serving form (the chunked family) is held
    untouched."""
    from distributed_pipeline_tpu.serving.engine import DecodeEngine
    geometry = dict(decode_slots=2, page_size=4, max_pages=9,
                    max_prompt_len=8)
    if case == "chunked_family":
        from tests.test_deepseek_v32 import TINY, build
        wl, _, tree = build(dict(TINY, dtype="bfloat16",
                                 param_dtype="bfloat16"))
        eng = DecodeEngine(wl, tree, max_len=32, **geometry)
        assert eng.chunked and eng.params is tree
    elif case == "described_tree":
        from flax import linen as nn
        wl = tiny_workload(dtype="bfloat16")
        tree = nn.meta.unbox(jax.eval_shape(wl.init_params,
                                            jax.random.PRNGKey(0)))
        eng = DecodeEngine(wl, tree, **geometry)
        want = jax.eval_shape(wl.model.serving_variables, tree)
        assert jax.tree_util.tree_structure(eng.params) == \
            jax.tree_util.tree_structure(want)
        assert all(isinstance(a, jax.ShapeDtypeStruct)
                   and (a.shape, a.dtype) == (b.shape, b.dtype)
                   for a, b in zip(_leaves(eng.params), _leaves(want)))
        assert eng.weights["leaves_cast"] == 2 * 4 + 1
        return
    else:
        wl = tiny_workload(
            dtype="float32" if case == "float32_model" else "bfloat16")
        tree = wl.init_params(jax.random.PRNGKey(3))
        if case == "served_tree":
            tree = wl.model.serving_variables(tree)
        eng = DecodeEngine(wl, tree, **geometry)
        assert wl.param_count(eng.params) == wl.param_count(tree)
    given = {id(x) for x in _leaves(tree)}
    assert all(id(x) in given for x in _leaves(eng.params))
    assert eng.weights["leaves_cast"] == 0
    assert eng.weights["bytes_serving"] == eng.weights["bytes_in"]


@pytest.mark.parametrize("spec", [False, True], ids=["plain", "spec"])
def test_hot_swap_recasts_and_recompiles_nothing(spec):
    """``set_params`` with a float32 tree after the first token: the
    engine holds the new tree's serving form (the dtypes the executables
    were compiled against), new tokens follow the new weights, the model
    draft's views follow, and nothing compiles in steady state."""
    wl = tiny_workload(dtype="bfloat16")
    old = wl.init_params(jax.random.PRNGKey(3))
    new = wl.init_params(jax.random.PRNGKey(4))
    kw = dict(decode_slots=2, page_size=4, max_prompt_len=8, max_len=SEQ,
              seed=0)
    if spec:
        kw.update(spec_tokens=2, spec_draft="model", draft_layers=1)
    prompt = np.arange(4, 10, dtype=np.int32)
    fresh = DecodeServer(wl, new, **kw)
    want_new = fresh.submit(prompt, max_new_tokens=6)
    fresh.drain()
    srv = DecodeServer(wl, old, sanitize=True, **kw)
    try:
        was = srv.submit(prompt, max_new_tokens=6)
        srv.drain()
        assert was.tokens != want_new.tokens
        steady = srv.recompile_count
        dtypes = [x.dtype for x in _leaves(srv.engine.params)]
        srv.set_params(new)
        assert [x.dtype for x in _leaves(srv.engine.params)] == dtypes
        assert srv.engine.weights["leaves_cast"] == 2 * 4 + 1
        got = srv.submit(prompt, max_new_tokens=6)
        srv.drain()
        assert got.tokens == want_new.tokens
        assert srv.recompile_count == steady
        if spec:
            held = {id(x) for x in _leaves(srv.engine.params)}
            assert all(id(x) in held
                       for x in _leaves(srv._draft_engine.params))
            assert srv._draft_engine.weights["leaves_cast"] == 0
    finally:
        srv.stop_sanitizer()


# ------------------------------------------------------- entry wiring

def _train_tiny_gpt2_run(tmp_path):
    from distributed_pipeline_tpu.parallel import make_mesh
    from distributed_pipeline_tpu.utils.trainer import TrainLoop

    wl = tiny_workload()
    data = load_data_from_args("train", batch_size=8, dataset="synthetic-lm",
                               seq_len=SEQ, vocab_size=VOCAB, seed=0)
    loop = TrainLoop(model=wl, data=data, batch_size=8, lr=1e-3,
                     ema_rate="0.99", learning_steps=0,
                     log_interval=10 ** 9, save_interval=10 ** 9,
                     mesh=make_mesh(dp=8), checkpoint_dir=str(tmp_path))
    for _ in range(2):
        loop.run_step(next(loop.data))
    loop.save()
    targs = dict(model_family="gpt2", model_size="base", vocab_size=VOCAB,
                 seq_len=SEQ, hidden_size=32, num_layers=2, num_heads=2,
                 dtype="float32", dataset="synthetic-lm", seed=0)
    with open(tmp_path / "training_args.json", "w") as f:
        json.dump(targs, f)
    return wl


def test_run_sample_gpt2_routes_through_serving(tmp_path):
    """run.sample's GPT-2 path decodes through the serving engine (one code
    path for one-shot and served decode) and still reports sane metrics;
    --num_batches 0 is a load-only run, not a ZeroDivisionError."""
    from distributed_pipeline_tpu.run import sample as run_sample

    _train_tiny_gpt2_run(tmp_path)
    ns = run_sample.create_parser().parse_args(
        ["--checkpoint_path", str(tmp_path), "--batch_size", "8",
         "--num_batches", "1"])
    res = run_sample.main(ns)
    assert res["params"] == "raw" and res["step"] == 2
    assert 0.0 <= res["decode_acc"] <= 1.0
    assert np.isfinite(res["eval_loss"])

    ns0 = run_sample.create_parser().parse_args(
        ["--checkpoint_path", str(tmp_path), "--batch_size", "8",
         "--num_batches", "0"])
    res0 = run_sample.main(ns0)
    assert res0["decode_acc"] is None and res0["eval_loss"] is None


def test_run_serve_end_to_end(tmp_path):
    """run.serve off a real run dir: synthetic workload, sanitize on,
    JSONL results out, serving-schema summary fields present."""
    from distributed_pipeline_tpu.run import serve as run_serve

    _train_tiny_gpt2_run(tmp_path)
    out = tmp_path / "served.jsonl"
    ns = run_serve.create_parser().parse_args(
        ["--checkpoint_path", str(tmp_path), "--decode_slots", "2",
         "--page_size", "4", "--max_prompt_len", "8",
         "--max_new_tokens", "4", "--synthetic_requests", "5",
         "--arrival_every_steps", "2", "--sanitize", "true",
         "--out", str(out)])
    res = run_serve.main(ns)
    assert res["requests"] == 5
    assert res["decode_tokens"] == 5 * 4
    assert res["decode_tokens_per_s_per_chip"] > 0
    assert res["time_to_first_token_s"] > 0
    assert res["ttft_p95_s"] >= res["ttft_p50_s"] >= 0
    assert res["compile_time_s"] > 0
    # phase-split contract: prefill+decode compiled exactly once (warmup);
    # the steady recompile gauge across the served run stays 0
    assert res["recompile_count"] == 0
    assert res["xla_compiles_total"] >= 2
    rows = [json.loads(l) for l in out.read_text().splitlines()]
    assert len(rows) == 5 and all(len(r["tokens"]) == 4 for r in rows)


def test_serve_settings_roundtrip():
    from distributed_pipeline_tpu.config.serve import ServeSettings

    s = ServeSettings.from_argv(
        ["--checkpoint_path", "/tmp/run", "--decode_slots", "16",
         "--page_size", "8", "--max_pages", "33"])
    assert (s.decode_slots, s.page_size, s.max_pages) == (16, 8, 33)
    s2 = ServeSettings.model_validate(json.loads(s.to_json()))
    assert s2 == s
