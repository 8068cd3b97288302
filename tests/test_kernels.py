"""Pallas fast-path parity suite (ISSUE 18): the flash-decode kernel
against the XLA gather path across page geometries (partial last pages,
trash-routed dead slots, prefix-cache shared pages), the DecodeServer
greedy-token identity + frozen-steady-compile contract under
``decode_impl="pallas"``, the fused AdamW+EMA update's bit-parity with the
staged optax chain (unsharded AND composed with ZeRO-1), the vocab-parallel
cross-entropy decomposition, and the schedule-derived HBM byte accounting
both bench legs land. Off-TPU the kernels run in Pallas interpreter mode —
same kernel logic, tier-1 speed."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distributed_pipeline_tpu.data import load_data_from_args
from distributed_pipeline_tpu.models import create_model_from_config
from distributed_pipeline_tpu.ops.flash_decode import (
    _pages_per_block,
    _schedule,
    decode_hbm_bytes,
    decode_page_census,
    flash_decode,
    paged_decode_attention,
    paged_span_attention,
    resolve_decode_impl,
    xla_paged_decode,
)
from distributed_pipeline_tpu.ops.fused_update import (
    fused_adamw_ema,
    update_hbm_bytes,
)
from distributed_pipeline_tpu.ops.xent import token_cross_entropy
from distributed_pipeline_tpu.parallel import make_mesh
from distributed_pipeline_tpu.serving import TRASH_PAGE, DecodeServer
from distributed_pipeline_tpu.utils.trainer import TrainLoop

# ----------------------------------------------------------- flash-decode


@pytest.fixture(params=["own_copies", "pipeline_operands"])
def fetch(request, monkeypatch):
    """Both ways a step's pages reach the kernel, whatever the pool's size
    here: the kernel's own copies of the live pages (a step of more than
    ``HIDDEN_STEP_BYTES``) and 2 G pipeline operands (the rest)."""
    from distributed_pipeline_tpu.ops import flash_decode as fd
    monkeypatch.setattr(fd, "HIDDEN_STEP_BYTES",
                        -1 if request.param == "own_copies" else 1 << 60)
    return request.param


def paged_case(rng, *, slots, n_pages, page_size, n_heads, head_dim,
               positions, table=None):
    """Random pool ([P, page_size, H * Dh], the stored shape) + block
    tables; page 0 is the trash page and is filled with large garbage so
    any accidental read of it shows up loudly."""
    P = 1 + slots * n_pages
    k = rng.standard_normal((P, page_size, n_heads * head_dim))
    v = rng.standard_normal((P, page_size, n_heads * head_dim))
    k[TRASH_PAGE] = 37.0
    v[TRASH_PAGE] = -53.0
    if table is None:
        table = 1 + np.arange(slots * n_pages).reshape(slots, n_pages)
    q = rng.standard_normal((slots, n_heads, head_dim))
    return (jnp.asarray(q, jnp.float32), jnp.asarray(k, jnp.float32),
            jnp.asarray(v, jnp.float32), jnp.asarray(table, jnp.int32),
            jnp.asarray(positions, jnp.int32))


def dense_reference(q, k_pool, v_pool, table, positions):
    """Straight-line numpy softmax over each slot's live prefix only."""
    q, k_pool, v_pool = map(np.asarray, (q, k_pool, v_pool))
    table, positions = np.asarray(table), np.asarray(positions)
    B, H, Dh = q.shape
    k_pool = k_pool.reshape(k_pool.shape[:2] + (H, Dh))
    v_pool = v_pool.reshape(v_pool.shape[:2] + (H, Dh))
    out = np.zeros_like(q)
    for b in range(B):
        n_live = positions[b] + 1
        ks = np.concatenate([k_pool[p] for p in table[b]], 0)[:n_live]
        vs = np.concatenate([v_pool[p] for p in table[b]], 0)[:n_live]
        for h in range(H):
            s = ks[:, h] @ q[b, h] * Dh ** -0.5
            p = np.exp(s - s.max())
            out[b, h] = (p / p.sum()) @ vs[:, h]
    return out


@pytest.mark.parametrize("page_size,n_pages,positions", [
    (4, 4, [0, 3, 7, 15]),      # empty-but-one, exact page edge, full
    (2, 8, [1, 4, 9, 14]),      # many small pages, interior positions
    (8, 2, [2, 5, 8, 12]),      # partial first page / spilled second
])
def test_flash_decode_matches_xla_across_geometries(page_size, n_pages,
                                                    positions, fetch):
    rng = np.random.default_rng(7)
    q, k, v, bt, pos = paged_case(
        rng, slots=4, n_pages=n_pages, page_size=page_size, n_heads=2,
        head_dim=8, positions=positions)
    got = np.asarray(flash_decode(q, k, v, bt, pos))
    ref = np.asarray(xla_paged_decode(q, k, v, bt, pos))
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(got, dense_reference(q, k, v, bt, pos),
                               rtol=2e-5, atol=2e-6)


def test_flash_decode_ignores_dead_pages_and_garbage_tails(fetch):
    """Entries past the live prefix of a block-table row may be anything
    (contract): point them at the garbage trash page and poison the dead
    rows of each last live page — the output must not move."""
    rng = np.random.default_rng(11)
    ps, n = 4, 4
    q, k, v, bt, pos = paged_case(rng, slots=3, n_pages=n, page_size=ps,
                                  n_heads=2, head_dim=8,
                                  positions=[1, 5, 9])
    clean = np.asarray(flash_decode(q, k, v, bt, pos))
    btp = np.asarray(bt).copy()
    kp, vp = np.asarray(k).copy(), np.asarray(v).copy()
    for b, p in enumerate(np.asarray(pos)):
        btp[b, p // ps + 1:] = TRASH_PAGE          # dead table tail
        last = btp[b, p // ps]
        kp[last, p % ps + 1:] = 1e4                 # dead rows in last page
        vp[last, p % ps + 1:] = -1e4
    got = np.asarray(flash_decode(q, jnp.asarray(kp), jnp.asarray(vp),
                                  jnp.asarray(btp), pos))
    np.testing.assert_array_equal(got, clean)


def test_flash_decode_prefix_cache_shared_pages(fetch):
    """Two slots listing the SAME physical page (PrefixCache sharing) just
    schedule two reads of it — parity must hold with divergent tails."""
    rng = np.random.default_rng(13)
    q, k, v, bt, pos = paged_case(
        rng, slots=2, n_pages=3, page_size=4, n_heads=2, head_dim=8,
        positions=[6, 10],
        table=np.asarray([[1, 2, 3], [1, 4, 5]]))  # page 1 shared head
    got = np.asarray(flash_decode(q, k, v, bt, pos))
    ref = np.asarray(xla_paged_decode(q, k, v, bt, pos))
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-6)


# Blocks of G pages a grid step. 16-row pages -> G = 16 (256 positions a
# block); 40 pages a slot is not a multiple of G: blocks of 16, 16 and 8.
BLOCK_CASES = {
    "one_live_position": [0],
    "ends_in_a_blocks_first_page": [5, 261, 520],
    "on_a_blocks_edge": [255, 511],
    "one_past_a_blocks_edge": [256, 512],
    "full_reservation_partial_last_block": [639, 600],
    "mixed_depths": [0, 15, 16, 255, 256, 400, 639],
}


@pytest.mark.parametrize("positions", list(BLOCK_CASES.values()),
                         ids=list(BLOCK_CASES))
def test_flash_decode_blocks_of_pages(positions, fetch):
    ps, n, H, Dh = 16, 40, 2, 8
    assert _pages_per_block(ps, n) == 16 and n % 16 != 0
    rng = np.random.default_rng(23)
    q, k, v, bt, pos = paged_case(
        rng, slots=len(positions), n_pages=n, page_size=ps, n_heads=H,
        head_dim=Dh, positions=positions)
    got = np.asarray(flash_decode(q, k, v, bt, pos))
    np.testing.assert_allclose(got, np.asarray(
        xla_paged_decode(q, k, v, bt, pos)), rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(got, dense_reference(q, k, v, bt, pos),
                               rtol=2e-5, atol=2e-6)
    # table entries past the live prefix may be anything: the schedule
    # never names them, also INSIDE a live block
    btp = np.asarray(bt).copy()
    for b, p in enumerate(positions):
        btp[b, p // ps + 1:] = 1 + (7 * b) % (len(positions) * n)
    moved = np.asarray(flash_decode(q, k, v, jnp.asarray(btp), pos))
    np.testing.assert_array_equal(moved, got)


def block_table_cases():
    """(id, block table, positions) at 16-row pages, 40 pages a slot: the
    geometries of ``BLOCK_CASES``, then slots that share their first twenty
    pages beside a released slot (table all trash, a stale position) —
    first, in the middle and last."""
    n = 40
    for name, positions in BLOCK_CASES.items():
        yield name, 1 + np.arange(len(positions) * n).reshape(-1, n), positions
    for name, released in [("released_first", 0), ("released_between", 1),
                           ("released_last", 2)]:
        table = 1 + np.arange(3 * n).reshape(3, n)
        live = [b for b in range(3) if b != released]
        table[live[1], :20] = table[live[0], :20]
        table[released, :] = TRASH_PAGE
        positions = np.asarray([340, 500, 5])
        positions[released] = 40
        yield name, table, positions.tolist()


TABLE_CASES = list(block_table_cases())


@pytest.mark.parametrize("table,positions", [c[1:] for c in TABLE_CASES],
                         ids=[c[0] for c in TABLE_CASES])
def test_flash_decode_schedule_copies_live_pages_only(table, positions):
    """The step table carries the count of live entries a column,
    ``min(G, n_live - blk * G)``: the kernel starts a copy for those and for
    no other. The live entries are the block table's own; no entry of a
    slot that lists none names the trash page (a released slot's stale
    position lists it). The traced table and the census's numpy one are
    the same function."""
    ps, n = 16, table.shape[1]
    g = _pages_per_block(ps, n)
    pos = np.asarray(positions, np.int32)
    got = _schedule(table, pos, ps, g, np)
    traced = _schedule(jnp.asarray(table, jnp.int32), jnp.asarray(pos), ps,
                       g, jnp)
    for a, b in zip(got, traced):
        np.testing.assert_array_equal(a, np.asarray(b))
    slot, blk, nb_live, n_steps, live, pages = got
    active = (table != TRASH_PAGE).any(axis=1)    # a released row is trash
    n_live = np.clip(pos // ps + 1, 0, n)
    assert n_steps == nb_live.sum() == np.maximum(-(-n_live // g), 1).sum()
    assert (live[int(n_steps):] == 0).all()       # no step, no copy
    for t in range(int(n_steps)):
        b = slot[t]
        assert live[t] == min(g, n_live[b] - blk[t] * g)
        assert (live[t] == g) or blk[t] == nb_live[b] - 1   # last block
        j = blk[t] * g + np.arange(live[t])
        np.testing.assert_array_equal(pages[t, :live[t]], table[b, j])
        if active[b]:
            assert (pages[t] != TRASH_PAGE).all(), (t, pages[t])
    # the page census: what is copied is what is live, a shared page once
    listed = {int(p) for b in range(len(pos)) for p in table[b, :n_live[b]]}
    assert decode_page_census(table, pos, ps) == (len(listed), len(listed))
    assert (TRASH_PAGE in listed) == (not active.all())
    # ... and a released slot whose position says so copies nothing at all
    assert decode_page_census(table, np.where(active, pos, -1), ps) == (
        len(listed - {TRASH_PAGE}),) * 2


@pytest.mark.parametrize("span", [0, 3], ids=["decode", "span3"])
@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_flash_decode_reads_no_trash_and_no_unlisted_page(kv, span, fetch):
    """A pool whose trash page and whose pages outside every slot's live
    prefix are poisoned (large finite values, their int8 scales too) gives
    the outputs of a clean pool, bit for bit: the kernel copies the pages
    that hold a live position and no other."""
    ps, n, H, Dh, B = 16, 40, 2, 8, 4
    rng = np.random.default_rng(37)
    P = 1 + B * n
    table = 1 + rng.permutation(B * n).reshape(B, n)
    depth = np.asarray([5, 300, 256, 640])          # live tokens a slot
    listed = np.zeros(P, bool)
    for b in range(B):
        listed[table[b, :-(-depth[b] // ps)]] = True
    assert not listed[TRASH_PAGE] and (~listed).sum() > B
    if kv == "int8":
        clean = [rng.integers(-127, 128, (P, ps, H * Dh)).astype(np.int8)
                 for _ in range(2)]
        scales = [(rng.uniform(0.1, 3.0, (P,)) / 127.0).astype(np.float32)
                  for _ in range(2)]
        bad, bad_scale = 127, 1e4
    else:
        clean = [np.asarray(jnp.asarray(
            rng.standard_normal((P, ps, H * Dh)), jnp.bfloat16))
            for _ in range(2)]
        scales, bad, bad_scale = [None, None], 3e4, None
    if span:
        q = rng.standard_normal((B, H, span, Dh))
        pos = depth[:, None] - span + np.arange(span)[None, :]
        seam = paged_span_attention
    else:
        q = rng.standard_normal((B, H, Dh))
        pos = depth - 1
        seam = paged_decode_attention

    def run(pools, scales):
        return np.asarray(seam(
            jnp.asarray(q, jnp.float32), *map(jnp.asarray, pools),
            jnp.asarray(table, jnp.int32), jnp.asarray(pos, jnp.int32),
            impl="pallas",
            scales_k=None if scales[0] is None else jnp.asarray(scales[0]),
            scales_v=None if scales[1] is None else jnp.asarray(scales[1])))

    want = run(clean, scales)
    poisoned, poisoned_scales = [], []
    for pool, sc, sign in zip(clean, scales, (1, -1)):
        pool = pool.copy()
        pool[~listed] = sign * bad
        poisoned.append(pool)
        if sc is not None:
            sc = sc.copy()
            sc[~listed] = bad_scale
        poisoned_scales.append(sc)
    got = run(poisoned, poisoned_scales)
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, want)


def test_flash_decode_ring_of_buffers(monkeypatch):
    """The kernel's own copies fly ``ring - 1`` steps ahead: the ring of
    two a wide pool's buffers leave room for (``RING_BYTES``) gives the
    outputs of the default four, bit for bit — also where the grid is
    shorter than the ring, and across a slot with nothing live."""
    from distributed_pipeline_tpu.ops import flash_decode as fd
    ps, n, H, Dh = 16, 40, 2, 8
    rng = np.random.default_rng(41)
    block_bytes = 16 * ps * H * Dh * 4
    monkeypatch.setattr(fd, "HIDDEN_STEP_BYTES", -1)    # its own copies
    rooms = (fd.RING_BYTES, 2 * fd.MAX_RING * block_bytes - 1)  # 4, then 2
    assert rooms[1] < rooms[0] and fd.MAX_RING == 4
    for positions in ([70], [639, -1, 3, 300, 255]):
        q, k, v, bt, pos = paged_case(
            rng, slots=len(positions), n_pages=n, page_size=ps, n_heads=H,
            head_dim=Dh, positions=positions)
        outs = []
        for room in rooms:
            monkeypatch.setattr(fd, "RING_BYTES", room)
            fd._flash_decode.clear_cache()
            outs.append(np.asarray(flash_decode(q, k, v, bt, pos)))
        np.testing.assert_array_equal(outs[1], outs[0])
        some = np.asarray(positions) >= 0      # nothing live reads zeros
        np.testing.assert_array_equal(outs[1][~some], 0.0)
        np.testing.assert_allclose(outs[1][some], np.asarray(
            xla_paged_decode(q, k, v, bt, pos))[some], rtol=2e-5, atol=2e-5)
    fd._flash_decode.clear_cache()


def test_flash_decode_inactive_slot_and_shared_prefix_in_blocks(fetch):
    """A released slot (table all trash, a stale position) beside two
    slots that share their first twenty pages (more than one block): the
    inactive row attends the trash page like the XLA arm, the others are
    untouched by it."""
    ps, n, H, Dh = 16, 40, 2, 8
    rng = np.random.default_rng(29)
    table = 1 + np.arange(3 * n).reshape(3, n)
    table[1, :20] = table[0, :20]              # shared prefix pages
    table[2, :] = TRASH_PAGE                   # inactive
    q, k, v, bt, pos = paged_case(
        rng, slots=3, n_pages=n, page_size=ps, n_heads=H, head_dim=Dh,
        positions=[340, 500, 40], table=table)
    got = np.asarray(flash_decode(q, k, v, bt, pos))
    ref = np.asarray(xla_paged_decode(q, k, v, bt, pos))
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(got[:2], dense_reference(
        q, k, v, bt, pos)[:2], rtol=2e-5, atol=2e-6)
    # a slot with NO live position (position -1) reads zeros, not NaNs
    none = np.asarray(flash_decode(q, k, v, bt, jnp.asarray([340, -1, 40])))
    np.testing.assert_array_equal(none[1], 0.0)
    np.testing.assert_allclose(none[0], got[0], rtol=1e-6)


def test_flash_decode_int8_scales_a_page_of_a_block(fetch):
    """Each page of a block carries its own K and V scale (a factor of 30
    apart here) through the step table."""
    ps, n, H, Dh, B = 16, 40, 2, 8, 3
    rng = np.random.default_rng(31)
    P = 1 + B * n
    pools = [jnp.asarray(rng.integers(-127, 128, (P, ps, H * Dh)), jnp.int8)
             for _ in range(2)]
    scales = [jnp.asarray(rng.uniform(0.1, 3.0, (P,)) / 127.0, jnp.float32)
              for _ in range(2)]
    bt = jnp.asarray(1 + rng.permutation(B * n).reshape(B, n), jnp.int32)
    q = jnp.asarray(rng.standard_normal((B, H, Dh)), jnp.float32)
    pos = jnp.asarray([3, 260, 639], jnp.int32)
    got = flash_decode(q, pools[0], pools[1], bt, pos, *scales)
    ref = xla_paged_decode(q, pools[0], pools[1], bt, pos, *scales)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)


def test_flash_decode_under_jit_and_seam_dispatch():
    """The seam is called from inside the engine's jitted decode step:
    tracing must work and forced impls must agree through it."""
    rng = np.random.default_rng(17)
    q, k, v, bt, pos = paged_case(rng, slots=2, n_pages=2, page_size=4,
                                  n_heads=2, head_dim=8, positions=[3, 6])
    f = jax.jit(functools.partial(paged_decode_attention, impl="pallas"))
    g = jax.jit(functools.partial(paged_decode_attention, impl="xla"))
    np.testing.assert_allclose(np.asarray(f(q, k, v, bt, pos)),
                               np.asarray(g(q, k, v, bt, pos)),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("kv", ["fp", "int8"])
@pytest.mark.parametrize("heads,head_dim", [(4, 8), (5, 64)],
                         ids=["H4xDh8", "H5xDh64"])
def test_paged_decode_through_the_merged_head_axis(heads, head_dim, kv):
    """Head shapes the pool's merged ``H * Dh`` axis changes (five heads:
    no power of two; ``Dh`` 64: GPT-2's, the width that was padded).
    Rows written by the pool's own writers — a prompt, then one token a
    slot — come back head for head on both arms: fp to float tolerance
    of a straight softmax over the rows as they were handed in, int8
    within the envelope quantized K/V leaves a softmax average
    (test_spec_decode's 0.05)."""
    from distributed_pipeline_tpu.serving.paged_kv import (
        write_prompt_kv, write_prompt_kv_q8, write_token_kv,
        write_token_kv_q8)
    rng = np.random.default_rng(19)
    B, ps, n = 3, 4, 3
    P = 1 + B * n
    lens = np.asarray([5, 9, 2])                  # live prompt tokens a slot
    table = jnp.asarray(1 + rng.permutation(B * n).reshape(B, n), jnp.int32)
    rows = {name: rng.standard_normal((B, heads, n * ps, head_dim))
            for name in ("k", "v")}
    tok = {name: rng.standard_normal((B, heads, head_dim))
           for name in ("k", "v")}
    valid = jnp.asarray(np.arange(n * ps)[None, :] < lens[:, None],
                        jnp.int32)
    pos = jnp.asarray(lens, jnp.int32)            # the token lands here
    pools, scales = {}, {}
    for name in ("k", "v"):
        kv_rows = jnp.asarray(rows[name], jnp.float32)
        kv_tok = jnp.asarray(tok[name], jnp.float32)
        if kv == "int8":
            pool = jnp.zeros((P, ps, heads * head_dim), jnp.int8)
            sc = jnp.zeros((P,), jnp.float32)
            pool, sc = write_prompt_kv_q8(pool, sc, table, kv_rows, valid)
            pools[name], scales[name] = write_token_kv_q8(
                pool, sc, table, kv_tok, pos)
        else:
            pool = jnp.full((P, ps, heads * head_dim), 37.0, jnp.float32)
            pool = write_prompt_kv(pool, table, kv_rows, valid)
            pools[name] = write_token_kv(pool, table, kv_tok, pos)
            scales[name] = None
        assert pools[name].shape == (P, ps, heads * head_dim)
    q = jnp.asarray(rng.standard_normal((B, heads, head_dim)), jnp.float32)
    ref = np.zeros((B, heads, head_dim))
    for b, n_live in enumerate(lens):
        ks = np.concatenate([rows["k"][b][:, :n_live],
                             tok["k"][b][:, None]], 1)     # [H, live+1, Dh]
        vs = np.concatenate([rows["v"][b][:, :n_live],
                             tok["v"][b][:, None]], 1)
        s = np.einsum("hd,hkd->hk", np.asarray(q)[b], ks) * head_dim ** -0.5
        p = np.exp(s - s.max(-1, keepdims=True))
        ref[b] = np.einsum("hk,hkd->hd", p / p.sum(-1, keepdims=True), vs)
    tol = dict(atol=0.05, rtol=0) if kv == "int8" else dict(
        rtol=2e-5, atol=2e-6)
    for arm in (xla_paged_decode, flash_decode):
        got = arm(q, pools["k"], pools["v"], table, pos,
                  scales["k"], scales["v"])
        np.testing.assert_allclose(np.asarray(got), ref, err_msg=arm.__name__,
                                   **tol)


def test_resolve_decode_impl_dispatch(monkeypatch):
    assert resolve_decode_impl("pallas") == "pallas"   # forced passes through
    assert resolve_decode_impl("xla") == "xla"
    if jax.default_backend() != "tpu":
        assert resolve_decode_impl("auto") == "xla"    # no TPU -> gather path
    with pytest.raises(ValueError, match="auto|pallas|xla"):
        resolve_decode_impl("cuda")
    # on a TPU the rule reads shapes and the pool's type, no model's name:
    # a row of whole lane tiles, a page block of whole tiles of the type
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for (ps, h, dh), kv, want in [
            ((16, 20, 64), jnp.bfloat16, "pallas"),    # GPT-2-large
            ((16, 12, 64), jnp.bfloat16, "pallas"),    # GPT-2-base
            ((16, 16, 128), jnp.bfloat16, "pallas"),
            ((16, 20, 64), jnp.int8, "pallas"),
            ((8, 20, 64), jnp.float32, "pallas"),
            ((8, 20, 64), jnp.bfloat16, "xla"),        # half a bf16 tile
            ((16, 5, 64), jnp.bfloat16, "xla"),        # row 320: 2.5 tiles
            ((16, 4, 8), jnp.bfloat16, "xla")]:
        assert resolve_decode_impl("auto", (9, ps, h, dh), kv) == want, (
            ps, h, dh, kv)
    assert resolve_decode_impl("auto") == "pallas"


def test_decode_hbm_bytes_counts_live_pages_only():
    """The byte model is the schedule: distinct LIVE pages x (K+V) — no
    copy is started for a last block's entries past the live prefix —
    q/out per slot, the step table; and it must scale with POSITION, not
    the page reservation."""
    ps, H, Dh = 4, 2, 8
    bt = np.asarray([[1, 2, 3], [4, 5, 6]])
    page = ps * H * Dh * 4
    qo = H * Dh * 4

    def tab(slots, n, quantized=False):  # G = n at these sizes: one block
        return slots * (6 + (3 if quantized else 1) * n) * 4

    got = decode_hbm_bytes(bt, np.asarray([0, 5]), ps, H, Dh)
    # slot 0: 1 live page; slot 1: 2 live pages; no trash page
    assert got == 3 * 2 * page + 2 * 2 * qo + tab(2, 3)
    assert decode_page_census(bt, np.asarray([0, 5]), ps) == (3, 3)
    # growing the reservation (dead tail) must not move the number
    bt_wide = np.concatenate([bt, np.full((2, 5), TRASH_PAGE)], 1)
    wide = decode_hbm_bytes(bt_wide, np.asarray([0, 5]), ps, H, Dh)
    assert wide == got - tab(2, 3) + tab(2, 8)     # only the table grows
    # a repeated page is fetched once
    shared = decode_hbm_bytes(np.asarray([[1, 1]]), np.asarray([7]),
                              ps, H, Dh)
    assert shared == 1 * 2 * page + 2 * qo + tab(1, 2)


def test_decode_hbm_bytes_dedups_shared_pages_across_slots():
    """ISSUE 20 satellite: dedup is by page-id SET across the whole
    schedule, not consecutive visits — a PrefixCache page shared by every
    slot is DMAd once. Hand count: slots [[1,2],[1,3]] both full — the
    pre-r22 consecutive-only dedup priced page 1 twice (4 page visits);
    the set census prices the 3 distinct pages."""
    ps, H, Dh = 4, 2, 8
    page = ps * H * Dh * 4
    qo = H * Dh * 4
    bt = np.asarray([[1, 2], [1, 3]])
    got = decode_hbm_bytes(bt, np.asarray([7, 7]), ps, H, Dh)
    assert got == 3 * 2 * page + 2 * 2 * qo + 2 * (6 + 2) * 4
    # int8 pool: pages priced at 1 byte/elt, q/out stay fp, the table
    # gains 2 G scale rows for the per-page scale pairs
    q8 = decode_hbm_bytes(bt, np.asarray([7, 7]), ps, H, Dh,
                          quantized=True)
    assert q8 == 3 * 2 * (ps * H * Dh) + 2 * 2 * qo + 2 * (6 + 3 * 2) * 4


def test_decode_hbm_bytes_follows_blocks_of_pages():
    """At 16-row pages a block is 16 pages: 40 pages a slot make 3 block
    columns a slot, each 6 + 16 words; a slot 260 positions deep has 17
    live pages in 2 live blocks, the second with ONE live entry (its third
    column is no step); a slot at the end of its reservation copies all 40
    of its pages, eight of them in its third block (40 is no multiple of
    16), and nothing for that block's other eight entries."""
    ps, H, Dh, n = 16, 2, 8, 40
    bt = 1 + np.arange(2 * n).reshape(2, n)
    page = ps * H * Dh * 4
    got = decode_hbm_bytes(bt, np.asarray([259, 639]), ps, H, Dh)
    assert got == (17 + 40) * 2 * page + 2 * 2 * H * Dh * 4 + (
        2 * 3 * (6 + 16) * 4)
    full = decode_hbm_bytes(bt, np.asarray([639, 639]), ps, H, Dh)
    assert full == 80 * 2 * page + 2 * 2 * H * Dh * 4 + 2 * 3 * 22 * 4
    assert decode_page_census(bt, np.asarray([639, 639]), ps) == (80, 80)


# ------------------------------------------- DecodeServer token identity

VOCAB, SEQ = 32, 16


@pytest.fixture(scope="module")
def serve_wl_params():
    wl = create_model_from_config(
        model_family="gpt2", vocab_size=VOCAB, seq_len=SEQ, hidden_size=32,
        num_layers=2, num_heads=2, dtype="float32")
    return wl, wl.init_params(jax.random.PRNGKey(3))


def test_decode_server_greedy_identical_pallas_vs_xla(serve_wl_params):
    """ISSUE 18 acceptance: greedy decode through the flash-decode kernel is
    token-for-token identical to the XLA paged path, and the kernel arm
    keeps the compile-exactly-once steady contract."""
    wl, params = serve_wl_params
    rng = np.random.default_rng(5)
    prompts = [rng.integers(4, VOCAB, (int(rng.integers(1, 8)),)).astype(
        np.int32) for _ in range(5)]
    outs, steady = {}, {}
    for impl in ("pallas", "xla"):
        srv = DecodeServer(wl, params, decode_slots=2, page_size=4,
                           max_prompt_len=8, max_len=SEQ, seed=0,
                           sanitize=True, decode_impl=impl)
        warm = srv.submit(prompts[0], max_new_tokens=2)
        srv.drain()
        after_warm = srv.recompile_count
        reqs = [warm] + [srv.submit(p, max_new_tokens=2 + i % 4)
                         for i, p in enumerate(prompts[1:])]
        srv.drain()
        outs[impl] = [r.tokens for r in reqs]
        steady[impl] = srv.recompile_count - after_warm
        assert srv.free_slots == 2
        assert srv.mgr.free_pages == srv.mgr.capacity
    assert outs["pallas"] == outs["xla"]
    assert steady["pallas"] == 0, \
        "flash-decode arm recompiled in steady state"
    assert steady["xla"] == 0


# ----------------------------------------------------------- fused update


def tiny_data(batch_size=8, seed=0):
    return load_data_from_args("train", batch_size=batch_size,
                               dataset="synthetic-lm", seq_len=16,
                               vocab_size=64, seed=seed)


def make_loop(tmp_path, **kw):
    kw.setdefault("batch_size", 8)
    kw.setdefault("lr", 1e-3)
    kw.setdefault("learning_steps", 1000)   # schedule state exercised
    kw.setdefault("log_interval", 10 ** 9)
    kw.setdefault("save_interval", 10 ** 9)
    kw.setdefault("mesh", make_mesh(dp=8))
    kw.setdefault("ema_rate", "0.9")
    kw.setdefault("seed", 5)
    data = kw.pop("data", None) or tiny_data(kw["batch_size"])
    wl = create_model_from_config(
        model_family="gpt2", vocab_size=64, seq_len=16, hidden_size=32,
        num_layers=2, num_heads=2, dtype="float32")
    return TrainLoop(model=wl, data=data, checkpoint_dir=str(tmp_path), **kw)


def test_fused_adamw_ema_matches_optax_chain():
    """One direct call against the staged optax chain on a random pytree:
    counts bit-identical; params, moments and EMA copies within 1 ulp
    (eager optax runs op-by-op while the kernel body compiles as one fused
    program, so FMA contraction may round a multiply-add once — inside the
    trainer BOTH paths are jitted and the losses are bitwise over the
    leading horizon, test below)."""
    rng = np.random.default_rng(23)
    lr, wd = 3e-3, 0.01
    params = {"w": jnp.asarray(rng.standard_normal((17, 9)), jnp.float32),
              "b": jnp.asarray(rng.standard_normal((9,)), jnp.float32)}
    grads = jax.tree_util.tree_map(
        lambda p: jnp.asarray(rng.standard_normal(p.shape), jnp.float32),
        params)
    opt = optax.adamw(lr, weight_decay=wd)
    state = opt.init(params)
    rates = {"0.9": params, "0.99": params}
    for _ in range(3):   # a few steps so counts/bias corrections move
        upd, state_ref = opt.update(grads, state, params)
        p_ref = optax.apply_updates(params, upd)
        e_ref = {r: jax.tree_util.tree_map(
            lambda e, p: e * float(r) + p * (1 - float(r)), rates[r], p_ref)
            for r in rates}
        p_f, state_f, e_f = fused_adamw_ema(
            params, grads, state, rates,
            lr_fn=lambda _c: jnp.asarray(lr, jnp.float32), weight_decay=wd)
        assert int(state_f[0].count) == int(state_ref[0].count)
        for a, b in zip(jax.tree_util.tree_leaves((p_ref, state_ref, e_ref)),
                        jax.tree_util.tree_leaves((p_f, state_f, e_f))):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=3e-7, atol=3e-7)
        params, state, rates = p_f, state_f, e_f


@pytest.mark.parametrize("zero1", [False, True],
                         ids=["unsharded", "zero1"])
def test_fused_trainer_losses_bit_identical(tmp_path, zero1):
    """ISSUE 18 acceptance: --fused_update must not change the math — the
    loss curve is bit-identical to the optax path over the leading horizon
    (the tail is pinned to closeness for the same 1-ulp fusion-rounding
    reason as the ZeRO-1 precedent), composed with --shard_optimizer in
    the second leg, where the per-replica state sharding must survive."""
    batches = [next(tiny_data(8, seed=1)) for _ in range(8)]
    loops = {f: make_loop(tmp_path / str(f), data=iter(batches),
                          shard_optimizer=zero1, fused_update=f)
             for f in (False, True)}
    losses = {f: [lp.run_step(b)["loss"] for b in batches]
              for f, lp in loops.items()}
    off = [float(x) for x in jax.device_get(losses[False])]
    on = [float(x) for x in jax.device_get(losses[True])]
    assert off[:4] == on[:4]
    np.testing.assert_allclose(off, on, rtol=2e-5)
    if zero1:  # fused path must keep the ZeRO layout, not regather it
        fp_f = loops[True].footprint()
        fp_o = loops[False].footprint()
        assert fp_f["opt_state_bytes_per_replica"] == \
            fp_o["opt_state_bytes_per_replica"]
        assert fp_f["ema_bytes_per_replica"] == \
            fp_o["ema_bytes_per_replica"]


def test_update_hbm_bytes_census():
    """(4+R) reads + (3+R) writes of every leaf plus the scalar row — the
    kernel-arm number the fusedupd bench leg lands."""
    params = {"a": jnp.zeros((10, 3)), "b": jnp.zeros((7,))}
    R, db = 2, 4
    got = update_hbm_bytes(params, n_ema_rates=R, dtype_bytes=db)
    assert got == sum((7 + 2 * R) * n * db + 3 * 4 * 128 for n in (30, 7))


# ------------------------------------------------------ vocab-parallel CE


@pytest.mark.parametrize("tp", [2, 4])
def test_vocab_parallel_xent_matches_replicated(tp):
    """The Megatron-style decomposition over vocab shards must reproduce
    the single-device NLL for targets owned by every shard (vmap with an
    axis name stands in for the tensor mesh axis — same collectives)."""
    rng = np.random.default_rng(29)
    B, T, V = 3, 5, 8 * tp
    logits = jnp.asarray(rng.standard_normal((B, T, V)), jnp.float32)
    targets = jnp.asarray(rng.integers(0, V, (B, T)), jnp.int32)
    ref = token_cross_entropy(logits, targets)
    shards = jnp.moveaxis(logits.reshape(B, T, tp, V // tp), 2, 0)
    got = jax.vmap(lambda l: token_cross_entropy(l, targets, axis_name="tp"),
                   axis_name="tp")(shards)
    for r in range(tp):  # identical on every rank, equal to the dense NLL
        np.testing.assert_allclose(np.asarray(got[r]), np.asarray(ref),
                                   rtol=1e-6, atol=1e-6)


def test_vocab_parallel_xent_bf16_inputs():
    """bf16 logits: statistics accumulate in f32 on both paths, so the
    sharded result tracks the replicated one at bf16 resolution."""
    rng = np.random.default_rng(31)
    B, T, V, tp = 2, 4, 16, 4
    logits = jnp.asarray(rng.standard_normal((B, T, V)),
                         jnp.bfloat16)
    targets = jnp.asarray(rng.integers(0, V, (B, T)), jnp.int32)
    ref = token_cross_entropy(logits, targets)
    shards = jnp.moveaxis(logits.reshape(B, T, tp, V // tp), 2, 0)
    got = jax.vmap(lambda l: token_cross_entropy(l, targets, axis_name="tp"),
                   axis_name="tp")(shards)
    assert bool(jnp.all(jnp.isfinite(got)))
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(ref),
                               rtol=2e-2, atol=2e-2)
