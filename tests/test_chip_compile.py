"""Kernels of the main path, compiled for a chip that is described and not
attached (``v5e:2x2``), at GPT-2-base widths — what interpret mode cannot
show: slices off the tiling, too much fast memory, dot forms Mosaic does not
take, and kernels GSPMD cannot partition. A compile that passes is a
compile, not a chip run.

The topology is described inside a module-scoped fixture (never at import:
only one process may hold the TPU library, and under pytest-xdist every
worker imports every test file), and every case lives in this one file so
that a single worker owns the library. The tests steer ``_interpret``
themselves; the program grows no option for it. The persistent compile
cache is off around these compiles: a chip entry cannot be read back
without a chip and would only warn.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from distributed_pipeline_tpu.ops import attention as attention_ops
from distributed_pipeline_tpu.ops import flash_attention as fa
from distributed_pipeline_tpu.ops import flash_decode as fd
from distributed_pipeline_tpu.ops import fused_update as fu
from distributed_pipeline_tpu.parallel.mesh import AXES

RATES = (0.5, 0.9, 0.99)  # the default --ema_rate


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    """data=2, fsdp=2 over the described 2x2 — chip_smoke.py --chips 4's."""
    return Mesh(np.array(topo.devices).reshape((2, 2, 1, 1, 1, 1)), AXES)


@pytest.fixture
def as_on_tpu(monkeypatch):
    """Take the code's TPU branches although jax here sees the CPU: real
    Mosaic lowering instead of interpret mode, 'auto' arms as on a TPU."""
    for mod in (fa, fd, fu):
        monkeypatch.setattr(mod, "_interpret", lambda: False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def assert_kernel(compiled, name):
    lines = [ln for ln in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert any(name in ln for ln in lines), (name, len(lines))


# ------------------------------------------------------------ fused update

@pytest.mark.parametrize("shape", [(768, 3072), (768,), (50257, 768)],
                         ids=["mlp", "bias", "wte"])
def test_fused_update_leaf_compiles(one_chip, as_on_tpu, shape):
    def f(p, g, mu, nu, e0, e1, e2, scalars):
        return fu._leaf_update(p, g, mu, nu, [e0, e1, e2], scalars,
                               0.9, 0.999, 1e-8, 0.0, RATES)

    x = sds(shape, jnp.float32, one_chip)
    c = jax.jit(f).lower(*([x] * 7),
                         sds((3,), jnp.float32, one_chip)).compile()
    assert_kernel(c, fu.KERNEL_NAME)


@pytest.mark.parametrize("layout", ["fsdp", "zero1"])
def test_fused_update_sharded_leaf_compiles_on_mesh(mesh4, as_on_tpu, layout):
    """As the trainer calls it on a mesh: the leaf fsdp-sharded, the kernel
    under shard_map — on the param layout, and on the finer ZeRO-1 layout
    (the kernel then emits the update and the add follows the gather).
    Without the wrapper Mosaic refuses ("cannot be automatically
    partitioned") — the fault that kept every multi-chip run with default
    flags from compiling."""
    pspec = P("fsdp", None)
    spec = P("fsdp", "data") if layout == "zero1" else pspec
    psh, sh = NamedSharding(mesh4, pspec), NamedSharding(mesh4, spec)
    shape = (768, 3072)
    args = ([sds(shape, jnp.float32, psh)] * 2      # p, g
            + [sds(shape, jnp.float32, sh)] * 5     # mu, nu, 3 EMA copies
            + [sds((3,), jnp.float32, NamedSharding(mesh4, P()))])
    outs = (psh, sh, sh, [sh] * 3)

    def wrapped(p, g, mu, nu, e0, e1, e2, scalars):
        return fu._leaf_update_on_mesh(mesh4, spec, pspec, p, g, mu, nu,
                                       [e0, e1, e2], scalars,
                                       0.9, 0.999, 1e-8, 0.0, RATES)

    def bare(p, g, mu, nu, e0, e1, e2, scalars):
        return fu._leaf_update(p, g, mu, nu, [e0, e1, e2], scalars,
                               0.9, 0.999, 1e-8, 0.0, RATES)

    c = jax.jit(wrapped, out_shardings=outs).lower(*args).compile()
    assert_kernel(c, fu.KERNEL_NAME)
    # per device: p and g halved (fsdp=2), the other five operands halved
    # or, under ZeRO-1, quartered
    per_leaf = 768 * 3072 * 4
    want = (2 / 2 + 5 / (4 if layout == "zero1" else 2)) * per_leaf
    assert c.memory_analysis().argument_size_in_bytes < 1.05 * want
    with pytest.raises(NotImplementedError, match="shard_map"):
        jax.jit(bare, out_shardings=outs).lower(*args).compile()


# --------------------------------------------------------- flash attention

def _flash_case(form, B):
    q = (B, 12, 1024, 64)
    if form == "causal":
        return q, None, True
    return q, (B, 1024), False  # DiffuSeq: bidirectional + pad mask


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd_bwd"])
@pytest.mark.parametrize("form", ["causal", "pad_mask"])
def test_flash_attention_compiles(one_chip, as_on_tpu, form, grad):
    qshape, mshape, causal = _flash_case(form, 8)
    q = sds(qshape, jnp.bfloat16, one_chip)
    args = [q, q, q]
    if mshape:
        args.append(sds(mshape, jnp.int32, one_chip))

    def fwd(q_, k_, v_, m_=None):
        return fa.flash_attention(q_, k_, v_, m_, causal)

    def loss(q_, k_, v_, m_=None):
        return fwd(q_, k_, v_, m_).astype(jnp.float32).sum()

    fn = jax.grad(loss, argnums=(0, 1, 2)) if grad else fwd
    c = jax.jit(fn).lower(*args).compile()
    assert_kernel(c, fa.FWD_KERNEL_NAME)
    if grad:
        assert_kernel(c, fa.BWD_KERNEL_NAME)


def test_flash_attention_batch_split_compiles_on_mesh(mesh4, as_on_tpu):
    """The dispatcher's 'auto' at seq 1024 on the 2x2 mesh: the flash
    kernel under shard_map, batch split four ways, sequence whole."""
    sh = NamedSharding(mesh4, P(("data", "fsdp"), None, None, None))
    q = sds((8, 12, 1024, 64), jnp.bfloat16, sh)

    def auto(q_, k_, v_):
        return attention_ops.dot_product_attention(q_, k_, v_, None, True)

    with mesh4:
        c = jax.jit(auto, out_shardings=sh).lower(q, q, q).compile()
        assert_kernel(c, fa.FWD_KERNEL_NAME)
        with pytest.raises(NotImplementedError, match="shard_map"):
            jax.jit(lambda q_, k_, v_: fa.flash_attention(
                q_, k_, v_, None, True),
                out_shardings=sh).lower(q, q, q).compile()


# ---------------------------------------------------------- the whole step

@pytest.mark.parametrize("zero1", [False, True], ids=["fsdp", "zero1"])
def test_sharded_train_step_compiles_on_mesh(mesh4, as_on_tpu, zero1):
    """The trainer's own step, default arms, lowered from shapes for the
    described 2x2 mesh (``TrainLoop._plan_state`` gives the layouts with no
    device work): a thin GPT-2 at seq 1024, so 'auto' is flash attention
    and the fused update, under the trainer's own shardings, scan and
    constraints. On the seed this raised "Mosaic kernels cannot be
    automatically partitioned" — with default flags, on any mesh."""
    from distributed_pipeline_tpu.models import create_model_from_config
    from distributed_pipeline_tpu.ops.fused_update import \
        resolve_fused_update
    from distributed_pipeline_tpu.parallel.sharding import replicated
    from distributed_pipeline_tpu.utils.trainer import TrainLoop, TrainState

    wl = create_model_from_config(
        model_family="gpt2", vocab_size=1000, seq_len=1024, hidden_size=128,
        num_layers=2, num_heads=2, dtype="bfloat16")
    lp = TrainLoop.__new__(TrainLoop)  # no state is allocated anywhere
    lp.workload, lp.mesh, lp.ema_rates = wl, mesh4, ("0.9", "0.99")
    lp.lr, lp.learning_steps, lp.warmup_steps, lp.weight_decay = \
        1e-3, 10, 0, 0.0
    lp.gradient_clipping, lp.partition_rules = -1.0, None
    lp.shard_optimizer = zero1
    lp.fused_update = resolve_fused_update("auto")
    lp._base_rng = jax.random.PRNGKey(0)
    lp.microbatch, lp.n_micro = 4, 2
    lp._note_compile = lambda *a: None
    assert lp.fused_update
    abs_params, abs_opt = lp._plan_state()
    lp._build_step_fns()

    def shaped(tree, shardings):
        return jax.tree_util.tree_map(
            lambda a, sh: sds(a.shape, a.dtype, sh), tree, shardings)

    state = TrainState(
        step=sds((), jnp.int32, replicated(mesh4)),
        params=shaped(abs_params, lp._pshard),
        opt_state=shaped(abs_opt, lp._oshard),
        ema={r: shaped(abs_params, lp._zshard) for r in lp.ema_rates})
    bs = lp._batch_sharding
    batch = {k: sds((lp.n_micro, lp.microbatch) + v.shape[1:], v.dtype,
                    bs[k] if isinstance(bs, dict) else bs)
             for k, v in wl.example_batch(1).items()}
    with mesh4:
        c = lp._train_step._jitted.lower(state, batch).compile()
    for name in (fa.FWD_KERNEL_NAME, fa.BWD_KERNEL_NAME, fu.KERNEL_NAME):
        assert_kernel(c, name)


# ------------------------------------------------------------ flash decode

def _decode_args(one_chip, kv_dtype, geom, L=0):
    """``geom`` = (slots, heads, head_dim): 16-token pages, 1024 tokens a
    slot."""
    B, H, Dh = geom
    ps, n = 16, 64
    qshape = (B, H, L, Dh) if L else (B, H, Dh)
    pool = sds((1 + B * n, ps, H * Dh), kv_dtype, one_chip)
    args = [sds(qshape, jnp.bfloat16, one_chip), pool, pool,
            sds((B, n), jnp.int32, one_chip),
            sds((B, L) if L else (B,), jnp.int32, one_chip)]
    if kv_dtype == jnp.int8:
        sc = sds((1 + B * n,), jnp.float32, one_chip)
        args += [sc, sc]
    return args


def kernel_calls(text, name):
    return [ln for ln in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in ln and name in ln]


@pytest.mark.parametrize("geom", [(8, 16, 128), (16, 20, 64), (16, 12, 64)],
                         ids=["H16xDh128", "H20xDh64", "H12xDh64"])
@pytest.mark.parametrize("kv", ["bf16", "int8"])
@pytest.mark.parametrize("span", [0, 4], ids=["decode", "span4"])
def test_flash_decode_compiles(one_chip, as_on_tpu, kv, span, geom):
    """The head-free kernel at the shape the old form ran on the chip and
    at GPT-2-large's and GPT-2-base's (``Dh`` 64, the cell's 16 slots x 64
    pages of 16): 'auto' picks it by the shape rule, and the compiler takes
    it as ONE custom call."""
    kv_dtype = jnp.int8 if kv == "int8" else jnp.bfloat16
    args = _decode_args(one_chip, kv_dtype, geom, span)
    q = args[0].shape                       # H, Dh come from the query
    assert fd.resolve_decode_impl(
        "auto", args[1].shape[:2] + (q[1], q[-1]), kv_dtype) == "pallas"
    seam = fd.paged_span_attention if span else fd.paged_decode_attention

    def f(q, pk, pv, bt, pos, sk=None, sv=None):
        return seam(q, pk, pv, bt, pos, impl="auto", scales_k=sk,
                    scales_v=sv)

    c = jax.jit(f).lower(*args).compile()
    assert len(kernel_calls(c.as_text(), fd.KERNEL_NAME)) == 1


# --------------------------------------- the serving programs and the pool

_HLO_OP = re.compile(r"= (\w+)\[([\d,]*)\]\{[^}]*\} ([\w-]+)\(")


def results_of_size(text, n_elements, op=None, exact=False):
    """Results of a compiled program's ops (all, or those named ``op``)
    that hold at least — or exactly — ``n_elements``."""
    found = []
    for ln in text.splitlines():
        m = _HLO_OP.search(ln)
        if not m or (op and m.group(3) != op):
            continue
        size = np.prod([int(d) for d in m.group(2).split(",") if d])
        if size == n_elements or (not exact and size > n_elements):
            found.append(f"{m.group(1)}[{m.group(2)}] {m.group(3)}")
    return found


def entry_parameters(text):
    """(dtype, dims) of the compiled program's own arguments: the
    ``parameter`` lines of its ENTRY computation (a fusion's body has
    parameters of its own)."""
    entry = text[text.index("\nENTRY "):]
    entry = entry[:entry.index("\n}")]
    found = []
    for ln in entry.splitlines():
        m = _HLO_OP.search(ln)
        if m and m.group(3) == "parameter":
            found.append((m.group(1), tuple(
                int(d) for d in m.group(2).split(",") if d)))
    return found


@pytest.mark.parametrize("heads", [12, 20])
@pytest.mark.parametrize("kv_quant", ["fp", "int8"], ids=["bf16", "int8"])
@pytest.mark.parametrize("program", ["decode", "prefill", "prefill1",
                                     "span4"])
def test_serving_programs_do_not_relayout_the_pool(one_chip, as_on_tpu,
                                                   program, kv_quant, heads):
    """The engine's own program bodies at GPT-2 widths (``Dh`` 64), two
    layers, 16 slots x 64 pages of 16. With ``Dh`` alone in the lanes the
    chip's compiler stored each ``[P, 16, H, 64]`` pool page-minor and
    copied it to row-major and back in every program (62 % of the serve
    cell's device time, PERF.md PR 28); stored ``[P, 16, H * 64]`` no
    program copies anything of a pool's size. Since PR 30 'auto' resolves
    the decode step and the verify span to the flash-decode kernel at these
    shapes: one call a layer, and the gathered view of every slot's
    reservation (``[slots, pages * page_size, H * Dh]``, then its head
    split: 30 % of the cell's device time, PERF.md PR 30) is written
    nowhere. The prefill attends the prompt's own K/V in XLA (prompts
    under 1024) and holds no kernel; it is held at eight rows (what a
    caller may still ask for) and, as ``prefill1``, at the ONE row the
    engine's token budget resolves for ``max_prompt_len`` 512 (PR 32: the
    shape the serve cell runs).

    Since PR 34 the engine, built on a described float32 tree, holds and
    compiles against the model's serving form: no program takes a block
    matrix or the head's table in float32 or converts one (3.09 GB read
    and cast in every decode step of GPT-2-large, PERF.md PR 34); the
    float32 arguments left are the LayerNorm vectors, an int8 pool's
    scales, and the two embedding tables the lookup gathers from."""
    from flax import linen as nn

    from distributed_pipeline_tpu.models import create_model_from_config
    from distributed_pipeline_tpu.serving.engine import DecodeEngine

    slots, ps, n, lp, bp, span, layers = 16, 16, 64, 512, 8, 4, 2
    wl = create_model_from_config(
        model_family="gpt2", vocab_size=1000, seq_len=n * ps,
        hidden_size=64 * heads, num_layers=layers, num_heads=heads,
        dtype="bfloat16")

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: sds(a.shape, a.dtype, one_chip), tree)

    params = on_chip(nn.meta.unbox(
        jax.eval_shape(wl.init_params, jax.random.PRNGKey(0))))
    eng = DecodeEngine(wl, params, decode_slots=slots, page_size=ps,
                       max_pages=1 + slots * n, max_prompt_len=lp,
                       prefill_batch=0 if program == "prefill1" else bp,
                       kv_quant=kv_quant,
                       spec_tokens=span if program == "span4" else 0)
    if program == "prefill1":
        bp = eng.prefill_batch
        assert bp == 1
    pools = [leaf for _, leaf in eng._pool_leaves() if leaf.ndim > 1]
    assert len(pools) == 4 and {p.size for p in pools} == {
        (1 + slots * n) * ps * heads * 64}
    cache = on_chip(eng.cache)

    def i32(*shape):
        return sds(shape, jnp.int32, one_chip)

    key = sds((2,), jnp.uint32, one_chip)
    state = (i32(slots), i32(slots))            # tokens, positions
    if program == "decode":
        step, args = eng._decode_step, (
            *state, i32(slots, n), i32(slots), key)
    elif program.startswith("prefill"):
        step, args = eng._prefill_step, (
            i32(bp, lp), i32(bp), i32(bp), i32(bp, n), *state, key)
    else:
        step, args = eng._verify_step, (
            i32(span, slots), *state, i32(slots, n), i32(slots), key)
    assert eng.weights["leaves_cast"] == 4 * layers + 1
    text = step._jitted.lower(eng.params, cache, *args).compile().as_text()
    assert results_of_size(text, pools[0].size, op="copy") == []
    d, vocab = 64 * heads, 1000
    given = entry_parameters(text)
    matrices = {(d, 3, heads, 64), (heads, 64, d), (d, 4 * d), (4 * d, d)}
    assert {dims for dt, dims in given if dt == "bf16"} >= matrices | {
        (vocab, d)}
    assert {dims for dt, dims in given if dt == "f32" and len(dims) > 1} \
        == {(vocab, d), (n * ps, d)}
    assert {dims for dt, dims in given if dt == "f32" and len(dims) == 1} \
        <= {(d,), (1 + slots * n,)}
    for size in (3 * d * d, d * d, 4 * d * d, vocab * d):
        assert results_of_size(text, size, op="convert", exact=True) == []
    if program.startswith("prefill"):
        assert "tpu_custom_call" not in text
        return
    assert fd.resolve_decode_impl(
        "auto", (1 + slots * n, ps, heads, 64), pools[0].dtype) == "pallas"
    assert len(kernel_calls(text, fd.KERNEL_NAME)) == layers
    assert results_of_size(text, slots * n * ps * heads * 64,
                           exact=True) == []


@pytest.mark.parametrize("h, dq", [(128, 192), (64, 256), (32, 128)],
                         ids=["full_h128_dq192", "window_h64_dq256",
                              "gqa_h32_dq128"])
def test_mla_block_attend_compiles_at_published_widths(one_chip, h, dq):
    """The chunked MLA prefill's attention block (ops/mla_attention.py) at
    the widths of DeepSeek-V3.2-Exp's layers and dots3-note-prev's full
    layers (128 heads, 192-wide queries and keys, 128-wide values), of
    dots3-note-prev's window layers (64 heads, 256-wide) and of
    Keye-VL-2.0's grouped-query layers (32 query heads of 128, each on its
    key head's repeated block), a chunk of 1024
    queries against a block of 512 keys: the contraction, the [1, queries]
    statistics rows and the carry updated in place are what interpret mode
    cannot refuse."""
    from distributed_pipeline_tpu.ops import mla_attention as ma

    dv, c, k = 128, 1024, 512
    carry = (sds((h, 1, c), jnp.float32, one_chip),
             sds((h, 1, c), jnp.float32, one_chip),
             sds((h, dv, c), jnp.float32, one_chip))
    compiled = ma.block_attend.lower(
        sds((h, dq, c), jnp.bfloat16, one_chip),
        sds((h, k, dq), jnp.bfloat16, one_chip),
        sds((h, dv, k), jnp.bfloat16, one_chip),
        sds((k, c), jnp.float32, one_chip), carry,
        scale=0.1352).compile()
    assert_kernel(compiled, ma.KERNEL_NAME)


@pytest.mark.parametrize("j, di", [(64, 128), (16, 64)],
                         ids=["j64_di128", "j16_di64"])
def test_lightning_index_scores_compiles_at_published_widths(one_chip, j,
                                                             di):
    """The indexer's score block (64 heads of 128, the latent families';
    16 heads of 64, Keye-VL-2.0's; 1024 queries against 512 keys): a head a
    grid step, the weighted sum resident in VMEM."""
    from distributed_pipeline_tpu.ops import mla_attention as ma

    c, k = 1024, 512
    compiled = ma.index_scores.lower(
        sds((j, di, c), jnp.bfloat16, one_chip),
        sds((j, 1, c), jnp.float32, one_chip),
        sds((k, di), jnp.bfloat16, one_chip)).compile()
    assert_kernel(compiled, ma.INDEX_KERNEL_NAME)


@pytest.mark.parametrize("tokens", [1024, 16], ids=["chunk", "decode_step"])
def test_grouped_expert_matmul_compiles_at_published_widths(one_chip,
                                                            tokens):
    """The expert layer's kernel (ops/grouped_matmul.py) at Keye-VL-2.0's
    sizes (128 experts of 2048 x 768, 8 a token; a chunk's 8,192
    assignments in tiles of 128 rows, a decode step's 128 in tiles of 16):
    the tokens' own block resident (4 MB a chunk, twice), the row table a
    ``(tile, 1)`` block a grid step and the tile's rows made from both on
    the matrix unit, an expert's three matrices double-buffered in VMEM
    (19 MB), the scalar-prefetched tile table in the weights' index maps."""
    from distributed_pipeline_tpu.ops import grouped_matmul as gm

    e, d, f, k = 128, 2048, 768, 8
    rows = tokens * k
    tile = gm.row_tile(rows)
    padded = gm.padded_rows(rows, e, tile)
    assert (tile, padded) == {8192: (128, 24448), 128: (16, 2048)}[rows]
    assert gm.rows_stay_resident(tokens, d, jnp.bfloat16)
    compiled = gm.grouped_swiglu.lower(
        sds((tokens, d), jnp.bfloat16, one_chip),
        sds((padded,), jnp.int32, one_chip),
        sds((e, d, f), jnp.bfloat16, one_chip),
        sds((e, d, f), jnp.bfloat16, one_chip),
        sds((e, f, d), jnp.bfloat16, one_chip),
        sds((padded // tile,), jnp.int32, one_chip),
        sds((1,), jnp.int32, one_chip), tile=tile).compile()
    assert_kernel(compiled, gm.KERNEL_NAME)
