"""Compile a cell's programs at the REAL size for a described (not attached)
v5e:2x2 and print ``memory_analysis()`` a device. Costs no chip time; run it
before a chip call that might not fit. A compile that passes is not a run.

    JAX_PLATFORMS=cpu python benchmark/scratch/compile_described.py train \
        --config gpt2-large --traffic train-packed-1k \
        --set 'mesh={"data": 2, "fsdp": 2}' --set global_batch=32
    JAX_PLATFORMS=cpu python benchmark/scratch/compile_described.py serve \
        --config gpt2-large --traffic serve-closed-chat [--slots 48]

The train step is the trainer's own (``TrainLoop._plan_state`` gives the
layouts from shapes; the recipe is tests/test_chip_compile.py's). The serve
programs are COPIES of the bodies of ``DecodeEngine``'s ``prefill_fn`` and
``decode_fn`` (the engine allocates its pool where it defines them, which a
described device cannot hold): good for sizes, not for anything else.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)

import jax                      # noqa: E402
import jax.numpy as jnp         # noqa: E402
import numpy as np              # noqa: E402
from jax.sharding import Mesh  # noqa: E402

from harness import family_for  # noqa: E402


def load(kind: str, name: str):
    with open(os.path.join(HERE, kind, name + ".json")) as f:
        return json.load(f)


def sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def as_on_tpu():
    """Take the program's TPU branches although jax here sees the CPU."""
    from distributed_pipeline_tpu.ops import (flash_attention as fa,
                                              flash_decode as fd,
                                              fused_update as fu)
    for mod in (fa, fd, fu):
        mod._interpret = lambda: False
    jax.default_backend = lambda: "tpu"


def report(name: str, compiled, t0: float) -> None:
    ma = compiled.memory_analysis()
    gb = 1e9
    text = compiled.as_text()
    print(f"{name}: compiled in {time.time() - t0:.1f} s; a device: "
          f"arguments {ma.argument_size_in_bytes / gb:.3f} GB, temp "
          f"{ma.temp_size_in_bytes / gb:.3f} GB, output "
          f"{ma.output_size_in_bytes / gb:.3f} GB, aliased "
          f"{ma.alias_size_in_bytes / gb:.3f} GB (a program that does "
          f"not fit the 15.75 GB of HBM is refused, so this one fits); "
          f"tpu_custom_call x"
          f"{text.count('tpu_custom_call')}, all-gather x"
          f"{text.count(' all-gather')}, all-reduce x"
          f"{text.count(' all-reduce')}, reduce-scatter x"
          f"{text.count(' reduce-scatter')}", flush=True)


def compile_train(cfg, traffic, topo) -> None:
    from distributed_pipeline_tpu.models import create_model_from_config
    from distributed_pipeline_tpu.ops.fused_update import \
        resolve_fused_update
    from distributed_pipeline_tpu.parallel.mesh import AXES
    from distributed_pipeline_tpu.parallel.sharding import replicated
    from distributed_pipeline_tpu.utils.trainer import TrainLoop, TrainState

    axes = traffic.get("mesh", {})
    dp, fsdp = axes.get("data", 1), axes.get("fsdp", 1)
    shape = [1] * len(AXES)
    shape[AXES.index("data")], shape[AXES.index("fsdp")] = dp, fsdp
    mesh = Mesh(np.array(topo.devices[:dp * fsdp]).reshape(shape), AXES)
    wl = create_model_from_config(
        seq_len=traffic["seq_len"], remat=bool(traffic.get("remat", False)),
        **family_for(cfg).program_flags(cfg))
    lp = TrainLoop.__new__(TrainLoop)       # no state is allocated anywhere
    lp.workload, lp.mesh = wl, mesh
    lp.ema_rates = tuple(str(traffic["ema_rate"]).split(","))
    lp.lr, lp.learning_steps = traffic["lr"], traffic["learning_steps"]
    lp.warmup_steps, lp.weight_decay = 0, traffic.get("weight_decay", 0.0)
    lp.gradient_clipping, lp.partition_rules = -1.0, None
    lp.shard_optimizer = False
    lp.fused_update = resolve_fused_update("auto")
    lp._base_rng = jax.random.PRNGKey(0)
    lp.microbatch = traffic["microbatch"]
    lp.n_micro = traffic["global_batch"] // traffic["microbatch"]
    lp._note_compile = lambda *a: None
    abs_params, abs_opt = lp._plan_state()
    lp._build_step_fns()

    def shaped(tree, shardings):
        return jax.tree_util.tree_map(
            lambda a, sh: sds(a.shape, a.dtype, sh), tree, shardings)
    state = TrainState(
        step=sds((), jnp.int32, replicated(mesh)),
        params=shaped(abs_params, lp._pshard),
        opt_state=shaped(abs_opt, lp._oshard),
        ema={r: shaped(abs_params, lp._zshard) for r in lp.ema_rates})
    bs = lp._batch_sharding
    batch = {k: sds((lp.n_micro, lp.microbatch) + v.shape[1:], v.dtype,
                    bs[k] if isinstance(bs, dict) else bs)
             for k, v in wl.example_batch(1).items()}
    t0 = time.time()
    with mesh:
        c = lp._train_step._jitted.lower(state, batch).compile()
    report(f"train step {dict(data=dp, fsdp=fsdp)} batch "
           f"{traffic['global_batch']}/{traffic['microbatch']} remat "
           f"{traffic.get('remat', False)}", c, t0)


def compile_serve(cfg, traffic, topo, slots: int) -> None:
    from distributed_pipeline_tpu.models import create_model_from_config

    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    fam = family_for(cfg)
    wl = create_model_from_config(seq_len=fam.dims(cfg)["positions"],
                                  **fam.program_flags(cfg))
    ps, max_len = traffic["page_size"], traffic["max_len"]
    lp = traffic["max_prompt_len"]
    bp = traffic.get("prefill_batch", 0) or min(slots, 8)
    pages_per_slot = -(-max_len // ps)
    max_pages = 1 + slots * pages_per_slot
    dm = wl.model.clone(decode=True, moe_no_drop=True, paged_pages=max_pages,
                        page_size=ps, decode_impl="auto", kv_quant="fp")
    from flax import linen as nn
    params = nn.meta.unbox(jax.eval_shape(wl.init_params,
                                          jax.random.PRNGKey(0)))
    params = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype, one), params)
    cache = jax.eval_shape(
        lambda p, i, m, bt: dm.apply(p, i, m, block_table=bt,
                                     mutable=["cache"])[1]["cache"],
        params, sds((bp, lp), jnp.int32, one), sds((bp, lp), jnp.int32, one),
        sds((bp, pages_per_slot), jnp.int32, one))
    cache = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype, one), cache)
    pool = sum(int(np.prod(a.shape)) * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves(cache))
    print(f"serve: {slots} slots x {pages_per_slot} pages of {ps}: pool "
          f"{pool / 1e9:.3f} GB, prefill batch {bp} x {lp}", flush=True)

    def prefill_fn(p, cache, ids, prompt_lens, slot_tables):
        pad = (jnp.arange(ids.shape[1])[None, :]
               < prompt_lens[:, None]).astype(jnp.int32)
        logits, mvars = dm.apply({**p, "cache": cache}, ids, pad,
                                 block_table=slot_tables, mutable=["cache"])
        last = jnp.take_along_axis(
            logits, jnp.maximum(prompt_lens - 1, 0)[:, None, None],
            axis=1)[:, 0]
        return mvars["cache"], jnp.argmax(last, -1)

    def decode_fn(p, cache, tokens, positions, block_table):
        logits, mvars = dm.apply({**p, "cache": cache}, tokens[:, None],
                                 None, cache_index=positions,
                                 block_table=block_table, mutable=["cache"])
        return mvars["cache"], jnp.argmax(logits[:, 0], -1)

    i32 = jnp.int32
    t0 = time.time()
    c = jax.jit(prefill_fn, donate_argnums=(1,)).lower(
        params, cache, sds((bp, lp), i32, one), sds((bp,), i32, one),
        sds((bp, pages_per_slot), i32, one)).compile()
    report("serve prefill", c, t0)
    t0 = time.time()
    c = jax.jit(decode_fn, donate_argnums=(1,)).lower(
        params, cache, sds((slots,), i32, one), sds((slots,), i32, one),
        sds((slots, pages_per_slot), i32, one)).compile()
    report("serve decode", c, t0)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("kind", choices=("train", "serve"))
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--slots", type=int, default=0)
    ap.add_argument("--set", action="append", default=[],
                    help="override a traffic key: key=json")
    ns = ap.parse_args()
    cfg, traffic = load("configs", ns.config), load("traffic", ns.traffic)
    for kv in ns.set:
        k, v = kv.split("=", 1)
        traffic[k] = json.loads(v)
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    as_on_tpu()
    if ns.kind == "train":
        compile_train(cfg, traffic, topo)
    else:
        compile_serve(cfg, traffic, topo,
                      ns.slots or traffic["decode_slots"])


if __name__ == "__main__":
    main()
