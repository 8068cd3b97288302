"""Driver for traffic of kind ``train``: the program's own ``TrainLoop``,
built as ``run.train`` builds it, fed through its loader and prefetch, driven
by ``next_batch`` -> ``run_step``.

Set-up builds ONE loop (the compiled step with its state), replaces its
freshly initialised parameters by the benchmark's own seed-made weights,
drives it through its first steps (three; two where the traffic file says so)
with the window's own call and feed,
and hands that same loop to the window. What those three steps produced —
each step's loss, the first gradient as the optimizer got it (Adam's first
moment after one step is 0.1 of it), the parameters' change after three — is
compared once the window has closed with the plain reference following the
same three batches (``check_train``).
"""

from __future__ import annotations

import contextlib
import gc
import math
import shutil
import statistics
import tempfile
import time
from typing import Any, Dict, List, Sequence

import numpy as np

from . import family_for, reference_for
from .corpus import make_corpus
from .stats import rate

FOLLOWED_STEPS = 3


# ------------------------------------------------------------ the comparison

def worst_leaf_gap(prog: Dict[str, float], want: Dict[str, float],
                   skip: frozenset = frozenset()) -> Dict[str, Any]:
    """Largest over the leaves of |program's norm - reference's norm| against
    the reference's norm of that leaf or of the median leaf, whichever is
    larger (some gradients are all but zero)."""
    med = statistics.median(want.values())
    worst, at = 0.0, ""
    for k, r in want.items():
        if k in skip:
            continue
        gap = abs(prog[k] - r) / max(r, med, 1e-30)
        if not math.isfinite(gap):
            gap = float("inf")
        if gap >= worst:
            worst, at = gap, k
    return {"value": worst, "leaf": at}


def check_train(got: Dict[str, Any], want: Dict[str, Any],
                limits: Dict[str, float]) -> Dict[str, Dict[str, Any]]:
    """Each number compared, beside its limit. ``got``/``want`` carry
    ``losses``, ``grad_norms``, ``delta_norms`` (``train_steps``' shape).
    Leaves whose reference gradient is under a thousandth of the median
    leaf's move under Adam by round-off alone and are left out of the
    change."""
    gmed = statistics.median(want["grad_norms"].values())
    still = frozenset(k for k, g in want["grad_norms"].items()
                      if g < 1e-3 * gmed)
    n = min(len(got["losses"]), len(want["losses"]))
    loss_gap = max(abs(a - b) / max(abs(b), 1e-30) for a, b in
                   zip(got["losses"][:n], want["losses"][:n]))
    grad = worst_leaf_gap(got["grad_norms"], want["grad_norms"])
    delta = worst_leaf_gap(got["delta_norms"], want["delta_norms"], still)
    if not math.isfinite(loss_gap):
        loss_gap = float("inf")
    out = {
        "loss_gap": {"value": loss_gap, "limit": limits["loss_gap"]},
        "grad_norm_gap": {"value": grad["value"], "leaf": grad["leaf"],
                          "limit": limits["grad_norm_gap"]},
        "delta_norm_gap": {"value": delta["value"], "leaf": delta["leaf"],
                           "limit": limits["delta_norm_gap"],
                           "leaves_left_out": len(still)},
    }
    for row in out.values():
        row["ok"] = bool(row["value"] <= row["limit"])
    return out


def rows_all_differ(batches: List[Dict[str, np.ndarray]]) -> bool:
    rows = np.concatenate([b["input_ids"] for b in batches])
    return len({r.tobytes() for r in rows}) == len(rows)


# ---------------------------------------------------------------- the program

def hyper(traffic: Dict[str, Any]) -> Dict[str, Any]:
    return {"lr": traffic["lr"], "learning_steps": traffic["learning_steps"],
            "weight_decay": traffic.get("weight_decay", 0.0),
            "b1": 0.9, "b2": 0.999, "eps": 1e-8}


def build_loop(cfg: Dict[str, Any], traffic: Dict[str, Any], seed: int,
               data_dir: str, devices: List[Any]):
    """The loop as ``run.train`` builds it from its own flags."""
    from distributed_pipeline_tpu.config.train import TrainSettings
    from distributed_pipeline_tpu.data import load_data_from_args
    from distributed_pipeline_tpu.models import create_model_from_config
    from distributed_pipeline_tpu.parallel.mesh import make_mesh
    from distributed_pipeline_tpu.utils.trainer import TrainLoop

    mesh_axes = traffic.get("mesh", {})
    argv = [x for k, v in family_for(cfg).program_flags(cfg).items()
            for x in ("--" + k, str(v))]
    argv += ["--seq_len", str(traffic["seq_len"]),
            "--data_dir", data_dir, "--seed", str(seed % (2 ** 31)),
            "--batch_size", str(traffic["global_batch"]),
            "--microbatch", str(traffic["microbatch"]),
            "--lr", str(traffic["lr"]),
            "--learning_steps", str(traffic["learning_steps"]),
            "--ema_rate", str(traffic["ema_rate"]),
            "--weight_decay", str(traffic.get("weight_decay", 0.0)),
            "--remat", str(bool(traffic.get("remat", False))).lower(),
            "--fsdp", str(mesh_axes.get("fsdp", 1)),
            "--dp", str(mesh_axes.get("data", 1))]
    args = TrainSettings.from_argv(argv)
    mesh = make_mesh(dp=args.dp, fsdp=args.fsdp, devices=devices)
    loop = TrainLoop(
        model=create_model_from_config(**args.dict()),
        data=load_data_from_args("train", **args.dict()),
        batch_size=args.batch_size, microbatch=args.microbatch, lr=args.lr,
        ema_rate=args.ema_rate, weight_decay=args.weight_decay,
        learning_steps=args.learning_steps, log_interval=10 ** 9,
        eval_interval=10 ** 9, save_interval=10 ** 9, mesh=mesh,
        seed=args.seed, prefetch_depth=args.prefetch_depth,
        dispatch_lag=args.dispatch_lag, fused_update=args.fused_update,
        sanitize=True)
    return loop


def seed_state(loop, cfg: Dict[str, Any], seed: int) -> None:
    """Put the benchmark's own weights in place of the loop's fresh init:
    made on the device in one jitted call, in the loop's own layouts; EMA
    copies restart from them, Adam's moments stay zero."""
    import jax
    import jax.numpy as jnp

    ref, fam = reference_for(cfg), family_for(cfg)
    shard = jax.tree_util.tree_map(lambda a: a.sharding, loop.state.params)
    make = jax.jit(
        lambda s: fam.to_program_tree(ref.make_weights(cfg, s), cfg),
        out_shardings=shard)
    with loop.mesh:
        params = make(ref.seed_arg(seed))
        ema = {r: jax.jit(
            lambda p: jax.tree_util.tree_map(jnp.copy, p),
            out_shardings=jax.tree_util.tree_map(lambda a: a.sharding, e))(
                params) for r, e in loop.state.ema.items()}
    loop.state = loop.state.replace(params=params, ema=ema)


def _adam_mu(opt_state) -> Any:
    for part in opt_state:
        if hasattr(part, "mu"):
            return part.mu
    raise RuntimeError("no Adam first moment in the optimizer state")


def follow_first_steps(loop, cfg: Dict[str, Any], seed: int,
                       n_steps: int = FOLLOWED_STEPS) -> Dict[str, Any]:
    """Drive the loop's first steps through the window's own call and feed;
    keep what the comparison needs (a few scalars a leaf and the three
    batches' ids)."""
    import jax
    import jax.numpy as jnp

    ref, fam = reference_for(cfg), family_for(cfg)
    norms = jax.jit(lambda t: {
        k: jnp.sqrt(jnp.sum(jnp.square(x)))
        for k, x in fam.from_program_tree(t, cfg).items()})
    shard = jax.tree_util.tree_map(lambda a: a.sharding, loop.state.params)

    def change(p, s):
        p0 = jax.lax.with_sharding_constraint(
            fam.to_program_tree(ref.make_weights(cfg, s), cfg), shard)
        return jax.tree_util.tree_map(lambda a, b: a - b, p, p0)
    delta_norms = jax.jit(lambda p, s: {
        k: jnp.sqrt(jnp.sum(jnp.square(x)))
        for k, x in fam.from_program_tree(change(p, s), cfg).items()})

    got: Dict[str, Any] = {"losses": [], "batches": []}
    for i in range(n_steps):
        batch = loop.next_batch()
        arrays = getattr(batch, "arrays", batch)
        host = {k: np.asarray(jax.device_get(v)) for k, v in arrays.items()}
        got["batches"].append({
            k: v.reshape((-1,) + v.shape[-1:]) for k, v in host.items()})
        metrics = loop.run_step(batch)
        got["losses"].append(float(jax.device_get(metrics["loss"])))
        if i == 0:
            with loop.mesh:
                mu = jax.device_get(norms(
                    {"params": _adam_mu(loop.state.opt_state)["params"]}))
            got["grad_norms"] = {k: float(v) / (1.0 - 0.9)
                                 for k, v in mu.items()}
    with loop.mesh:
        got["delta_norms"] = {k: float(v) for k, v in jax.device_get(
            delta_norms(loop.state.params, ref.seed_arg(seed))).items()}
    return got


def weight_specs(cfg: Dict[str, Any], n: int) -> Dict[str, Any]:
    """How the reference's weights are split over ``n`` chips (mesh axis
    ``x``): each on its largest dimension that divides, else whole."""
    from jax.sharding import PartitionSpec as P

    def spec(shape):
        for ax in sorted(range(len(shape)), key=lambda a: -shape[a]):
            if shape[ax] % n == 0 and shape[ax] >= n:
                return P(*[("x" if a == ax else None)
                           for a in range(len(shape))])
        return P()
    return {k: spec(s) for k, s in
            reference_for(cfg).param_shapes(cfg).items()}


def reference_steps(cfg: Dict[str, Any], traffic: Dict[str, Any], seed: int,
                    batches: List[Dict[str, np.ndarray]], devices: List[Any],
                    precision: str = "float32") -> Dict[str, Any]:
    """The plain reference over the same batches, from the same seed's
    weights. Over several chips its weights are split on their largest
    dimension so that it fits; the arithmetic is unchanged."""
    import jax
    from jax.sharding import Mesh, NamedSharding

    ref = reference_for(cfg)
    n = len(devices)
    if n > 1:
        mesh = Mesh(np.array(devices), ("x",))
        out = {k: NamedSharding(mesh, s)
               for k, s in weight_specs(cfg, n).items()}
        make = jax.jit(lambda s: ref.make_weights(cfg, s), out_shardings=out)
    else:
        make = jax.jit(lambda s: ref.make_weights(cfg, s))
    w0 = make(ref.seed_arg(seed))
    return ref.train_steps(
        w0, cfg, batches, hyper(traffic),
        rows_per_block=traffic["reference_rows_per_block"],
        precision=precision)


CONTROLS = ("int8", "fp8", "bfloat16", "half_batch")


def read_controls(cfg, traffic, seed, batches, devices, want,
                  names: Sequence[str] = CONTROLS) -> Dict[str, Any]:
    """Calibration only: the reference put in the program's place in the
    precisions below the configuration's (int8, the one the v5e's matrix
    unit has, and fp8), in the configuration's own (bfloat16), and with half
    of each batch left out (what a data-parallel step whose exchange between
    chips is left out computes on each replica), each compared with the
    float32 reference exactly as the program is."""
    if "1" in names:
        names = CONTROLS
    out: Dict[str, Any] = {}
    for name in names:
        if name == "half_batch":
            rows = [{k: v[:len(v) // 2] for k, v in b.items()}
                    for b in batches]
            got = reference_steps(cfg, traffic, seed, rows, devices)
        else:
            got = reference_steps(cfg, traffic, seed, batches, devices, name)
        out[name] = check_train(got, want, traffic["limits"])
    return out


# ------------------------------------------------------------------- a run

def run(cell: Dict[str, Any], cfg: Dict[str, Any], traffic: Dict[str, Any],
        *, seed: int, seconds: float, trace: bool, devices: List[Any],
        t_process: float, annotate, profiler,
        control: Sequence[str] = ()) -> Dict[str, Any]:
    """One run of a train cell. ``annotate(name)`` is a context manager that
    labels host time in the trace; ``profiler`` has ``start()`` and
    ``stop() -> TraceSummary`` (run.py owns both)."""
    import jax

    chips = len(devices)
    marks: List[List[Any]] = []

    def mark(name: str) -> None:
        marks.append([name, time.perf_counter() - t_process])
    mark("imports")
    data_dir = tempfile.mkdtemp(prefix="bench_corpus_")
    try:
        c = traffic["corpus"]
        make_corpus(data_dir, seed, n_words=c["n_words"],
                    words_per_side=c["words_per_side"],
                    n_train=c["n_train_lines"])
        from distributed_pipeline_tpu.utils import logger
        logger.configure(dir=data_dir, format_strs=["log"])
        loop = build_loop(cfg, traffic, seed, data_dir, devices)
        mark("loop_built")
        seed_state(loop, cfg, seed)
        jax.block_until_ready(loop.state.params)
        mark("weights_from_seed")
        first = follow_first_steps(
            loop, cfg, seed, traffic.get("reference_steps", FOLLOWED_STEPS))
        mark("first_steps_followed")
        batches = first.pop("batches")

        tokens_per_step = traffic["global_batch"] * traffic["seq_len"]
        traced: Dict[str, Any] = {}
        steps = 0
        jax.block_until_ready(loop.state)
        recompiles0 = loop.steady_recompile_count
        t0 = time.perf_counter()
        setup_s = t0 - t_process
        if trace:
            profiler.start()
            t_trace0, steps_trace0 = time.perf_counter(), 0
        while True:
            now = time.perf_counter()
            if trace and not traced and (
                    now - t_trace0 >= profiler.seconds
                    or now - t0 >= seconds):
                jax.block_until_ready(loop.state)
                t_trace1 = time.perf_counter()
                traced = {"steps": steps - steps_trace0,
                          "seconds": t_trace1 - t_trace0,
                          "summary": profiler.stop()}
                continue
            if now - t0 >= seconds:
                break
            with annotate("next_batch"):
                batch = loop.next_batch()
            with annotate("run_step"):
                metrics = loop.run_step(batch)
            steps += 1
        with annotate("harness"):
            jax.block_until_ready(loop.state)
        t1 = time.perf_counter()
        window_s = t1 - t0
        loop.flush_metrics()
        last_loss = float(jax.device_get(metrics["loss"])) if steps else None
        recompiles = loop.steady_recompile_count - recompiles0
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices)
        n_ema = len(loop.ema_rates)
        evidence = loop.program_evidence()
        loop.stop_sanitizer()
        close = getattr(loop.data, "close", None)
        if close is not None:
            with contextlib.suppress(Exception):
                close()
        del loop, batch, metrics
        gc.collect()
    finally:
        shutil.rmtree(data_dir, ignore_errors=True)

    t_ref0 = time.perf_counter()
    want = reference_steps(cfg, traffic, seed, batches, devices)
    checks = check_train(first, want, traffic["limits"])
    differ = rows_all_differ(batches)
    checks["rows_all_differ"] = {"value": int(differ), "limit": 1,
                                 "ok": differ}
    checks["steady_recompiles"] = {"value": recompiles, "limit": 0,
                                   "ok": recompiles == 0}
    checks["last_loss_finite"] = {
        "value": last_loss, "limit": "finite",
        "ok": last_loss is not None and math.isfinite(last_loss)}
    reference_s = time.perf_counter() - t_ref0
    extra = read_controls(cfg, traffic, seed, batches, devices, want,
                          control) if control else {}

    return {
        "attempted": steps, "failed": 0,
        "checks": checks,
        "end_to_end": {
            "train_tok_s_chip": rate(steps * tokens_per_step,
                                     window_s) / chips,
            "setup_s": setup_s},
        "memory_peak_bytes": int(peak),
        "traced": traced,
        "counters": {
            "steps": steps, "window_s": window_s,
            "tokens_per_step": tokens_per_step,
            "n_params": reference_for(cfg).param_count(cfg),
            "dims": family_for(cfg).dims(cfg),
            "n_ema": n_ema, "chips": chips,
            "traced_steps": traced.get("steps"),
            "traced_seconds": traced.get("seconds"),
            "program": evidence, "reference_s": reference_s,
            "losses": first["losses"], "reference_losses": want["losses"],
            "last_loss": last_loss, "setup_marks": marks,
            "control": extra},
    }
