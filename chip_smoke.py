#!/usr/bin/env python3
"""chip_smoke.py — quickest proof that the system still starts on the chip.

    python chip_smoke.py            # one chip: train -> save -> serve
    python chip_smoke.py --chips 4  # four chips: 1-device vs data=2,fsdp=2
    python chip_smoke.py --only decode|launcher  # one side check, one chip

Default run (what the driver runs, one TPU chip), through the CLIs a user
types, each phase a child process so each has the chip to itself and this
process never initialises a JAX backend:

1. probe   — a child reports ``jax.devices()``; no TPU, no run.
2. train   — ``python -m distributed_pipeline_tpu.run.train`` GPT-2-base at
             the published width (768/12/12, vocab 50257, seq 1024, bf16)
             on a small text corpus made from the seed (``make_corpus``
             says why not synthetic-lm), a few steps, ``--sanitize true``,
             one checkpoint at the last step. Required: finite, falling
             loss; 0 steady recompiles; the flash and fused Mosaic kernels
             IN the compiled step
             (``tpu_custom_call``, read from the program's own text by the
             trainer); the checkpoint finalized.
3. serve   — ``python -m distributed_pipeline_tpu.run.serve`` off that
             checkpoint: a few greedy requests, hundreds of tokens each.
             Required: every request answered in full; 0 steady
             recompiles; the same prompt twice gives the same tokens.
4. reference — a child decodes the same prompts with the plain greedy
             ``models/sampling.py::gpt2_decode``. Required: every request's
             first token (prefill's) equals the server's. The common prefix
             of the rest is printed; a later divergence is reported with
             the two logits' gap (a model a few steps old in bf16 has
             near-ties), not failed.

``--chips 4`` runs, in ONE child that drives all four chips, the same
GPT-2-base steps on a one-device mesh and on a ``data=2, fsdp=2`` mesh at
the same global batch and seed, and nothing else. Required: per-step losses
agree within the stated tolerance; state really split over four devices.

``--only decode`` runs the flash-decode kernel against the XLA arm on the
same inputs, alone (the default run meets it inside the served model), at
GPT-2-large's head shape and at ``H16 / Dh128``, and says what ``auto``
resolves to at each and how long a call of each arm takes.
``--only launcher`` runs ``run.train --distributed --nprocs 1`` (the
supervised, restartable run) twice into one directory and requires that
the worker trained on the TPU and that the second run resumed. Each runs
that phase and nothing else; the driver gives neither.

The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``.
Any failed phase makes it ``"ok": false`` and the exit code non-zero; on a
machine without a TPU the run fails at the probe.

Phases are functions that take their sizes as arguments (``REAL`` below is
what the command line runs; it offers no size option) so that
tests/test_chip_smoke.py can walk the same control flow at a tiny size.
"""

from __future__ import annotations

import argparse
import csv
import dataclasses
import json
import math
import os
import shutil
import signal
import subprocess
import sys
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

ROOT = os.path.dirname(os.path.abspath(__file__))
PACKAGE = "distributed_pipeline_tpu"
OUT_DIR = os.path.join(ROOT, "chip_smoke_out")  # git-ignored; cleared at start


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Everything that scales a run. ``model_argv`` is run.train's own
    model flags; ``requests`` is (prompt_len, new_tokens, count) groups —
    the first two requests of the first group share one prompt."""

    model_argv: Tuple[str, ...]
    vocab_size: int
    # the training text (make_corpus): lexicon size, words a side per line
    # (src and trg each fill half a sequence), train / valid lines
    corpus: Tuple[int, int, int, int]
    batch: int
    microbatch: int
    steps: int
    lr: float
    requests: Tuple[Tuple[int, int, int], ...]
    decode_slots: int
    page_size: int
    max_prompt_len: int
    mesh_steps: int
    # four-chip phase: |loss_1dev - loss_2x2| allowed at every step. The two
    # runs differ only in reduction order (per-device batch 16 vs 4, then
    # an all-reduce) seen through bf16 activations and Adam's sign-like
    # first steps — 1.4e-4 at most in the builder's four-chip run (PR 22);
    # a layout bug moves the loss by whole units.
    loss_tol: float
    child_timeout_s: float
    # --only decode: each geometry (slots, heads, head_dim, page_size, pages
    # a slot, span links), and the largest |pallas - xla| allowed as a share
    # of the largest output: six bf16 rounding steps (2**-8 each). The
    # builder's chip run (PR 22) read 2.3 to 2.4 steps for bf16 pools and
    # 1.0 to 1.8 for int8; one wrong page among a slot's live ones moves it
    # by tens.
    decode_geoms: Tuple[Tuple[int, int, int, int, int, int], ...] = (
        (8, 16, 128, 16, 64, 4), (16, 20, 64, 16, 64, 4))
    decode_tol: float = 6 * 2.0 ** -8
    # --only launcher: a thin model (the ring is what is shown, not the
    # model) and the steps of each of the two runs
    ring_argv: Tuple[str, ...] = (
        "--model_family", "gpt2", "--vocab_size", "512", "--seq_len", "128",
        "--hidden_size", "128", "--num_layers", "2", "--num_heads", "2",
        "--dtype", "bfloat16", "--dataset", "synthetic-lm",
        "--batch_size", "8", "--microbatch", "8")
    ring_steps: int = 3


REAL = Sizes(
    model_argv=("--model_family", "gpt2", "--model_size", "base",
                "--vocab_size", "50257", "--seq_len", "1024",
                "--dtype", "bfloat16"),
    vocab_size=50257, corpus=(300, 520, 64, 16),
    # sized from memory_analysis() of the step compiled for a described
    # v5e: batch 16 / microbatch 8 peaks near 9.5 of 16 GB on one chip
    batch=16, microbatch=8, steps=6, lr=6e-4,
    requests=((384, 256, 3), (200, 128, 3)),
    decode_slots=8, page_size=16, max_prompt_len=512,
    mesh_steps=4, loss_tol=5e-3, child_timeout_s=900.0)


# ------------------------------------------------------------------ helpers

def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def child_env() -> Dict[str, str]:
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("TPU_LOG_DIR", "disabled")
    return env


def run_child(cmd: Sequence[str], log_path: str, timeout_s: float
              ) -> Tuple[int, str]:
    """Run one child to its end (own session; the whole group is killed
    on timeout so nothing is left holding the chip). Returns (rc, stdout);
    stderr goes to ``log_path``."""
    os.makedirs(os.path.dirname(log_path), exist_ok=True)
    with open(log_path, "ab") as err:
        proc = subprocess.Popen(list(cmd), cwd=ROOT, env=child_env(),
                                stdout=subprocess.PIPE, stderr=err,
                                start_new_session=True)
        try:
            out, _ = proc.communicate(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            os.killpg(proc.pid, signal.SIGKILL)
            out, _ = proc.communicate()
            return 124, out.decode(errors="replace")
    return proc.returncode, out.decode(errors="replace")


def run_py_child(func: str, spec: Dict[str, Any], name: str,
                 timeout_s: float) -> Tuple[int, Optional[Dict[str, Any]]]:
    """Run ``chip_smoke.<func>(spec_path)`` in a fresh interpreter; the
    child writes its result to ``spec["result"]``."""
    spec_path = os.path.join(OUT_DIR, f"{name}_spec.json")
    spec = dict(spec, result=os.path.join(OUT_DIR, f"{name}_result.json"))
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(spec_path, "w") as f:
        json.dump(spec, f)
    rc, _ = run_child(
        [sys.executable, "-c",
         f"import chip_smoke, sys; chip_smoke.{func}(sys.argv[1])",
         spec_path],
        os.path.join(OUT_DIR, f"{name}.log"), timeout_s)
    try:
        with open(spec["result"]) as f:
            return rc, json.load(f)
    except (OSError, ValueError):
        return rc, None


def tail(path: str, n: int = 25) -> str:
    try:
        with open(path, errors="replace") as f:
            return "".join(f.readlines()[-n:])
    except OSError:
        return ""


def platform_failures(device: Optional[Dict[str, Any]]) -> List[str]:
    if not device:
        return ["the phase reported no device"]
    if device.get("platform") != "tpu":
        return [f"ran on platform {device.get('platform')!r}, not 'tpu'"]
    return []


def finish(phase: str, res: Dict[str, Any]) -> Dict[str, Any]:
    res["phase"] = phase
    res["ok"] = not res["failures"]
    for f in res["failures"]:
        say(f"{phase}: FAIL — {f}")
    say(f"{phase}: {'ok' if res['ok'] else 'FAILED'}")
    return res


# ------------------------------------------------------------------- phases

def _probe_child(spec_path: str) -> None:
    with open(spec_path) as f:
        spec = json.load(f)
    from distributed_pipeline_tpu.utils.perf import device_summary
    with open(spec["result"], "w") as f:
        json.dump({"device": device_summary()}, f)


def phase_probe(timeout_s: float = 300.0) -> Dict[str, Any]:
    """What JAX finds here, asked of a child so this process stays off it."""
    rc, got = run_py_child("_probe_child", {}, "probe", timeout_s)
    res: Dict[str, Any] = {"failures": [], "device": (got or {}).get("device")}
    if rc != 0 or got is None:
        res["failures"].append(
            f"device probe exited {rc}:\n"
            + tail(os.path.join(OUT_DIR, "probe.log")))
    else:
        say(f"probe: jax.devices() -> {res['device']}")
        res["failures"] += platform_failures(res["device"])
    return finish("probe", res)


def make_corpus(sizes: Sizes, seed: int) -> str:
    """A small text corpus from the seed, in run.train's own ``--data_dir``
    format: words drawn Zipf-like from a short lexicon (they hash into the
    model's full vocabulary), long enough to fill every sequence.

    Why not ``--dataset synthetic-lm``: its tokens are uniform over the
    whole vocabulary, and at vocab 50257 nothing about it can be learned
    in six steps — on the chip its loss read 10.9807, 10.9806, 10.9753,
    10.9810, 10.9869 (builder's run, PR 22), flat inside batch noise, so
    "the loss falls" could not tell a working update from a broken one.
    Text with a skewed unigram distribution gives every step a gradient
    that a working optimizer turns into a fall well above that noise."""
    import numpy as np

    n_words, side, n_train, n_valid = sizes.corpus
    rng = np.random.default_rng(seed)
    words = np.array([f"w{i}" for i in range(n_words)])
    p = 1.0 / np.arange(1, n_words + 1)
    p /= p.sum()
    data_dir = os.path.join(OUT_DIR, "corpus")
    os.makedirs(data_dir, exist_ok=True)
    for split, n in (("train", n_train), ("valid", n_valid)):
        with open(os.path.join(data_dir, f"{split}.jsonl"), "w") as f:
            for _ in range(n):
                f.write(json.dumps({
                    "src": " ".join(rng.choice(words, side, p=p)),
                    "trg": " ".join(rng.choice(words, side, p=p))}) + "\n")
    return data_dir


def phase_train(run_dir: str, sizes: Sizes, seed: int) -> Dict[str, Any]:
    """run.train through its CLI; every requirement read back from what the
    run itself wrote (progress.csv, goodput_attempt000.json, the
    checkpoint)."""
    cmd = [sys.executable, "-m", f"{PACKAGE}.run.train", *sizes.model_argv,
           "--data_dir", make_corpus(sizes, seed), "--seed", str(seed),
           "--batch_size", str(sizes.batch),
           "--microbatch", str(sizes.microbatch),
           "--lr", str(sizes.lr),
           "--learning_steps", str(sizes.steps),
           "--save_interval", str(sizes.steps),
           "--eval_interval", "1000000", "--log_interval", "1",
           "--sanitize", "true", "--checkpoint_path", run_dir]
    say("train: " + " ".join(cmd[1:]))
    log = os.path.join(OUT_DIR, "train.log")
    rc, out = run_child(cmd, log, sizes.child_timeout_s)
    with open(os.path.join(OUT_DIR, "train.out"), "w") as f:
        f.write(out)
    res: Dict[str, Any] = {"failures": []}
    if rc != 0:
        res["failures"].append(f"run.train exited {rc}:\n" + tail(log))
        return finish("train", res)

    with open(os.path.join(run_dir, "progress.csv")) as f:
        rows = list(csv.DictReader(f))
    res["losses"] = losses = [float(r["loss"]) for r in rows if r.get("loss")]
    say(f"train: losses by step {[round(x, 4) for x in losses]}")
    if len(losses) != sizes.steps:
        res["failures"].append(
            f"{len(losses)} losses logged for {sizes.steps} steps")
    if not losses or not all(math.isfinite(x) for x in losses):
        res["failures"].append(f"non-finite loss in {losses}")
    elif not losses[-1] < losses[0]:
        res["failures"].append(
            f"loss did not fall: first {losses[0]}, last {losses[-1]}")

    with open(os.path.join(run_dir, "goodput_attempt000.json")) as f:
        rec = json.load(f)
    prog = rec.get("program") or {}
    res["device"] = prog.get("device")
    res["compile_time_s"] = rec.get("compile_time_s")
    res["steady_recompile_count"] = rec.get("steady_recompile_count")
    res["attention_impl"] = prog.get("attention_impl")
    res["fused_update"] = prog.get("fused_update")
    res["tpu_custom_calls"] = kernels = prog.get("tpu_custom_calls") or {}
    sps = [float(r["steps_per_sec"]) for r in rows if r.get("steps_per_sec")]
    res["step_time_s"] = round(1.0 / sps[-1], 4) if sps and sps[-1] else None
    say(f"train: device {res['device']}; step time {res['step_time_s']} s "
        f"(last log window); compile_time_s {res['compile_time_s']}; "
        f"attention arm {res['attention_impl']}, update arm "
        f"{'fused' if res['fused_update'] else 'optax'}; "
        f"tpu_custom_call census {kernels}")
    res["failures"] += platform_failures(res["device"])
    if res["steady_recompile_count"] != 0:
        res["failures"].append(
            f"steady_recompile_count {res['steady_recompile_count']} != 0")
    # an arm that was selected must be IN the program, not assumed
    if res["attention_impl"] == "pallas" and not (
            kernels.get("flash_attention_fwd")
            and kernels.get("flash_attention_bwd")):
        res["failures"].append(
            f"flash attention selected but absent from the compiled "
            f"step: {kernels}")
    if res["fused_update"] and not kernels.get("fused_adamw_ema"):
        res["failures"].append(
            f"fused update selected but absent from the compiled step: "
            f"{kernels}")
    ckpt = os.path.join(run_dir, f"model_{sizes.steps:06d}")
    if not os.path.exists(os.path.join(ckpt, "_CHECKPOINT_METADATA")):
        res["failures"].append(f"checkpoint {ckpt} is not finalized")
    if [n for n in os.listdir(run_dir) if ".orbax-checkpoint-tmp" in n]:
        res["failures"].append("an unfinalized checkpoint was left behind")
    return finish("train", res)


def make_requests(sizes: Sizes, seed: int) -> List[Dict[str, Any]]:
    import numpy as np

    rng = np.random.default_rng(seed)
    reqs = []
    for plen, new, count in sizes.requests:
        for _ in range(count):
            reqs.append({"prompt_ids": rng.integers(
                4, sizes.vocab_size, (plen,)).tolist(),
                "max_new_tokens": new})
    reqs[1] = dict(reqs[0])  # the same prompt, sent twice
    return reqs


def phase_serve(run_dir: str, sizes: Sizes, seed: int,
                extra_argv: Sequence[str] = ()) -> Dict[str, Any]:
    """run.serve (single replica) through its CLI off the saved run."""
    reqs = make_requests(sizes, seed)
    prompt_file = os.path.join(OUT_DIR, "requests.jsonl")
    served = os.path.join(OUT_DIR, "served.jsonl")
    with open(prompt_file, "w") as f:
        for r in reqs:
            f.write(json.dumps(r) + "\n")
    cmd = [sys.executable, "-m", f"{PACKAGE}.run.serve",
           "--checkpoint_path", run_dir, "--prompt_file", prompt_file,
           "--out", served, "--decode_slots", str(sizes.decode_slots),
           "--page_size", str(sizes.page_size),
           "--max_prompt_len", str(sizes.max_prompt_len),
           "--temperature", "0.0", "--sanitize", "true", *extra_argv]
    say("serve: " + " ".join(cmd[1:]))
    log = os.path.join(OUT_DIR, "serve.log")
    rc, out = run_child(cmd, log, sizes.child_timeout_s)
    res: Dict[str, Any] = {"failures": [], "served": served,
                           "requests": reqs}
    if rc != 0:
        res["failures"].append(f"run.serve exited {rc}:\n" + tail(log))
        return finish("serve", res)
    summary = json.loads(out.strip().splitlines()[-1])
    res["summary"] = summary
    res["device"] = summary.get("device")
    say(f"serve: device {res['device']}; decode arm chosen by "
        f"resolve_decode_impl: {summary.get('decode_impl')}; compile_s "
        f"{summary.get('compile_s')}; recompile_count "
        f"{summary.get('recompile_count')}; "
        f"{summary.get('decode_tokens')} tokens in "
        f"{summary.get('wall_s')} s wall (compiles included)")
    res["failures"] += platform_failures(res["device"])
    if summary.get("recompile_count") != 0:
        res["failures"].append(
            f"recompile_count {summary.get('recompile_count')} != 0")
    with open(served) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    if len(rows) != len(reqs):
        res["failures"].append(
            f"{len(rows)} of {len(reqs)} requests answered")
    for i, (row, req) in enumerate(zip(rows, reqs)):
        if len(row["tokens"]) != req["max_new_tokens"]:
            res["failures"].append(
                f"request {i}: {len(row['tokens'])} tokens, asked "
                f"{req['max_new_tokens']}")
    if len(rows) > 1 and rows[0]["tokens"] != rows[1]["tokens"]:
        res["failures"].append(
            "the same prompt sent twice gave different tokens")
    return finish("serve", res)


def _reference_child(spec_path: str) -> None:
    """Plain greedy decode of the served prompts (models/sampling.py::
    gpt2_decode, dense cache, no paging, no scheduler) off the same
    checkpoint, compared token by token with what the server answered."""
    with open(spec_path) as f:
        spec = json.load(f)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_pipeline_tpu.models.sampling import gpt2_decode
    from distributed_pipeline_tpu.run.sample import load_run
    from distributed_pipeline_tpu.utils.perf import (
        device_summary, enable_persistent_compilation_cache)

    enable_persistent_compilation_cache()
    wl, params, _targs, _step, _which = load_run(spec["run_dir"])
    with open(spec["served"]) as f:
        served = [json.loads(line) for line in f if line.strip()]
    reqs = spec["requests"]
    groups: Dict[Tuple[int, int], List[int]] = {}
    for i, r in enumerate(reqs):
        groups.setdefault((len(r["prompt_ids"]), r["max_new_tokens"]),
                          []).append(i)
    rows: List[Optional[Dict[str, Any]]] = [None] * len(reqs)
    for (plen, new), idxs in groups.items():
        ids = np.zeros((len(idxs), plen + new), np.int32)
        for j, i in enumerate(idxs):
            ids[j, :plen] = reqs[i]["prompt_ids"]
        ref = np.asarray(jax.jit(
            lambda p, x, plen=plen: gpt2_decode(wl, p, x, plen))(
                params, jnp.asarray(ids)))
        logits_fn = jax.jit(lambda p, x: wl.model.apply(
            p, x, jnp.ones_like(x)).astype(jnp.float32))
        for j, i in enumerate(idxs):
            want = ref[j, plen:].tolist()
            got = served[i]["tokens"]
            k = next((t for t, (a, b) in enumerate(zip(want, got))
                      if a != b), min(len(want), len(got)))
            row: Dict[str, Any] = {
                "request": i, "prompt_len": plen, "new_tokens": new,
                "first_token_equal": bool(got and want[0] == got[0]),
                "common_prefix": k}
            if k < new and k < len(got):
                # the plain model's own logits where the two part ways:
                # how far below its pick it scores the server's token
                lg = np.asarray(logits_fn(
                    params, jnp.asarray(ref[j:j + 1])))[0, plen + k - 1]
                row["logit_gap_at_divergence"] = float(
                    lg[want[k]] - lg[got[k]])
            rows[i] = row
    with open(spec["result"], "w") as f:
        json.dump({"device": device_summary(), "rows": rows}, f)


def phase_reference(run_dir: str, serve_res: Dict[str, Any],
                    sizes: Sizes) -> Dict[str, Any]:
    rc, got = run_py_child(
        "_reference_child",
        {"run_dir": run_dir, "served": serve_res["served"],
         "requests": serve_res["requests"]},
        "reference", sizes.child_timeout_s)
    res: Dict[str, Any] = {"failures": []}
    if rc != 0 or got is None:
        res["failures"].append(
            f"reference decode exited {rc}:\n"
            + tail(os.path.join(OUT_DIR, "reference.log")))
        return finish("reference", res)
    res.update(got)
    res["failures"] += platform_failures(got.get("device"))
    for row in got["rows"]:
        gap = row.get("logit_gap_at_divergence")
        say(f"reference: request {row['request']} (prompt "
            f"{row['prompt_len']}, {row['new_tokens']} new): first token "
            f"{'equal' if row['first_token_equal'] else 'DIFFERS'}, common "
            f"prefix {row['common_prefix']}/{row['new_tokens']}"
            + (f", logit gap where they part {gap:.4g}"
               if gap is not None else ""))
        if not row["first_token_equal"]:
            res["failures"].append(
                f"request {row['request']}: first token differs from the "
                f"plain greedy decode (gap {gap})")
    return finish("reference", res)


def _mesh_child(spec_path: str) -> None:
    """One process, every chip of the host: the same steps on a one-device
    mesh and on data=2, fsdp=2, same global batch, same seed."""
    with open(spec_path) as f:
        spec = json.load(f)
    import gc

    import jax
    import numpy as np
    from jax.sharding import Mesh

    from distributed_pipeline_tpu.config.train import TrainSettings
    from distributed_pipeline_tpu.data import load_data_from_args
    from distributed_pipeline_tpu.models import create_model_from_config
    from distributed_pipeline_tpu.parallel.mesh import AXES, make_mesh
    from distributed_pipeline_tpu.utils.perf import (
        device_summary, enable_persistent_compilation_cache)
    from distributed_pipeline_tpu.utils.trainer import TrainLoop

    enable_persistent_compilation_cache()
    args = TrainSettings.from_argv(
        [*spec["model_argv"], "--data_dir", spec["data_dir"],
         "--seed", str(spec["seed"]), "--batch_size", str(spec["batch"]),
         "--microbatch", str(spec["microbatch"]), "--lr", str(spec["lr"]),
         "--learning_steps", str(spec["steps"])])
    out: Dict[str, Any] = {"device": device_summary(), "runs": {}}
    one = Mesh(np.array(jax.devices()[:1]).reshape((1,) * len(AXES)), AXES)
    for name, mesh in (("1dev", one), ("data2_fsdp2", None)):
        if mesh is None:
            mesh = make_mesh(dp=2, fsdp=2)
        loop = TrainLoop(
            model=create_model_from_config(**args.dict()),
            data=load_data_from_args("train", **args.dict()),
            batch_size=args.batch_size, microbatch=args.microbatch,
            lr=args.lr, ema_rate=args.ema_rate,
            learning_steps=args.learning_steps, log_interval=10 ** 9,
            save_interval=10 ** 9, mesh=mesh, seed=args.seed,
            fused_update=args.fused_update, sanitize=True)
        losses = [float(loop.run_step(next(loop.data))["loss"])
                  for _ in range(spec["steps"])]
        state = (loop.state.params, loop.state.opt_state, loop.state.ema)
        leaves = [x for x in jax.tree_util.tree_leaves(state)
                  if hasattr(x, "addressable_shards") and x.ndim]
        per_dev: Dict[str, int] = {}
        split = 0
        for leaf in leaves:
            if leaf.addressable_shards[0].data.shape != leaf.shape:
                split += 1
            for sh in leaf.addressable_shards:
                per_dev[str(sh.device)] = (per_dev.get(str(sh.device), 0)
                                           + sh.data.nbytes)
        ma = loop._train_step.compiled.memory_analysis()
        out["runs"][name] = {
            "mesh": {k: int(v) for k, v in mesh.shape.items() if v > 1},
            "losses": losses,
            "state_bytes": int(sum(x.nbytes for x in leaves)),
            "state_bytes_per_device": per_dev,
            "leaves": len(leaves), "leaves_split": split,
            "step_argument_bytes_per_device": int(
                ma.argument_size_in_bytes),
            "step_temp_bytes_per_device": int(ma.temp_size_in_bytes),
            "compile_time_s": round(loop.compile_time_s or 0.0, 2),
            "steady_recompile_count": loop.steady_recompile_count,
            "program": loop.program_evidence(),
        }
        loop.stop_sanitizer()
        del loop, state, leaves
        gc.collect()
    with open(spec["result"], "w") as f:
        json.dump(out, f)


def phase_mesh(sizes: Sizes, seed: int, n_chips: int = 4) -> Dict[str, Any]:
    rc, got = run_py_child(
        "_mesh_child",
        {"model_argv": list(sizes.model_argv), "seed": seed,
         "data_dir": make_corpus(sizes, seed), "batch": sizes.batch,
         "microbatch": sizes.microbatch, "lr": sizes.lr,
         "steps": sizes.mesh_steps}, "mesh", sizes.child_timeout_s)
    res: Dict[str, Any] = {"failures": []}
    if rc != 0 or got is None:
        res["failures"].append(
            f"mesh comparison exited {rc}:\n"
            + tail(os.path.join(OUT_DIR, "mesh.log"), 40))
        return finish("mesh", res)
    res.update(got)
    res["failures"] += platform_failures(got.get("device"))
    if (got.get("device") or {}).get("count") != n_chips:
        res["failures"].append(
            f"{(got.get('device') or {}).get('count')} devices, "
            f"need {n_chips}")
    a, b = got["runs"]["1dev"], got["runs"]["data2_fsdp2"]
    say(f"mesh: device {got.get('device')}")
    say(f"mesh: step  loss(1 device)  loss(data=2,fsdp=2)  |diff|  "
        f"(tolerance {sizes.loss_tol})")
    for i, (x, y) in enumerate(zip(a["losses"], b["losses"])):
        say(f"mesh: {i + 1:>4}  {x:<14.6f}  {y:<19.6f}  {abs(x - y):.2e}")
        if not (math.isfinite(x) and math.isfinite(y)
                and abs(x - y) <= sizes.loss_tol):
            res["failures"].append(
                f"step {i + 1}: losses {x} vs {y} differ by more than "
                f"{sizes.loss_tol}")
    for name, run in (("1 device", a), ("data=2,fsdp=2", b)):
        worst = max(run["state_bytes_per_device"].values())
        say(f"mesh: {name}: {run['leaves_split']}/{run['leaves']} state "
            f"leaves split; state {run['state_bytes']} B, most on one "
            f"device {worst} B ({worst / run['state_bytes']:.3f}); "
            f"step args/device {run['step_argument_bytes_per_device']} B, "
            f"temp {run['step_temp_bytes_per_device']} B; compile "
            f"{run['compile_time_s']} s; arms {run['program']}")
        if run["steady_recompile_count"] != 0:
            res["failures"].append(
                f"{name}: steady_recompile_count "
                f"{run['steady_recompile_count']} != 0")
    per_dev = b["state_bytes_per_device"]
    if len(per_dev) != n_chips:
        res["failures"].append(
            f"state lives on {len(per_dev)} devices, not {n_chips}")
    # fsdp=2 halves every leaf it can split; GPT-2's [50257, 768] embedding
    # has an odd vocab dim and stays whole, so ~2/3, not 1/2, is the floor
    if (not b["leaves_split"]
            or max(per_dev.values()) > 0.75 * b["state_bytes"]):
        res["failures"].append(
            f"state is not split over the mesh: {b['leaves_split']} "
            f"leaves split, {max(per_dev.values())} of "
            f"{b['state_bytes']} B on one device")
    return finish("mesh", res)


def _decode_child(spec_path: str) -> None:
    """flash-decode (``impl="pallas"``) against the XLA arm on one set of
    random pools a geometry: bf16 and int8, single token and span."""
    with open(spec_path) as f:
        spec = json.load(f)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_pipeline_tpu.ops import flash_decode as fd
    from distributed_pipeline_tpu.utils.perf import (
        device_summary, enable_persistent_compilation_cache)

    enable_persistent_compilation_cache()
    rng = np.random.default_rng(spec["seed"])
    out: Dict[str, Any] = {"device": device_summary(), "cases": {},
                           "auto_resolves_to": {}}

    def call_ms(fn, args, calls=10):
        jax.block_until_ready(fn(*args))
        t0 = time.perf_counter()
        for _ in range(calls):
            got = fn(*args)
        jax.block_until_ready(got)
        return 1e3 * (time.perf_counter() - t0) / calls

    for B, H, Dh, ps, n, L in spec["geoms"]:
        shape = f"H{H}xDh{Dh}"
        n_pool = 1 + B * n
        table = jnp.asarray(1 + rng.permutation(B * n).reshape(B, n),
                            jnp.int32)
        depth = rng.integers(L, n * ps, (B,))       # live tokens a slot
        for kv in ("bf16", "int8"):
            if kv == "int8":
                pools = [jnp.asarray(
                    rng.integers(-127, 128, (n_pool, ps, H * Dh)), jnp.int8)
                    for _ in range(2)]
                scales = [jnp.asarray(
                    rng.uniform(0.5, 1.5, (n_pool,)) / 127.0, jnp.float32)
                    for _ in range(2)]
            else:
                pools = [jnp.asarray(
                    rng.standard_normal((n_pool, ps, H * Dh)), jnp.bfloat16)
                    for _ in range(2)]
                scales = [None, None]
            out["auto_resolves_to"][f"{shape}.{kv}"] = fd.resolve_decode_impl(
                "auto", (n_pool, ps, H, Dh), pools[0].dtype)
            for span in (0, L):
                if span:
                    q = rng.standard_normal((B, H, span, Dh))
                    pos = depth[:, None] - span + np.arange(span)[None, :]
                    seam = fd.paged_span_attention
                else:
                    q = rng.standard_normal((B, H, Dh))
                    pos = depth - 1
                    seam = fd.paged_decode_attention
                args = (jnp.asarray(q, jnp.bfloat16), pools[0], pools[1],
                        table, jnp.asarray(pos, jnp.int32))
                # the kernel's page census (the span form runs a slot's
                # links as pseudo-slots on repeated table rows)
                census = fd.decode_page_census(
                    np.repeat(np.asarray(table), max(span, 1), axis=0),
                    pos.reshape(-1), ps)
                arms = {impl: jax.jit(
                    lambda *a, impl=impl: seam(
                        *a, impl=impl, scales_k=scales[0],
                        scales_v=scales[1])) for impl in ("pallas", "xla")}
                got = {impl: np.asarray(fn(*args).astype(jnp.float32))
                       for impl, fn in arms.items()}
                out["cases"][
                    f"{shape}.{kv}_{'span' if span else 'decode'}"] = {
                    "finite": bool(np.isfinite(got["pallas"]).all()),
                    "max_abs_diff_vs_xla": float(
                        np.abs(got["pallas"] - got["xla"]).max()),
                    "max_abs_xla": float(np.abs(got["xla"]).max()),
                    "live_tokens": int(depth.sum()),
                    "pages_live": census[0], "pages_copied": census[1],
                    "call_ms": {impl: call_ms(fn, args)
                                for impl, fn in arms.items()}}
    with open(spec["result"], "w") as f:
        json.dump(out, f)


def phase_decode(sizes: Sizes, seed: int) -> Dict[str, Any]:
    rc, got = run_py_child(
        "_decode_child",
        {"geoms": [list(g) for g in sizes.decode_geoms], "seed": seed},
        "decode", sizes.child_timeout_s)
    res: Dict[str, Any] = {"failures": []}
    if rc != 0 or got is None:
        res["failures"].append(
            f"flash-decode check exited {rc}:\n"
            + tail(os.path.join(OUT_DIR, "decode.log")))
        return finish("decode", res)
    res.update(got)
    say(f"decode: device {got['device']}; geometries (slots, heads, "
        f"head_dim, page, pages, span) {sizes.decode_geoms}; 'auto' "
        f"resolves to {got['auto_resolves_to']}; tolerance "
        f"{sizes.decode_tol} of the largest output")
    res["failures"] += platform_failures(got["device"])
    for name, c in got["cases"].items():
        say(f"decode: {name}: finite {c['finite']}, largest |pallas - xla| "
            f"{c['max_abs_diff_vs_xla']:.6g} at outputs up to "
            f"{c['max_abs_xla']:.4g}; a call (host clock, "
            f"{c['live_tokens']} live tokens): pallas "
            f"{c['call_ms']['pallas']:.3f} ms, xla "
            f"{c['call_ms']['xla']:.3f} ms; the kernel's schedule copies "
            f"{c['pages_copied']} distinct pages a pool, "
            f"{c['pages_live']} are live")
        if c["pages_copied"] != c["pages_live"]:
            res["failures"].append(
                f"{name}: the schedule copies {c['pages_copied']} pages "
                f"where {c['pages_live']} hold a live position")
        if not (c["finite"] and c["max_abs_diff_vs_xla"]
                <= sizes.decode_tol * c["max_abs_xla"]):
            res["failures"].append(
                f"{name}: pallas differs from xla by "
                f"{c['max_abs_diff_vs_xla']} (finite: {c['finite']})")
    return finish("decode", res)


def phase_launcher(sizes: Sizes, seed: int) -> Dict[str, Any]:
    """``run.train --distributed --nprocs 1`` twice into one run directory:
    the supervised worker must find the TPU (before PR 22 the launcher
    pinned it to the CPU and said nothing) and the second run must resume
    where the first stopped."""
    run_dir = os.path.join(OUT_DIR, "ring_run")
    res: Dict[str, Any] = {"failures": [], "attempts": []}
    for k in (1, 2):
        cmd = [sys.executable, "-m", f"{PACKAGE}.run.train",
               "--distributed", "--nprocs", "1",
               "--log_dir", os.path.join(OUT_DIR, f"ring_logs{k}"),
               *sizes.ring_argv, "--seed", str(seed),
               "--learning_steps", str(k * sizes.ring_steps),
               "--save_interval", str(sizes.ring_steps),
               "--eval_interval", "1000000", "--log_interval", "1",
               "--sanitize", "true", "--checkpoint_path", run_dir]
        say(f"launcher: run {k}: " + " ".join(cmd[1:]))
        log = os.path.join(OUT_DIR, f"launcher{k}.log")
        rc, _ = run_child(cmd, log, sizes.child_timeout_s)
        if rc != 0:
            res["failures"].append(f"run {k} exited {rc}:\n" + tail(log))
            return finish("launcher", res)
    with open(os.path.join(run_dir, "attempts.jsonl")) as f:
        res["attempts"] = att = [json.loads(x) for x in f if x.strip()]
    with open(os.path.join(run_dir, "goodput_attempt000.json")) as f:
        prog = json.load(f).get("program") or {}
    res["device"] = prog.get("device")
    spans = [(a.get("start_step"), a.get("end_step")) for a in att]
    say(f"launcher: worker's device {res['device']}; attempts "
        f"(start_step, end_step) {spans}; steady recompiles "
        f"{[a.get('steady_recompile_count') for a in att]}; update arm "
        f"{'fused' if prog.get('fused_update') else 'optax'}, kernels "
        f"{prog.get('tpu_custom_calls')}")
    res["failures"] += platform_failures(res["device"])
    n = sizes.ring_steps
    if spans != [(0, n), (n, 2 * n)]:
        res["failures"].append(
            f"expected steps {[(0, n), (n, 2 * n)]} (the second run "
            f"resuming), got {spans}")
    if any(a.get("steady_recompile_count") for a in att):
        res["failures"].append("steady recompiles in a supervised attempt")
    return finish("launcher", res)


# --------------------------------------------------------------------- main

def run_one_chip(sizes: Sizes, seed: int) -> Tuple[bool, Optional[Dict]]:
    probe = phase_probe()
    if not probe["ok"]:
        return False, probe["device"]
    run_dir = os.path.join(OUT_DIR, "run")
    if not phase_train(run_dir, sizes, seed)["ok"]:
        return False, probe["device"]
    serve = phase_serve(run_dir, sizes, seed)
    if not serve["ok"]:
        return False, probe["device"]
    return phase_reference(run_dir, serve, sizes)["ok"], probe["device"]


def run_four_chips(sizes: Sizes, seed: int) -> Tuple[bool, Optional[Dict]]:
    probe = phase_probe()
    if not probe["ok"]:
        return False, probe["device"]
    mesh = phase_mesh(sizes, seed)
    return mesh["ok"], mesh.get("device") or probe["device"]


def run_only(which: str, sizes: Sizes, seed: int
             ) -> Tuple[bool, Optional[Dict]]:
    probe = phase_probe()
    if not probe["ok"]:
        return False, probe["device"]
    phase = {"decode": phase_decode, "launcher": phase_launcher}[which]
    return phase(sizes, seed)["ok"], probe["device"]


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1 (default): train -> save -> serve on one chip; "
                         "4: the sharded-training comparison, nothing else")
    ap.add_argument("--only", choices=("decode", "launcher"),
                    help="one side check on one chip and nothing else: "
                         "flash-decode against the XLA arm, or the "
                         "supervised launcher finding the TPU and resuming")
    ap.add_argument("--seed", type=int, default=102,
                    help="weights, data and prompts are made from it")
    ns = ap.parse_args(argv)
    ok, device = False, None
    try:
        if not os.path.isdir(os.path.join(ROOT, PACKAGE)):
            say(f"no {PACKAGE}/ beside this script: nothing to run")
        else:
            shutil.rmtree(OUT_DIR, ignore_errors=True)
            os.makedirs(OUT_DIR)
            if ns.only:
                ok, device = run_only(ns.only, REAL, ns.seed)
            elif ns.chips == 4:
                ok, device = run_four_chips(REAL, ns.seed)
            else:
                ok, device = run_one_chip(REAL, ns.seed)
    finally:
        # the contract's last line, and nothing after it
        print(json.dumps({"ok": bool(ok), "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
