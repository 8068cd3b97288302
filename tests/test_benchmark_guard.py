"""The benchmark under tier-1's guard.

``benchmark/`` measures the program from outside (``BENCHMARK.json``,
``benchmark/run.py``) and imports nothing of it, so nothing in ``tests/``
ran it: its own tests, its cells' rehearsals, and the names by
which its readers find the program's work in a trace. A renamed jitted
function or ``pallas_call`` moves no end-to-end metric and passes every other
test, and leaves a per-layer metric ``null`` from then on. Three guards:

* the benchmark's own tests, one child ``pytest`` a file, as a user runs
  them (``benchmark/tests/conftest.py`` sets its own four CPU devices);
* every cell of ``BENCHMARK.json`` rehearsed on the CPU through ``run.py``;
* each kernel and program name a reader asks the trace for, against the
  name the package gives: the benchmark's side is read off the benchmark
  (its constants, or what a reader asks a recording trace for), the
  package's side off its constants and off executables it compiled here.
"""

import glob
import importlib
import json
import os
import re
import subprocess
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
OWN_TESTS = sorted(
    os.path.relpath(p, ROOT)
    for p in glob.glob(os.path.join(BENCH, "tests", "test_*.py")))
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    CELLS = [w["name"] for w in json.load(_f)["workloads"]]


def child_env():
    """A user's shell on the CPU: tier-1's eight virtual devices are not
    handed down, so the benchmark's conftest sets the four it documents."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    return env


# ------------------------------------------------- the benchmark's own tests

# One assertion of the benchmark's own tests cannot hold from PR 33 on, and
# no PR but a `benchmark` one may edit the file it is in: it holds PR 32's
# metric to the END of `per_layer` ("appended, not inserted"), and every
# later PR has to append its metrics behind it. What else that test holds
# (the entry as PR 32 listed it) is held below (PERF.md section 7, 15).
OUTDATED = ("benchmark/tests/test_prefill_reader.py::"
            "test_benchmark_json_lists_the_metric_for_the_dense_serve_cell")


@pytest.mark.parametrize("path", OWN_TESTS, ids=os.path.basename)
def test_benchmark_own_tests_pass(path):
    out = subprocess.run(
        [sys.executable, "-m", "pytest", path, "-q", "-p", "no:cacheprovider",
         "--deselect", OUTDATED],
        capture_output=True, text=True, timeout=600, cwd=ROOT,
        env=child_env())
    assert out.returncode == 0, (out.stdout + out.stderr)[-2000:]


def test_accepted_entries_stand_as_their_prs_listed_them():
    """The entry the deselected assertion above looked at, and the order
    the driver reads: what PR 32's benchmark had is a prefix of every list,
    PR 33's entries lie behind it, PR 35's behind those and PR 37's six
    (the loops' own account of their ticks) last."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index("prefill_mfu.serve.dense")
    assert bench["per_layer"][at] == {
        "name": "prefill_mfu.serve.dense", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "model step",
        "moves": "serve_tok_s", "workloads": ["gpt2-large.serve.closed16"]}
    assert at == 20 and names[at + 1:] == [
        "step_mfu.serve.mixed", "prefill_mfu.serve.mixed",
        "decode_hbm_roofline.mixed_latent", "attended_kv_share.mixed",
        "mla_block_attend_roofline.mixed",
        "lightning_index_scores_roofline.mixed",
        "step_mfu.serve.sparse_gqa", "prefill_mfu.serve.sparse_gqa",
        "decode_hbm_roofline.sparse_gqa", "attended_kv_share.sparse_gqa",
        "expert_rows_needed_share", "mla_block_attend_roofline.sparse_gqa",
        "lightning_index_scores_roofline.sparse_gqa",
        "grouped_expert_matmul_roofline",
        "tick_stall_share.serve", "device_dry_dispatch_share.serve",
        "tick_between_share.serve", "decode_tick_device_share.serve",
        "step_stall_share.train", "device_dry_dispatch_share.train"]
    assert [c["name"] for c in bench["configs"]] == [
        "gpt2-base", "gpt2-large", "deepseek-v3.2-exp-ep16",
        "dots3-note-prev-ep16", "keye-vl-2.0-30b-a3b-pp8"]
    assert CELLS == ["gpt2-base.train.seq1024",
                     "gpt2-large.serve.closed16",
                     "deepseek-v3.2-exp-ep16.serve.closed-long16",
                     "dots3-note-prev-ep16.serve.closed-mixed16",
                     "keye-vl-2.0-30b-a3b-pp8.serve.closed-long-reason16"]


# ------------------------------------------------------- every cell, walked

@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearses_on_the_cpu(cell):
    """``run.py --rehearse`` walks the cell's driver end to end at the
    files' tiny sizes: build, warm, window, drain, reference."""
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", "2147483777", "--seconds", "2", "--rehearse"],
        capture_output=True, text=True, timeout=600, cwd=ROOT,
        env=child_env())
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["rehearsal"] and line["failed"] == 0
    assert line["metrics"] == {}      # a CPU run reports no device metric
    assert line["checks"]
    if "served_logit_gap" in line["checks"]:
        assert line["checks"]["served_logit_gap"]["tokens"] > 0


# ---------------------------------------- the names the readers look for

@pytest.fixture(scope="module")
def bench_run():
    """``benchmark/run.py`` as a module, with ``benchmark/`` importable for
    the readers it loads by path."""
    sys.path.insert(0, BENCH)
    try:
        import run
        yield run
    finally:
        sys.path.remove(BENCH)


def constant(name):
    """The name a reader's module keeps as a constant."""
    return lambda reader: [reader.__globals__[name]]


def asked(reader):
    """What a reader with its literals inside asks the trace for, off a
    trace that records the questions and holds nothing."""
    names = []

    class Trace:
        @staticmethod
        def op_seconds(name):
            names.append(name)
            return 0.0, 0
        module_seconds = op_seconds
    assert reader({
        "trace": Trace, "peaks": {"flops_bf16": 1.0, "hbm_bytes_s": 1.0},
        "traffic": {"global_batch": 2, "seq_len": 16},
        "counters": {"traced_steps": 2, "chips": 1, "dims": {
            "heads": 2, "head_dim": 16, "layers": 2, "width": 32}}}) is None
    return names


def kernel_case(metric, benchmark_side, module, attr):
    return pytest.param(
        metric, benchmark_side, module, attr,
        id=re.sub(r"_(hbm_roofline|roofline|time_share)$", "", metric))


@pytest.mark.parametrize("metric, benchmark_side, module, attr", [
    kernel_case("flash_attention_fwd_roofline", asked,
                "flash_attention", "FWD_KERNEL_NAME"),
    kernel_case("flash_attention_bwd_roofline", asked,
                "flash_attention", "BWD_KERNEL_NAME"),
    kernel_case("fused_adamw_ema_time_share", asked,
                "fused_update", "KERNEL_NAME"),
    kernel_case("flash_decode_hbm_roofline", constant("KERNEL"),
                "flash_decode", "KERNEL_NAME"),
    kernel_case("mla_block_attend_roofline", constant("ATTEND_KERNEL"),
                "mla_attention", "KERNEL_NAME"),
    kernel_case("lightning_index_scores_roofline", constant("INDEX_KERNEL"),
                "mla_attention", "INDEX_KERNEL_NAME"),
    kernel_case("mla_block_attend_roofline.mixed", constant("ATTEND_KERNEL"),
                "mla_attention", "KERNEL_NAME"),
    kernel_case("lightning_index_scores_roofline.mixed",
                constant("INDEX_KERNEL"), "mla_attention",
                "INDEX_KERNEL_NAME"),
    kernel_case("mla_block_attend_roofline.sparse_gqa",
                constant("ATTEND_KERNEL"), "mla_attention", "KERNEL_NAME"),
    kernel_case("lightning_index_scores_roofline.sparse_gqa",
                constant("INDEX_KERNEL"), "mla_attention",
                "INDEX_KERNEL_NAME"),
    kernel_case("grouped_expert_matmul_roofline",
                constant("GROUPED_KERNEL"), "grouped_matmul", "KERNEL_NAME"),
])
def test_kernel_names_the_readers_look_for(bench_run, metric, benchmark_side,
                                           module, attr):
    """``tests/test_chip_compile.py`` holds the compiled HLO to the
    package's constant; this holds the constant to what the reader asks."""
    reader, _ = bench_run.load_reader(metric)
    ops = importlib.import_module(f"distributed_pipeline_tpu.ops.{module}")
    assert getattr(ops, attr) in benchmark_side(reader)


def compiled_name(step):
    """The XLA module's name of an ``AOTStep`` that has run: what the
    profiler names the program's device events by."""
    return re.match(r"HloModule (\w+)", step.compiled.as_text()).group(1)


@pytest.fixture(scope="module")
def train_programs(tmp_path_factory):
    from tests.test_trainer import make_loop
    loop = make_loop(tmp_path_factory.mktemp("guard_loop"))
    loop.run_step(next(loop.data))
    return {"train": compiled_name(loop._train_step)}


def served_programs(wl, params):
    from distributed_pipeline_tpu.serving import DecodeServer
    server = DecodeServer(wl, params, decode_slots=2, page_size=4,
                          max_prompt_len=8, max_len=16)
    server.submit(np.arange(4, 9, dtype=np.int32), max_new_tokens=3)
    server.drain()
    return {k: compiled_name(v)
            for k, v in server.engine.executables().items()}


@pytest.fixture(scope="module")
def gpt2_programs():
    from tests.test_serving import tiny_workload
    wl = tiny_workload()
    return served_programs(wl, wl.init_params(jax.random.PRNGKey(3)))


@pytest.fixture(scope="module")
def chunked_programs():
    from tests.test_deepseek_v32 import TINY, build
    wl, _, tree = build(TINY)
    return served_programs(wl, tree)


@pytest.fixture(scope="module")
def windowed_programs():
    from tests.test_dots3_note import TINY, build
    wl, _, tree = build(TINY)
    return served_programs(wl, tree)


@pytest.fixture(scope="module")
def grouped_programs():
    from tests.test_keye_vl2 import TINY, build
    wl, _, tree = build(TINY)
    return served_programs(wl, tree)


@pytest.mark.parametrize("metric, benchmark_side, programs, phase", [
    pytest.param("fused_adamw_ema_time_share", asked, "train_programs",
                 "train", id="jit_train_step"),
    pytest.param("decode_hbm_roofline", constant("DECODE_PROGRAM"),
                 "gpt2_programs", "decode", id="jit_decode_fn"),
    pytest.param("decode_hbm_roofline.sparse_latent",
                 constant("DECODE_PROGRAM"), "chunked_programs", "decode",
                 id="jit_decode_fn.sparse_latent"),
    pytest.param("prefill_mfu.serve", constant("PREFILL_PROGRAM"),
                 "chunked_programs", "prefill", id="jit_prefill_chunk_fn"),
    pytest.param("prefill_mfu.serve.dense", constant("PREFILL_PROGRAM"),
                 "gpt2_programs", "prefill", id="jit_prefill_fn"),
    pytest.param("decode_hbm_roofline.mixed_latent",
                 constant("DECODE_PROGRAM"), "windowed_programs", "decode",
                 id="jit_decode_fn.mixed_latent"),
    pytest.param("prefill_mfu.serve.mixed", constant("PREFILL_PROGRAM"),
                 "windowed_programs", "prefill",
                 id="jit_prefill_chunk_fn.mixed"),
    pytest.param("decode_hbm_roofline.sparse_gqa",
                 constant("DECODE_PROGRAM"), "grouped_programs", "decode",
                 id="jit_decode_fn.sparse_gqa"),
    pytest.param("prefill_mfu.serve.sparse_gqa", constant("PREFILL_PROGRAM"),
                 "grouped_programs", "prefill",
                 id="jit_prefill_chunk_fn.sparse_gqa"),
    pytest.param("decode_tick_device_share.serve",
                 constant("DECODE_PROGRAM"), "gpt2_programs", "decode",
                 id="jit_decode_fn.tick"),
])
def test_program_names_the_readers_look_for(request, bench_run, metric,
                                            benchmark_side, programs, phase):
    """Each program a reader sums the device time of, compiled here at the
    tiny widths of the trainer's and the servers' own tests."""
    reader, _ = bench_run.load_reader(metric)
    assert request.getfixturevalue(programs)[phase] in benchmark_side(reader)


def test_counter_names_the_new_readers_sum():
    """The counters a reader of the grouped family asks ``serve.fetch``
    for, against the names its programs return them under."""
    from distributed_pipeline_tpu.models import deepseek_v32, keye_vl2
    sys.path.insert(0, BENCH)
    try:
        from harness import work_keye_vl2
        from readers import keye_vl2 as reader
    finally:
        sys.path.remove(BENCH)
    names = deepseek_v32.COUNTERS + keye_vl2.GROUPED_COUNTERS
    assert reader.ROWS in names
    counted = dict.fromkeys(names, 1.0)
    with open(os.path.join(BENCH, "configs",
                           "keye-vl-2.0-30b-a3b-pp8.json")) as f:
        cfg = json.load(f)
    assert work_keye_vl2.flops_needed(cfg, tokens=1, head_tokens=1,
                                      counted=counted) > 0
    assert work_keye_vl2.decode_bytes_needed(cfg, steps=1,
                                             counted=counted) > 0
    assert work_keye_vl2.sizes(cfg)["index_key"] \
        == keye_vl2.KeyeVL2Config().indexer_head_dim
