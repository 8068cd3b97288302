"""Time-to-signal contracts: the streaming budget-aware bench, the
persistent-compilation-cache wiring, and the trainer's AOT compile metrics.

The r5 postmortem (VERDICT.md weak #1-2): bench.py printed its single JSON
line only at the very end, so a driver timeout captured ZERO of the twelve
legs' work. These tests pin the replacement contract — headline-first leg
order, incremental JSONL persistence, budget-skip markers that still yield a
parseable final line — and the compile-cache path that makes warm runs
near-compile-free.
"""

import json
import os
import subprocess
import sys

import jax
import pytest

from distributed_pipeline_tpu.config.train import TrainSettings
from distributed_pipeline_tpu.parallel import make_mesh
from distributed_pipeline_tpu.parallel.launcher import _worker_env
from distributed_pipeline_tpu.utils.perf import (
    AOTStep,
    enable_persistent_compilation_cache,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------ bench harness

@pytest.fixture(scope="module")
def bench_run(tmp_path_factory):
    """One constrained-budget bench subprocess, shared by the contract
    tests: BENCH_BUDGET_S=1 forces every leg after the headline to be
    budget-skipped (the headline is exempt by contract)."""
    tmp = tmp_path_factory.mktemp("bench")
    # Pre-seed the HISTORY with a prior run's row: the append contract
    # (ISSUE 14) says bench extends the time series, never truncates it.
    history = tmp / "history.jsonl"
    history.write_text(json.dumps(
        {"name": "diffuseq-base-seq128", "tokens_per_sec_per_chip": 1.0,
         "run_id": "prior-run", "t": 0.0}) + "\n")
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "BENCH_BUDGET_S": "1",
        "BENCH_ARTIFACT": str(tmp / "legs.jsonl"),
        "BENCH_HISTORY": str(history),
        "JAX_COMPILATION_CACHE_DIR": str(tmp / "cache"),
        # glob: the headline + its satellite twins — enough legs to
        # observe ordering and skipping without a multi-minute test
        # (BENCH_ONLY without a wildcard is an EXACT match now)
        "BENCH_ONLY": "diffuseq-base-seq128*",
    })
    # The conftest's 8-fake-device XLA_FLAGS would leak into the subprocess
    # and change the bench's dp=-1 mesh; the bench contract is about the
    # default single-device CPU environment.
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "bench.py"], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=420)
    return proc, tmp / "legs.jsonl", history


def test_bench_budget_exits_zero_with_parseable_json(bench_run):
    proc, _, _ = bench_run
    assert proc.returncode == 0, proc.stderr[-2000:]
    final = json.loads(proc.stdout.strip().splitlines()[-1])
    assert final["configs"], final
    assert final["budget_s"] == 1.0


def test_bench_headline_leg_completes_first(bench_run):
    proc, _, _ = bench_run
    final = json.loads(proc.stdout.strip().splitlines()[-1])
    head = final["configs"][0]
    # The headline leg is exempt from the budget guard: it carries real
    # numbers (and the compile/steady split) even when the budget is blown
    # before it finishes.
    assert head["name"] == "diffuseq-base-seq128"
    assert "skipped" not in head and "error" not in head
    assert head["tokens_per_sec_per_chip"] > 0
    assert head["compile_s"] > 0
    assert head["first_step_s"] >= head["compile_s"]
    assert final["value"] == head["tokens_per_sec_per_chip"]


def test_bench_budget_exhaustion_yields_skip_markers(bench_run):
    proc, _, _ = bench_run
    final = json.loads(proc.stdout.strip().splitlines()[-1])
    skipped = [c for c in final["configs"] if c.get("skipped") == "budget"]
    assert skipped, "1s budget must skip every non-headline leg"
    assert all(set(c) == {"name", "skipped"} for c in skipped)
    # every leg is accounted for: completed or explicitly skipped
    # (headline + prefetch A/B twin + zero1 A/B + trace A/B + chaos +
    # elastic + tune + mpmd-pipe + noaccum + moe8 + moe8-cf1 + scan +
    # fusedupd)
    assert len(final["configs"]) == 13


def test_bench_artifact_is_valid_jsonl_of_all_legs(bench_run):
    proc, artifact, _ = bench_run
    final = json.loads(proc.stdout.strip().splitlines()[-1])
    rows = [json.loads(line) for line in
            artifact.read_text().strip().splitlines()]
    # the incrementally-persisted artifact IS the final configs list — a
    # timeout after leg k would still have left rows 0..k on disk
    assert rows == final["configs"]


def test_bench_headline_row_carries_the_cost_ledger(bench_run):
    """ISSUE 14 acceptance: the headline train row carries a POPULATED
    ledger — collective_bytes_per_step present, mfu_gap_* summing with
    the (unrounded) mfu to exactly 1 (residual-by-construction, 1e-6),
    padding waste inside [0, 1]."""
    from distributed_pipeline_tpu.obs import ledger as ledger_lib

    proc, _, _ = bench_run
    final = json.loads(proc.stdout.strip().splitlines()[-1])
    head = final["configs"][0]
    assert "collective_bytes_per_step" in head
    for term in ledger_lib.GAP_TERMS:
        assert term in head and head[term] >= 0
    assert abs(ledger_lib.gap_sum_identity(head) - 1.0) < 1e-6
    assert 0 <= head["padding_waste_frac"] <= 1
    assert head["flops_per_execution"] > 0
    assert head["bytes_accessed"] > 0


def test_bench_history_appends_without_truncating(bench_run):
    """The bench_history.jsonl contract (ISSUE 14): bench APPENDS every
    leg row stamped with one run_id per invocation — the pre-seeded
    prior run's row survives, the new rows share a fresh id, and the
    sentinel's grouping sees two runs in file order."""
    from distributed_pipeline_tpu.chaos.goodput import read_journal
    from distributed_pipeline_tpu.obs import regress as regress_lib

    proc, artifact, history = bench_run
    rows = read_journal(str(history))
    assert rows[0]["run_id"] == "prior-run", "history was truncated"
    new = [r for r in rows if r.get("run_id") != "prior-run"]
    artifact_rows = [json.loads(l) for l in
                     artifact.read_text().strip().splitlines()]
    assert len(new) == len(artifact_rows)
    assert len({r["run_id"] for r in new}) == 1  # one id per invocation
    assert all("t" in r for r in new)
    runs = regress_lib.group_runs(rows)
    assert len(runs) == 2 and runs[0][0] == "prior-run"


@pytest.mark.lint
def test_regress_sentinel_exits_nonzero_on_injected_regression(tmp_path):
    """CI wiring (ISSUE 14): ``python -m distributed_pipeline_tpu.obs.
    regress`` must exit nonzero when the newest recorded run regresses
    past the band, and zero on a flat history — the property a CI job
    gates on."""
    def rows(tps3):
        return [json.dumps({"name": "diffuseq-base-seq128",
                            "tokens_per_sec_per_chip": tps,
                            "mfu": 0.5, "peak_live_bytes": 100,
                            "recompile_count": 0, "run_id": f"r{i}",
                            "t": 1.0})
                for i, tps in enumerate([1000, 1005, tps3], 1)]

    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    flat, reg = tmp_path / "flat.jsonl", tmp_path / "reg.jsonl"
    flat.write_text("\n".join(rows(1002)) + "\n")
    reg.write_text("\n".join(rows(900)) + "\n")
    base = [sys.executable, "-m", "distributed_pipeline_tpu.obs.regress",
            "--history"]
    ok = subprocess.run(base + [str(flat)], capture_output=True,
                        text=True, env=env, cwd=REPO)
    assert ok.returncode == 0, ok.stderr
    assert json.loads(ok.stdout)["verdict"] == "ok"
    bad = subprocess.run(base + [str(reg)], capture_output=True,
                         text=True, env=env, cwd=REPO)
    assert bad.returncode == 1, (bad.returncode, bad.stderr)
    assert json.loads(bad.stdout)["verdict"] == "regressed"
    assert "regressed" in bad.stderr  # the human table names the leg


def test_bench_only_exact_match_with_optional_glob():
    """BENCH_ONLY leg selection (ISSUE 9 satellite): a bare name is an
    EXACT match — the old substring filter made
    BENCH_ONLY=diffuseq-base-seq128 run SEVEN legs, the chaos leg
    included — and a wildcard pattern is an fnmatch glob."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_for_test", os.path.join(REPO, "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    legs = [(n, None) for n in (
        "diffuseq-base-seq128", "diffuseq-base-seq128-prefetch",
        "diffuseq-base-seq128-zero1", "diffuseq-base-seq128-chaos",
        "diffuseq-base-seq128-tune",
        "gpt2-serve-decode-b64", "gpt2-serve-spec-decode",
        "gpt2-serve-decode-int8", "gpt2-base-decode-oneshot-b1",
        "gpt2-serve-fleet-chaos", "gpt2-serve-autoscale")]
    names = lambda got: [n for n, _ in got]
    assert names(bench.select_legs(legs, "diffuseq-base-seq128")) == \
        ["diffuseq-base-seq128"]
    assert names(bench.select_legs(legs, "diffuseq-base-seq128*")) == \
        ["diffuseq-base-seq128", "diffuseq-base-seq128-prefetch",
         "diffuseq-base-seq128-zero1", "diffuseq-base-seq128-chaos",
         "diffuseq-base-seq128-tune"]
    assert names(bench.select_legs(legs, "*serve-decode*")) == \
        ["gpt2-serve-decode-b64", "gpt2-serve-decode-int8"]
    # the fleet leg must NOT ride the headline glob (it sits after it so
    # a timeout degrades to an error row, never a blocked headline)
    assert names(bench.select_legs(legs, "gpt2-serve-fleet-chaos")) == \
        ["gpt2-serve-fleet-chaos"]
    # same contract for the autoscale leg (ISSUE 17): gpt2-named, so the
    # diffuseq headline glob can never pick it up
    assert names(bench.select_legs(legs, "gpt2-serve-autoscale")) == \
        ["gpt2-serve-autoscale"]
    assert bench.select_legs(legs, "") == legs
    assert bench.select_legs(legs, "no-such-leg") == []


# ----------------------------------------------------- serving decode legs

@pytest.fixture(scope="module")
def serve_bench_run(tmp_path_factory):
    """One bench subprocess filtered to the three serving decode legs
    (ISSUE 7): parsed rows must land in the JSONL artifact with the
    serving schema columns."""
    tmp = tmp_path_factory.mktemp("serve_bench")
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "BENCH_BUDGET_S": "240",
        "BENCH_ARTIFACT": str(tmp / "legs.jsonl"),
        "JAX_COMPILATION_CACHE_DIR": str(tmp / "cache"),
        "BENCH_ONLY": "*serve-decode*",
        "BENCH_HISTORY": "",
    })
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "bench.py"], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=420)
    return proc, tmp / "legs.jsonl"


def test_serve_bench_legs_land_parsed_rows(serve_bench_run):
    """The three continuous-batching legs (slots 1 / 8 / 64) complete and
    carry the serving schema: decode_tokens_per_s_per_chip and
    time_to_first_token_s, plus the steady recompile_count gauge at 0
    (prefill/decode compiled exactly once, in warmup)."""
    proc, artifact = serve_bench_run
    assert proc.returncode == 0, proc.stderr[-2000:]
    rows = {r["name"]: r for r in
            (json.loads(line) for line in
             artifact.read_text().strip().splitlines())}
    from distributed_pipeline_tpu.obs import ledger as ledger_lib

    for slots in (1, 8, 64):
        row = rows[f"gpt2-serve-decode-b{slots}"]
        assert "error" not in row and "skipped" not in row, row
        assert row["batch"] == slots
        assert row["decode_tokens_per_s_per_chip"] > 0
        assert row["time_to_first_token_s"] > 0
        assert row["ttft_p95_s"] >= 0
        assert row["compile_s"] > 0
        assert row["recompile_count"] == 0, (
            "steady-state serving recompiled", row)
        # ISSUE 14 acceptance (b8 named explicitly): serve rows carry a
        # populated decode ledger with the exact gap-sum identity and
        # steady recompiles still 0
        assert "collective_bytes_per_step" in row
        assert abs(ledger_lib.gap_sum_identity(row) - 1.0) < 1e-6
        assert 0 <= row["padding_waste_frac"] <= 1
        assert 0 <= row["prefill_padding_waste_frac"] <= 1


def test_serve_bench_final_json_carries_rows(serve_bench_run):
    proc, artifact = serve_bench_run
    final = json.loads(proc.stdout.strip().splitlines()[-1])
    rows = [json.loads(line) for line in
            artifact.read_text().strip().splitlines()]
    assert rows == final["configs"]
    # continuous batching scales decode throughput with occupancy: 64
    # full slots must beat one slot by a wide margin even on CPU (the
    # >= 3x acceptance ratio vs one-shot b1 is asserted on the real
    # artifact's serve-vs-oneshot-decode row, emitted in full runs)
    by = {r["name"]: r for r in rows}
    assert (by["gpt2-serve-decode-b64"]["decode_tokens_per_s_per_chip"]
            > 3 * by["gpt2-serve-decode-b1"]
            ["decode_tokens_per_s_per_chip"])


# ------------------------------------------------- serving fleet leg

@pytest.fixture(scope="module")
def fleet_bench_run(tmp_path_factory):
    """One bench subprocess filtered to the serving-fleet resilience leg
    (ISSUE 11): 3 replica worker processes, Poisson load, one injected
    kill_replica mid-request, one checkpoint hot-swap. chaos-marked: it
    spawns a real multi-process fleet."""
    tmp = tmp_path_factory.mktemp("fleet_bench")
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "BENCH_BUDGET_S": "240",
        "BENCH_LEG_BUDGET_S": "240",
        "BENCH_ARTIFACT": str(tmp / "legs.jsonl"),
        "JAX_COMPILATION_CACHE_DIR": str(tmp / "cache"),
        "BENCH_ONLY": "gpt2-serve-fleet-chaos",
        "BENCH_HISTORY": "",
    })
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "bench.py"], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=420)
    return proc, tmp / "legs.jsonl"


@pytest.mark.slow
@pytest.mark.chaos
def test_fleet_bench_leg_meets_serving_slos(fleet_bench_run):
    """The acceptance row: zero dropped admitted requests, >= 1 replay
    (the injected kill), hot-swap ok, TTFT p50/p95 inside the documented
    SLO bounds, and the serving ledger accounting every replica-second."""
    proc, artifact = fleet_bench_run
    assert proc.returncode == 0, proc.stderr[-2000:]
    rows = {r["name"]: r for r in
            (json.loads(line) for line in
             artifact.read_text().strip().splitlines())}
    row = rows["gpt2-serve-fleet-chaos"]
    assert "error" not in row and "skipped" not in row, row
    assert row["dropped"] == 0
    assert row["replayed"] >= 1
    assert row["swap_ok"] is True and row["swap_step"] == 4
    assert row["ttft_p50_s"] <= row["slo_p50_s"]
    assert row["ttft_p95_s"] <= row["slo_p95_s"]
    assert row["accounted_frac"] == pytest.approx(1.0, abs=0.05)
    assert row["completed"] == row["requests"]
    assert row["replay_s"] >= 0 and row["fleet_attempts"] >= 4


# -------------------------------------------------- autoscale fleet leg

@pytest.fixture(scope="module")
def autoscale_bench_run(tmp_path_factory):
    """One bench subprocess filtered to the autoscaling-fleet leg
    (ISSUE 17): three fleet runs over one checkpoint — affinity A/B,
    static-max baseline, and --replicas 1 under the SLO autoscaler on
    seeded diurnal traffic. BENCH_HISTORY is SET (unlike the other leg
    fixtures): the acceptance also covers the row landing in the
    history file under the regression sentinel's grouping."""
    tmp = tmp_path_factory.mktemp("autoscale_bench")
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "BENCH_BUDGET_S": "600",
        "BENCH_LEG_BUDGET_S": "600",
        "BENCH_ARTIFACT": str(tmp / "legs.jsonl"),
        "JAX_COMPILATION_CACHE_DIR": str(tmp / "cache"),
        "BENCH_ONLY": "gpt2-serve-autoscale",
        "BENCH_HISTORY": str(tmp / "history.jsonl"),
    })
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "bench.py"], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=700)
    return proc, tmp / "legs.jsonl", tmp / "history.jsonl"


@pytest.mark.slow
@pytest.mark.chaos
def test_autoscale_bench_leg_meets_acceptance(autoscale_bench_run):
    """ISSUE 17 acceptance row: >= 1 journaled scale-up AND drain-based
    scale-down, zero drops, p95 TTFT under the documented CPU SLO, the
    autoscaled replica-seconds bill strictly below the static-max
    baseline, affinity's fleet-wide prefix hit rate strictly above
    least-loaded's, and the ledger closing at accounted_frac 1.0 with
    paid_idle booked."""
    proc, artifact, _ = autoscale_bench_run
    assert proc.returncode == 0, proc.stderr[-2000:]
    rows = {r["name"]: r for r in
            (json.loads(line) for line in
             artifact.read_text().strip().splitlines())}
    row = rows["gpt2-serve-autoscale"]
    assert "error" not in row and "skipped" not in row, row
    assert row["dropped"] == 0
    assert row["completed"] == row["requests"]
    assert row["scale_ups"] >= 1
    assert row["scale_downs"] >= 1
    assert row["ttft_p50_s"] <= row["slo_p50_s"]
    assert row["ttft_p95_s"] <= row["slo_p95_s"]
    assert row["replica_seconds"] < row["static_replica_seconds"]
    assert row["replica_seconds_saved_frac"] > 0
    assert row["prefix_hit_rate_affinity"] > \
        row["prefix_hit_rate_least_loaded"]
    assert row["paid_idle_s"] is not None and row["paid_idle_s"] >= 0
    assert row["accounted_frac"] == pytest.approx(1.0, abs=0.05)


@pytest.mark.slow
@pytest.mark.chaos
def test_autoscale_bench_row_lands_in_history(autoscale_bench_run):
    """ISSUE 17 satellite: the new leg's row rides bench_history.jsonl
    under the obs/regress.py sentinel — stamped with the invocation's
    run_id and grouped as one run by the sentinel's own reader."""
    from distributed_pipeline_tpu.chaos.goodput import read_journal
    from distributed_pipeline_tpu.obs import regress as regress_lib

    proc, _, history = autoscale_bench_run
    assert proc.returncode == 0, proc.stderr[-2000:]
    rows = read_journal(str(history))
    mine = [r for r in rows if r["name"] == "gpt2-serve-autoscale"]
    assert len(mine) == 1 and "error" not in mine[0]
    assert mine[0].get("run_id") and "t" in mine[0]
    runs = regress_lib.group_runs(rows)
    assert len(runs) == 1 and runs[0][0] == mine[0]["run_id"]


# ------------------------------------------------------ auto-tuner leg

@pytest.fixture(scope="module")
def tune_bench_run(tmp_path_factory):
    """One bench subprocess filtered to the auto-tuner leg (ISSUE 13):
    a screen-only budgeted layout search on the forced-host dp=2 CPU
    mesh. slow-marked consumer: the leg spawns ~9 measurement children."""
    tmp = tmp_path_factory.mktemp("tune_bench")
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "BENCH_BUDGET_S": "240",
        "BENCH_LEG_BUDGET_S": "240",
        "BENCH_ARTIFACT": str(tmp / "legs.jsonl"),
        "JAX_COMPILATION_CACHE_DIR": str(tmp / "cache"),
        "BENCH_ONLY": "diffuseq-base-seq128-tune",
        "BENCH_HISTORY": "",
    })
    env.pop("XLA_FLAGS", None)
    env.pop("DPT_TUNE_INJECT", None)
    proc = subprocess.run(
        [sys.executable, "bench.py"], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=420)
    return proc, tmp / "legs.jsonl"


@pytest.mark.slow
def test_tune_bench_leg_reproduces_or_beats_hand_tuned(tune_bench_run):
    """The acceptance row: the tuner's winner reproduces or beats the
    hand-tuned family table within the +-3% band, every enumerated
    candidate is accounted (measured + pruned + rejected + skipped ==
    enumerated), and the winner holds steady recompiles at 0."""
    proc, artifact = tune_bench_run
    assert proc.returncode == 0, proc.stderr[-2000:]
    rows = {r["name"]: r for r in
            (json.loads(line) for line in
             artifact.read_text().strip().splitlines())}
    row = rows["diffuseq-base-seq128-tune"]
    assert "error" not in row and "skipped" not in row, row
    assert row["winner_vs_baseline"] >= 1.0 - row["noise_band_pct"] / 100
    assert (row["measured"] + row["pruned"] + row["rejected"]
            + row["skipped"]) == row["enumerated"]
    assert row["enumerated"] > row["measured"] > 0
    assert row["steady_recompile_count"] == 0
    assert row["winner"].startswith("diffuseq-m")


# ------------------------------------------------- trace-overhead A/B leg

@pytest.fixture(scope="module")
def trace_bench_run(tmp_path_factory):
    """One bench subprocess filtered to the trace-overhead A/B leg
    (ISSUE 12): span tracing ON vs OFF, paired-interleaved at headline
    settings."""
    tmp = tmp_path_factory.mktemp("trace_bench")
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "BENCH_BUDGET_S": "240",
        "BENCH_ARTIFACT": str(tmp / "legs.jsonl"),
        "JAX_COMPILATION_CACHE_DIR": str(tmp / "cache"),
        "BENCH_ONLY": "diffuseq-base-seq128-trace",
        "BENCH_HISTORY": "",
    })
    env.pop("XLA_FLAGS", None)
    env.pop("DPT_TRACE", None)  # the leg arms its ON arm itself
    proc = subprocess.run(
        [sys.executable, "bench.py"], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=420)
    return proc, tmp / "legs.jsonl"


def test_trace_ab_leg_emits_paired_delta_row(trace_bench_run):
    """The trace-overhead guard's schema: the leg carries the paired
    ab_* fields and a non-empty ON-arm shard (a disarmed tracer would
    'prove' a zero cost nobody pays), and the derived trace-ab-delta
    row restates the same paired numbers. The +-3% noise-band claim is
    about the captured full-run artifact, not asserted here — a loaded
    CI box would flake it; what IS pinned is that both arms ran
    interleaved with even (position-balanced) rounds."""
    proc, artifact = trace_bench_run
    assert proc.returncode == 0, proc.stderr[-2000:]
    rows = {r["name"]: r for r in
            (json.loads(line) for line in
             artifact.read_text().strip().splitlines())}
    leg = rows["diffuseq-base-seq128-trace"]
    assert "error" not in leg and "skipped" not in leg, leg
    assert leg["ab_method"] == "paired-interleaved"
    assert leg["ab_rounds"] % 2 == 0
    assert leg["trace_events"] > 0
    assert leg["steps_per_s"] > 0 and leg["ab_off_steps_per_s"] > 0
    delta = rows["trace-ab-delta"]
    assert delta["delta_pct"] == leg["ab_delta_pct"]
    assert delta["on_steps_per_s"] == leg["steps_per_s"]
    assert delta["off_steps_per_s"] == leg["ab_off_steps_per_s"]
    assert delta["trace_events"] == leg["trace_events"]


# ------------------------------------------------ compilation-cache wiring

def test_compilation_cache_flag_roundtrips_through_settings():
    s = TrainSettings.from_argv(["--compilation_cache_dir", "off"])
    assert s.compilation_cache_dir == "off"
    assert TrainSettings().compilation_cache_dir == "auto"
    # and through the JSON path (the --config_json workflow)
    s2 = TrainSettings.model_validate(json.loads(s.to_json()))
    assert s2.compilation_cache_dir == "off"
    # a directory of one's own is JAX_COMPILATION_CACHE_DIR's to name
    with pytest.raises(SystemExit):
        TrainSettings.from_argv(["--compilation_cache_dir", "/tmp/cc"])
    with pytest.raises(ValueError, match="JAX_COMPILATION_CACHE_DIR"):
        enable_persistent_compilation_cache("/tmp/cc")


def test_enable_persistent_cache_resolution(tmp_path, monkeypatch):
    """The one rule: the variable if set (and nothing else written or
    exported), else one fixed path in the checkout — the same whatever
    the run directory — and 'off'."""
    from distributed_pipeline_tpu.utils import perf

    fixed = str(tmp_path / "fixed")
    monkeypatch.setattr(perf, "DEFAULT_COMPILE_CACHE_DIR", fixed)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        assert enable_persistent_compilation_cache("off") == ""
        # unset: the fixed path, identical for two different run dirs
        # (run dirs no longer enter into it at all)
        a = TrainSettings.from_argv(["--checkpoint_path", "/tmp/run_a"])
        b = TrainSettings.from_argv(["--checkpoint_path", "/tmp/run_b"])
        got = [enable_persistent_compilation_cache(s.compilation_cache_dir)
               for s in (a, b)]
        assert got == [fixed, fixed] and os.path.isdir(fixed)
        assert jax.config.jax_compilation_cache_dir == fixed
        assert "JAX_COMPILATION_CACHE_DIR" not in os.environ  # no export
        assert perf.DEFAULT_COMPILE_CACHE_DIR == fixed
        # the real default sits in the checkout, under a fixed name
        assert os.path.basename(os.path.dirname(os.path.dirname(
            perf.__file__))) == "distributed_pipeline_tpu"
        # set: that directory, and no other made
        outside = str(tmp_path / "outside")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outside)
        os.rmdir(fixed)
        assert enable_persistent_compilation_cache("auto") == outside
        assert jax.config.jax_compilation_cache_dir == outside
        assert os.environ["JAX_COMPILATION_CACHE_DIR"] == outside
        assert sorted(os.listdir(tmp_path)) == ["outside"]
        # 'off' wins over the variable, and leaves it alone
        assert enable_persistent_compilation_cache("off") == ""
        assert jax.config.jax_compilation_cache_dir is None
        assert os.environ["JAX_COMPILATION_CACHE_DIR"] == outside
    finally:
        # "off" resets jax's once-only cache object too — leaving it
        # initialized would pin this tmp dir for the whole test process
        enable_persistent_compilation_cache("off")


def test_cache_dir_reaches_worker_env(tmp_path, monkeypatch):
    """No hand-down: a worker inherits the variable when the caller set
    it, and is given none when not (it then resolves the fixed path)."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    env = _worker_env(1, 2, "127.0.0.1:9999", 2, run_timestamp="20260803")
    assert env["JAX_COMPILATION_CACHE_DIR"] == str(tmp_path)
    assert env["JAX_PROCESS_INDEX"] == "1"
    assert env["DPT_RUN_TIMESTAMP"] == "20260803"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    env = _worker_env(1, 2, "127.0.0.1:9999", 2)
    assert "JAX_COMPILATION_CACHE_DIR" not in env


def test_launcher_forwards_cache_env_to_ring(monkeypatch, tmp_path):
    """The launcher neither takes nor passes a cache directory any more:
    the ring gets no such argument and the environment is left as found."""
    from distributed_pipeline_tpu.parallel import launcher

    from tests._fake_ring import make_fake_ring

    fake = make_fake_ring()
    monkeypatch.setattr(launcher, "_run_worker_ring", fake)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert launcher.run_argv_as_distributed("mod", [], nprocs=2) == 0
    assert "cache_dir" not in fake.calls[0]
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == str(tmp_path)


# ------------------------------------------------- AOT compile-time metrics

def _tiny_loop(tmp_path, tag):
    from distributed_pipeline_tpu.data import load_data_from_args
    from distributed_pipeline_tpu.models import create_model_from_config
    from distributed_pipeline_tpu.utils.trainer import TrainLoop

    wl = create_model_from_config(
        model_family="gpt2", vocab_size=64, seq_len=16, hidden_size=32,
        num_layers=2, num_heads=2, dtype="float32")
    data = load_data_from_args("train", batch_size=8, dataset="synthetic-lm",
                               seq_len=16, vocab_size=64, seed=0)
    return TrainLoop(model=wl, data=data, batch_size=8, lr=1e-3,
                     learning_steps=100, log_interval=10 ** 9,
                     save_interval=10 ** 9, mesh=make_mesh(dp=8),
                     checkpoint_dir=str(tmp_path / tag), seed=5)


def test_aot_compile_metrics_and_cache_hit_path(tmp_path, monkeypatch):
    """compile_time_s/time_to_first_step_s are populated by the first step,
    and a RESUMED TrainLoop under a warm persistent cache compiles
    measurably faster — the exact elastic-restart path the cache exists
    for. The resume leg doubles as a regression test for donating
    orbax-restored buffers into a cache-deserialized executable (jaxlib
    0.4.37 CPU heap corruption; trainer copies restored trees)."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    try:
        enable_persistent_compilation_cache()

        cold = _tiny_loop(tmp_path, "run")
        assert cold.compile_time_s is None  # nothing compiled at build time
        cold.run_step(next(cold.data))
        assert cold.compile_time_s > 0
        assert cold.time_to_first_step_s >= cold.compile_time_s
        assert os.listdir(str(tmp_path / "cache")), \
            "persistent cache wrote nothing"
        cold.save()

        warm = _tiny_loop(tmp_path, "run")  # same dir: auto-resumes
        assert warm.step == 1
        warm.run_step(next(warm.data))
        warm.run_step(next(warm.data))  # steady state past the restore
        # The XLA compile is the dominant share of the cold number; a cache
        # hit replaces it with a disk read. 0.7 leaves headroom for the
        # (uncached) trace+lower share while still failing if the cache
        # silently stopped hitting.
        assert warm.compile_time_s < cold.compile_time_s * 0.7, (
            warm.compile_time_s, cold.compile_time_s)
    finally:
        enable_persistent_compilation_cache("off")


def test_aot_step_recompiles_on_shape_change():
    calls = []
    step = AOTStep(jax.jit(lambda x: x * 2), "mul",
                   on_compile=lambda n, s: calls.append((n, s)))
    import jax.numpy as jnp
    a = step(jnp.ones((4,)))
    b = step(jnp.ones((4,)))          # same shape: no recompile
    assert len(calls) == 1
    c = step(jnp.ones((8,)))          # shape change: falls back to recompile
    assert len(calls) == 2
    assert float(a.sum()) == 8 and float(b.sum()) == 8
    assert float(c.sum()) == 16
    assert step.compile_time_s == pytest.approx(sum(s for _, s in calls))


def test_get_batch_length_hook_feeds_samples(tmp_path):
    """The reference's get_batch_length user hook: overriding it changes the
    cumulative ``samples`` gauge without touching the loop."""
    import numpy as np

    from distributed_pipeline_tpu.utils import logger
    from distributed_pipeline_tpu.utils.trainer import TrainLoop

    class HalfCounted(TrainLoop):
        def get_batch_length(self, batch):
            return super().get_batch_length(batch) // 2

    from distributed_pipeline_tpu.data import load_data_from_args
    from distributed_pipeline_tpu.models import create_model_from_config
    wl = create_model_from_config(
        model_family="gpt2", vocab_size=64, seq_len=16, hidden_size=32,
        num_layers=2, num_heads=2, dtype="float32")
    data = load_data_from_args("train", batch_size=8, dataset="synthetic-lm",
                               seq_len=16, vocab_size=64, seed=0)
    loop = HalfCounted(model=wl, data=data, batch_size=8, lr=1e-3,
                       learning_steps=100, log_interval=10 ** 9,
                       save_interval=10 ** 9, mesh=make_mesh(dp=8),
                       checkpoint_dir=str(tmp_path), seed=5)
    with logger.scoped_configure(format_strs=[]):
        loop.run_step(next(loop.data))
        loop.run_step(next(loop.data))
        kvs = logger.getkvs()
    assert kvs["samples"] == 2 * (8 // 2)  # hook value, not step*batch
    assert loop.get_batch_length(next(loop.data)) == 4


# ------------------------------------------- pallas fast-path legs (ISSUE 18)

@pytest.fixture(scope="module")
def decode_kernel_bench_run(tmp_path_factory):
    """One bench subprocess filtered to the flash-decode kernel leg: the
    same serve loop twice (decode_impl pallas vs xla) over one checkpoint,
    with the kernel arm's schedule-derived HBM bytes landed next to the
    XLA twin's cost-analysis bytes. BENCH_HISTORY is SET — the acceptance
    covers the row riding the history file."""
    tmp = tmp_path_factory.mktemp("decode_kernel_bench")
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "BENCH_BUDGET_S": "600",
        "BENCH_LEG_BUDGET_S": "600",
        "BENCH_ARTIFACT": str(tmp / "legs.jsonl"),
        "JAX_COMPILATION_CACHE_DIR": str(tmp / "cache"),
        "BENCH_ONLY": "gpt2-serve-decode-kernel",
        "BENCH_HISTORY": str(tmp / "history.jsonl"),
    })
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "bench.py"], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=700)
    return proc, tmp / "legs.jsonl", tmp / "history.jsonl"


@pytest.mark.slow
@pytest.mark.chaos
def test_decode_kernel_bench_leg_meets_acceptance(decode_kernel_bench_run):
    """ISSUE 18 acceptance row: greedy tokens identical to the XLA paged
    path, zero steady-window recompiles on BOTH arms, and the kernel's
    per-token HBM bytes strictly below the gather path's."""
    proc, artifact, history = decode_kernel_bench_run
    assert proc.returncode == 0, proc.stderr[-2000:]
    rows = {r["name"]: r for r in
            (json.loads(line) for line in
             artifact.read_text().strip().splitlines())}
    row = rows["gpt2-serve-decode-kernel"]
    assert "error" not in row and "skipped" not in row, row
    assert row["tokens_identical_to_xla"] is True
    assert row["recompile_count"] == 0
    assert row["xla_recompile_count"] == 0
    assert row["decode_hbm_bytes_per_token"] < \
        row["xla_decode_bytes_per_token"]
    assert 0 < row["hbm_bytes_ratio"] < 1
    assert row["decode_tokens_per_s_per_chip"] > 0
    hist = [json.loads(line) for line in
            history.read_text().strip().splitlines()]
    mine = [r for r in hist if r["name"] == "gpt2-serve-decode-kernel"]
    assert len(mine) == 1 and mine[0].get("run_id")


@pytest.fixture(scope="module")
def fusedupd_bench_run(tmp_path_factory):
    """One bench subprocess filtered to the fused-update twin of the
    headline train leg: same model/step with --fused_update, the kernel's
    read/write-census bytes landed next to the staged optax chain's
    cost-analysis bytes."""
    tmp = tmp_path_factory.mktemp("fusedupd_bench")
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "BENCH_BUDGET_S": "600",
        "BENCH_LEG_BUDGET_S": "600",
        "BENCH_ARTIFACT": str(tmp / "legs.jsonl"),
        "JAX_COMPILATION_CACHE_DIR": str(tmp / "cache"),
        "BENCH_ONLY": "diffuseq-base-seq128-fusedupd",
        "BENCH_HISTORY": str(tmp / "history.jsonl"),
    })
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "bench.py"], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=700)
    return proc, tmp / "legs.jsonl", tmp / "history.jsonl"


@pytest.mark.slow
@pytest.mark.chaos
def test_fusedupd_bench_leg_meets_acceptance(fusedupd_bench_run):
    """ISSUE 18 acceptance row: the fused-update leg completes with real
    throughput, its one-pass update bytes strictly below the staged
    chain's, and the row rides the history file."""
    proc, artifact, history = fusedupd_bench_run
    assert proc.returncode == 0, proc.stderr[-2000:]
    rows = {r["name"]: r for r in
            (json.loads(line) for line in
             artifact.read_text().strip().splitlines())}
    row = rows["diffuseq-base-seq128-fusedupd"]
    assert "error" not in row and "skipped" not in row, row
    assert row["fused_update"] is True
    assert row["tokens_per_sec_per_chip"] > 0
    assert row["update_hbm_bytes_per_step"] < \
        row["xla_update_bytes_per_step"]
    assert 0 < row["update_bytes_ratio"] < 1
    hist = [json.loads(line) for line in
            history.read_text().strip().splitlines()]
    mine = [r for r in hist if r["name"] == "diffuseq-base-seq128-fusedupd"]
    assert len(mine) == 1 and mine[0].get("run_id")
