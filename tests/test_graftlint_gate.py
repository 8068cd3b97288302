"""Tier-1 CI gate: graftlint over the shipped code must be clean against
the committed baseline (graftlint_baseline.json at the repo root). A new
hazard — PRNG reuse, host sync under jit, donation misuse, impurity,
recompile pattern, compat bypass — fails this test until it is either
fixed or explicitly audited into the baseline."""

import os

import pytest

from distributed_pipeline_tpu.analysis import AnalysisCache, Baseline, \
    run_paths
from distributed_pipeline_tpu.analysis.cache import CACHE_NAME

pytestmark = pytest.mark.lint

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASELINE = os.path.join(ROOT, "graftlint_baseline.json")
GATED_PATHS = [
    os.path.join(ROOT, "distributed_pipeline_tpu"),
    os.path.join(ROOT, "__graft_entry__.py"),
    # the steady-state-throughput tests drive the trainer's outer loop
    # directly — exactly where GL007 (host-sync-in-loop) hazards breed
    os.path.join(ROOT, "tests", "test_device_prefetch.py"),
    # the serving tests drive the decode scheduler's host loop — the same
    # per-step host-sync breeding ground (the serving/ package itself is
    # inside the distributed_pipeline_tpu walk above)
    os.path.join(ROOT, "tests", "test_serving.py"),
    # the chaos tests drive TrainLoop outer loops + fault hooks (chaos/
    # itself rides the package walk above)
    os.path.join(ROOT, "tests", "test_chaos.py"),
    # the partition/ZeRO-1 tests drive TrainLoop outer loops AND handle
    # shardings directly — both GL007 and GL008 territory
    os.path.join(ROOT, "tests", "test_partition.py"),
    # the elastic/watchdog tests drive TrainLoop outer loops across
    # topology changes (GL007) and assert on restored sharded state
    os.path.join(ROOT, "tests", "test_elastic.py"),
    # the serving-fleet tests drive router/fleet host loops and the
    # replica protocol (GL007 territory once real decode rides them)
    os.path.join(ROOT, "tests", "test_fleet.py"),
    # the observability tests drive TrainLoop outer loops (GL007) and
    # exercise the trace/export layer GL009 polices timing flows into
    os.path.join(ROOT, "tests", "test_obs.py"),
    # the auto-tuner tests drive measurement TrainLoops (GL007) and
    # handle rule tables / spec trees directly (GL008 territory)
    os.path.join(ROOT, "tests", "test_tune.py"),
    # the cost-ledger tests drive TrainLoop/DecodeServer outer loops
    # (GL007) and are exactly where inline FLOPs math would breed (GL010)
    os.path.join(ROOT, "tests", "test_ledger.py"),
    # the analysis tests themselves: their helper code drives the
    # linter's own surfaces, and gating them keeps the fixture-builder
    # helpers honest against every rule
    os.path.join(ROOT, "tests", "test_analysis.py"),
    # the MPMD tests drive the pipeline driver's host step loop and the
    # StageMath jit surfaces (GL007 territory: per-step host syncs on
    # link frames are the design, stray ones inside jit are not)
    os.path.join(ROOT, "tests", "test_mpmd.py"),
    # the transport tests drive socket/file replica clients and the
    # fleet e2e rings over both wires — router/fleet host-loop territory
    # (GL007) like test_fleet.py, which they import helpers from
    os.path.join(ROOT, "tests", "test_transport.py"),
    # the autoscaler tests drive the fleet poll loop + scale decisions
    # and the elastic e2e ring — the same host-loop breeding ground
    os.path.join(ROOT, "tests", "test_autoscale.py"),
    # the kernel parity tests drive DecodeServer host loops and TrainLoop
    # outer steps (GL007) and sit next to the one sanctioned pallas_call
    # home — exactly where a stray call outside ops/ would breed (GL012)
    os.path.join(ROOT, "tests", "test_kernels.py"),
    # the speculative-decode tests drive DecodeServer host loops through
    # the verify seam (GL007) and handle the int8 pool/scale sidecars
    # directly — where unpoliced host<->device syncs and stray
    # quantization math would breed next
    os.path.join(ROOT, "tests", "test_spec_decode.py"),
]


@pytest.fixture(scope="module")
def gate_run():
    """One lint of the gated paths shared by the gate tests, through the
    content-hash cache beside the baseline (ISSUE 15 satellite: the
    gated path list grows every PR — unchanged modules must not be
    reparsed on every `pytest -m lint` run). The cache can only memoize
    per-file work; the cross-module pass recomputes from summaries, so
    a warm cache changes wall time, never findings."""
    cache = AnalysisCache(os.path.join(ROOT, CACHE_NAME))
    return run_paths(GATED_PATHS, cache=cache)


def test_committed_baseline_exists_and_is_valid():
    bl = Baseline.load(BASELINE)
    for e in bl.entries:  # every entry must carry its audit trail fields
        assert {"rule", "path", "snippet", "fingerprint"} <= set(e)


def test_package_lints_clean_against_baseline(gate_run):
    findings, n_files = gate_run
    assert n_files > 40  # the walk really covered the package
    new, _ = Baseline.load(BASELINE).split(findings)
    report = "\n".join(
        f"  {os.path.relpath(f.path, ROOT)}:{f.line}: {f.rule} {f.message}"
        for f in new)
    assert not new, (
        f"graftlint found {len(new)} new hazard(s) — fix them or audit "
        f"them into graftlint_baseline.json (python -m "
        f"distributed_pipeline_tpu.analysis --write-baseline <paths>):\n"
        f"{report}")


def test_lint_gate_script_runs_clean():
    """scripts/lint_gate.sh is the CI entry point: the changed-files
    annotation pass plus the cached whole-program pass, gated paths
    imported from THIS module so the two gates cannot drift. It must
    exit 0 on the current tree."""
    import subprocess
    import sys

    script = os.path.join(ROOT, "scripts", "lint_gate.sh")
    assert os.path.exists(script)
    proc = subprocess.run(
        ["bash", script], cwd=ROOT, capture_output=True, text=True,
        env={**os.environ, "PYTHON": sys.executable}, timeout=300)
    assert proc.returncode == 0, (
        f"lint_gate.sh failed (rc={proc.returncode}):\n"
        f"{proc.stdout}\n{proc.stderr}")
    # the whole-program pass reported, against the committed baseline
    assert "OK" in proc.stderr + proc.stdout
    assert "graftlint_baseline.json" in proc.stderr + proc.stdout


def test_baseline_has_no_stale_entries(gate_run):
    """Entries whose finding no longer exists are audit debt: the flagged
    line changed or was fixed, so the entry vouches for nothing. Keeps
    the committed file honest (regenerate it after fixing a finding)."""
    findings, _ = gate_run
    live = {f.fingerprint for f in findings}
    stale = [e for e in Baseline.load(BASELINE).entries
             if e["fingerprint"] not in live]
    assert not stale, (
        "baseline entries no longer match any finding (regenerate with "
        f"--write-baseline): {[e['snippet'] for e in stale]}")
