"""Auto-tuner tests (ISSUE 13): candidate enumeration/validation, the
successive-halving search driver (determinism, budget, prune, resume),
the child-measurement scaffold with injected faults, the artifact
round-trip into run/train.py, and the replica-platform launcher satellite.

The search driver is exercised with FAKE measure functions (deterministic,
instant) so its contracts — identical journal + winner across runs,
static rejection before any measurement, OOM/timeout pruning that never
aborts, resume replaying completed trials — are pinned without spawning
children. The child scaffold and the CLI get small REAL subprocess runs
(2 forced CPU host devices, tiny models) so the end-to-end path stays
honest.
"""

import json
import os
import subprocess
import sys

import pytest

from distributed_pipeline_tpu.models import create_model_from_config
from distributed_pipeline_tpu.parallel.partition import (
    load_partition_artifact,
    parse_partition_rules,
    rules_for_workload,
    rules_from_json,
    rules_to_json,
)
from distributed_pipeline_tpu.tune import candidates as cand_lib
from distributed_pipeline_tpu.tune import measure as measure_lib
from distributed_pipeline_tpu.tune import search as search_lib

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = dict(model_family="diffuseq", model_size="base", seq_len=64,
            vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
            dtype="float32")


@pytest.fixture(scope="module")
def tiny_workload():
    return create_model_from_config(**TINY)


@pytest.fixture(scope="module")
def tiny_shapes(tiny_workload):
    return cand_lib.param_shapes(tiny_workload)


@pytest.fixture(scope="module")
def tiny_rules(tiny_workload):
    return rules_for_workload(tiny_workload)


def _cands(rules, n=2, **kw):
    return cand_lib.enumerate_candidates(rules, n, prefix="t-", **kw)


# ------------------------------------------------------------ enumeration

def test_mesh_splits_cover_the_device_count_deterministically():
    splits = cand_lib.mesh_splits(8)
    for s in splits:
        prod = 1
        for v in s.values():
            prod *= v
        assert prod == 8
    assert len(splits) == len({tuple(sorted(s.items())) for s in splits})
    assert splits == cand_lib.mesh_splits(8)  # deterministic order
    assert {"data": 2, "fsdp": 2, "tensor": 2} in splits
    assert cand_lib.mesh_splits(1) == [{"data": 1, "fsdp": 1, "tensor": 1}]


def test_rule_variants_mutate_axes(tiny_rules):
    variants = dict(cand_lib.rule_variants(tiny_rules))
    assert set(variants) == {"family", "replicate", "swap-fsdp-tensor",
                             "no-fsdp", "no-tensor"}
    assert variants["family"] == tiny_rules
    # swap really swaps: serialize and compare axis names
    fam = json.dumps(rules_to_json(variants["family"]))
    swp = json.dumps(rules_to_json(variants["swap-fsdp-tensor"]))
    assert fam.count('"fsdp"') == swp.count('"tensor"')
    assert fam.count('"tensor"') == swp.count('"fsdp"')
    # the drop variants carry none of the dropped axis
    assert '"fsdp"' not in json.dumps(rules_to_json(variants["no-fsdp"]))
    assert '"tensor"' not in json.dumps(
        rules_to_json(variants["no-tensor"]))


def test_enumerate_baseline_first_and_cap_preserves_it(tiny_rules):
    cands = _cands(tiny_rules, 2)
    assert cands[0].is_baseline
    assert cands[0].mesh == {"data": 2, "fsdp": 1, "tensor": 1}
    assert cands == _cands(tiny_rules, 2)  # deterministic
    capped = _cands(tiny_rules, 2, max_candidates=3)
    assert len(capped) == 3 and capped[0].is_baseline
    # zero1 only enumerated where the data axis is > 1
    assert all(c.mesh.get("data", 1) > 1
               for c in cands if c.shard_optimizer)


def test_validation_rejects_before_any_compile(tiny_rules, tiny_shapes):
    from jax.sharding import PartitionSpec as P

    cands = _cands(tiny_rules, 2)
    base = cands[0]
    # wrong device count: mesh product mismatch
    ok, reason, _ = cand_lib.validate_candidate(base, tiny_shapes, 4, 8)
    assert not ok and "product" in reason
    # microbatch that the batch-sharding axes cannot divide
    ok, reason, _ = cand_lib.validate_candidate(base, tiny_shapes, 2, 7)
    assert not ok and "divisible" in reason
    # a table without a catch-all: uncovered leaves reject statically
    bad = cand_lib.Candidate(cid="bad", mesh=dict(base.mesh),
                             rules=((r"attn/qkv$", P("fsdp")),),
                             rules_tag="partial", shard_optimizer=False)
    ok, reason, _ = cand_lib.validate_candidate(bad, tiny_shapes, 2, 8)
    assert not ok and reason.startswith("rules:")
    # an overlong spec rejects statically too
    bad2 = cand_lib.Candidate(
        cid="bad2", mesh=dict(base.mesh),
        rules=((r".*", P(None, None, None, None, None, None, "fsdp")),),
        rules_tag="overlong", shard_optimizer=False)
    ok, reason, _ = cand_lib.validate_candidate(bad2, tiny_shapes, 2, 8)
    assert not ok and reason.startswith("rules:")
    # a tensor-size-2 mesh whose table shards nothing over tensor is
    # degenerate (pure compute replication)
    degen = next(c for c in cands
                 if c.mesh.get("tensor") == 2 and c.rules_tag == "replicate")
    ok, reason, _ = cand_lib.validate_candidate(degen, tiny_shapes, 2, 8)
    assert not ok and "degenerate" in reason


def test_duplicate_layouts_share_a_signature(tiny_rules, tiny_shapes):
    cands = {c.cid: c for c in _cands(tiny_rules, 2)}
    # on a pure-DP mesh every table variant materializes the same
    # (fully-replicated) layout: one signature
    sig_fam = cand_lib.layout_signature(cands["t-m2x1x1-family-z0"],
                                        tiny_shapes)
    sig_rep = cand_lib.layout_signature(cands["t-m2x1x1-replicate-z0"],
                                        tiny_shapes)
    assert sig_fam == sig_rep
    # distinct meshes never collide
    sig_fsdp = cand_lib.layout_signature(cands["t-m1x2x1-family-z0"],
                                         tiny_shapes)
    assert sig_fsdp != sig_fam
    # the zero toggle is part of the program identity where dp > 1
    sig_z1 = cand_lib.layout_signature(cands["t-m2x1x1-family-z1"],
                                       tiny_shapes)
    assert sig_z1 != sig_fam


# ------------------------------------------------------- search (fakes)

def _fake_measure(calls=None):
    """Deterministic fake: rate is a pure function of the candidate."""
    def fn(cand, steps):
        if calls is not None:
            calls.append((cand.cid, steps))
        rate = (10.0 + (2.0 if cand.shard_optimizer else 0.0)
                - 0.5 * cand.mesh.get("fsdp", 1)
                - 0.25 * cand.mesh.get("tensor", 1))
        return {"steps_per_s": rate, "opt_state_bytes_per_replica": 128,
                "peak_live_bytes": 0, "steady_recompile_count": 0}
    return fn


def _fake_pair(a, b):
    return {"ab_delta_pct": -0.5, "ab_rounds": 6, "ab_window_steps": 4,
            "a": {"steps_per_s": 11.0}, "b": {"steps_per_s": 10.9}}


def _run(tmp_path, rules, shapes, name="t.jsonl", **kw):
    jp = os.path.join(str(tmp_path), name)
    defaults = dict(candidates=_cands(rules, 2), shapes=shapes,
                    n_devices=2, global_microbatch=8,
                    measure_fn=_fake_measure(), pair_fn=_fake_pair,
                    journal_path=jp, budget_s=1e9, screen_steps=4)
    defaults.update(kw)
    return search_lib.run_search(**defaults), jp


def _strip_clock(rows):
    return [{k: v for k, v in r.items() if k not in ("t", "dur_s")}
            for r in rows]


def test_search_is_deterministic(tmp_path, tiny_rules, tiny_shapes):
    """Same candidates + same (deterministic) measurements -> identical
    trial journal and winner across independent runs."""
    s1, j1 = _run(tmp_path, tiny_rules, tiny_shapes, name="a.jsonl")
    s2, j2 = _run(tmp_path, tiny_rules, tiny_shapes, name="b.jsonl")
    assert s1["winner"] == s2["winner"]
    assert s1["counts"] == s2["counts"]
    assert _strip_clock(search_lib.read_trials(j1)) == \
        _strip_clock(search_lib.read_trials(j2))


def test_static_rejects_never_reach_measurement(tmp_path, tiny_rules,
                                                tiny_shapes):
    calls = []
    s, jp = _run(tmp_path, tiny_rules, tiny_shapes,
                 measure_fn=_fake_measure(calls))
    rows = search_lib.read_trials(jp)
    rejected = {r["cid"] for r in rows if r.get("status") == "rejected"}
    assert rejected, "the n=2 space must contain static rejects"
    assert rejected.isdisjoint({cid for cid, _ in calls})
    # accounting closes over the screen rung
    c = s["counts"]
    assert (c["rejected"] + c["measured"] + c["pruned"] + c["skipped"]
            == c["enumerated"] == s["accounted"])
    # duplicates carry their keeper's cid in the reason
    dup = [r for r in rows if "duplicate-layout-of" in r.get("reason", "")]
    assert dup


def test_error_rows_prune_without_aborting(tmp_path, tiny_rules,
                                           tiny_shapes):
    """An OOM/timeout candidate (the child scaffold folds both to an
    {'error': ...} row) lands as a pruned trial; the search completes
    and still produces a winner from the healthy candidates."""
    inner = _fake_measure()

    def flaky(cand, steps):
        if "z1" in cand.cid:
            return {"error": "RESOURCE_EXHAUSTED: fake OOM"}
        if "no-fsdp" in cand.cid:
            return {"error": "child exceeded its 5s timeout"}
        return inner(cand, steps)

    s, jp = _run(tmp_path, tiny_rules, tiny_shapes, measure_fn=flaky)
    assert s["winner"] is not None
    assert "z1" not in s["winner"]["cid"]
    rows = search_lib.read_trials(jp)
    pruned = [r for r in rows if r.get("status") == "pruned"]
    assert pruned and all("error" in r["result"] for r in pruned)
    assert s["counts"]["pruned"] >= 2
    assert s["accounted"] == s["counts"]["enumerated"]


def test_budget_skips_are_journaled_and_accounted(tmp_path, tiny_rules,
                                                  tiny_shapes):
    """A clock that expires after the first trials: later candidates
    journal as skipped, the ranking proceeds on what WAS measured, and
    the baseline (measured first) is always in it."""
    now = [0.0]

    def clock():
        now[0] += 30.0
        return now[0]

    s, jp = _run(tmp_path, tiny_rules, tiny_shapes, budget_s=120.0,
                 clock=clock, screen_only=True)
    c = s["counts"]
    assert c["skipped"] > 0 and c["measured"] > 0
    assert c["rejected"] + c["measured"] + c["pruned"] + c["skipped"] \
        == c["enumerated"]
    assert s["baseline_steps_per_s"] is not None
    assert s["winner"] is not None


def test_resume_replays_completed_and_retries_skipped(tmp_path, tiny_rules,
                                                      tiny_shapes):
    """An interrupted tune resumed: completed trials replay from the
    journal (zero re-measures), budget-skipped trials are retried with
    the fresh budget, and the final winner matches an uninterrupted
    run's."""
    now = [0.0]

    def expiring_clock():
        now[0] += 30.0
        return now[0]

    s1, jp = _run(tmp_path, tiny_rules, tiny_shapes, name="r.jsonl",
                  budget_s=120.0, clock=expiring_clock, screen_only=True)
    assert s1["counts"]["skipped"] > 0
    calls = []
    s2, _ = _run(tmp_path, tiny_rules, tiny_shapes, name="r.jsonl",
                 measure_fn=_fake_measure(calls))
    measured_first = s1["counts"]["measured"]
    # only the previously-skipped screen trials (plus halving/finals
    # rungs) are measured now — never the already-completed screen rows
    screen_calls = [cid for cid, steps in calls if steps == 4]
    assert len(screen_calls) == s2["counts"]["enumerated"] \
        - s2["counts"]["rejected"] - s2["counts"]["pruned"] \
        - measured_first
    full, _ = _run(tmp_path, tiny_rules, tiny_shapes, name="full.jsonl")
    assert s2["winner"]["cid"] == full["winner"]["cid"]


def test_finals_pick_the_abba_winner(tmp_path, tiny_rules, tiny_shapes):
    """ab_delta_pct > 0 (challenger faster) flips the winner to arm B;
    <= 0 keeps the screen leader."""
    s_keep, _ = _run(tmp_path, tiny_rules, tiny_shapes, name="k.jsonl",
                     pair_fn=lambda a, b: {
                         "ab_delta_pct": -1.0,
                         "a": {"steps_per_s": 12.0},
                         "b": {"steps_per_s": 11.0}})
    s_flip, _ = _run(tmp_path, tiny_rules, tiny_shapes, name="f.jsonl",
                     pair_fn=lambda a, b: {
                         "ab_delta_pct": 2.0,
                         "a": {"steps_per_s": 11.0},
                         "b": {"steps_per_s": 12.0}})
    assert s_keep["winner"]["cid"] != s_flip["winner"]["cid"]
    assert s_flip["winner"]["steps_per_s"] == 12.0
    # finals arm rows only re-time: the winner's footprint/recompile
    # gauges fall back to its rung trial row (either arm)
    assert s_keep["winner"]["steady_recompile_count"] == 0
    assert s_flip["winner"]["steady_recompile_count"] == 0


# ----------------------------------------------------- artifact round-trip

def test_artifact_roundtrip_through_partition_rules(tmp_path, tiny_rules,
                                                    tiny_shapes):
    s, _ = _run(tmp_path, tiny_rules, tiny_shapes)
    cands = {c.cid: c for c in _cands(tiny_rules, 2)}
    winner = cands[s["winner"]["cid"]]
    path = str(tmp_path / "artifact.json")
    payload = search_lib.write_artifact(path, winner, s, model=TINY)
    # the artifact is valid --partition_rules input VERBATIM
    rules = parse_partition_rules(path)
    assert rules == winner.rules
    # and the full loader exposes the mesh + ZeRO recommendations
    art = load_partition_artifact(path)
    assert art["rules"] == winner.rules
    assert art["mesh"] == winner.mesh
    assert art["shard_optimizer"] == winner.shard_optimizer
    assert payload["tuned"]["cid"] == winner.cid
    # a plain rule LIST (the pre-tuner shape) still parses and reports
    # no recommendations
    plain = str(tmp_path / "plain.json")
    with open(plain, "w") as f:
        json.dump(rules_to_json(winner.rules), f)
    art2 = load_partition_artifact(plain)
    assert art2["rules"] == winner.rules
    assert art2["mesh"] is None and art2["shard_optimizer"] is None


def test_rules_json_roundtrip_includes_tuple_entries(tiny_rules):
    wire = rules_to_json(tiny_rules)
    assert rules_from_json(wire) == tiny_rules
    # the embedding rule's ("tensor","fsdp") tuple survives as a list
    assert any(isinstance(e, list)
               for _, spec in wire for e in spec)


def test_apply_tuned_layout_respects_explicit_mesh_flags():
    from distributed_pipeline_tpu.config.train import TrainSettings
    from distributed_pipeline_tpu.run.train import (apply_tuned_layout,
                                                    mesh_flags_default)
    from distributed_pipeline_tpu.utils import logger

    art = {"rules": None,
           "mesh": {"data": 2, "fsdp": 4, "tensor": 1},
           "shard_optimizer": True}
    with logger.scoped_configure(format_strs=[]):
        args = TrainSettings()
        assert mesh_flags_default(args)
        tuned = apply_tuned_layout(args, art, n_devices=8)
        assert (tuned.dp, tuned.fsdp) == (2, 4)
        assert tuned.shard_optimizer is True
        # wrong device count: the MESH recommendation is refused (an
        # artifact tuned for another box must not break this one), but
        # the ZeRO recommendation still applies — it is device-count-
        # independent (dp=1 degenerates to the param layout)
        same = apply_tuned_layout(args, art, n_devices=4)
        assert same.dp == -1 and same.fsdp == 1
        assert same.shard_optimizer is True
        # a mesh tuned at a different batch shape is refused too: the
        # run's global microbatch must divide data x fsdp x expert, or
        # the TrainLoop constructor would crash after model build
        small = TrainSettings.from_argv(["--batch_size", "4",
                                         "--microbatch", "4"])
        kept_small = apply_tuned_layout(small, art, n_devices=8)
        assert kept_small.dp == -1 and kept_small.fsdp == 1
        # explicit mesh flags always win
        explicit = TrainSettings.from_argv(["--dp", "8"])
        assert not mesh_flags_default(explicit)
        kept = apply_tuned_layout(explicit, art, n_devices=8)
        assert kept.dp == 8 and kept.fsdp == 1


def test_tune_settings_roundtrip():
    from distributed_pipeline_tpu.config.tune import TuneSettings

    s = TuneSettings.from_argv(["--family", "gpt2", "--n_devices", "4",
                                "--screen_only", "true",
                                "--budget_s", "33",
                                "--peak_bytes_ceiling", "1e9"])
    assert (s.family, s.n_devices, s.screen_only, s.budget_s) == \
        ("gpt2", 4, True, 33.0)
    assert s.peak_bytes_ceiling == 1e9
    s2 = TuneSettings.model_validate(json.loads(s.to_json()))
    assert s2 == s


def test_peak_bytes_ceiling_ranks_out_with_closed_accounting(
        tmp_path, tiny_rules, tiny_shapes):
    """The memory-headroom objective (ISSUE 14 satellite; the r15 NOTE's
    unwired ranking input): candidates whose measured peak_live_bytes
    exceed --peak_bytes_ceiling are journaled as over_ceiling and never
    win — even when they are the fastest — and the accounting invariant
    extends to close over the new bucket. A replayed over-ceiling row
    re-ranks under the CURRENT ceiling, so a later tune with more
    headroom reuses the measurement instead of re-spawning a child."""
    def measure(cand, steps):
        base = _fake_measure()(cand, steps)
        # the FASTEST candidates (zero1 arms) also have the biggest
        # footprint: the ceiling must beat raw speed ranking
        base["peak_live_bytes"] = 5_000 if cand.shard_optimizer else 100
        return base

    s, jp = _run(tmp_path, tiny_rules, tiny_shapes, name="ceil.jsonl",
                 measure_fn=measure, screen_only=True,
                 peak_bytes_ceiling=1_000.0)
    c = s["counts"]
    assert c["over_ceiling"] > 0
    assert (c["rejected"] + c["measured"] + c["pruned"] + c["skipped"]
            + c["over_ceiling"]) == c["enumerated"] == s["accounted"]
    assert s["peak_bytes_ceiling"] == 1_000.0
    # without the ceiling the zero1 arm wins (fastest fake rate); with it
    # the winner must be a within-ceiling candidate
    assert s["winner"] is not None
    assert not s["winner"]["shard_optimizer"]
    rows = search_lib.read_trials(jp)
    over = [r for r in rows if r.get("status") == "over_ceiling"]
    assert over and all(
        (r["result"] or {}).get("peak_live_bytes", 0) > 1_000
        for r in over)
    # resume under a HIGHER ceiling: replayed rows re-rank, no re-measures
    calls = []
    s2, _ = _run(tmp_path, tiny_rules, tiny_shapes, name="ceil.jsonl",
                 measure_fn=_fake_measure(calls), screen_only=True,
                 peak_bytes_ceiling=1e9)
    assert not calls, "resume must replay the journal, not re-measure"
    assert s2["counts"]["over_ceiling"] == 0
    assert s2["winner"]["shard_optimizer"]  # the fast arm wins again


# ------------------------------------------------- export fold (obs/)

def test_export_folds_tune_journal_into_timeline(tmp_path):
    from distributed_pipeline_tpu.obs.export import chrome_trace

    jp = tmp_path / "tune_trials.jsonl"
    rows = [
        {"kind": "trial", "rung": 0, "cid": "m2-family-z0",
         "status": "measured", "t": 100.0, "dur_s": 5.0,
         "result": {"steps_per_s": 12.5}},
        {"kind": "trial", "rung": 0, "cid": "m2-bad",
         "status": "rejected", "t": 95.0,
         "reason": "degenerate"},
    ]
    jp.write_text("".join(json.dumps(r) + "\n" for r in rows))
    trace = chrome_trace(str(tmp_path))
    tune_evs = [e for e in trace["traceEvents"]
                if e.get("cat") == "tune"]
    spans = [e for e in tune_evs if e["ph"] == "X"]
    instants = [e for e in tune_evs if e["ph"] == "i"]
    assert len(spans) == 1 and len(instants) == 1
    assert spans[0]["dur"] == pytest.approx(5.0 * 1e6)
    assert spans[0]["args"]["steps_per_s"] == 12.5
    assert instants[0]["args"]["reason"] == "degenerate"


# ----------------------------------------- replica platform (satellite)

def test_worker_env_platform_knob(monkeypatch):
    """The one rule for every spawner: the parent's JAX_PLATFORMS if set,
    else no pin — and virtual devices only for an explicit cpu."""
    from distributed_pipeline_tpu.parallel.launcher import _worker_env

    # parent unpinned (a TPU host): nothing pinned, no virtual devices
    monkeypatch.delenv("JAX_PLATFORMS")
    monkeypatch.setenv("XLA_FLAGS", "--some_flag=1")
    bare = _worker_env(0, 1, "127.0.0.1:1", 2)
    assert "JAX_PLATFORMS" not in bare
    assert bare["XLA_FLAGS"] == "--some_flag=1"
    # parent on cpu (tests, dev rings): pinned, with its virtual devices
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    cpu = _worker_env(0, 1, "127.0.0.1:1", 2)
    assert cpu["JAX_PLATFORMS"] == "cpu"
    assert cpu["XLA_FLAGS"] == ("--some_flag=1 "
                                "--xla_force_host_platform_device_count=2")
    # an explicit platform wins over the parent's, and adds no forcing
    tpu = _worker_env(0, 1, "127.0.0.1:1", 2, platform="tpu")
    assert tpu["JAX_PLATFORMS"] == "tpu"
    assert tpu["XLA_FLAGS"] == "--some_flag=1"
    # "" = never pin: whatever the environment carries passes through
    inherit = _worker_env(0, 1, "127.0.0.1:1", 2, platform="")
    assert inherit["JAX_PLATFORMS"] == "cpu"
    assert inherit["XLA_FLAGS"] == "--some_flag=1"


def test_launcher_threads_worker_platform(monkeypatch):
    from distributed_pipeline_tpu.parallel import launcher

    from tests._fake_ring import make_fake_ring

    fake = make_fake_ring()
    monkeypatch.setattr(launcher, "_run_worker_ring", fake)
    assert launcher.run_argv_as_distributed(
        "mod", [], nprocs=1, worker_platform="tpu") == 0
    assert fake.calls[0]["platform"] == "tpu"
    fake2 = make_fake_ring()
    monkeypatch.setattr(launcher, "_run_worker_ring", fake2)
    launcher.run_argv_as_distributed("mod", [], nprocs=1)
    assert fake2.calls[0]["platform"] is None  # no default pins cpu
    # more workers than one host's chips can go to: a message, never a
    # silent fall to the CPU — an exception from the library ...
    with pytest.raises(ValueError, match="JAX_PLATFORMS=cpu"):
        launcher.run_argv_as_distributed("mod", [], nprocs=2,
                                         worker_platform="tpu")
    # ... for every count the elastic schedule names, before any spawn
    fake3 = make_fake_ring()
    monkeypatch.setattr(launcher, "_run_worker_ring", fake3)
    monkeypatch.setenv(launcher.FORCE_NPROCS_ENV, "1,2")
    with pytest.raises(launcher.WorkersDoNotFitHost, match="--nprocs 2"):
        launcher.run_argv_as_distributed("mod", [], nprocs=1,
                                         worker_platform="tpu")
    assert fake3.calls == []
    monkeypatch.delenv(launcher.FORCE_NPROCS_ENV)
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(ValueError, match="unpinned"):
        launcher.run_argv_as_distributed("mod", [], nprocs=2)
    # ... and the exit message from the CLI
    monkeypatch.setattr(launcher, "get_main_modname", lambda: "mod")
    with pytest.raises(SystemExit, match="unpinned"):
        launcher.parse_and_autorun(
            launcher.create_distributed_parser(),
            ["--distributed", "--nprocs", "2"])


def test_fleet_threads_replica_platform(tmp_path):
    from distributed_pipeline_tpu.serving.fleet import ServingFleet

    calls = []

    def fake_launch(mod, argv, **kw):
        calls.append(kw)
        return 0

    fleet = ServingFleet(str(tmp_path / "one"), 1, "mod", [],
                         replica_platform="tpu", launch_fn=fake_launch)
    fleet.start()
    fleet.stop(join_timeout_s=5.0)
    assert [c["worker_platform"] for c in calls] == ["tpu"]
    # replicas are processes and a chip belongs to one: two need cpu
    with pytest.raises(ValueError, match="2 fleet replicas"):
        ServingFleet(str(tmp_path / "two"), 2, "mod", [],
                     replica_platform="tpu", launch_fn=fake_launch)
    ServingFleet(str(tmp_path / "cpu"), 2, "mod", [],
                 launch_fn=fake_launch)  # inherits the tests' cpu
    # the autoscaler's way in is held to the same rule: no second replica
    # beside a live one, a replacement for a dead one is fine
    import threading
    gate = threading.Event()

    def held_launch(mod, argv, **kw):
        gate.wait(10.0)
        return 0

    grow = ServingFleet(str(tmp_path / "grow"), 1, "mod", [],
                        replica_platform="tpu", launch_fn=held_launch)
    grow.start()
    try:
        with pytest.raises(ValueError, match="beside 1 live"):
            grow.add_replica()
        assert grow.n_replicas == 1
    finally:
        gate.set()
    grow._threads[0].join(10.0)
    assert grow.add_replica() == 1
    grow.stop(join_timeout_s=5.0)


def test_serve_settings_replica_platform_default_auto():
    from distributed_pipeline_tpu.config.serve import ServeSettings

    s = ServeSettings.from_argv(["--checkpoint_path", "x"])
    assert s.replica_platform == "auto"
    s2 = ServeSettings.from_argv(["--checkpoint_path", "x",
                                  "--replica_platform", "cpu"])
    assert s2.replica_platform == "cpu"


# ------------------------------------------- real children (subprocess)

def _child_base_env(n_devices=2):
    env = measure_lib.child_env(n_devices)
    env.pop("DPT_TUNE_INJECT", None)
    return env


def test_measure_child_real_run_and_injected_faults():
    """One real single-arm child on a 2-device forced mesh, then the two
    injected faults: OOM raises before the jax import (fast pruned row),
    a hang trips the parent's timeout — both fold to error rows, and the
    error path never raises."""
    spec = {"cid": "t-m2x1x1-family-z0", "family": "diffuseq",
            "size": "base", "batch": 8, "microbatch": 8, "seq_len": 64,
            "vocab": 256, "hidden": 64, "layers": 2, "heads": 4,
            "dtype": "float32", "seed": 0,
            "mesh": {"data": 2, "fsdp": 1, "tensor": 1},
            "shard_optimizer": False, "rules": None}
    row = measure_lib.run_child(
        "distributed_pipeline_tpu.tune.measure",
        ["--spec", json.dumps(spec), "--steps", "2", "--warmup", "1"],
        env=_child_base_env(), timeout_s=120, cwd=REPO, tag="t")
    assert "error" not in row, row
    assert row["steps_per_s"] > 0 and row["dp"] == 2
    assert row["steady_recompile_count"] == 0
    assert row["opt_state_bytes_per_replica"] > 0

    env = _child_base_env()
    env["DPT_TUNE_INJECT"] = "oom:*family*"
    oom = measure_lib.run_child(
        "distributed_pipeline_tpu.tune.measure",
        ["--spec", json.dumps(spec), "--steps", "2"],
        env=env, timeout_s=60, cwd=REPO, tag="t")
    assert "RESOURCE_EXHAUSTED" in oom["error"]

    env["DPT_TUNE_INJECT"] = "timeout:*family*"
    hung = measure_lib.run_child(
        "distributed_pipeline_tpu.tune.measure",
        ["--spec", json.dumps(spec), "--steps", "2"],
        env=env, timeout_s=3, cwd=REPO, tag="t")
    assert "timeout" in hung["error"]


@pytest.fixture(scope="module")
def tune_cli_run(tmp_path_factory):
    """One real CLI tune on the forced 2-device CPU mesh: 4 candidates
    (baseline + one measured + one statically rejected + one
    OOM-injected), screen-only. Shared by the CLI-contract and the
    train-consumes-artifact tests."""
    tmp = tmp_path_factory.mktemp("tune_cli")
    out_dir = str(tmp / "tune")
    env = _child_base_env()
    env["DPT_TUNE_INJECT"] = "oom:*m1x1x2-family*"
    proc = subprocess.run(
        [sys.executable, "-m", "distributed_pipeline_tpu.run.tune",
         "--family", "diffuseq", "--n_devices", "2",
         "--screen_only", "true", "--max_candidates", "4",
         "--budget_s", "120", "--screen_steps", "2", "--warmup_steps", "1",
         "--batch_size", "8", "--seq_len", "64", "--vocab_size", "256",
         "--hidden_size", "64", "--num_layers", "2", "--num_heads", "4",
         "--dtype", "float32", "--child_timeout_s", "90",
         "--out_dir", out_dir],
        env=env, capture_output=True, text=True, timeout=300, cwd=REPO)
    return proc, out_dir


def test_tune_cli_journals_and_emits_artifact(tune_cli_run):
    proc, out_dir = tune_cli_run
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    fam = out["families"]["diffuseq"]
    c = fam["counts"]
    # 4 enumerated: baseline measured, one oom-injected -> pruned, the
    # degenerate m1x1x2-replicate statically rejected, one more measured
    assert c["enumerated"] == 4
    assert (c["measured"] + c["pruned"] + c["rejected"] + c["skipped"]
            == 4 == fam["accounted"])
    assert c["pruned"] >= 1, "injected OOM must land as a pruned row"
    assert c["rejected"] >= 1
    assert fam["winner"]["cid"] == "diffuseq-m2x1x1-family-z0"
    assert fam["baseline_steps_per_s"] > 0
    rows = search_lib.read_trials(os.path.join(out_dir,
                                               "tune_trials.jsonl"))
    pruned = [r for r in rows if r.get("status") == "pruned"]
    assert pruned and "RESOURCE_EXHAUSTED" in \
        pruned[0]["result"]["error"]
    assert os.path.exists(fam["artifact"])


def test_tune_cli_resume_replays_journal(tune_cli_run):
    """Re-running the identical tune resumes from the journal: no new
    children (fast), identical winner, same trial accounting."""
    proc, out_dir = tune_cli_run
    first = json.loads(proc.stdout.strip().splitlines()[-1])
    rows_before = search_lib.read_trials(
        os.path.join(out_dir, "tune_trials.jsonl"))
    env = _child_base_env()  # note: NO injection this time — pruned
    # trials replay from the journal rather than re-running
    proc2 = subprocess.run(
        [sys.executable, "-m", "distributed_pipeline_tpu.run.tune",
         "--family", "diffuseq", "--n_devices", "2",
         "--screen_only", "true", "--max_candidates", "4",
         "--budget_s", "120", "--screen_steps", "2", "--warmup_steps", "1",
         "--batch_size", "8", "--seq_len", "64", "--vocab_size", "256",
         "--hidden_size", "64", "--num_layers", "2", "--num_heads", "4",
         "--dtype", "float32", "--child_timeout_s", "90",
         "--out_dir", out_dir],
        env=env, capture_output=True, text=True, timeout=120, cwd=REPO)
    assert proc2.returncode == 0, proc2.stderr[-2000:]
    out2 = json.loads(proc2.stdout.strip().splitlines()[-1])
    fam1 = first["families"]["diffuseq"]
    fam2 = out2["families"]["diffuseq"]
    assert fam2["winner"] == fam1["winner"]
    assert fam2["counts"] == fam1["counts"]
    rows_after = search_lib.read_trials(
        os.path.join(out_dir, "tune_trials.jsonl"))
    trial_rows = lambda rows: [r for r in rows if r["kind"] == "trial"]
    assert trial_rows(rows_after) == trial_rows(rows_before)


def test_train_auto_tune_inline_screen(tmp_path):
    """--auto_tune: the screen runs inline before training (rank 0
    measures under the budget, writes <run_dir>/tune_artifact.json, the
    run consumes it) and a SECOND run in the same dir reuses the
    artifact instead of re-tuning (the restart-attempt contract)."""
    run_dir = str(tmp_path / "run")
    env = _child_base_env()
    cmd = [sys.executable, "-m", "distributed_pipeline_tpu.run.train",
           "--auto_tune", "true", "--auto_tune_budget_s", "18",
           "--checkpoint_path", run_dir,
           "--batch_size", "8", "--microbatch", "8", "--seq_len", "64",
           "--vocab_size", "256", "--hidden_size", "64",
           "--num_layers", "2", "--num_heads", "2", "--dtype", "float32",
           "--diffusion_steps", "50", "--ema_rate", "0.9",
           "--learning_steps", "2", "--save_interval", "1000000",
           "--eval_interval", "1000000", "--log_interval", "1000000"]
    train = subprocess.run(cmd, env=env, capture_output=True, text=True,
                           timeout=240, cwd=REPO)
    assert train.returncode == 0, (train.stderr or train.stdout)[-2000:]
    art_path = os.path.join(run_dir, "tune_artifact.json")
    assert os.path.exists(art_path)
    art = load_partition_artifact(art_path)
    assert art["rules"] is not None and art["mesh"] is not None
    rows = search_lib.read_trials(os.path.join(run_dir,
                                               "tune_trials.jsonl"))
    measured = [r for r in rows if r.get("status") == "measured"]
    assert measured, "the inline screen measured nothing"
    # the budget is a hard guard: an 18s budget cannot have measured the
    # whole 2-device space (9 distinct candidates x ~7s children)
    assert any(r.get("status") == "skipped" for r in rows)
    # second run: artifact reused, no re-tune (trial journal unchanged)
    train2 = subprocess.run(cmd, env=env, capture_output=True, text=True,
                            timeout=180, cwd=REPO)
    assert train2.returncode == 0, (train2.stderr or train2.stdout)[-2000:]
    rows2 = search_lib.read_trials(os.path.join(run_dir,
                                                "tune_trials.jsonl"))
    assert rows2 == rows


def test_train_consumes_artifact_with_steady_recompiles_zero(
        tune_cli_run, tmp_path):
    """The tune -> train handoff: run/train.py --partition_rules
    <artifact> on the matching device count applies the tuned mesh and
    completes a short sanitized run with steady recompiles 0."""
    proc, out_dir = tune_cli_run
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    artifact = out["families"]["diffuseq"]["artifact"]
    run_dir = str(tmp_path / "run")
    env = _child_base_env()
    train = subprocess.run(
        [sys.executable, "-m", "distributed_pipeline_tpu.run.train",
         "--partition_rules", artifact,
         "--checkpoint_path", run_dir,
         "--batch_size", "8", "--microbatch", "8", "--seq_len", "64",
         "--vocab_size", "256", "--hidden_size", "64",
         "--num_layers", "2", "--num_heads", "2", "--dtype", "float32",
         "--diffusion_steps", "50", "--ema_rate", "0.9",
         "--learning_steps", "3", "--save_interval", "1000000",
         "--eval_interval", "1000000", "--log_interval", "1000000",
         "--sanitize", "true"],
        env=env, capture_output=True, text=True, timeout=180, cwd=REPO)
    assert train.returncode == 0, (train.stderr or train.stdout)[-2000:]
    # the tuned mesh recommendation (dp=2 on the 2 forced devices) was
    # applied — the run's own goodput record proves the steady state
    rec = json.load(open(os.path.join(run_dir,
                                      "goodput_attempt000.json")))
    assert rec["steady_recompile_count"] == 0
    log = (train.stdout or "") + (train.stderr or "")
    assert "applying tuned mesh recommendation" in log
