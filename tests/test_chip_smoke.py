"""chip_smoke.py on the CPU, at a tiny size: the control flow is walked end
to end (train CLI -> save -> serve CLI -> plain-decode reference), every
phase runs clean, and the script still cannot pass — the platform is not
``tpu``. A phase made to fail fails the script too."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

NOT_TPU = ["ran on platform 'cpu', not 'tpu'"]

TINY = chip_smoke.Sizes(
    model_argv=("--model_family", "gpt2", "--hidden_size", "32",
                "--num_layers", "2", "--num_heads", "2",
                "--vocab_size", "64", "--seq_len", "32",
                "--dtype", "float32"),
    vocab_size=64, corpus=(12, 16, 32, 16),
    batch=16, microbatch=8, steps=4, lr=1e-2,
    requests=((8, 6, 2), (5, 4, 2)),
    decode_slots=4, page_size=4, max_prompt_len=16,
    mesh_steps=2, loss_tol=1e-3, child_timeout_s=300.0,
    decode_geoms=((2, 2, 8, 4, 3, 2), (2, 2, 64, 16, 10, 2)),
    decode_tol=6 * 2.0 ** -8,
    ring_argv=("--model_family", "gpt2", "--hidden_size", "32",
               "--num_layers", "2", "--num_heads", "2", "--vocab_size", "64",
               "--seq_len", "16", "--dtype", "float32",
               "--dataset", "synthetic-lm", "--batch_size", "8",
               "--microbatch", "8"),
    ring_steps=2)


@pytest.fixture
def smoke(tmp_path, monkeypatch):
    monkeypatch.setattr(chip_smoke, "OUT_DIR", str(tmp_path / "out"))
    os.makedirs(chip_smoke.OUT_DIR)
    return chip_smoke


def test_phases_run_clean_on_cpu_and_fail_only_on_platform(smoke, tmp_path):
    run_dir = str(tmp_path / "out" / "run")
    train = smoke.phase_train(run_dir, TINY, seed=7)
    assert train["failures"] == NOT_TPU, train["failures"]
    assert len(train["losses"]) == TINY.steps
    assert train["losses"][-1] < train["losses"][0]
    assert train["steady_recompile_count"] == 0
    # off-TPU the auto arms are the XLA/optax ones and no Mosaic kernel
    # is in the program: nothing to demand, nothing found
    assert train["attention_impl"] == "xla" and not train["fused_update"]
    assert not any(train["tpu_custom_calls"].values())

    serve = smoke.phase_serve(run_dir, TINY, seed=7)
    assert serve["failures"] == NOT_TPU, serve["failures"]
    assert serve["summary"]["decode_impl"] == "xla"
    assert set(serve["summary"]["compile_s"]) == {"serve_prefill",
                                                  "serve_decode"}

    ref = smoke.phase_reference(run_dir, serve, TINY)
    assert ref["failures"] == NOT_TPU, ref["failures"]
    assert all(r["first_token_equal"] for r in ref["rows"])


def test_script_cannot_pass_on_a_cpu(smoke, capsys):
    rc = smoke.main([])
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert rc != 0
    assert json.loads(last) == {
        "ok": False,
        "device": {"platform": "cpu", "kind": "cpu", "count": 8}}


def test_a_failed_phase_fails_the_script(smoke, capsys, monkeypatch):
    """Everything passes except one phase: still not ok, still non-zero."""
    tpu = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    good = {"ok": True, "failures": [], "device": tpu}
    monkeypatch.setattr(smoke, "phase_probe",
                        lambda: dict(good, phase="probe"))
    monkeypatch.setattr(smoke, "phase_train",
                        lambda *a, **k: dict(good, phase="train"))
    monkeypatch.setattr(smoke, "phase_reference",
                        lambda *a, **k: dict(good, phase="reference"))
    monkeypatch.setattr(
        smoke, "phase_serve",
        lambda *a, **k: dict(good, phase="serve", ok=False,
                             failures=["recompile_count 3 != 0"]))
    assert smoke.main([]) == 1
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last == {"ok": False, "device": tpu}
    # and with that phase repaired the same wiring passes
    monkeypatch.setattr(smoke, "phase_serve",
                        lambda *a, **k: dict(good, phase="serve"))
    assert smoke.main([]) == 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last == {"ok": True, "device": tpu}


@pytest.mark.parametrize("which", ["decode", "launcher"])
def test_side_checks_run_clean_on_cpu(smoke, capsys, monkeypatch, which):
    """--only decode / --only launcher at a tiny size: the check itself
    holds (the interpreted kernel agrees with the XLA arm; the supervised
    worker trains, and the second run resumes) and only the platform
    fails it."""
    res = {"decode": smoke.phase_decode,
           "launcher": smoke.phase_launcher}[which](TINY, seed=7)
    assert res["failures"] == NOT_TPU, res["failures"]
    if which == "decode":
        assert set(res["cases"]) == {
            f"{shape}.{kv}_{form}" for shape in ("H2xDh8", "H2xDh64")
            for kv in ("bf16", "int8") for form in ("decode", "span")}
        assert set(res["auto_resolves_to"].values()) == {"xla"}  # no TPU
    else:
        assert [(a["start_step"], a["end_step"])
                for a in res["attempts"]] == [(0, 2), (2, 4)]
    # through the command line it is that phase and nothing else
    tpu = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    ran = []
    monkeypatch.setattr(smoke, "phase_probe", lambda: {
        "ok": True, "failures": [], "device": tpu})
    for name in ("train", "serve", "reference", "mesh", "decode",
                 "launcher"):
        monkeypatch.setattr(
            smoke, f"phase_{name}",
            lambda *a, _n=name, **k: ran.append(_n) or {
                "ok": True, "failures": [], "device": tpu})
    assert smoke.main(["--only", which]) == 0
    assert ran == [which]
    assert json.loads(capsys.readouterr().out.strip().splitlines()[-1]) \
        == {"ok": True, "device": tpu}


def test_mesh_phase_on_virtual_devices(smoke, monkeypatch):
    """--chips 4's phase on four virtual CPU devices: losses agree, state
    is split four ways, and it fails on the platform alone."""
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=4")
    res = smoke.phase_mesh(TINY, seed=7)
    assert res["failures"] == NOT_TPU, res["failures"]
    one, four = res["runs"]["1dev"], res["runs"]["data2_fsdp2"]
    assert four["mesh"] == {"data": 2, "fsdp": 2}
    assert four["leaves_split"] > 0 and one["leaves_split"] == 0
    assert max(four["state_bytes_per_device"].values()) \
        < one["state_bytes"]
