"""Config bridge tests (SURVEY.md §4 recommends: argparse<->json<->pydantic
round-trip semantics of the reference config/base.py)."""

import argparse
import json
from typing import Literal

import pytest

from distributed_pipeline_tpu.config import (
    ArgparseCompatibleBaseModel as S,
    TrainSettings,
    item,
)
from distributed_pipeline_tpu.utils.perf import (
    enable_persistent_compilation_cache,
)


class Inner(S):
    alpha: float = item(0.5, "inner alpha")
    kind: Literal["a", "b"] = item("a", "inner kind")


class Demo(S):
    lr: float = item(1e-4, "learning rate")
    steps: int = item(100)
    use_ema: bool = item(True)
    name: str = item("demo")
    inner: Inner = Inner()


def test_to_argparse_defaults():
    ns = Demo.to_argparse().parse_args([])
    cfg = Demo.from_argparse(ns)
    assert cfg == Demo()


def test_cli_overrides_and_nested_group():
    ns = Demo.to_argparse().parse_args(
        ["--lr", "3e-4", "--alpha", "0.9", "--kind", "b", "--use_ema", "false"]
    )
    cfg = Demo.from_argparse(ns)
    assert cfg.lr == 3e-4
    assert cfg.inner.alpha == 0.9
    assert cfg.inner.kind == "b"
    assert cfg.use_ema is False


@pytest.mark.parametrize("val,expect", [("true", True), ("0", False), ("YES", True)])
def test_bool_coercion(val, expect):
    ns = Demo.to_argparse().parse_args(["--use_ema", val])
    assert Demo.from_argparse(ns).use_ema is expect


def test_literal_choices_rejected():
    with pytest.raises(SystemExit):
        Demo.to_argparse().parse_args(["--kind", "zzz"])


def test_leftover_keys_rejected():
    # Reference asserts no unconsumed namespace keys (config/base.py:30).
    ns = argparse.Namespace(lr=1.0, steps=1, use_ema=True, name="x", alpha=0.1,
                            kind="a", BOGUS=1)
    with pytest.raises(ValueError, match="BOGUS"):
        Demo.from_argparse(ns)


def test_json_round_trip(tmp_path):
    cfg = Demo(lr=7e-5, inner=Inner(alpha=0.25))
    p = tmp_path / "cfg.json"
    cfg.save_json(str(p))
    assert Demo.parse_file(str(p)) == cfg


def test_extra_keys_forbidden():
    with pytest.raises(Exception):
        Demo(bogus=1)


def test_train_settings_defaults_match_reference():
    # Defaults copied from reference config/train.py:6-41.
    ts = TrainSettings()
    assert ts.batch_size == 2048
    assert ts.microbatch == 64
    assert ts.learning_steps == 320000
    assert ts.ema_rate == "0.5,0.9,0.99"
    assert ts.seed == 102


def test_config_json_overrides_cli(tmp_path):
    # --config_json short-circuits the CLI (reference config/train.py:70-77).
    cfg = TrainSettings(lr=5e-4, seq_len=256)
    p = tmp_path / "train.json"
    cfg.save_json(str(p))
    parser = TrainSettings.to_argparse(add_json=True)
    ns = parser.parse_args(["--config_json", str(p)])
    loaded = TrainSettings.from_argparse(ns)
    assert loaded.lr == 5e-4 and loaded.seq_len == 256


def test_flat_dict_for_model_factory():
    # create_model_from_config(**args.dict()) surface (reference run/train.py:71).
    d = TrainSettings().dict()
    assert "lr" in d and "seq_len" in d and "dp" in d


def test_json_dump_is_loadable_config():
    # README.md:18-21 one-liner: default config dump must parse back.
    blob = TrainSettings().to_json()
    assert TrainSettings.model_validate(json.loads(blob)) == TrainSettings()


def test_config_json_rejects_explicit_default_flag(tmp_path):
    """A flag explicitly set to its default value still conflicts with
    --config_json (true mutual exclusivity, reference config/train.py:63-67).
    The parsed argv is carried on the namespace (as the launcher and
    from_argv record it), never sniffed from the process's sys.argv."""
    from distributed_pipeline_tpu.config.train import TrainSettings

    cfg = tmp_path / "c.json"
    cfg.write_text(TrainSettings().to_json())
    default_lr = TrainSettings().lr
    argv = ["--lr", str(default_lr), "--config_json", str(cfg)]
    parser = TrainSettings.to_argparse(add_json=True)
    ns = parser.parse_args(argv)
    ns._parsed_argv = argv  # what parse_and_autorun/from_argv attach
    with pytest.raises(SystemExit):
        TrainSettings.from_argparse(ns)


def test_config_json_ignores_hosting_process_argv(tmp_path, monkeypatch):
    """A programmatic parse (no recorded argv) must not abort on flags that
    belong to the hosting process's command line."""
    import sys
    from distributed_pipeline_tpu.config.train import TrainSettings

    cfg = tmp_path / "c.json"
    cfg.write_text(TrainSettings().to_json())
    monkeypatch.setattr(sys, "argv", ["driver.py", "--seed", "7"])
    parser = TrainSettings.to_argparse(add_json=True)
    ns = parser.parse_args(["--config_json", str(cfg)])
    settings = TrainSettings.from_argparse(ns)  # must not raise
    assert settings.seed == TrainSettings().seed


def test_abbreviated_flags_rejected():
    """ADVICE r2: allow_abbrev=False — a prefix-abbreviated flag (--log_int)
    must be an argparse error, not silently accepted (it would dodge the
    --config_json mutual-exclusivity scan, which matches exact field names)."""
    from distributed_pipeline_tpu.config.train import TrainSettings

    parser = TrainSettings.to_argparse(add_json=True)
    with pytest.raises(SystemExit):
        parser.parse_args(["--log_int", "50"])
    ns = parser.parse_args(["--log_interval", "50"])  # exact name still works
    assert ns.log_interval == 50


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
