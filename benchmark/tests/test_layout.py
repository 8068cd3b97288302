"""Every name in BENCHMARK.json resolves to its files; names and units use
only the allowed characters; the per-layer entries agree with their files."""

import json
import os
import re

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


BENCH = load(ROOT, "BENCHMARK.json")


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmark"]
    assert 1 <= BENCH["run_seconds"] <= 51
    cells = len(BENCH["workloads"])
    assert sum(c["chips"] == 4 for c in BENCH["workloads"]) <= max(
        1, cells // 4)


WIDTH = re.compile(r"(_dim|_rank)$|hidden|intermediate|latent|state|proj|"
                   r"head_size|expansion|experts_per|^n_embd$|^n_inner$")


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_cell_resolves_to_its_files(cell):
    import run as bench_run
    from harness import driver_for, family_for, reference_for
    found = bench_run.resolve_cell(cell["name"])
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    # the data names its code: a driver for the kind of traffic, an adapter
    # and a plain reference for the family of models
    assert callable(driver_for(found["traffic"]).run)
    fam, ref = family_for(found["config"]), reference_for(found["config"])
    assert fam.program_flags(found["config"])
    assert ref.param_count(found["config"]) > 0
    assert "rehearse" in found["traffic"] and "rehearse" in found["config"]
    names = {m["name"] for m in found["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert found["per_layer"], "a cell reports at least one per-layer metric"
    assert any("mfu" in m["name"].split(".")[0].split("_")
               for m in found["per_layer"]), "no whole-step share of the peak"
    for m in found["per_layer"]:
        reader, spec = bench_run.load_reader(m["name"])
        assert callable(reader)
        for key in ("layer", "unit", "better", "source", "moves"):
            assert spec[key] == m[key], (m["name"], key)
        assert m["moves"] in names, (m["name"], "moves a metric the cell "
                                     "does not report")


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_agrees_with_its_entry(entry):
    """What every configuration must satisfy, whatever its family: the
    file's source and cuts are the entry's, no cut names a width, and each
    key it says it changed is in the file."""
    cfg = load(ROOT, entry["file"])
    assert cfg["source"] == entry["source"]
    assert cfg["reduced"] == entry["reduced"]
    assert entry["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))
    for key in entry["reduced"]:
        assert NAME.match(key) and key in cfg, key
        assert not WIDTH.search(key), f"{key}: a width may never be cut"
    assert any(c["config"] == entry["name"] for c in BENCH["workloads"])
    files = [c["file"] for c in BENCH["configs"]]
    assert len(files) == len(set(files))


def test_names_units_and_lines():
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for row in BENCH[group]:
            assert NAME.match(row["name"]), row["name"]
            names.append((group in ("end_to_end", "per_layer"), row["name"]))
            for key in ("why", "layer", "source"):
                if key in row:
                    assert 1 <= len(row[key]) <= 200 and "\n" not in row[key] \
                        and "\t" not in row[key], (row["name"], key)
    assert len(names) == len(set(names))
    for row in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(row["unit"]), row
        assert row["better"] in ("lower", "higher")
        assert row["source"] in ("device_trace", "program_span",
                                 "program_counter", "host_clock")
    for row in BENCH["end_to_end"]:
        assert set(row) <= {"name", "unit", "better", "bound", "source",
                            "workloads"}
        assert 0.01 <= row["bound"] <= 0.1
        assert row["source"] in ("host_clock", "device_trace")
    for row in BENCH["per_layer"]:
        assert set(row) <= {"name", "unit", "better", "source", "layer",
                            "moves", "workloads"}
    for c in BENCH["workloads"]:
        assert NAME.match(c["config"]) and NAME.match(c["traffic"])
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_files_under_paths_have_plain_names():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for base, dirs, files in os.walk(HERE):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in files:
            rel = os.path.relpath(os.path.join(base, f), ROOT)
            assert ok.match(rel), rel
