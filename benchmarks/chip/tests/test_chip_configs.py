"""Configuration files against the program's registry, and the benchmark's
files against ``BENCHMARK.json``."""

import json
import re
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import harness  # noqa: E402

CONFIGS = sorted(p.stem for p in (harness.ROOT / "configs").glob("*.json"))
BENCH = json.loads((harness.REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("name", CONFIGS)
def test_config_is_the_registry_entry_but_for_reduced_keys(name):
    from repro.configs import get_config

    conf = harness.config_file(name)
    reg = get_config(conf["registry"])
    for key, field in conf["program_fields"].items():
        used = conf["config"][key]
        if key in conf["reduced"]:
            cut = conf["reduced"][key]
            assert used == cut["used"]
            assert getattr(reg, field) == cut["published"], key
        else:
            assert getattr(reg, field) == used, key
    assert set(conf["reduced"]) <= set(conf["program_fields"])
    cfg = harness.model_config(conf)
    assert cfg.n_layers == conf["config"]["num_hidden_layers"]


def test_benchmark_names_files_that_exist():
    for c in BENCH["configs"]:
        assert NAME.match(c["name"])
        conf = harness.config_file(c["name"])
        assert c["file"] == f"benchmarks/chip/configs/{c['name']}.json"
        assert sorted(c["reduced"]) == sorted(conf["reduced"])
        assert c["source"] == conf["source"]
        assert (harness.ROOT / "configs" / f"{c['name']}.py").exists()
    for w in BENCH["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        cell = harness.cell(w["name"])
        assert (cell["config"], cell["traffic"], cell["chips"]) == (
            w["config"], w["traffic"], w["chips"])
        harness.traffic_mix(w["traffic"])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"])
        assert hasattr(harness.metric_reader(m["name"]), "read")


def test_peaks_by_device_kind():
    p = harness.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peaks("cpu")
