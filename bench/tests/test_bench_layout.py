"""BENCHMARK.json and the files the harness finds by name."""
import json
import os

import bench_tiny  # noqa: F401  (puts the repository on the path)
import pytest

from bench import harness

SPEC = harness.spec()
NAMES = {"configs", "workloads", "end_to_end", "per_layer"}


def test_benchmark_json_has_exactly_the_contract_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", *NAMES}
    assert SPEC["command"][1] == "bench/run.py"
    assert SPEC["paths"] == ["bench"]


@pytest.mark.parametrize("entry", SPEC["configs"], ids=lambda e: e["name"])
def test_config_file_states_source_reduced_and_assumed(entry):
    path = os.path.join(harness.ROOT, entry["file"])
    assert entry["file"] == f"bench/configs/{entry['name']}.json"
    c = harness.config_file(entry["name"])
    assert c["source"] == entry["source"]
    assert sorted(c["reduced"]) == sorted(entry["reduced"])
    assert isinstance(c["assumed"], dict) and c["assumed"]
    with open(path) as f:
        assert json.load(f) == c


@pytest.mark.parametrize("wl", SPEC["workloads"], ids=lambda w: w["name"])
def test_workload_finds_its_config_traffic_and_job(wl):
    harness.find(SPEC["configs"], wl["config"], "config")
    mix = harness.traffic_file(wl["traffic"])
    assert callable(harness.job_class(mix["job"]))
    assert wl["chips"] in (1, 4)
    assert wl["name"] == f"{wl['config']}.{wl['traffic']}"


@pytest.mark.parametrize("metric", SPEC["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_has_a_reader_and_moves_a_reported_metric(metric):
    assert callable(harness.layer_reader(metric["name"]))
    moves = harness.find(SPEC["end_to_end"], metric["moves"], "metric")
    for w in metric["workloads"]:
        harness.find(SPEC["workloads"], w, "workload")
        assert harness.applies(moves, w)


def test_every_cell_reports_setup_another_end_to_end_and_a_layer():
    for wl in SPEC["workloads"]:
        e2e = [m["name"] for m in SPEC["end_to_end"]
               if harness.applies(m, wl["name"])]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert any(harness.applies(m, wl["name"]) for m in SPEC["per_layer"])


def test_peaks_are_keyed_by_device_kind_and_unknown_kinds_refused():
    assert harness.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        harness.peaks("cpu")
