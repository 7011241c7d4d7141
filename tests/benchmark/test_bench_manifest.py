"""BENCHMARK.json and its data files keep to the contract, and the check
that run.py makes at start refuses what a later edit would most easily break."""

import copy
import json
import os
import shutil
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import published  # noqa: E402
from benchmark.harness import manifest  # noqa: E402


def test_the_committed_manifest_passes_its_own_check():
    m = manifest.Manifest(REPO)
    manifest.check(m)
    assert m.doc["command"] == ["python3", "benchmark/run.py"]
    assert m.doc["paths"] == ["benchmark", "tests/benchmark"]
    assert isinstance(m.doc["run_seconds"], int) and 10 <= m.doc["run_seconds"] <= 51
    assert set(m.doc) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 * 1024


def test_bounds_follow_the_contract():
    m = manifest.Manifest(REPO)
    for e in m.doc["end_to_end"]:
        assert set(e) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= e["bound"] <= 0.1
        assert e["source"] in ("host_clock", "device_trace")
    for p in m.doc["per_layer"]:
        assert set(p) <= {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert "bound" not in p


def test_every_cell_finds_its_files_by_name_and_its_metrics_by_traffic_kind():
    m = manifest.Manifest(REPO)
    for cell in m.doc["workloads"]:
        assert cell["chips"] == 1 and len(cell["why"]) <= 200
        kind = m.traffic(cell["traffic"])["kind"]
        config = m.config(cell["config"])
        assert m.config_entry(cell["config"])["source"] == config["source"]
        listed = {p["name"] for p in m.doc["per_layer"]
                  if cell["name"] in p.get("workloads", [cell["name"]])}
        found = {f["name"] for f in m.layer_metrics_of(cell["name"])}
        assert found == listed, (cell["name"], kind)
        assert len(m.end_to_end_of(cell["name"])) >= 2


def test_a_configuration_lists_every_key_it_changed_and_never_a_width():
    """The rule is `published.check`; what each source says is a file beside
    the test data, one per configuration, so a new one brings its own."""
    m = manifest.Manifest(REPO)
    for entry in m.doc["configs"]:
        doc = published.check(m, entry["name"])
        assert {"hidden_size", "intermediate_size"} <= set(doc["widths"])
    listed = copy.deepcopy(m)  # the same, with a width listed as cut
    listed.doc["configs"][0]["reduced"].append("intermediate_size")
    with pytest.raises(AssertionError, match="intermediate_size is a width"):
        published.check(listed, listed.doc["configs"][0]["name"])


def _mutate(tmp_path, change):
    root = tmp_path / "root"
    shutil.copytree(os.path.join(REPO, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "harness", "run.py"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        doc = json.load(fh)
    change(doc, root)
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    return manifest.Manifest(str(root))


def _bad_name(doc, root):
    doc["workloads"][0]["name"] = "mistral7b agents8"


def _bad_unit(doc, root):
    doc["end_to_end"][0]["unit"] = "tokens per second"


def _moves_nothing(doc, root):
    doc["per_layer"][0]["moves"] = "goodput"


def _moves_unreported(doc, root):
    # rpc.ttft_p50_ms lists the agent cells, which do not report ttft_p80_ms
    for p in doc["per_layer"]:
        if p["name"] == "rpc.ttft_p50_ms":
            p["moves"] = "ttft_p80_ms"


def _missing_traffic(doc, root):
    os.remove(root / "benchmark" / "traffic" / "agents8.json")


def _missing_config(doc, root):
    os.remove(root / "benchmark" / "configs" / "mistral-7b-int8.json")


def _too_many(doc, root):
    extra = copy.deepcopy(doc["end_to_end"][1])
    extra["name"] = "tpot_p90_ms"
    doc["end_to_end"].append(extra)


def _no_setup(doc, root):
    doc["end_to_end"] = [e for e in doc["end_to_end"] if e["name"] != "setup_s"]


def _metric_without_file(doc, root):
    os.remove(root / "benchmark" / "layer_metrics" / "device.idle_pct.json")


def _twice(doc, root):
    doc["workloads"].append(copy.deepcopy(doc["workloads"][0]))


def _wrong_kind(doc, root):
    # a closed-loop metric listed for an open-loop cell
    for p in doc["per_layer"]:
        if p["name"] == "batcher.slot_use_pct":
            p["workloads"].append("mistral7b-longprompt")


@pytest.mark.parametrize("change", [
    _bad_name, _bad_unit, _moves_nothing, _moves_unreported, _missing_traffic,
    _missing_config, _too_many, _no_setup, _metric_without_file, _twice,
    _wrong_kind,
], ids=lambda f: f.__name__.strip("_"))
def test_the_check_refuses(tmp_path, change):
    with pytest.raises((manifest.ManifestError, FileNotFoundError)):
        manifest.check(_mutate(tmp_path, change))
