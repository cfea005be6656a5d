"""BENCHMARK.json and the data files it names: well-formed, resolvable, and
every name in them known to the harness."""

import json
import os

import pytest

from bench_paths import REPO, TOY_MANIFEST  # noqa: F401 - sets sys.path

from harness import manifest as M
from harness.checks import CHECKS
from harness.generators import GENERATORS
from harness.readers import READERS
from harness.system import BUILDERS

MANIFESTS = [None, TOY_MANIFEST]


@pytest.mark.parametrize("path", MANIFESTS, ids=["repo", "toy"])
def test_manifest_is_well_formed(path):
    man = M.load_manifest(path)
    assert M.problems(man) == []
    assert os.path.getsize(path or os.path.join(REPO, "BENCHMARK.json")) \
        < 64 * 1024


@pytest.mark.parametrize("path", MANIFESTS, ids=["repo", "toy"])
def test_every_cell_resolves_to_known_names(path):
    man = M.load_manifest(path)
    for w in man["workloads"]:
        cell = M.cell(man, w["name"])
        assert cell["config"]["builder"] in BUILDERS
        assert cell["traffic"]["generator"] in GENERATORS
        assert cell["traffic"]["check"] in CHECKS
        reported = {m["name"] for m in cell["end_to_end"]}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell["per_layer"], f"{w['name']} reports no per-layer metric"
        for m in cell["per_layer"]:
            spec = M.load_json_named(man, "metrics", m["name"])
            own = M.load_reader_module(man, m["name"])
            assert own is not None or spec["reader"] in READERS
            assert m["moves"] in reported, (m["name"], m["moves"])


def test_repo_manifest_command_and_paths():
    man = M.load_manifest()
    assert man["command"] == ["python3", "benchmarks/run.py"]
    for p in man["paths"]:
        assert os.path.isdir(os.path.join(REPO, p))
    assert sum(w["chips"] == 4 for w in man["workloads"]) <= max(
        1, len(man["workloads"]) // 4)


def test_every_configuration_file_is_named_by_the_manifest():
    man = M.load_manifest()
    named = {c["file"] for c in man["configs"]}
    found = {os.path.join("benchmarks", "configs", f)
             for f in os.listdir(os.path.join(REPO, "benchmarks", "configs"))}
    assert named == found
    mixes = {w["traffic"] + ".json" for w in man["workloads"]}
    assert mixes == set(os.listdir(os.path.join(REPO, "benchmarks",
                                                "traffic")))
    metrics = {m["name"] + ".json" for m in man["per_layer"]}
    assert metrics == set(os.listdir(os.path.join(REPO, "benchmarks",
                                                  "metrics")))


def test_configuration_file_states_the_published_widths():
    man = M.load_manifest()
    with open(os.path.join(REPO, man["configs"][0]["file"])) as f:
        cfg = json.load(f)
    for role in ("embedder", "reranker"):
        m = cfg["models"][role]
        assert (m["hidden_size"], m["num_hidden_layers"],
                m["num_attention_heads"], m["intermediate_size"],
                m["vocab_size"]) == (384, 6, 12, 1536, 30522)
    dep = cfg["deployment"]
    # the index rounds its capacity to a power of two: the file states one,
    # so the 6.44 GB the cell is sized by is what a search scans
    assert dep["index_capacity"] & (dep["index_capacity"] - 1) == 0
    assert dep["index_warm_rows"] < dep["index_capacity"]
    assert dep["index_capacity"] * dep["index_dimensions"] * 2 == 6442450944
    numbers = set(cfg["limits"])
    assert {"failed_requests", "generator_problems"} <= numbers


def test_a_bad_manifest_is_refused():
    man = M.load_manifest()
    man = json.loads(json.dumps(man))
    man["end_to_end"][0]["unit"] = "docs per second"
    man["per_layer"][0]["moves"] = "nothing"
    man["workloads"][0]["name"] = "has space"
    got = M.problems(man)
    assert any("bad unit" in g for g in got)
    assert any("moves" in g for g in got)
    assert any("bad name" in g for g in got)
