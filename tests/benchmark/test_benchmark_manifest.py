"""BENCHMARK.json and the files it names: well-formed, resolvable, and every
name in them either a built-in of the harness or a file under ``paths``."""

import json
import os
import shutil

import pytest

from bench_paths import REPO, TOY_MANIFEST  # noqa: F401 - sets sys.path

from harness import manifest as M
from harness.checks import CHECKS
from harness.generators import GENERATORS
from harness.layouts import LAYOUTS
from harness.readers import READERS, WORK
from harness.system import BUILDERS

MANIFESTS = [None, TOY_MANIFEST]
TOY = os.path.dirname(TOY_MANIFEST)


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
        assert callable(M.resolve(man, "builders", cell["config"]["builder"]))
        assert callable(M.resolve(man, "generators",
                                  cell["traffic"]["generator"]))
        assert callable(M.resolve(man, "checks", cell["traffic"]["check"]))
        assert set(cell["config"]["layouts"]) == set(cell["config"]["models"])
        reported = {m["name"] for m in cell["end_to_end"]}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell["per_layer"], f"{w['name']} reports no per-layer metric"
        for m in cell["per_layer"]:
            spec = M.load_json_named(man, "metrics", m["name"])
            own = M.load_reader_module(man, m["name"])
            assert own is not None or spec["reader"] in READERS
            if "work" in spec.get("params", {}):
                assert callable(M.resolve(man, "work", spec["params"]["work"]))
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


def test_every_module_found_by_name_is_named_by_the_manifest():
    """No orphan code: a module under ``benchmarks/<kind>/`` is there
    because a configuration, a mix or a metric of ``BENCHMARK.json`` names
    it. (The directories may be absent: no cell has needed one yet.)"""
    man = M.load_manifest()
    named = {kind: set() for kind in M.KINDS}
    for w in man["workloads"]:
        cell = M.cell(man, w["name"])
        named["builders"].add(cell["config"]["builder"])
        named["generators"].add(cell["traffic"]["generator"])
        named["checks"].add(cell["traffic"]["check"])
        for role, model in cell["config"]["models"].items():
            named["layouts"].add(model.get("layout") or M.ROLE_LAYOUT[role])
        for m in cell["per_layer"]:
            spec = M.load_json_named(man, "metrics", m["name"])
            named["work"].add(spec.get("params", {}).get("work"))
    for kind in M.KINDS:
        folder = os.path.join(REPO, "benchmarks", kind)
        found = {f[:-3] for f in os.listdir(folder)
                 if f.endswith(".py")} if os.path.isdir(folder) else set()
        assert found <= named[kind], f"benchmarks/{kind}: {found - named[kind]}"


@pytest.mark.parametrize("kind,name,want", [
    ("builders", "qa_rest_server", BUILDERS["qa_rest_server"]),
    ("generators", "closed_loop_posts", GENERATORS["closed_loop_posts"]),
    ("checks", "answer", CHECKS["answer"]),
    ("work", "knn_search", WORK["knn_search"]),
    ("layouts", "gpt2", LAYOUTS["gpt2"]),
    ("layouts", "bert", LAYOUTS["bert"]),
])
def test_resolve_finds_a_built_in(kind, name, want):
    assert M.resolve(M.load_manifest(TOY_MANIFEST), kind, name) is want


@pytest.mark.parametrize("kind,name,export", [
    ("builders", "toy_qa", "build"),
    ("generators", "toy_posts", "Generator"),
    ("checks", "toy_answer", "Check"),
    ("work", "toy_step", "work"),
    ("layouts", "toy_renamed", "layout"),
])
def test_resolve_finds_a_file(kind, name, export):
    man = M.load_manifest(TOY_MANIFEST)
    got = M.resolve(man, kind, name)
    module = M.load_named_module(man, kind, name)
    assert module.__file__ == os.path.join(TOY, kind, name + ".py")
    assert got is getattr(module, export)
    assert M.KINDS[kind][2] == export
    # ... and only where the manifest's paths lead: the repo's own manifest
    # does not search the toy's directory
    with pytest.raises(M.ManifestError):
        M.resolve(M.load_manifest(), kind, name)


def test_resolve_says_where_it_looked():
    man = M.load_manifest(TOY_MANIFEST)
    with pytest.raises(M.ManifestError) as err:
        M.resolve(man, "builders", "nobody")
    said = str(err.value)
    assert "builders/nobody.py" in said
    for p in man["paths"]:
        assert p in said
    for builtin in BUILDERS:
        assert builtin in said
    with pytest.raises(M.ManifestError, match="bad name"):
        M.resolve(man, "builders", "../harness/system")


def _toy_copy(tmp_path) -> tuple[dict, str]:
    """The toy benchmark copied to where a test may break it; a path of a
    manifest may be absolute."""
    root = str(tmp_path / "toy")
    shutil.copytree(TOY, root)
    man = M.load_manifest(os.path.join(root, "BENCHMARK.json"))
    man["paths"] = ["benchmarks", root]
    for c in man["configs"]:
        c["file"] = os.path.join(root, "configs", os.path.basename(c["file"]))
    return man, root


def _edit(path: str, change) -> None:
    with open(path) as f:
        data = json.load(f)
    change(data)
    with open(path, "w") as f:
        json.dump(data, f)


@pytest.mark.parametrize("kind,name", [
    ("builders", "qa_rest_server"), ("generators", "commit_feeder"),
    ("checks", "ingest"), ("work", "embed_step"), ("layouts", "gpt2")])
def test_a_file_with_the_name_of_a_built_in_is_refused(tmp_path, kind, name):
    man, root = _toy_copy(tmp_path)
    assert M.problems(man) == []
    os.makedirs(os.path.join(root, kind), exist_ok=True)
    with open(os.path.join(root, kind, name + ".py"), "w") as f:
        f.write(f"{M.KINDS[kind][2]} = None\n")
    with pytest.raises(M.ManifestError, match="name of a built-in"):
        M.resolve(man, kind, name)
    # a cell that runs the built-in says so before a run
    if kind != "work":          # no toy metric names a built-in work
        assert any("name of a built-in" in p for p in M.problems(man))


@pytest.mark.parametrize("what,file,change", [
    ("builder", "configs/toy-rag-byname.json",
     lambda c: c.update(builder="nobody")),
    ("generator", "traffic/toy_answer_byname.json",
     lambda t: t.update(generator="nobody")),
    ("check", "traffic/toy_answer_byname.json",
     lambda t: t.update(check="nobody")),
    ("layout", "configs/toy-rag-byname.json",
     lambda c: c["models"]["decoder"].update(layout="nobody")),
    ("work", "metrics/toy.decode_step_roofline.json",
     lambda m: m["params"].update(work="nobody")),
    ("reader", "metrics/toy.decode_step_roofline.json",
     lambda m: m.update(reader="nobody")),
])
def test_problems_reports_what_a_cell_names_and_nothing_provides(
        tmp_path, what, file, change):
    man, root = _toy_copy(tmp_path)
    assert M.problems(man) == []
    _edit(os.path.join(root, file), change)
    got = M.problems(man)
    assert got and all("toy_answer_byname" in g and "nobody" in g
                       for g in got), got


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
