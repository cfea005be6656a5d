"""``BENCHMARK.json`` and the files it names.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own and is found by the NAME in the
manifest: ``<path>/traffic/<mix>.json`` and ``<path>/metrics/<metric>.json``
(with an optional reader ``<metric>.py`` beside it), searched over every
directory in the manifest's ``paths`` (relative to the checkout's root, as
every path in a manifest is); a configuration's file is the ``file`` of its
entry. A later PR adds a cell by adding files and entries.

CODE is found by the same rule (:func:`resolve`): a configuration's
``builder``, a mix's ``generator`` and ``check``, a roofline's ``work`` and
a model's ``layout`` name either a built-in of the harness or a module
``<path>/<kind>/<name>.py`` (:data:`KINDS` says what each exports). A name
that is both is refused: a new file can never change what an accepted cell
runs.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
# kind -> (the harness module and dict that hold the built-ins, what a
# by-name module ``<path>/<kind>/<name>.py`` exports):
#   builders    build(config, traffic, seed) -> System
#   generators  Generator(traffic) with prepare(system), run(system, seconds,
#               tick) -> Window
#   checks      Check(config, traffic, seed) with exact, collect, compare,
#               needed_flops
#   work        work(ctx, runs) -> (flops, bytes), for trace_module_roofline
#   layouts     layout: a model layout (harness/layouts.py says what it gives)
KINDS = {
    "builders": (".system", "BUILDERS", "build"),
    "generators": (".generators", "GENERATORS", "Generator"),
    "checks": (".checks", "CHECKS", "Check"),
    "work": (".readers", "WORK", "work"),
    "layouts": (".layouts", "LAYOUTS", "layout"),
}
# the layout of a model whose entry names none: what the harness built
# before layouts had names
ROLE_LAYOUT = {"embedder": "bert", "reranker": "bert", "decoder": "gpt2"}


_LOADED: dict = {}      # path -> the by-name module loaded from it


class ManifestError(ValueError):
    pass


def load_manifest(path: str | None = None) -> dict:
    path = path or os.path.join(ROOT, "BENCHMARK.json")
    with open(path) as f:
        return json.load(f)


def _find(manifest: dict, kind: str, name: str, ext: str) -> str | None:
    for p in manifest["paths"]:
        cand = os.path.join(ROOT, p, kind, name + ext)
        if os.path.exists(cand):
            return cand
    return None


def load_json_named(manifest: dict, kind: str, name: str) -> dict:
    path = _find(manifest, kind, name, ".json")
    if path is None:
        raise ManifestError(
            f"no {kind}/{name}.json under any of {manifest['paths']}")
    with open(path) as f:
        return json.load(f)


def load_named_module(manifest: dict, kind: str, name: str):
    """The module ``<path>/<kind>/<name>.py`` under one of the manifest's
    ``paths``, or None. The name is a NAME (no slash, no ``..``), so the
    file lies under ``paths`` or is not found."""
    if not NAME_RE.match(name):
        raise ManifestError(f"{kind}: bad name {name!r}")
    path = _find(manifest, kind, name, ".py")
    if path is None:
        return None
    if path not in _LOADED:     # as an import: one module a file
        spec = importlib.util.spec_from_file_location(
            f"bench_{kind}_" + re.sub(r"\W", "_", name), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _LOADED[path] = mod
    return _LOADED[path]


def load_reader_module(manifest: dict, metric: str):
    """The metric's own reader ``<metric>.py`` beside its json, or None."""
    return load_named_module(manifest, "metrics", metric)


def resolve(manifest: dict, kind: str, name: str):
    """The built-in ``name`` of ``kind``, else what the module
    ``<path>/<kind>/<name>.py`` exports (:data:`KINDS`)."""
    module, table, export = KINDS[kind]
    builtins = getattr(importlib.import_module(module, __package__), table)
    own = load_named_module(manifest, kind, name)
    if name in builtins:
        if own is not None:
            raise ManifestError(
                f"{kind}/{name}.py has the name of a built-in of the "
                f"harness: a file may not change what {name!r} runs")
        return builtins[name]
    if own is None:
        raise ManifestError(
            f"no {kind[:-1] if kind.endswith('s') else kind} {name!r}: not "
            f"among the built-ins {sorted(builtins)} and no "
            f"{kind}/{name}.py under any of {manifest['paths']}")
    if not hasattr(own, export):
        raise ManifestError(f"{own.__file__} exports no {export!r}")
    return getattr(own, export)


def layouts_for(manifest: dict, config: dict) -> dict:
    """role -> the layout of each model the configuration's file holds:
    the one its ``layout`` names, else the role's present one."""
    out = {}
    for role, model in config["models"].items():
        name = model.get("layout") or ROLE_LAYOUT.get(role)
        if name is None:
            raise ManifestError(f"models.{role} names no layout")
        out[role] = resolve(manifest, "layouts", name)
    return out


def cell(manifest: dict, name: str) -> dict:
    """One cell with everything it names resolved: ``{"cell", "config"
    (the loaded file, with ``layouts``: role -> its model's layout),
    "traffic", "end_to_end", "per_layer"}``."""
    found = [w for w in manifest["workloads"] if w["name"] == name]
    if not found:
        raise ManifestError(
            f"no workload {name!r}; have "
            f"{[w['name'] for w in manifest['workloads']]}")
    w = found[0]
    entry = [c for c in manifest["configs"] if c["name"] == w["config"]]
    if not entry:
        raise ManifestError(f"workload {name!r} names no config")
    with open(os.path.join(ROOT, entry[0]["file"])) as f:
        config = json.load(f)
    config["layouts"] = layouts_for(manifest, config)

    def in_cell(metric: dict) -> bool:
        return name in metric.get("workloads", [name])

    return {
        "cell": w,
        "config_entry": entry[0],
        "config": config,
        "traffic": load_json_named(manifest, "traffic", w["traffic"]),
        "end_to_end": [m for m in manifest["end_to_end"] if in_cell(m)],
        "per_layer": [m for m in manifest["per_layer"] if in_cell(m)],
    }


def unresolved(manifest: dict, name: str) -> list[str]:
    """What a run of cell ``name`` would look for and not find: its
    configuration's and mix's files, its builder, generator and check, its
    models' layouts, and each per-layer metric's file, reader and work."""
    from .readers import READERS

    try:
        c = cell(manifest, name)
    except (ManifestError, OSError, KeyError, ValueError) as exc:
        return [f"{name}: {exc}"]
    out = []
    wanted = [("builders", c["config"].get("builder")),
              ("generators", c["traffic"].get("generator")),
              ("checks", c["traffic"].get("check"))]
    for m in c["per_layer"]:
        try:
            spec = load_json_named(manifest, "metrics", m["name"])
            own = load_reader_module(manifest, m["name"])
        except (ManifestError, ValueError) as exc:
            out.append(f"{name}: {exc}")
            continue
        if own is None and spec.get("reader") not in READERS:
            out.append(f"{name}: {m['name']} names the reader "
                       f"{spec.get('reader')!r}, not among {sorted(READERS)}, "
                       f"and ships no metrics/{m['name']}.py")
        if own is not None and not hasattr(own, "read"):
            out.append(f"{name}: {own.__file__} exports no 'read'")
        if "work" in spec.get("params", {}):
            wanted.append(("work", spec["params"]["work"]))
    for kind, wanted_name in wanted:
        try:
            resolve(manifest, kind, str(wanted_name))
        except ManifestError as exc:
            out.append(f"{name}: {exc}")
    return out


def problems(manifest: dict) -> list[str]:
    """What the contract would refuse before a run, as far as the file
    alone shows it, and what a run of each cell would not find."""
    out: list[str] = []
    keys = set(manifest)
    if keys != TOP_KEYS:
        out.append(f"top-level keys {sorted(keys ^ TOP_KEYS)} differ")
    if not 1 <= manifest.get("run_seconds", 0) <= 51:
        out.append("run_seconds outside 1..51")
    names: set[str] = set()
    e2e = {m["name"] for m in manifest["end_to_end"]}
    cells = {w["name"] for w in manifest["workloads"]}
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        seen: set[str] = set()
        for item in manifest[group]:
            n = item.get("name", "")
            if not NAME_RE.match(n):
                out.append(f"{group}: bad name {n!r}")
            if n in seen:
                out.append(f"{group}: duplicate name {n!r}")
            seen.add(n)
        if group in ("end_to_end", "per_layer"):
            if names & seen:
                out.append(f"metric names reused: {names & seen}")
            names |= seen
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if not UNIT_RE.match(m.get("unit", "")):
            out.append(f"{m['name']}: bad unit {m.get('unit')!r}")
        if m.get("better") not in ("lower", "higher"):
            out.append(f"{m['name']}: better is {m.get('better')!r}")
        if m.get("source") not in SOURCES:
            out.append(f"{m['name']}: source {m.get('source')!r}")
        for w in m.get("workloads", []):
            if w not in cells:
                out.append(f"{m['name']}: unknown workload {w!r}")
    for m in manifest["end_to_end"]:
        if set(m) - {"name", "unit", "better", "bound", "source",
                     "workloads"}:
            out.append(f"{m['name']}: extra keys")
        if m["source"] not in ("host_clock", "device_trace"):
            out.append(f"{m['name']}: end-to-end source {m['source']}")
        if not 0.01 <= m.get("bound", 0) <= 0.1:
            out.append(f"{m['name']}: bound {m.get('bound')}")
    if "setup_s" not in e2e:
        out.append("no setup_s")
    for m in manifest["per_layer"]:
        if set(m) - {"name", "unit", "better", "source", "layer", "moves",
                     "workloads"}:
            out.append(f"{m['name']}: extra keys")
        if m.get("moves") not in e2e:
            out.append(f"{m['name']}: moves {m.get('moves')!r}")
        if not m.get("layer") or "\n" in m["layer"]:
            out.append(f"{m['name']}: layer")
    used = {w["config"] for w in manifest["workloads"]}
    for c in manifest["configs"]:
        if c["name"] not in used:
            out.append(f"config {c['name']} is used by no cell")
        if not any(c["file"].startswith(p + "/") for p in manifest["paths"]):
            out.append(f"config {c['name']}: file outside paths")
        if len(c.get("source", "")) > 200 or len(c.get("why", "")) > 200:
            out.append(f"config {c['name']}: source/why over 200 chars")
    pairs = set()
    for w in manifest["workloads"]:
        if w.get("chips") not in (1, 4):
            out.append(f"{w['name']}: chips {w.get('chips')}")
        if not 1 <= len(w.get("why", "")) <= 200:
            out.append(f"{w['name']}: why is {len(w.get('why', ''))} chars")
        if (w["config"], w["traffic"]) in pairs:
            out.append(f"{w['name']}: config/traffic pair repeated")
        pairs.add((w["config"], w["traffic"]))
        if not NAME_RE.match(w["traffic"]):
            out.append(f"{w['name']}: traffic name")
        out.extend(unresolved(manifest, w["name"]))
    return out
