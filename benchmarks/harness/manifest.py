"""``BENCHMARK.json`` and the data files it names.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own and is found by the NAME in the
manifest: ``<path>/traffic/<mix>.json`` and ``<path>/metrics/<metric>.json``
(with an optional reader ``<metric>.py`` beside it), searched over every
directory in the manifest's ``paths`` (relative to the checkout's root, as
every path in a manifest is); a configuration's file is the ``file`` of its
entry. A later PR adds a cell by adding files and entries.
"""

from __future__ import annotations

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


def load_reader_module(manifest: dict, metric: str):
    """The metric's own reader ``<metric>.py`` beside its json, or None."""
    path = _find(manifest, "metrics", metric, ".py")
    if path is None:
        return None
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + re.sub(r"\W", "_", metric), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell(manifest: dict, name: str) -> dict:
    """One cell with everything it names resolved: ``{"cell", "config"
    (entry + loaded file), "traffic", "end_to_end", "per_layer"}``."""
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


def problems(manifest: dict) -> list[str]:
    """What the contract would refuse before a run, as far as the file
    alone shows it."""
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
    return out
