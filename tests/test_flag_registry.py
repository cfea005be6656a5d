"""The single flag registry (`internals/config.py::FLAG_REGISTRY`): every
`PATHWAY_TPU_*` knob is declared exactly once, the `PathwayConfig`
properties are generated from the declarations, and the README flag
tables are generated output — so docs, env parsing, and defaults cannot
drift apart."""

import os
import re

import pytest

from pathway_tpu.internals import config as C


def _readme_block(group: str) -> str:
    path = os.path.join(os.path.dirname(__file__), "..", "README.md")
    text = open(path, encoding="utf-8").read()
    m = re.search(
        rf"<!-- flags:{group} -->\n(.*?)<!-- /flags:{group} -->",
        text, re.S,
    )
    assert m, f"README missing <!-- flags:{group} --> block"
    return m.group(1).strip()


@pytest.mark.parametrize(
    "group",
    ["pipeline", "query", "observability", "fault", "fleet", "tuning"],
)
def test_readme_tables_are_generated_output(group):
    """README tables match `render_flag_table` byte-for-byte; regenerate
    with `python -m pathway_tpu.internals.config` after editing a Flag."""
    assert _readme_block(group) == C.render_flag_table(group).strip()


def test_registry_env_and_attr_unique():
    envs = [f.env for f in C.FLAG_REGISTRY]
    assert len(envs) == len(set(envs))
    attrs = [f.attr for f in C.FLAG_REGISTRY]
    assert len(attrs) == len(set(attrs))


def test_every_attr_resolves_on_live_config():
    for f in C.FLAG_REGISTRY:
        assert hasattr(C.pathway_config, f.attr), f.attr


def test_defaults_when_env_unset(monkeypatch):
    for f in C.FLAG_REGISTRY:
        monkeypatch.delenv(f.env, raising=False)
        assert f.read() == f.default, f.env


def test_env_overrides_and_clamps(monkeypatch):
    monkeypatch.setenv("PATHWAY_TPU_SPEC_DECODE", "0")
    assert C.pathway_config.spec_decode is False
    monkeypatch.setenv("PATHWAY_TPU_SPEC_DECODE_K", "0")  # min 1 clamps
    assert C.pathway_config.spec_k == 1
    monkeypatch.setenv("PATHWAY_TPU_SPEC_DECODE_DRAFT_LAYERS", "2")
    assert C.pathway_config.spec_draft_layers == 2


@pytest.mark.parametrize("raw,want", [
    ("int8", "int8"), ("1", "int8"), ("true", "int8"), ("INT8", "int8"),
    ("0", ""), ("", ""), ("off", ""), ("fp8", ""),
])
def test_kv_quant_parse(monkeypatch, raw, want):
    monkeypatch.setenv("PATHWAY_TPU_KV_QUANT", raw)
    assert C.pathway_config.kv_quant == want


def test_every_declared_doc_nonempty():
    for f in C.FLAG_REGISTRY:
        assert f.doc.strip(), f.env
        assert f.env.startswith(("PATHWAY_TPU_", "PATHWAY_")), f.env


def test_kill_switch_declarations_well_formed():
    """`kill_switch=True` requires a `pinned_by` test path under tests/;
    `pinned_by` without `kill_switch` is a declaration typo. Whether the
    named file still pins the env var is the analyzer's job (GL301)."""
    for f in C.FLAG_REGISTRY:
        if f.kill_switch:
            assert f.pinned_by, f"{f.env}: kill_switch without pinned_by"
            assert f.pinned_by.startswith("tests/"), f.env
        else:
            assert f.pinned_by is None, f"{f.env}: pinned_by without kill_switch"


def test_reload_declarations_valid():
    """Every flag declares how its value is consumed: `"live"` (re-read
    per use, safe to hot-flip) or `"construction"` (read once when the
    consuming object is built — the tuner's `flag_overrides` refuses to
    flip these without `construction=True`)."""
    for f in C.FLAG_REGISTRY:
        assert f.reload in ("live", "construction"), f.env


def test_tunable_specs_well_formed():
    """Flags carrying a `tunable` search spec must declare a healthy
    space (finite bounds, ≥ 2 candidate rungs, default inside) — the
    analyzer enforces this repo-wide as GL204."""
    from pathway_tpu.analysis.flag_hygiene import check_tunable_bounds

    tunables = [f for f in C.FLAG_REGISTRY if f.tunable is not None]
    assert len(tunables) >= 15  # the searchable surface stays real
    assert check_tunable_bounds(C.FLAG_REGISTRY) == []


def test_lock_sanitizer_flag_default_off(monkeypatch):
    monkeypatch.delenv("PATHWAY_TPU_LOCK_SANITIZER", raising=False)
    assert C.pathway_config.lock_sanitizer is False
    monkeypatch.setenv("PATHWAY_TPU_LOCK_SANITIZER", "1")
    assert C.pathway_config.lock_sanitizer is True


def test_env_choke_points(monkeypatch):
    """`env_interpolate` / `environ_snapshot` are the ONLY sanctioned
    raw-environment accessors outside config.py (analyzer rule GL202)."""
    monkeypatch.setenv("PATHWAY_TPU_CHOKE_PROBE", "abc")
    assert C.env_interpolate("PATHWAY_TPU_CHOKE_PROBE") == "abc"
    assert C.env_interpolate("PATHWAY_TPU_CHOKE_ABSENT") is None
    snap = C.environ_snapshot(**{"PATHWAY_TPU_CHOKE_PROBE": "xyz"})
    assert snap["PATHWAY_TPU_CHOKE_PROBE"] == "xyz"
    assert snap["PATHWAY_TPU_CHOKE_PROBE"] != os.environ["PATHWAY_TPU_CHOKE_PROBE"]
    assert "PATH" in snap  # a real copy of the environment, plus overrides
