"""Fused-vs-unfused equivalence for the stateless operator-chain fusion
(``engine/graph.py:fuse_chains``, scheduler plan rewrite).

The fusion contract: for ANY pipeline, running with PATHWAY_FUSION on and
off must produce byte-identical final states — same keys, same values, same
error-row placement — because fusion only removes intermediate ``Batch``
materialisation and per-node consolidation, never changes per-row
semantics. Randomized insert/retract streams (every retraction targets a
live row) probe this over chains of select / filter / rowwise-apply ops,
including chains where rows carry ERROR values.
"""

import random

import pytest

import pathway_tpu as pw
from pathway_tpu.internals import config as config_mod
from pathway_tpu.internals import run as run_mod
from tests.utils import _capture_rows

KDOM = ["a", "b", "c", "d", "e"]


@pytest.fixture(autouse=True)
def _clear_persistence():
    # pw.run(persistence_config=...) sets a module-global that would leak
    # replay/snapshot behavior into every later test in the session
    yield
    config_mod.set_persistence_config(None)


def _gen_events(rng: random.Random, n: int, vmax: int = 20):
    """Valid delta stream over (k: str, v: int): every retraction targets a
    currently-live row, so every prefix is a valid collection."""
    live: list[tuple] = []
    events = []
    for _ in range(n):
        if live and rng.random() < 0.35:
            row = live.pop(rng.randrange(len(live)))
            events.append((*row, -1))
        else:
            row = (rng.choice(KDOM), rng.randrange(vmax))
            if row in live:  # keep per-key multiplicity in {0, 1}
                continue
            live.append(row)
            events.append((*row, 1))
    return events


def _with_times(rng: random.Random, events):
    """Non-decreasing even times with random epoch breaks (event order is
    preserved, so retractions still follow their insertions)."""
    t, out = 2, []
    for e in events:
        if rng.random() < 0.4:
            t += 2
        out.append((*e[:-1], t, e[-1]))
    return out


def _final_state(build, schema, rows, fusion: bool, monkeypatch):
    monkeypatch.setenv("PATHWAY_FUSION", "1" if fusion else "0")
    pw.clear_graph()
    t = pw.debug.table_from_rows(schema, rows, is_stream=True)
    state, cols = _capture_rows(build(t))
    stats = run_mod.LAST_RUN_STATS
    fused_chains = stats.fused_chains if stats is not None else 0
    canon = sorted((k, tuple(map(str, r))) for k, r in state.items())
    return canon, cols, fused_chains


def _check(build, seed, monkeypatch, n=60, expect_fusion=True):
    rng = random.Random(seed)
    S = pw.schema_from_types(k=str, v=int)
    rows = _with_times(rng, _gen_events(rng, n))
    fused = _final_state(build, S, rows, True, monkeypatch)
    unfused = _final_state(build, S, rows, False, monkeypatch)
    assert fused[0] == unfused[0], (
        f"fused final state diverged from unfused (seed={seed})\n"
        f"fused: {fused[0]}\nunfused: {unfused[0]}"
    )
    assert fused[1] == unfused[1], "column names diverged"
    if expect_fusion:
        assert fused[2] > 0, "pipeline was expected to produce a fused chain"
    assert unfused[2] == 0, "PATHWAY_FUSION=0 must disable fusion"


def _chain_select_filter(t):
    s = t.select(t.k, w=t.v * 2 + 1)
    f = s.filter(s.w > 7)
    return f.select(f.k, x=f.w - 3, y=f.k + "!")


def _chain_deep(t):
    s1 = t.select(t.k, a=t.v + 1, b=t.v % 3)
    f1 = s1.filter(s1.b != 0)
    s2 = f1.select(f1.k, c=f1.a * f1.b, b=f1.b)
    f2 = s2.filter(s2.c > 2)
    return f2.select(f2.k, d=f2.c - f2.b)


def _chain_apply(t):
    s = t.select(t.k, w=pw.apply_with_type(lambda v: v * v, int, t.v))
    f = s.filter(s.w < 200)
    return f.select(f.k, z=pw.apply_with_type(str, str, f.w))


SEEDS = range(5)


@pytest.mark.parametrize("seed", SEEDS)
def test_fused_equals_unfused_select_filter(seed, monkeypatch):
    _check(_chain_select_filter, seed, monkeypatch)


@pytest.mark.parametrize("seed", SEEDS)
def test_fused_equals_unfused_deep_chain(seed, monkeypatch):
    _check(_chain_deep, seed, monkeypatch)


@pytest.mark.parametrize("seed", SEEDS)
def test_fused_equals_unfused_apply_chain(seed, monkeypatch):
    _check(_chain_apply, seed, monkeypatch)


@pytest.mark.parametrize("seed", range(3))
def test_fused_equals_unfused_error_rows(seed, monkeypatch):
    """ERROR values (division by zero) must flow through a fused chain
    exactly as through the unfused one: same surviving rows, same
    fill_error replacements."""

    def build(t):
        s = t.select(t.k, q=100 // (t.v - 3))  # v == 3 rows become ERROR
        f = s.filter(pw.fill_error(s.q > 0, False))
        return f.select(f.k, r=pw.fill_error(f.q * 2, -1))

    _check(build, seed, monkeypatch, n=40)


def test_fusion_skips_stateful_boundaries(monkeypatch):
    """A groupby in the middle must break the chain — the reduce output
    still matches, and only the stateless segments fuse."""

    def build(t):
        s = t.select(t.k, w=t.v + 10)
        g = s.groupby(s.k).reduce(s.k, total=pw.reducers.sum(s.w))
        return g.select(g.k, big=g.total * 2)

    rng = random.Random(7)
    S = pw.schema_from_types(k=str, v=int)
    rows = _with_times(rng, _gen_events(rng, 50))
    fused = _final_state(build, S, rows, True, monkeypatch)
    unfused = _final_state(build, S, rows, False, monkeypatch)
    assert fused[0] == unfused[0]


def test_fused_chain_reported_in_stats(monkeypatch):
    monkeypatch.setenv("PATHWAY_FUSION", "1")
    pw.clear_graph()
    t = pw.debug.table_from_rows(
        pw.schema_from_types(k=str, v=int),
        [("a", 1, 2, 1), ("b", 5, 2, 1)],
        is_stream=True,
    )
    state, _ = _capture_rows(_chain_deep(t))
    stats = run_mod.LAST_RUN_STATS
    snap = stats.snapshot()
    assert snap["fused_chains"] >= 1
    assert snap["fused_nodes"] >= 2
    assert set(snap) >= {"steps_skipped", "operators", "connectors"}


# ------------------------------------------------------- persistence


def _run_wordcount_fused(src_dir, out_file, store, fusion, monkeypatch):
    """One 'process lifetime': csv -> fusable select/filter chain ->
    groupby/count -> jsonlines sink, with operator persistence."""
    monkeypatch.setenv("PATHWAY_FUSION", "1" if fusion else "0")
    pw.clear_graph()

    class InSchema(pw.Schema):
        word: str

    words = pw.io.fs.read(
        str(src_dir), format="csv", schema=InSchema, mode="static",
        persistent_id="words-src",
    )
    # a fusable stateless chain ahead of the stateful groupby
    cleaned = words.select(w=words.word + "")
    kept = cleaned.filter(cleaned.w != "skipme")
    tagged = kept.select(kept.w, word=kept.w)
    counts = tagged.groupby(tagged.word).reduce(
        tagged.word, count=pw.reducers.count()
    )
    pw.io.jsonlines.write(counts, str(out_file))
    pw.run(
        persistence_config=pw.persistence.Config.simple_config(
            pw.persistence.Backend.filesystem(store)
        )
    )
    stats = run_mod.LAST_RUN_STATS
    return stats.fused_chains if stats is not None else 0


def _final_counts(out_file):
    import json

    state: dict[str, int] = {}
    with open(out_file) as f:
        entries = [json.loads(line) for line in f]
    for e in sorted(entries, key=lambda e: e["time"]):
        if e["diff"] > 0:
            state[e["word"]] = e["count"]
        elif state.get(e["word"]) == e["count"]:
            del state[e["word"]]
    return state


def test_persistence_roundtrip_across_fused_graph(tmp_path, monkeypatch):
    """Snapshot under a fused plan, resume under the same fused plan: the
    fused members are stateless (never snapshotted) and operator signatures
    shift deterministically, so the resumed run combines old snapshot with
    new input exactly-once."""
    src = tmp_path / "src"
    src.mkdir()
    store = tmp_path / "store"
    (src / "a.csv").write_text("word\ncat\ndog\ncat\nskipme\n")
    fused = _run_wordcount_fused(
        src, tmp_path / "o1.jsonl", store, True, monkeypatch
    )
    assert fused >= 1, "the select/filter chain should have fused"
    assert _final_counts(tmp_path / "o1.jsonl") == {"cat": 2, "dog": 1}

    (src / "b.csv").write_text("word\ncat\nbird\n")
    _run_wordcount_fused(src, tmp_path / "o2.jsonl", store, True, monkeypatch)
    assert _final_counts(tmp_path / "o2.jsonl") == {
        "cat": 3, "dog": 1, "bird": 1,
    }
