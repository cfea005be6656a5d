"""The readers of what the program itself records (``harness/
program_trace.py``): the idle-attribution split on hand-made events and on a
small recorded list of the chip's (``fixtures/host_regions_ingest.json``),
each new metric through ``per_layer_metrics`` on the repo's own manifest, and
a program with nothing to read (the parent) left out of the line."""

import json
import os

import pytest

from bench_paths import REPO  # noqa: F401 - sets sys.path

import run as bench_run
from harness import manifest as M
from harness import program_trace as P
from harness import trace as T

from pathway_tpu.engine import probes, tracing

FIXTURE = os.path.join(REPO, "tests", "benchmark", "fixtures",
                       "host_regions_ingest.json")
NEW = {
    "ingest_saturated": {
        "ingest.epoch_wait_ms_p50", "ingest.epoch_run_ms_p50",
        "ingest.idle_attributed_pct"},
    "retrieve_rerank_closed32": {
        "retrieve.rest_queue_wait_ms_p50", "retrieve.requests_per_epoch",
        "retrieve.queries_per_search_dispatch",
        "retrieve.idle_attributed_pct"},
}


def region(thread, name, start, end, **stats):
    return (thread, name, start, end - start, stats)


def test_nested_regions_flatten_to_their_deepest():
    regions = [
        region(1, "pw.engine.epoch", 0, 100, t=1),
        region(1, "pw.engine.op", 10, 60, op="Rowwise:9"),
        region(1, "pw.embed.drain", 20, 30),
        region(1, "pw.engine.consolidate", 50, 60),
        region(1, "pw.engine.on_time_end", 90, 100, op="Subscribe:4"),
        region(1, "pw.engine.wait_ready", 120, 150),
    ]
    got = [(a, b, [n for n, _s in stack])
           for a, b, stack in P.leaf_segments(regions)]
    e, o = "pw.engine.epoch", "pw.engine.op"
    assert got == [
        (0, 10, [e]), (10, 20, [e, o]), (20, 30, [e, o, "pw.embed.drain"]),
        (30, 50, [e, o]), (50, 60, [e, o, "pw.engine.consolidate"]),
        (60, 90, [e]), (90, 100, [e, "pw.engine.on_time_end"]),
        (120, 150, ["pw.engine.wait_ready"]),
    ]
    # a child that outlives its parent by a tick is cut at the parent's end
    late = [region(1, "a", 0, 10), region(1, "b", 5, 12)]
    assert [(a, b) for a, b, _ in P.leaf_segments(late)] == [(0, 5), (5, 10)]


def test_idle_is_split_by_time_among_the_deepest_regions():
    regions = [
        region(1, "pw.engine.epoch", 0, 100, t=1),
        region(1, "pw.engine.op", 10, 60, op="Rowwise:9"),
        region(1, "pw.embed.drain", 20, 30),
        region(1, "pw.engine.wait_ready", 100, 200),
        # while the pump waits, the connector's thread is the cause
        region(2, "pw.connector.commit", 120, 150, rows=4),
        region(3, "pw.embed.tokenize", 140, 160),
    ]
    by_region, by_op = P.attribute_idle([(0, 40), (90, 130)], regions)
    assert by_region == {
        "pw.engine.epoch": 10 + 10,          # 0-10, 90-100
        "pw.engine.op": 10 + 10,             # 10-20, 30-40
        "pw.embed.drain": 10,
        "pw.connector.commit": 30,           # 120-150 of the wait
        "pw.embed.tokenize": 10,             # 150-160: what thread 2 left
        "pw.engine.wait_ready": 20 + 40,     # 100-120, 160-200
        "unattributed": 20,                  # 200-220: under no region
    }
    assert by_op == {"Rowwise:9": 30}
    assert sum(by_region.values()) == 40 + 130
    # no epoch is whole inside the slice (the profiler keeps a region that
    # began and ended in the session): the engine's thread is still the one
    # that ran its operators and waits; the epoch's own time is unnamed
    by_region, by_op = P.attribute_idle([(0, 40), (90, 130)], regions[1:])
    assert by_region == {
        "unattributed": 10 + 10 + 20, "pw.engine.op": 10 + 10,
        "pw.embed.drain": 10, "pw.connector.commit": 30,
        "pw.embed.tokenize": 10, "pw.engine.wait_ready": 20 + 40}
    assert by_op == {"Rowwise:9": 30}
    # no thread ran the engine: nothing to attribute to
    assert P.attribute_idle([(0, 10)], regions[4:]) is None
    assert P.attribute_idle([(0, 10)], []) is None


def recorded():
    with open(FIXTURE) as f:
        loaded = json.load(f)
    loaded["regions"] = [tuple(r) for r in loaded["regions"]]
    loaded["ops"] = [tuple(e) for e in loaded["ops"]]
    return loaded


def test_the_split_on_a_recorded_piece_of_the_chips_trace():
    rec = recorded()
    gaps = T.gaps(rec["ops"], rec["lo"], rec["hi"])
    idle = sum(d for _s, d in gaps)
    assert idle == rec["expect"]["idle_ns"]
    by_region, by_op = P.attribute_idle(gaps, rec["regions"])
    assert sum(by_region.values()) == idle
    assert by_region == rec["expect"]["by_region_ns"]
    assert by_op == rec["expect"]["by_op_ns"]
    named = idle - by_region.get("unattributed", 0)
    assert 100.0 * named / idle == pytest.approx(
        rec["expect"]["attributed_pct"])


def summary_of(rec):
    loaded = {"devices": {"/device:TPU:0": {"modules": [],
                                            "ops": rec["ops"]}},
              "lines": {}}
    return T.TraceSummary(loaded, lo=rec["lo"], hi=rec["hi"])


def context(rec=None):
    ctx = {"trace": None, "counters": {}, "slice_counters": {},
           "lifetime_counters": {}, "spans": {}, "facts": {}}
    if rec is not None:
        ctx["trace"] = summary_of(rec)
        ctx["host_regions"] = rec["regions"]
    return ctx


def finished(kind, wait_ms, e2e_ms, **attrs):
    span = tracing.start_span(kind, **attrs)
    span.event("admit", at=span.t0 + wait_ms / 1e3)
    span.event("drain", at=span.t0 + e2e_ms / 1e3)
    span.finish()


@pytest.fixture
def program_state():
    """The ring and the two counter families as a run would leave them."""
    tracing.reset_traces()
    probes.REGISTRY.remove("knn_search_queries", "device_dispatch")
    for wait, e2e in ((1500.0, 3300.0), (1700.0, 3500.0), (1900.0, 3900.0)):
        finished("epoch", wait, e2e, t=int(e2e))
    finished("epoch", 10.0, 150.0, t=7, requests=[41])
    finished("epoch", 12.0, 160.0, t=8, requests=[42, 43, 44])
    for wait in (4300.0, 4400.0, 4500.0):
        finished("rest", wait, wait + 150.0)
    probes.record_knn_search(1, 16)
    probes.record_knn_search(5, 16)
    yield
    tracing.reset_traces()
    probes.REGISTRY.remove("knn_search_queries", "device_dispatch")


def test_each_new_metric_through_per_layer_metrics(program_state, capsys):
    man = M.load_manifest()
    rec = recorded()
    got = bench_run.per_layer_metrics(
        man, M.cell(man, "ingest_saturated"), context(rec))
    assert set(got) == NEW["ingest_saturated"] | {"ingest.device_idle_pct"}
    assert got["ingest.epoch_wait_ms_p50"] == {"value": 1500.0, "unit": "ms"}
    assert got["ingest.epoch_run_ms_p50"]["value"] == pytest.approx(1800.0)
    assert got["ingest.idle_attributed_pct"] == {
        "value": pytest.approx(rec["expect"]["attributed_pct"]), "unit": "%"}
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["phase"] == "host_attribution"
    assert line["idle_s"] == pytest.approx(rec["expect"]["idle_ns"] / 1e9)
    assert set(line) >= {"idle_by_region_s", "idle_by_op_s", "region_s",
                         "op_s"}

    got = bench_run.per_layer_metrics(
        man, M.cell(man, "retrieve_rerank_closed32"), context(rec))
    assert set(got) == NEW["retrieve_rerank_closed32"] | {
        "retrieve.device_idle_pct"}
    assert got["retrieve.rest_queue_wait_ms_p50"] == {
        "value": 4400.0, "unit": "ms"}
    assert got["retrieve.requests_per_epoch"] == {
        "value": 2.0, "unit": "requests/epoch"}
    assert got["retrieve.queries_per_search_dispatch"] == {
        "value": 3.0, "unit": "queries/dispatch"}
    assert got["retrieve.idle_attributed_pct"]["unit"] == "%"
    assert got["retrieve.idle_attributed_pct"]["value"] <= 100.0


def test_epoch_medians_are_over_the_windows_commits(program_state):
    """Set-up's commits are still in the ring and are another population
    (an epoch grows with what was ingested): the file names the window's
    counter, and the reader takes that many of the newest spans."""
    man = M.load_manifest()
    ctx = context()
    ctx["counters"] = {"commits_landed": 2}
    got = bench_run.per_layer_metrics(
        man, M.cell(man, "ingest_saturated"), ctx)
    assert got["ingest.epoch_wait_ms_p50"]["value"] == pytest.approx(11.0)
    assert got["ingest.epoch_run_ms_p50"]["value"] == pytest.approx(144.0)


def test_a_program_with_nothing_to_read_leaves_the_metrics_out(capsys):
    """The parent of the PR that added the regions, the span kinds and the
    counter: every reader returns nothing and none raises."""
    tracing.reset_traces()
    probes.REGISTRY.remove("knn_search_queries", "device_dispatch")
    man = M.load_manifest()
    rec = dict(recorded(), regions=[])
    for cell, new in NEW.items():
        for ctx in (context(), context(rec)):
            got = bench_run.per_layer_metrics(man, M.cell(man, cell), ctx)
            assert not set(got) & new
    assert "host_attribution" not in capsys.readouterr().out


def test_the_readers_sit_where_the_manifest_test_allows():
    """``benchmarks/metrics`` holds json alone; a reader's code is found
    under the second of the manifest's ``paths``."""
    man = M.load_manifest()
    for names in NEW.values():
        for name in names:
            assert M.load_reader_module(man, name) is not None
            path = M._find(man, "metrics", name, ".py")
            assert os.path.relpath(path, REPO).startswith(
                os.path.join("tests", "benchmark", "metrics"))
    assert all(f.endswith(".json") for f in os.listdir(
        os.path.join(REPO, "benchmarks", "metrics")))
