"""The reduction from a trace to numbers, on hand-made events and on a
small recorded trace of the chip (``fixtures/ingest_trace_40ms.json``)."""

import json
import os

import pytest

from bench_paths import REPO  # noqa: F401 - sets sys.path

from harness import readers, trace as T

FIXTURE = os.path.join(REPO, "tests", "benchmark", "fixtures",
                       "ingest_trace_40ms.json")


def recorded():
    with open(FIXTURE) as f:
        loaded = json.load(f)
    for dev in loaded["devices"].values():
        for k in dev:
            dev[k] = [tuple(e) for e in dev[k]]
    return loaded


def test_union_counts_overlaps_once():
    events = [("a", 0, 10), ("b", 5, 10), ("c", 30, 5), ("d", 31, 2)]
    assert T.union_ns(events) == 20
    assert T.union_ns([]) == 0


def test_gaps_are_the_idle_stretches_longest_first():
    events = [("a", 10, 10), ("b", 50, 10)]
    assert T.gaps(events, 0, 100) == [(60, 40), (20, 30), (0, 10)]


def test_clip_cuts_events_at_the_window():
    assert T.clip([("a", 0, 10), ("b", 20, 10)], 5, 25) == [
        ("a", 5, 5), ("b", 20, 5)]


def test_module_names_lose_their_program_id():
    assert T.base_name("jit__embed_fn_packed(2939019315982159664)") == \
        "jit__embed_fn_packed"
    assert T.by_name([("m(1)", 0, 5), ("m(2)", 9, 5), ("n", 3, 1)]) == {
        "m": (2, 10), "n": (1, 1)}


def test_summary_of_hand_made_events():
    loaded = {"devices": {"/device:TPU:0": {
        "modules": [("jit_step(7)", 0, 400), ("jit_other(8)", 600, 100)],
        "ops": [("%fusion", 0, 300), ("%copy", 350, 50), ("%dot", 600, 100)],
    }}, "lines": {}}
    s = T.TraceSummary(loaded, window_s=1e-6)
    assert s.busy_s == pytest.approx(450e-9)
    assert s.window_s == pytest.approx(1e-6)
    assert s.module_seconds("jit_step") == (1, pytest.approx(400e-9))
    s.spans = [("commit3", 0, 500)]
    b = s.breakdown()
    assert b["device_ops"][0] == ["jit_step", pytest.approx(400e-9)]
    assert b["idle_gaps"][0] == ["commit3", pytest.approx(200e-9)]
    assert b["idle_gaps"][1][0] == "commit3"      # 300..350
    ctx = {"trace": s}
    assert readers.trace_idle(ctx, {}) == pytest.approx(55.0)


def test_a_trace_with_no_device_event_is_refused():
    with pytest.raises(ValueError):
        T.TraceSummary({"devices": {}, "lines": {"/host:CPU": []}})
    with pytest.raises(ValueError):
        T.TraceSummary({"devices": {"/device:TPU:0": {
            "modules": [], "ops": []}}, "lines": {}})


def test_recorded_trace_gives_known_idle_and_module_times():
    s = T.TraceSummary(recorded())
    # the fixture: 40 ms of a saturated-ingest run on one v5e chip; four
    # runs of the embed executable of 10.16 ms each fall into it
    runs, seconds = s.module_seconds("embed_fn")
    assert runs == 4
    assert seconds == pytest.approx(0.0400, abs=0.0005)
    per_run = [d for n, _a, d in s.modules if "embed_fn" in n][0]
    assert per_run == pytest.approx(10.16e6, rel=0.01)
    assert s.n_devices == 1
    assert 0.0 < s.busy_s <= s.window_s
    idle = 100.0 * (1.0 - s.busy_s / s.window_s)
    assert idle == pytest.approx(readers.trace_idle({"trace": s}, {}))
    assert 0.0 <= idle < 5.0     # the slice is back-to-back embed batches
    top = s.breakdown()["device_ops"]
    assert top[0][0] == "jit__embed_fn_packed"
    assert len(top) <= 10 and len(s.breakdown()["idle_gaps"]) <= 5


def test_a_share_above_100_fails_the_run_rather_than_print():
    with pytest.raises(readers.ShareAbove100):
        readers._share("embed_step", 104.0)
    assert readers._share("embed_step", 99.0) == 99.0
    assert readers.counter_ratio(
        {"counters": {"a": 1.0, "b": 4.0}},
        {"numerator": "a", "denominator": "b", "scale": 100.0,
         "share": True}) == 25.0
    with pytest.raises(readers.ShareAbove100):
        readers.counter_ratio(
            {"counters": {"a": 5.0, "b": 4.0}},
            {"numerator": "a", "denominator": "b", "scale": 100.0,
             "share": True})


def test_readers_that_find_nothing_return_nothing():
    ctx = {"trace": None, "counters": {}, "spans": {}}
    assert readers.trace_idle(ctx, {}) is None
    assert readers.trace_module_roofline(ctx, {"modules": "x",
                                               "work": "knn_search"}) is None
    assert readers.span_median(ctx, {"span": "commit_ms"}) is None
    assert readers.counter_ratio(ctx, {"numerator": "a",
                                       "denominator": "b"}) is None
