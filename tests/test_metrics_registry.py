"""MetricsRegistry tests — thread safety, snapshot consistency, histogram
quantiles, ledger-shim shapes, and the PATHWAY_TPU_METRICS kill switch
(engine/probes.py)."""

import threading

import pytest

from pathway_tpu.engine import probes
from pathway_tpu.engine.probes import MetricsRegistry


@pytest.fixture
def registry():
    return MetricsRegistry()


def test_counter_gauge_histogram_roundtrip(registry):
    registry.counter_add("reqs", 2, kind="a")
    registry.counter_add("reqs", 3, kind="a")
    registry.counter_add("reqs", 5, kind="b")
    registry.gauge_set("occ", 0.5, server="s1")
    registry.gauge_add("occ", 0.25, server="s1")
    for v in (0.001, 0.002, 0.004):
        registry.observe("lat", v, phase="decode")
    assert registry.labelled("reqs", "kind") == {"a": 5.0, "b": 5.0}
    assert registry.gauge_value("occ", server="s1") == 0.75
    s = registry.hist_summary("lat", phase="decode")
    assert s["count"] == 3
    assert s["sum"] == pytest.approx(0.007)


def test_eight_writer_threads_lose_no_increments(registry):
    """Satellite: the historical lost-update race, now impossible — 8
    writer threads hammer one counter, one gauge, and one histogram;
    every increment must survive."""
    THREADS, PER = 8, 2000
    barrier = threading.Barrier(THREADS)

    def hammer(tid: int):
        barrier.wait()
        for i in range(PER):
            registry.counter_add("hammer", 1, kind="x")
            registry.gauge_add("hammer_gauge", 1.0)
            registry.observe("hammer_lat", 1e-3 * ((i % 10) + 1))

    workers = [
        threading.Thread(target=hammer, args=(t,)) for t in range(THREADS)
    ]
    for w in workers:
        w.start()
    for w in workers:
        w.join()
    total = THREADS * PER
    assert registry.labelled("hammer", "kind") == {"x": float(total)}
    assert registry.gauge_value("hammer_gauge") == float(total)
    s = registry.hist_summary("hammer_lat")
    assert s["count"] == total


def test_snapshot_is_one_consistent_dict(registry):
    registry.counter_add("c", 4, kind="k")
    registry.gauge_set("g", 1.5)
    registry.observe("h", 0.01)
    snap = registry.snapshot()
    assert set(snap) == {"counters", "gauges", "histograms"}
    (cs,) = snap["counters"]["c"]["series"]
    assert cs == {"labels": {"kind": "k"}, "value": 4.0}
    (gs,) = snap["gauges"]["g"]["series"]
    assert gs["value"] == 1.5
    fam = snap["histograms"]["h"]
    (hs,) = fam["series"]
    assert len(hs["buckets"]) == len(fam["bounds"]) + 1  # +Inf overflow
    assert sum(hs["buckets"]) == hs["count"] == 1
    # mutating the snapshot must not touch the registry
    cs["value"] = 999.0
    assert registry.labelled("c", "kind") == {"k": 4.0}


def test_histogram_quantiles_are_sane(registry):
    # 100 observations spread over two decades; p50/p95 must bracket the
    # true quantiles within one factor-2 bucket
    vals = [0.001 * (1 + i % 100) for i in range(100)]
    for v in vals:
        registry.observe("q", v)
    s = registry.hist_summary("q")
    assert s["count"] == 100
    assert 0.025 <= s["p50"] <= 0.1
    assert s["p50"] < s["p95"] <= 0.2
    assert s["mean"] == pytest.approx(sum(vals) / 100)


def test_overflow_bucket_catches_huge_observations(registry):
    registry.observe("big", 1e6)
    snap = registry.snapshot()
    (hs,) = snap["histograms"]["big"]["series"]
    assert hs["buckets"][-1] == 1
    assert sum(hs["buckets"][:-1]) == 0


def test_ledger_shims_keep_shapes():
    probes.reset_dispatch_counts()
    probes.reset_cascade_stats()
    probes.reset_prefix_stats()
    probes.reset_spec_stats()

    probes.record_device_dispatch("embed_submit", 3)
    counts = probes.dispatch_counts()
    assert counts["embed_submit"] == 3
    assert isinstance(counts["embed_submit"], int)

    probes.record_cascade("cheap", pairs=32, flops=1e9)
    probes.record_cascade("full", pairs=8, flops=5e8)
    cs = probes.cascade_stats()
    assert cs["pairs"] == {"cheap": 32, "full": 8}
    assert cs["gflops"] == {"cheap": 1.0, "full": 0.5}
    assert cs["survivor_rate"] == 0.25

    probes.record_prefix("requests", 1)
    probes.record_prefix("hit_tokens", 48)
    probes.record_prefix("miss_tokens", 16)
    probes.record_prefix("cached_bytes", 1024)
    probes.record_prefix("cached_bytes", -256)
    ps = probes.prefix_stats()
    assert ps["hit_rate"] == 0.75
    assert ps["prefill_tokens_saved"] == 48
    assert ps["counts"]["cached_bytes"] == 768
    assert ps["cached_bytes"] == 768

    probes.record_spec("drafted", 12)
    probes.record_spec("accepted", 9)
    probes.record_spec("emitted", 13)
    probes.record_spec("verify_steps", 4)
    ss = probes.spec_stats()
    assert ss["acceptance_rate"] == 0.75
    assert ss["tokens_per_dispatch"] == 3.25

    probes.reset_dispatch_counts()
    probes.reset_cascade_stats()
    probes.reset_prefix_stats()
    probes.reset_spec_stats()
    assert probes.dispatch_counts() == {}
    assert probes.prefix_stats()["hit_rate"] == 0.0
    assert probes.spec_stats()["acceptance_rate"] == 0.0


def test_kill_switch_disables_writes_not_resets(monkeypatch):
    monkeypatch.setenv("PATHWAY_TPU_METRICS", "0")
    r = MetricsRegistry()
    assert not r.enabled
    r.counter_add("dead", 5, kind="x")
    r.gauge_set("dead_g", 1.0)
    r.observe("dead_h", 0.1)
    snap = r.snapshot()
    assert not snap["counters"] and not snap["gauges"]
    assert not snap["histograms"]
    monkeypatch.setenv("PATHWAY_TPU_METRICS", "1")
    r.counter_add("alive", 1, kind="x")
    assert r.labelled("alive", "kind") == {"x": 1.0}
    r.reset()
    assert r.snapshot() == {"counters": {}, "gauges": {}, "histograms": {}}


def test_serving_and_unified_snapshot_shapes():
    probes.reset_prefix_stats()
    probes.reset_spec_stats()
    probes.reset_latency_metrics()
    probes.record_prefix("requests", 1)
    probes.record_prefix("hit_tokens", 8)
    probes.record_prefix("miss_tokens", 8)
    probes.observe_latency("ttft_seconds", 0.05, "decode")
    serving = probes.serving_snapshot()
    assert set(serving) == {
        "prefix", "spec", "cascade", "dispatch",
        "occupancy", "latency", "lanes", "tenants", "kv_parked_bytes",
        "retrieval",
    }
    assert serving["prefix"]["hit_rate"] == 0.5
    assert serving["latency"]["ttft_seconds"]["count"] == 1
    uni = probes.unified_snapshot()
    assert uni["scheduler"] is None
    assert uni["serving"]["prefix"]["hit_rate"] == 0.5
    assert set(uni["registry"]) == {"counters", "gauges", "histograms"}
    probes.reset_prefix_stats()
    probes.reset_latency_metrics()
