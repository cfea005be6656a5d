"""Host regions on the profiler's clock (``tracing.region``), the ``epoch``
and ``rest`` request spans, the ring a kind, and the counters that came with
them. CPU only: these check names, nesting, order and counts, never a time."""

import gc
import glob
import json
import os
import threading
import time
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import pathway_tpu as pw
from pathway_tpu.engine import probes, tracing
from tests.benchmark.bench_paths import BENCH  # noqa: F401 - sets sys.path
from pathway_tpu.engine.scheduler import Scheduler


def _session(tmp_path, body):
    """Run ``body`` inside a profiler session with the options the
    benchmark's tracer sets; the ``pw.`` events of the host plane as the
    benchmark's reader loads them: ``[(thread, name, start_ns,
    duration_ns, stats)]``."""
    from harness.program_trace import load_host_regions

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    return load_host_regions(max(glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime))


class _Numbers(pw.io.python.ConnectorSubject):
    def run(self):
        for c in range(3):
            for i in range(4):
                self.next(x=c * 10 + i)
            self.commit()
            time.sleep(0.05)    # the pump runs dry between commits


class _X(pw.Schema):
    x: int


def _toy_pipeline():
    @pw.udf
    def inc(x: int) -> int:
        return x + 1

    t = pw.io.python.read(_Numbers(), schema=_X, autocommit_duration_ms=None)
    got = []
    pw.io.subscribe(
        t.select(y=inc(t.x)),
        on_change=lambda key, row, time, is_addition: got.append(row["y"]))
    return got


def _inside(child, parent):
    return (child[0] == parent[0] and parent[2] <= child[2]
            and child[2] + child[3] <= parent[2] + parent[3])


def test_a_session_around_a_toy_pipeline_finds_the_engines_regions(tmp_path):
    got = _toy_pipeline()
    events = _session(tmp_path, pw.run)
    assert sorted(got) == [1, 2, 3, 4, 11, 12, 13, 14, 21, 22, 23, 24]
    by_name = {}
    for e in events:
        by_name.setdefault(e[1], []).append(e)
    commits = by_name["pw.connector.commit"]
    assert {"connector", "rows"} <= set(commits[0][4])
    assert [c[4]["rows"] for c in commits][:3] == [4, 4, 4]
    epochs = [e for e in by_name["pw.engine.epoch"] if e[4]["rows"]]
    assert len(epochs) == 3 and all(e[4]["rows"] == 4 for e in epochs)
    # epoch contains op contains consolidate, all on the engine's thread,
    # which is not the connector's
    assert commits[0][0] != epochs[0][0]
    for epoch in epochs:
        ops = [o for o in by_name["pw.engine.op"] if _inside(o, epoch)]
        assert len(ops) == 3
        assert {"op", "rows_in"} <= set(ops[0][4])
        assert all(o[4]["rows_in"] == 4 for o in ops)
        for op in ops:
            inside = [c for c in by_name["pw.engine.consolidate"]
                      if _inside(c, op)]
            assert inside, op
            # every row of these batches is alone under its key: decided
            # without a look at its content
            assert all(c[4]["rows"] == 4 and c[4]["compared"] == 0
                       for c in inside), inside
        ends = [s for s in by_name["pw.engine.on_time_end"]
                if _inside(s, epoch)]
        assert [s[4]["op"].rsplit(":", 1)[0] for s in ends] == ["Subscribe"]
        assert sorted(o[4]["op"].rsplit(":", 1)[0] for o in ops) == [
            "Rowwise", "Subscribe", "python-connector"]
    # the pump waits for a ready time between the commits
    assert by_name["pw.engine.wait_ready"]
    assert all(e[0] == epochs[0][0] for e in by_name["pw.engine.wait_ready"])


def test_without_a_session_a_region_records_nothing(tmp_path):
    _toy_pipeline()
    pw.run()                        # every region runs, no session is open
    with tracing.region("pw.test.before", t=1):
        pass
    events = _session(tmp_path, lambda: time.sleep(0.01))
    assert events == []


def test_a_full_collection_is_a_region_and_a_young_one_is_not(tmp_path):
    def body():
        gc.collect(0)
        gc.collect()
    names = [e[1] for e in _session(tmp_path, body)]
    assert names.count("pw.gc") == 1


def test_a_region_is_the_annotation_and_touches_no_registry():
    before = probes.REGISTRY.snapshot()
    region = tracing.region("pw.embed.tokenize", rows=7)
    assert type(region) is jax.profiler.TraceAnnotation
    with region:
        pass
    assert probes.REGISTRY.snapshot() == before


def test_an_ingest_run_leaves_the_five_ingest_regions(tmp_path):
    from pathway_tpu.models.embedder import SentenceEmbedderModel
    from pathway_tpu.models.transformer import TransformerConfig
    from pathway_tpu.ops.knn import BruteForceKnnIndex

    cfg = TransformerConfig(vocab_size=64, hidden=16, layers=1, heads=2,
                            intermediate=32, max_position=32)

    def body():
        model = SentenceEmbedderModel(cfg=cfg, max_length=16)
        try:
            vectors = model.embed_batch(["a b c", "d e"])
        finally:
            model.close()
        index = BruteForceKnnIndex(16, reserved_space=16)
        index.add([1, 2], vectors)

    rows = {}
    for _thread, name, _start, _dur, stats in _session(tmp_path, body):
        if name.startswith(("pw.embed.", "pw.index.")):
            rows.setdefault(name, []).append(stats["rows"])
    assert rows == {
        "pw.embed.tokenize": [2], "pw.embed.h2d": [2],
        "pw.embed.dispatch": [2], "pw.embed.drain": [2],
        "pw.index.append": [2]}


def test_epoch_spans_order_wait_and_no_span_for_an_empty_epoch():
    tracing.reset_traces()
    _toy_pipeline()
    pw.run()
    spans = tracing.recent_traces(kind="epoch")
    assert len(spans) == 3
    for s in spans:
        assert [e["name"] for e in s["events"]] == [
            "enqueue", "admit", "drain"]
        times = [e["t_ms"] for e in s["events"]]
        assert times == sorted(times)
        assert 0 <= s["metrics"]["queue_wait_ms"] <= s["metrics"]["e2e_ms"]
        assert s["attrs"]["rows"] == 4
        assert s["attrs"]["operators_stepped"] == 3
        assert s["attrs"]["t"] > 0
    assert probes.REGISTRY.hist_summary(
        "queue_wait_seconds", phase="epoch")["count"] >= 3
    # an epoch that carried no rows leaves no span
    sched = Scheduler(pw.G.engine_graph, [])
    sched._run_epoch(2, {})
    assert sched.stats.epochs_total == 1
    assert len(tracing.recent_traces(kind="epoch")) == 3


class _Query(pw.Schema):
    q: str


def test_rest_spans_order_and_the_identifier_shared_with_the_epoch():
    from pathway_tpu.io.http import _RestConnector

    tracing.reset_traces()
    queries, writer = pw.io.http.rest_connector(
        port=0, schema=_Query, delete_completed_queries=True)
    writer(queries.select(ans=queries.q + "!"))
    conns = list(pw.G.connectors)
    rest = next(c for c in conns if isinstance(c, _RestConnector))
    answers = []

    def client():
        rest.webserver._started.wait(timeout=20)
        try:
            for q in ("hi", "ho"):
                req = urllib.request.Request(
                    f"http://127.0.0.1:{rest.webserver.port}/",
                    data=json.dumps({"q": q}).encode(),
                    headers={"Content-Type": "application/json"})
                answers.append(json.loads(
                    urllib.request.urlopen(req, timeout=15).read()))
        finally:
            for c in conns:
                c._stop.set()
                c.close()

    thread = threading.Thread(target=client, daemon=True)
    thread.start()
    pw.run()
    thread.join(timeout=20)
    assert [a["ans"] for a in answers] == ["hi!", "ho!"]
    spans = tracing.recent_traces(kind="rest")
    assert len(spans) == 2
    epochs = tracing.recent_traces(kind="epoch")
    for s in spans:
        assert [e["name"] for e in s["events"]] == [
            "enqueue", "commit", "admit", "resolve", "drain"]
        times = [e["t_ms"] for e in s["events"]]
        assert times == sorted(times)
        assert 0 <= s["metrics"]["queue_wait_ms"] <= s["metrics"]["e2e_ms"]
        assert s["server"] == "/"
        # the request's key is the span's id and an attr of its epoch's span
        mine = [e for e in epochs if s["id"] in e["attrs"].get("requests", [])]
        assert len(mine) == 1
        assert mine[0]["attrs"]["t"] == s["events"][1]["t"]
    # each request in one epoch, and each completed query's retraction in
    # one too: its own, or the one the next request opened or joined
    assert sum(len(e["attrs"].get("requests", [])) for e in epochs) == 2
    assert sum(e["attrs"]["rows"] for e in epochs) == 4
    assert probes.REGISTRY.hist_summary(
        "queue_wait_seconds", phase="rest")["count"] >= 2


def test_the_ring_keeps_each_kind_apart(monkeypatch):
    monkeypatch.setenv("PATHWAY_TPU_TRACE_RING", "3")
    tracing.reset_traces()
    first = tracing.start_span("epoch", t=1)
    first.finish()
    for i in range(7):
        tracing.start_span("embed", server="ring-kinds", texts=i).finish()
    tracing.start_span("epoch", t=2).finish()
    assert [s["attrs"]["t"] for s in tracing.recent_traces(kind="epoch")] \
        == [1, 2]
    embeds = tracing.recent_traces(kind="embed")
    assert [s["attrs"]["texts"] for s in embeds] == [4, 5, 6]
    # all kinds together come oldest first, as they finished
    everything = tracing.recent_traces()
    assert [s["kind"] for s in everything] == [
        "epoch", "embed", "embed", "embed", "epoch"]
    assert tracing.recent_traces(n=2) == everything[-2:]
    assert len(tracing.recent_traces(server="ring-kinds")) == 3


def test_an_event_can_be_stamped_at_another_threads_reading():
    span = tracing.start_span("rest", request_id=9)
    span.event("admit", at=span.t0 + 0.010)
    span.event("drain", at=span.t0 + 0.030)
    done = span.finish()
    assert done["metrics"] == {"e2e_ms": 30.0, "queue_wait_ms": 10.0}
    tracing.NULL_SPAN.event("admit", at=1.0)      # the kill switch's twin


def test_a_search_counts_its_queries_as_asked_and_as_searched():
    from pathway_tpu.ops.knn import BruteForceKnnIndex

    index = BruteForceKnnIndex(8, reserved_space=32)
    rng = np.random.default_rng(0)
    index.add(list(range(20)), rng.normal(size=(20, 8)).astype(np.float32))
    before = dict(probes.REGISTRY.labelled("knn_search_queries", "padded"))
    searches = probes.dispatch_counts().get("knn_search", 0)
    index.search(rng.normal(size=(3, 8)).astype(np.float32), 2)
    index.search(rng.normal(size=(8,)).astype(np.float32), 2)
    after = probes.REGISTRY.labelled("knn_search_queries", "padded")
    assert after["0"] - before.get("0", 0) == 4       # 3 + 1 asked
    assert after["1"] - before.get("1", 0) == 32      # two buckets of 16
    assert probes.dispatch_counts()["knn_search"] - searches == 2


def test_compilations_are_counted_and_exposed_on_metrics():
    from pathway_tpu.internals.http_server import registry_text

    def counter(name):
        family = probes.REGISTRY.snapshot()["counters"].get(name, {})
        return sum(s["value"] for s in family.get("series", []))

    compiles, seconds = counter("compiles"), counter("compile_seconds")

    @jax.jit
    def fresh(x):
        return x * 3.0 + 41.0

    fresh(jnp.ones((5, 3))).block_until_ready()
    assert counter("compiles") >= compiles + 1
    assert counter("compile_seconds") > seconds
    tracing.start_span("epoch", t=1).finish()
    probes.record_knn_search(1, 16)
    text = registry_text()
    assert "pathway_tpu_compiles_total " in text
    assert "pathway_tpu_compile_seconds_total " in text
    assert 'pathway_tpu_knn_search_queries_total{padded="0"}' in text
    probes.record_consolidate(10, 2)
    text = registry_text()
    assert 'pathway_tpu_consolidate_rows_total{content="0"}' in text
    assert 'pathway_tpu_consolidate_rows_total{content="1"}' in text
    assert 'pathway_tpu_e2e_seconds_count{phase="epoch"}' in text
