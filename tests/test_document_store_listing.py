"""``/v1/inputs`` and ``/v1/statistics`` stay a live listing, and what a commit
costs them stays what the commit brings.

Through the engine: a warm-up commit and eight commits of equal size, then an
update of one document's metadata and a deletion. After each step the two
standing queries' results equal a plain Python listing of what was ingested;
and each of the eight commits adds the SAME number to
``consolidate_rows{content=1}`` and calls ``serialize_value`` the same number
of times — flat, not growing with the store: the test that the standing
tuple's O(documents so far) a commit does not come back. Counts, never
times."""

import queue
import threading

import numpy as np
import pandas as pd

import pathway_tpu as pw
from pathway_tpu.engine import probes
from pathway_tpu.engine import value as value_mod
from pathway_tpu.internals.json import Json, unwrap_json
from pathway_tpu.stdlib.indexing import BruteForceKnnFactory
from pathway_tpu.xpacks.llm.document_store import DocumentStore

COMMIT_DOCS = 12


@pw.udf
def _embed(text: str) -> np.ndarray:
    return np.array([1.0, float(len(text) % 7), 1.0])


class _Feeder(pw.io.python.ConnectorSubject):
    """One engine commit per ``commits.put([(diff, doc), ...])``."""

    def __init__(self):
        super().__init__()
        self.commits: queue.Queue = queue.Queue()
        self.counter = 0
        self.key_of: dict[int, int] = {}

    def run(self) -> None:
        while (commit := self.commits.get()) is not None:
            for diff, doc in commit:
                values = {"data": doc["text"],
                          "_metadata": dict(doc["meta"])}
                if diff > 0:
                    # the connector keys its rows by arrival number
                    self.key_of[id(doc)] = value_mod.hash_values(self.counter)
                    self.counter += 1
                    self.next(**values)
                else:
                    self._remove(self.key_of[id(doc)], values)
            self.commit()


class _Schema(pw.Schema):
    data: str
    _metadata: pw.Json


def _doc(i, **meta):
    return {"text": f"document number {i} " + "word " * (i % 5),
            "meta": {"path": f"doc{i}.txt", "modified_at": 100 + i, **meta}}


class _Pipeline:
    def __init__(self):
        self.feeder = _Feeder()
        docs = pw.io.python.read(self.feeder, schema=_Schema,
                                 autocommit_duration_ms=None)
        store = DocumentStore(docs, retriever_factory=BruteForceKnnFactory(
            dimensions=3, embedder=_embed))
        inputs = store.inputs_query(pw.debug.table_from_pandas(pd.DataFrame(
            {"metadata_filter": [None], "filepath_globpattern": [None]})))
        stats = store.statistics_query(pw.debug.table_from_pandas(
            pd.DataFrame({"_dummy": [1]})).without("_dummy"))
        self.landed = threading.Condition()
        self.changes = 0            # rows of `chunked_docs` seen, either sign
        self.doc_time = -1          # the time of the last of them
        self.closed: dict[str, int] = {}    # subscription -> last time ended
        self.sent = 0
        self.results: dict[str, object] = {}

        def keep(name):
            def on_change(key, row, time, is_addition):
                if is_addition:
                    self.results[name] = unwrap_json(row["result"])
            return on_change

        def on_doc(key, row, time, is_addition):
            with self.landed:
                self.changes += 1
                self.doc_time = time

        def ended(name):
            def on_time_end(time):
                with self.landed:
                    self.closed[name] = time
                    self.landed.notify_all()
            return on_time_end

        # each subscription has a thread of its own: a commit has landed
        # when all three have seen its time end
        pw.io.subscribe(inputs, on_change=keep("inputs"),
                        on_time_end=ended("inputs"))
        pw.io.subscribe(stats, on_change=keep("statistics"),
                        on_time_end=ended("statistics"))
        pw.io.subscribe(store.chunked_docs, on_change=on_doc,
                        on_time_end=ended("docs"))
        self.thread = threading.Thread(target=pw.run, daemon=True)
        self.thread.start()

    def commit(self, changes) -> None:
        self.sent += len(changes)
        self.feeder.commits.put(changes)
        with self.landed:
            assert self.landed.wait_for(
                lambda: self.changes == self.sent and all(
                    self.closed.get(name, -1) >= self.doc_time
                    for name in ("inputs", "statistics", "docs")),
                timeout=120), (self.changes, self.sent, self.closed)

    def stop(self) -> None:
        self.feeder.commits.put(None)
        for c in pw.G.connectors:
            c._stop.set()
            c.close()
        self.thread.join(timeout=60)


def _listing(held):
    by_path = {}
    for doc in held:
        by_path[doc["meta"]["path"]] = doc["meta"]
    return sorted(by_path.values(), key=lambda m: m["path"])


def _check(pipeline, held):
    assert sorted(pipeline.results["inputs"], key=lambda m: m["path"]) \
        == _listing(held)
    stats = pipeline.results["statistics"]
    assert stats["file_count"] == len(held)
    newest = float(max(d["meta"]["modified_at"] for d in held))
    assert stats["last_modified"] == newest
    assert stats["last_indexed"] == newest


def test_the_listing_is_live_and_a_commit_costs_what_it_brings(monkeypatch):
    # what consolidation serialises, and what the reducers hash: both were
    # O(documents so far) a commit. (The standing query's own reply is the
    # whole listing: the UDF that formats it is not counted here.)
    from pathway_tpu.engine import scheduler as scheduler_mod

    serialised, hashed, consolidating = [0], [0], [False]
    real_serialize = value_mod.serialize_value
    real_consolidate = scheduler_mod.consolidate_counted
    real_hash = Json.__hash__

    def serialize(value, out):
        serialised[0] += consolidating[0]
        return real_serialize(value, out)

    def consolidate(batch):
        consolidating[0] = True
        try:
            return real_consolidate(batch)
        finally:
            consolidating[0] = False

    def json_hash(self):
        hashed[0] += 1
        return real_hash(self)

    monkeypatch.setattr(value_mod, "serialize_value", serialize)
    monkeypatch.setattr(scheduler_mod, "consolidate_counted", consolidate)
    monkeypatch.setattr(Json, "__hash__", json_hash)
    # the native column hasher would hide the plain leaves: count them all
    monkeypatch.setattr(value_mod, "_native_hash_col", None)
    pipeline = _Pipeline()
    try:
        held = [_doc(i) for i in range(COMMIT_DOCS)]
        pipeline.commit([(1, d) for d in held])     # warm-up
        _check(pipeline, held)
        compared, leaves, hashes = [], [], []
        for c in range(1, 9):
            new = [_doc(c * COMMIT_DOCS + i) for i in range(COMMIT_DOCS)]
            before = probes.REGISTRY.labelled(
                "consolidate_rows", "content").get("1", 0)
            leaves_before, hashes_before = serialised[0], hashed[0]
            held = held + new
            pipeline.commit([(1, d) for d in new])
            _check(pipeline, held)
            compared.append(probes.REGISTRY.labelled(
                "consolidate_rows", "content")["1"] - before)
            leaves.append(serialised[0] - leaves_before)
            hashes.append(hashed[0] - hashes_before)
        assert len(held) == 9 * COMMIT_DOCS
        # every commit swaps the standing results for new ones: some rows
        # share a key, and their number follows the commit, not the store
        assert compared[0] > 0 and len(set(compared)) == 1, compared
        assert len(set(leaves)) == 1, leaves
        assert hashes[0] > 0 and len(set(hashes)) == 1, hashes
        # an update of one document's metadata ...
        old = held[5]
        moved = {"text": old["text"],
                 "meta": dict(old["meta"], modified_at=999, owner="someone")}
        held = [d for d in held if d is not old] + [moved]
        pipeline.commit([(-1, old), (1, moved)])
        _check(pipeline, held)
        assert pipeline.results["statistics"]["last_modified"] == 999.0
        # ... and a deletion
        gone = held[20]
        held = [d for d in held if d is not gone]
        pipeline.commit([(-1, gone)])
        _check(pipeline, held)
        assert gone["meta"]["path"] not in {
            m["path"] for m in pipeline.results["inputs"]}
        gone = moved
        held = [d for d in held if d is not gone]
        pipeline.commit([(-1, gone)])
        _check(pipeline, held)
        assert pipeline.results["statistics"]["last_modified"] \
            == float(max(d["meta"]["modified_at"] for d in held))
    finally:
        pipeline.stop()
