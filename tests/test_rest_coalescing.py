"""Requests that wait at an epoch's start ride it together: the REST
connector joins the commit time it opened last while no pump has taken it
(``Scheduler.inject_open``). CPU only: identities, orders and counts, never a
time. The engine is held by an event inside a UDF, not by sleeping; a wait
here is for a state, with a deadline that fails the test."""

import json
import queue
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

import pathway_tpu as pw
from pathway_tpu.engine import probes, tracing
from pathway_tpu.engine.batch import Batch
from pathway_tpu.engine.operators.core import InputNode
from pathway_tpu.engine.operators.external_index import ExternalIndexNode
from pathway_tpu.engine.scheduler import Scheduler
from pathway_tpu.io.http import _RestConnector
from pathway_tpu.ops import knn
from pathway_tpu.stdlib.indexing import BruteForceKnn, DataIndex

DIM = 8
DEADLINE_S = 60.0


def _vector(name: str) -> np.ndarray:
    """``d7`` and ``q7`` are the same direction: q7's best match is d7."""
    return np.random.default_rng(int(name[1:])).normal(size=DIM)


def _until(cond, what: str):
    """Wait for a state (never a fixed time) or fail."""
    end = time.monotonic() + DEADLINE_S
    while not cond():
        assert time.monotonic() < end, f"never came: {what}"
        time.sleep(0.002)


class _Fed(pw.io.python.ConnectorSubject):
    """One engine commit a ``put(rows)``; ``None`` ends the stream."""

    def __init__(self):
        super().__init__()
        self.commits: queue.Queue = queue.Queue()

    def put(self, *rows: dict) -> None:
        self.commits.put(rows)

    def run(self) -> None:
        while (rows := self.commits.get()) is not None:
            for row in rows:
                self.next(**row)
            self.commit()


class _Doc(pw.Schema):
    doc: str


class _Gate(pw.Schema):
    name: str


class _Query(pw.Schema):
    q: str


class _Service:
    """A retrieval route over a live index, a document feed, and a second
    feed whose rows HOLD the epoch that carries them until the test lets go:
    whatever is committed meanwhile waits at the pump."""

    def __init__(self):
        self.held: dict[str, threading.Event] = {}
        self.let_go: dict[str, threading.Event] = {}
        self.landed = []            # one entry a document commit's on_time_end
        self.gate_epochs = []       # rows of each gate commit's epoch
        self.searches = []          # queries of each index.search call
        self.answers: dict[str, dict] = {}

        @pw.udf
        def vec_of(name: str) -> np.ndarray:
            return _vector(name)

        @pw.udf
        def hold(name: str) -> str:
            if name in self.let_go:
                self.held[name].set()
                assert self.let_go[name].wait(DEADLINE_S), f"{name} never let go"
            return name

        self.docs_feed, self.gate_feed = _Fed(), _Fed()
        docs = pw.io.python.read(self.docs_feed, schema=_Doc,
                                 autocommit_duration_ms=None)
        docs = docs.select(doc=docs.doc, vec=vec_of(docs.doc))
        gate = pw.io.python.read(self.gate_feed, schema=_Gate,
                                 autocommit_duration_ms=None)
        # ``on_time_end`` comes after EVERY epoch: count those with rows
        def epochs_with_rows(table, into: list, note):
            rows_now = []

            def on_time_end(time):
                if rows_now:
                    into.append(note(time, rows_now))
                    rows_now.clear()

            pw.io.subscribe(
                table, on_time_end=on_time_end,
                on_change=lambda key, row, time, is_addition:
                    rows_now.append(row))

        epochs_with_rows(gate.select(name=hold(gate.name)), self.gate_epochs,
                         lambda time, rows: len(rows))
        epochs_with_rows(docs, self.landed, lambda time, rows: time)
        queries, writer = pw.io.http.rest_connector(
            port=0, schema=_Query, delete_completed_queries=True)
        asked = queries.select(q=queries.q, qvec=vec_of(queries.q))
        index = DataIndex(docs, BruteForceKnn(docs.vec, dimensions=DIM,
                                              reserved_space=64, metric="cos"))
        found = index.query_as_of_now(asked.qvec, number_of_matches=3,
                                      with_distances=True)
        writer(found.select(docs=pw.this.doc, dist=pw.this["_pw_dist"]))
        self.connectors = list(pw.G.connectors)
        self.rest = next(c for c in self.connectors
                         if isinstance(c, _RestConnector))
        for c in self.connectors:
            c.heartbeat_ms = 10     # the sources' frontiers park nothing long
        self.index_node = next(n for n in pw.G.engine_graph.nodes
                               if isinstance(n, ExternalIndexNode))
        self.errors = []
        self._engine = threading.Thread(target=self._run, daemon=True)

    def _run(self) -> None:
        try:
            pw.run()
        except BaseException as exc:  # noqa: BLE001 - re-raised by stop()
            self.errors.append(exc)

    # -- the test's side ---------------------------------------------------

    def start(self, monkeypatch) -> "_Service":
        search = knn.BruteForceKnnIndex.search

        def counted(index, queries, k):
            self.searches.append(len(queries))
            return search(index, queries, k)

        monkeypatch.setattr(knn.BruteForceKnnIndex, "search", counted)
        self._engine.start()
        _until(lambda: self.rest._sched is not None
               and self.rest.webserver._started.is_set(), "the server")
        self.sched = self.rest._sched
        return self

    def stop(self) -> None:
        for ev in self.let_go.values():
            ev.set()
        self.docs_feed.commits.put(None)
        self.gate_feed.commits.put(None)
        for c in self.connectors:
            c._stop.set()
            c.close()
        self._engine.join(timeout=DEADLINE_S)
        assert not self._engine.is_alive()
        if self.errors:
            raise self.errors[0]

    def add_docs(self, *names: str) -> None:
        """Commit documents and wait for the commit's ``on_time_end``."""
        n = len(self.landed)
        self.docs_feed.put(*({"doc": d} for d in names))
        _until(lambda: len(self.landed) > n, "the document commit")

    def hold_epoch(self, name: str) -> None:
        """Commit a gate row and wait until its epoch is running, held."""
        self.held[name], self.let_go[name] = threading.Event(), threading.Event()
        self.gate_feed.put({"name": name})
        assert self.held[name].wait(DEADLINE_S), f"{name} never ran"

    def post(self, q: str) -> dict:
        req = urllib.request.Request(
            f"http://127.0.0.1:{self.rest.webserver.port}/",
            data=json.dumps({"q": q}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=DEADLINE_S) as resp:
            self.answers[q] = json.loads(resp.read())
        return self.answers[q]

    def post_all(self, names) -> list[threading.Thread]:
        """Post each from its own client; returns once every one of them
        has been committed by the connector (none can be answered while an
        epoch is held)."""
        before = self.rest_commits()
        threads = [threading.Thread(target=self.post, args=(q,), daemon=True)
                   for q in names]
        for t in threads:
            t.start()
        _until(lambda: self.rest_commits() == before + len(threads),
               "the requests' commits")
        return threads

    def rest_commits(self) -> int:
        stats = self.sched.stats.connectors.get(self.rest.node.id)
        return stats.commits if stats is not None else 0

    def idle(self) -> None:
        """Every completed query retracted and nothing left to pump."""
        _until(lambda: not self.index_node._answered
               and self.sched.pending_backlog() == 0, "an idle engine")


@pytest.fixture
def service(monkeypatch):
    tracing.reset_traces()
    svc = _Service().start(monkeypatch)
    try:
        yield svc
    finally:
        svc.stop()


def _join(threads) -> None:
    for t in threads:
        t.join(timeout=DEADLINE_S)
        assert not t.is_alive()


def _epochs_of(request_ids) -> list[dict]:
    wanted = set(request_ids)
    return [e for e in tracing.recent_traces(kind="epoch")
            if wanted & set(e["attrs"].get("requests", ()))]


def _rest_spans(server: str = "/") -> list[dict]:
    return [s for s in tracing.recent_traces(kind="rest")
            if s["server"] == server]


@pytest.mark.parametrize("n", [2, 8, 20])
def test_requests_posted_while_an_epoch_is_held_ride_one_epoch(service, n):
    service.add_docs(*(f"d{i}" for i in range(24)))
    knn_before = probes.dispatch_counts().get("knn_search", 0)
    asked_before = probes.REGISTRY.labelled(
        "knn_search_queries", "padded").get("0", 0)
    service.hold_epoch("g1")
    names = [f"q{i}" for i in range(n)]
    threads = service.post_all(names)
    assert not service.answers
    service.let_go["g1"].set()
    _join(threads)
    service.idle()
    together = dict(service.answers)
    spans = _rest_spans()
    assert len(spans) == n
    # ONE epoch carried all of them, at the time every commit was told
    (epoch,) = _epochs_of(s["id"] for s in spans)
    assert sorted(epoch["attrs"]["requests"]) == sorted(s["id"] for s in spans)
    assert {s["events"][1]["t"] for s in spans} == {epoch["attrs"]["t"]}
    assert epoch["attrs"]["rows"] == n
    # and ONE search dispatch carried all of their queries
    assert service.searches == [n]
    assert probes.dispatch_counts()["knn_search"] == knn_before + 1
    assert probes.REGISTRY.labelled(
        "knn_search_queries", "padded")["0"] == asked_before + n
    # each reply is the one its query gets alone
    for q in names:
        service.idle()
        alone = service.post(q)
        assert alone["docs"] == together[q]["docs"]
        assert alone["docs"][0] == "d" + q[1:]
        assert alone["dist"] == pytest.approx(together[q]["dist"], abs=1e-6)
    assert service.searches == [n] + [1] * n


def test_a_lone_request_at_an_idle_engine_opens_a_fresh_time(service):
    service.add_docs("d1", "d2", "d3")
    opened = []
    inject_open = service.sched.inject_open

    def spy(node, fresh_time, batch, request_id=None):
        t = inject_open(node, fresh_time, batch, request_id)
        opened.append((request_id, fresh_time, t))
        return t

    service.sched.inject_open = spy
    for q in ("q1", "q2", "q3"):
        service.idle()
        assert service.post(q)["docs"][0] == "d" + q[1:]
    spans = _rest_spans()
    assert len(spans) == 3
    asked = [(rid, fresh, t) for rid, fresh, t in opened if rid is not None]
    assert [rid for rid, _, _ in asked] == [s["id"] for s in spans]
    # nothing of its own was waiting, so each took the fresh time it brought
    assert all(t == fresh for _, fresh, t in asked)
    times = [s["events"][1]["t"] for s in spans]
    assert times == sorted(set(times))
    for s in spans:
        (epoch,) = _epochs_of([s["id"]])
        assert epoch["attrs"]["requests"] == [s["id"]]
        assert epoch["attrs"]["rows"] == 1
        # it waited for the pump and for nobody's company: no window, no
        # timer (the sources' heartbeat here is 10 ms, the default 500)
        assert s["metrics"]["queue_wait_ms"] <= s["metrics"]["e2e_ms"]
        assert s["metrics"]["queue_wait_ms"] < 400


def test_retractions_ride_the_next_open_time(service):
    n = 6
    service.add_docs(*(f"d{i}" for i in range(12)))
    service.hold_epoch("g1")
    threads = service.post_all([f"q{i}" for i in range(n)])
    # a second held epoch behind the requests': their replies come while it
    # runs, so the retractions queue behind it — and so does what arrives
    service.held["g2"], service.let_go["g2"] = (
        threading.Event(), threading.Event())
    service.gate_feed.put({"name": "g2"})
    _until(lambda: service.sched.pending_backlog() == 2, "g2 queued")
    service.let_go["g1"].set()
    _join(threads)
    assert service.held["g2"].wait(DEADLINE_S)
    _until(lambda: service.rest_commits() == 2 * n, "the retractions")
    assert len(service.index_node._answered) == n
    late = service.post_all(["q7", "q8"])
    assert service.sched.pending_backlog() == 1
    service.let_go["g2"].set()
    _join(late)
    service.idle()
    (shared,) = [e for e in tracing.recent_traces(kind="epoch")
                 if len(e["attrs"].get("requests", ())) == 2]
    # n retractions and two new requests in one epoch, one search for the two
    assert shared["attrs"]["rows"] == n + 2
    assert service.searches == [n, 2]
    assert service.answers["q7"]["docs"][0] == "d7"
    assert not service.index_node._answered


def test_a_request_sees_every_commit_that_landed_before_it_was_posted(service):
    service.add_docs(*(f"d{i}" for i in range(8)))
    service.hold_epoch("g1")
    early = service.post_all(["q40", "q41"])      # share a time below d40's
    service.docs_feed.put({"doc": "d40"})
    _until(lambda: service.sched.pending_backlog() == 2, "d40 queued")
    service.held["g2"], service.let_go["g2"] = (
        threading.Event(), threading.Event())
    service.gate_feed.put({"name": "g2"})
    _until(lambda: service.sched.pending_backlog() == 3, "g2 queued")
    landed = len(service.landed)
    service.let_go["g1"].set()
    _join(early)
    # as of their arrival d40 had not landed
    assert "d40" not in service.answers["q40"]["docs"]
    _until(lambda: len(service.landed) > landed, "d40's on_time_end")
    assert service.held["g2"].wait(DEADLINE_S)
    # posted AFTER d40's on_time_end, while the engine is busy again and
    # retractions of the early two wait at the pump: they join a time, and
    # it lies above d40's
    late = service.post_all(["q40", "q42"])
    service.let_go["g2"].set()
    _join(late)
    assert service.answers["q40"]["docs"][0] == "d40"
    assert service.answers["q40"]["dist"][0] == pytest.approx(-1.0, abs=1e-2)
    spans = _rest_spans()
    assert len(spans) == 4
    times = sorted(s["events"][1]["t"] for s in spans)
    assert times[0] == times[1] < service.landed[-1] < times[2] == times[3]


def test_a_connector_that_is_not_rest_never_joins(service):
    service.hold_epoch("g1")
    service.gate_feed.put({"name": "a"})
    service.gate_feed.put({"name": "b"}, {"name": "c"})
    _until(lambda: service.sched.pending_backlog() == 2, "two commits queued")
    service.let_go["g1"].set()
    _until(lambda: len(service.gate_epochs) == 3, "the commits' epochs")
    # a commit is a unit its subscribers count: each kept its own epoch
    assert service.gate_epochs == [1, 1, 2]


def test_eight_threads_hammering_one_connector(monkeypatch):
    """Time never runs backwards, no frontier decreases, no time is pumped
    twice and no row is lost, with the heartbeat advancing between them."""
    queries, writer = pw.io.http.rest_connector(
        port=0, schema=_Query, delete_completed_queries=False)
    seen = []
    pw.io.subscribe(queries, on_change=lambda key, row, time, is_addition:
                    seen.append((time, row["q"])))
    (rest,) = pw.G.connectors
    rest.heartbeat_ms = 1
    pumped, frontiers, used = [], [], []
    run_epoch, advance = Scheduler._run_epoch, Scheduler.advance_source

    def spy_epoch(sched, t, injected):
        pumped.append((t, sched.current_time))
        return run_epoch(sched, t, injected)

    def spy_advance(sched, node, new_time):
        with sched._lock:
            frontiers.append((sched._source_frontiers.get(node.id), new_time))
            return advance(sched, node, new_time)

    monkeypatch.setattr(Scheduler, "_run_epoch", spy_epoch)
    monkeypatch.setattr(Scheduler, "advance_source", spy_advance)
    threads_n, each = 8, 150
    errors = []

    def hammer(w: int) -> None:
        try:
            for i in range(each):
                key = w * each + i + 1
                used.append(rest.commit_rows([(key, (f"{w}.{i}",), 1)]))
        except BaseException as exc:  # noqa: BLE001 - reported below
            errors.append(exc)

    def drive() -> None:
        _until(lambda: rest._sched is not None, "the scheduler")
        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            threads = [threading.Thread(target=hammer, args=(w,), daemon=True)
                       for w in range(threads_n)]
            for t in threads:
                t.start()
            _join(threads)
        finally:
            sys.setswitchinterval(old)
            rest._stop.set()
            rest.close()

    driver = threading.Thread(target=drive, daemon=True)
    driver.start()
    pw.run()
    driver.join(timeout=DEADLINE_S)
    assert not driver.is_alive() and not errors
    times = [t for t, _ in pumped]
    assert all(t > before for t, before in pumped)          # never backwards
    assert times == sorted(set(times))                      # none twice
    assert all(old is None or new >= old for old, new in frontiers)
    assert sorted(q for _, q in seen) == sorted(
        f"{w}.{i}" for w in range(threads_n) for i in range(each))
    # every row ran in the epoch of the time its commit was told
    assert sorted(t for t, _ in seen) == sorted(used)
    # and the hammering did share epochs (else this test proves little)
    assert len(set(used)) < len(used)


@pytest.mark.parametrize("pump", ["run", "run_available"])
def test_a_time_is_open_until_the_pump_takes_it(pump):
    graph = pw.G.engine_graph
    a = InputNode(graph, ["x"], name="a")
    b = InputNode(graph, ["x"], name="b")
    sched = Scheduler(graph, [a, b])

    def one(key):
        return Batch.from_rows(["x"], [(key, (key,), 1)])

    assert sched.inject_open(a, 10, one(1)) == 10
    assert sched.inject_open(a, 12, one(2)) == 10       # still waiting: joins
    assert sched.inject_open(b, 14, one(3)) == 14       # a time of its own
    sched.inject(a, 16, one(4))                         # plain inject: not open
    assert sched.inject_open(a, 18, one(5)) == 10
    assert sched.inject_open(a, 20, Batch.from_rows(["x"], [])) == 10
    ran = []
    run_epoch = sched._run_epoch
    sched._run_epoch = lambda t, injected: (
        ran.append((t, sum(len(x) for bs in injected.values() for x in bs))),
        run_epoch(t, injected))
    getattr(sched, pump)()
    assert ran == [(10, 3), (14, 1), (16, 1)]
    # taken: what comes now opens the fresh time it brings
    assert sched.inject_open(a, 22, one(6)) == 22
    assert sched.inject_open(a, 24, one(7)) == 22
    assert sched.inject_open(b, 26, one(8)) == 26


@pytest.mark.parametrize("n,dispatches", [(32, 1), (33, 2), (70, 3)])
def test_search_splits_a_batch_above_the_largest_bucket(monkeypatch, n,
                                                        dispatches):
    rng = np.random.default_rng(7)
    index = knn.BruteForceKnnIndex(DIM, reserved_space=256)
    index.add(list(range(200)), rng.normal(size=(200, DIM)).astype(np.float32))
    queries = rng.normal(size=(n, DIM)).astype(np.float32)
    buckets = []
    record = knn.record_knn_search
    monkeypatch.setattr(knn, "record_knn_search", lambda nq, bucket: (
        buckets.append((nq, bucket)), record(nq, bucket)))
    split = index.search(queries, 5)
    assert len(buckets) == dispatches == -(-n // knn._MAX_SEARCH_BUCKET)
    assert sum(nq for nq, _ in buckets) == n
    assert all(b <= knn._MAX_SEARCH_BUCKET for _, b in buckets)
    # the unsplit reference: the same search with the guard out of reach
    monkeypatch.setattr(knn, "_MAX_SEARCH_BUCKET", 1 << 20)
    del buckets[:]
    whole = index.search(queries, 5)
    assert len(buckets) == 1
    assert len(split) == len(whole) == n
    for got, want in zip(split, whole):
        assert [k for k, _ in got] == [k for k, _ in want]
        assert [s for _, s in got] == pytest.approx([s for _, s in want],
                                                    abs=1e-6)


def test_the_first_search_compiles_every_bucket():
    """A served index meets its largest bucket under load: that executable
    is there from the first search on (shapes no other test here uses)."""
    rng = np.random.default_rng(11)
    index = knn.BruteForceKnnIndex(DIM, reserved_space=512)
    index.add(list(range(300)), rng.normal(size=(300, DIM)).astype(np.float32))
    before = knn._search_kernel._cache_size()
    assert len(index.search(rng.normal(size=DIM).astype(np.float32), 7)) == 1
    compiled = knn._search_kernel._cache_size()
    assert compiled == before + len(knn._SEARCH_BUCKETS)
    for n in (20, 40):      # bucket 32, then 32 and 16
        assert len(index.search(
            rng.normal(size=(n, DIM)).astype(np.float32), 7)) == n
    assert knn._search_kernel._cache_size() == compiled
