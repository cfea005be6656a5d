"""Traffic generators: ONE general generator per kind of load, driven by a
mix's data file (``benchmarks/traffic/<mix>.json``: lengths, clients,
outstanding commits, sharing). A later PR adds a mix by adding a file. The
mix names its generator (``"generator"``), found by ``manifest.resolve``:
one of :data:`GENERATORS`, or ``Generator`` of a
``<path>/generators/<name>.py``.

Each generator has ``prepare`` (before the window: inputs made from the
seed, the served path warmed at the window's own concurrency — set-up) and
``run`` (the measured window). ``run`` returns the end-to-end numbers taken
by the host's clock over ALL the work and ALL the time of the window, the
counts, the benchmark's own spans, and what the comparison needs.
"""

from __future__ import annotations

import threading
import time

from . import stats
from .system import System, device_barrier, post


class Window:
    """What a generator hands back."""

    def __init__(self):
        self.end_to_end: dict[str, float] = {}
        self.attempted = 0
        self.failed = 0
        self.counters: dict[str, float] = {}
        self.spans: dict[str, list[float]] = {}
        self.span_marks: list[tuple[str, float, float]] = []  # name, t0, t1
        self.facts: dict = {}
        self.sample: dict = {}
        self.problems: list[str] = []
        self.t0 = self.t1 = 0.0
        # the program's counters as the window opened and as it closed
        self.counters_open: dict = {}
        self.counters_close: dict = {}


class HostWatch:
    """What the host did to the window, for the earlier lines (never a
    metric): the longest stretch in which a thread that sleeps 20 ms was not
    scheduled (the process stood still or something held the interpreter's
    lock), and the collector's longest pause and full collections. A reply
    stall with neither beside it lies in the program's pipeline."""

    def __init__(self):
        import gc

        self.gc = gc
        self.pauses: list[tuple[float, float]] = []   # (when, seconds)
        self.collections: list[tuple[float, float, int]] = []
        self._stop = threading.Event()
        self._t_gc = 0.0
        self._thread = threading.Thread(target=self._run, daemon=True)

    def _on_gc(self, phase: str, info: dict) -> None:
        now = time.perf_counter()
        if phase == "start":
            self._t_gc = now
        else:
            self.collections.append((now, now - self._t_gc,
                                     info["generation"]))

    def _run(self) -> None:
        last = time.perf_counter()
        while not self._stop.wait(0.02):
            now = time.perf_counter()
            if now - last > 0.25:
                self.pauses.append((now, now - last))
            last = now

    def __enter__(self):
        self.gc.callbacks.append(self._on_gc)
        self._thread.start()
        return self

    def __exit__(self, *exc) -> None:
        self._stop.set()
        self._thread.join()
        self.gc.callbacks.remove(self._on_gc)

    def facts(self, t0: float, t1: float) -> dict:
        """Inside the window ``[t0, t1]``: ``[seconds after it opened,
        length]`` of every pause over 0.25 s and of the longest collection,
        and how many full collections ran."""
        pauses = [[round(at - t0, 2), round(s, 3)]
                  for at, s in self.pauses if t0 <= at <= t1]
        inside = [c for c in self.collections if t0 <= c[0] <= t1]
        longest = max(inside, key=lambda c: c[1], default=None)
        return {
            "host_pauses": pauses[:10],
            "gc_longest": [round(longest[0] - t0, 2), round(longest[1], 3),
                           longest[2]] if longest else None,
            "gc_full_collections": sum(c[2] == 2 for c in inside),
        }


class CommitFeeder:
    """A connector that always has a backlog: commits of ``commit_docs``
    distinct documents, ``outstanding`` of them queued at any time (the next
    is queued before the previous lands, so the engine never waits for the
    generator). A document counts once its commit's ``on_time_end`` has
    landed AND the device barrier after it has passed.

    Documents become retrievable a commit at a time, so a window cut at a
    fixed instant would count whole commits and quantise the rate (15 or 16
    commits in 20 s: 6.7 %). The window therefore runs from one landing to
    another: it opens when the first commit of the run lands (the pipeline
    is then full) and closes at the first landing at or after
    ``--seconds``. The rate is every document landed in it over all of its
    time; a stall at the end lengthens the window and counts in full."""

    def __init__(self, traffic: dict):
        self.traffic = traffic

    def prepare(self, system: System) -> None:
        t = self.traffic
        self.commits = system.make_commits(t["pool_commits"], t["commit_docs"])
        # every shape compiled and the index past its first appends
        warm = system.make_commits(t["warm_commits"], t["commit_docs"])
        for commit in warm:
            system.put(commit)
        system.wait_closed(system.committed_docs)
        device_barrier()

    def run(self, system: System, seconds: float, on_tick=None) -> Window:
        t = self.traffic
        w = Window()
        queued_at: dict[int, float] = {}
        landed: list[tuple[float, int, float]] = []  # t_landed, docs, span
        base_commits = system.commits_closed
        nxt = 0

        def put_next():
            nonlocal nxt
            if nxt < len(self.commits):
                queued_at[nxt] = time.perf_counter()
                system.put(self.commits[nxt])
                nxt += 1

        for _ in range(t["outstanding"]):
            put_next()
        seen = 0
        closing = False
        while True:
            with system.landed:
                ok = system.landed.wait_for(
                    lambda: system.commits_closed - base_commits > seen,
                    timeout=120)
            if not ok:
                w.problems.append("a commit did not land within 120 s")
                break
            closed_now = system.commits_closed - base_commits
            if not closing:
                for _ in range(closed_now - seen):
                    put_next()          # before the barrier: never starve
            device_barrier()
            now = time.perf_counter()
            for c in range(seen, closed_now):
                landed.append((now, len(self.commits[c]),
                               (now - queued_at[c]) * 1e3))
                w.span_marks.append((f"commit{c}", queued_at[c], now))
            seen = closed_now
            if not w.t0:
                w.t0 = now              # the window opens at a landing
                w.counters_open = system.counters()
            if on_tick is not None:
                on_tick(now, w.t0)
            if not closing and now >= w.t0 + seconds:
                w.t1 = now              # ... and closes at one
                w.counters_close = system.counters()
                closing = True
            if closing and seen >= nxt:
                break
        if not w.t1:
            w.t1 = time.perf_counter()
        inside = [x for x in landed if w.t0 < x[0] <= w.t1]
        docs = sum(x[1] for x in inside)
        w.end_to_end["ingest_docs_per_s"] = docs / (w.t1 - w.t0)
        w.attempted = docs
        w.counters["docs_landed"] = docs
        w.counters["commits_landed"] = len(inside)
        w.spans["commit_ms"] = [x[2] for x in inside]
        w.facts["window_s"] = w.t1 - w.t0
        w.facts["commits_in_flight_at_window_end"] = sum(
            1 for x in landed if x[0] > w.t1)
        w.facts["commits_queued"] = nxt
        if nxt >= len(self.commits):
            w.problems.append(
                f"the pool of {len(self.commits)} commits was exhausted")
        w.sample["window_doc_ids"] = [
            d for c, x in enumerate(landed) if w.t0 < x[0] <= w.t1
            for d, _t in self.commits[c]]
        return w


class ClosedLoopPosts:
    """``clients`` closed-loop clients: each sends its next request only
    after the previous reply has been parsed; every request is a NEW
    distinct query. Latency is the client's: POST sent to whole reply
    parsed. A failed or refused request is counted in ``failed`` and stays
    in the percentiles as a miss."""

    def __init__(self, traffic: dict):
        self.traffic = traffic

    def payload(self, query: str) -> dict:
        body = dict(self.traffic["body"])
        body[self.traffic["query_field"]] = query
        return body

    def prepare(self, system: System) -> None:
        t = self.traffic
        dep = system.dep
        n_docs = dep["setup_commits"] * dep["commit_docs"]
        self.queries = system.corpus.queries(
            t["pool_queries"] + t["warm_rounds"] * t["clients"],
            t["query_words"], 0, n_docs)
        self.url = system.url + t["route"]
        # warm the served path at the window's own concurrency
        for r in range(t["warm_rounds"]):
            batch = [self.queries.pop() for _ in range(t["clients"])]
            errors: list = []

            def one(q):
                try:
                    post(self.url, self.payload(q[1]), t["timeout_s"])
                except Exception as exc:  # noqa: BLE001 - reported below
                    errors.append(repr(exc))

            threads = [threading.Thread(target=one, args=(q,)) for q in batch]
            for th in threads:
                th.start()
            for th in threads:
                th.join()
            if errors:
                raise RuntimeError(
                    f"warm-up round {r}: {len(errors)} requests failed: "
                    f"{errors[0]}")
        device_barrier()

    def run(self, system: System, seconds: float, on_tick=None) -> Window:
        """The window runs from one reply to another and holds whole ROUNDS:
        it opens at the first reply of the run (every client is then in
        flight; what came before is set-up) and closes at the first reply
        at or after ``--seconds`` later that completes a multiple of
        ``clients`` replies since it opened. Replies come in bursts (the
        engine answers an epoch's requests together), and a window cut at a
        fixed instant counts whole bursts. In a closed loop reply ``i + clients`` stands where reply
        ``i`` stood one round earlier, so a window of whole rounds holds
        whole periods wherever in a burst it opened. The rate is every
        request answered in it over all of its time."""
        t = self.traffic
        w = Window()
        lock = threading.Lock()
        nxt = [0]
        since = [0]             # replies since the window opened
        stop = threading.Event()
        done: list[tuple] = []  # (index, t_sent, t_done, reply | None, error)
        began = time.perf_counter()
        give_up = began + seconds + 2 * t["timeout_s"]

        def client():
            while not stop.is_set():
                with lock:
                    i = nxt[0]
                    if i >= len(self.queries):
                        return
                    nxt[0] += 1
                sent = time.perf_counter()
                try:
                    reply, err = post(self.url, self.payload(
                        self.queries[i][1]), t["timeout_s"]), None
                except Exception as exc:  # noqa: BLE001 - counted as failed
                    reply, err = None, repr(exc)
                end = time.perf_counter()
                with lock:
                    done.append((i, sent, end, reply, err))
                    if not w.t0:
                        w.t0 = end          # the window opens at a reply
                        w.counters_open = system.counters()
                    elif w.t0 and not w.t1:
                        since[0] += 1
                        if end >= w.t0 + seconds \
                                and since[0] % t["clients"] == 0:
                            w.t1 = end      # ... and closes at one
                            w.counters_close = system.counters()
                            stop.set()
                if end > give_up:
                    stop.set()

        threads = [threading.Thread(target=client, daemon=True)
                   for _ in range(t["clients"])]
        for th in threads:
            th.start()
        while not stop.is_set() and any(th.is_alive() for th in threads):
            time.sleep(0.05)
            if on_tick is not None:
                on_tick(time.perf_counter(), w.t0)
        for th in threads:  # a late reply is late, not lost: wait for it
            th.join(timeout=t["timeout_s"] + 5)
        with lock:
            records = list(done)
        if not w.t1:
            w.t1 = max([r[2] for r in records] + [time.perf_counter()])
            w.problems.append("the window never closed on a reply")
        inside = [r for r in records if w.t0 < r[2] <= w.t1]
        ok = [r for r in inside if r[4] is None]
        bad = [r for r in inside if r[4] is not None]
        length = max(w.t1 - w.t0, 1e-9)
        w.attempted = len(inside)
        w.failed = len(bad)
        w.sample = {"records": ok, "queries": self.queries}
        w.facts["window_s"] = length
        w.facts["ramp_s"] = w.t0 - began
        w.facts["requests_in_flight_at_window_end"] = sum(
            1 for r in records if r[2] > w.t1)
        w.facts["first_errors"] = [r[4] for r in bad[:3]]
        # how the replies came: [seconds after the window opened, replies]
        # of every burst (replies less than 0.25 s apart)
        bursts: list[list[float]] = []
        for r in sorted(records, key=lambda r: r[2]):
            if bursts and r[2] - bursts[-1][2] < 0.25:
                bursts[-1][1] += 1
                bursts[-1][2] = r[2]
            else:
                bursts.append([round(r[2] - w.t0, 2), 1, r[2]])
        w.facts["reply_bursts"] = [b[:2] for b in bursts[:40]]
        # ... and every stretch of a second or more without a reply
        ends = sorted(r[2] for r in records)
        w.facts["reply_gaps"] = [
            [round(a - w.t0, 2), round(b - a, 2)]
            for a, b in zip(ends, ends[1:]) if b - a >= 1.0][:10]
        if nxt[0] >= len(self.queries):
            w.problems.append(
                f"the pool of {len(self.queries)} queries was exhausted")
        w.counters["requests_completed"] = len(ok)
        lat = stats.latencies_with_misses(
            [(r[2] - r[1]) * 1e3 for r in ok], len(bad),
            t["timeout_s"] * 1e3)
        w.end_to_end["requests_per_s"] = len(ok) / length
        if lat:
            w.end_to_end["request_p50_ms"] = stats.percentile(lat, 50)
            w.end_to_end["request_p95_ms"] = stats.percentile(lat, 95)
        else:
            w.problems.append("no request completed inside the window")
        w.span_marks = [(f"request{r[0]}", r[1], r[2]) for r in inside]
        return w


GENERATORS = {
    "commit_feeder": CommitFeeder,
    "closed_loop_posts": ClosedLoopPosts,
}
