"""Micro-batched query serving over a :class:`FusedRAGPipeline`.

Per-query dispatch wastes the device when queries arrive concurrently:
``_fused_retrieve`` / ``_fused_retrieve_rerank_batch`` already take
``(Qb, S)`` query batches, so N requests landing in the same short window
can share ONE dispatch instead of paying N round trips. The
:class:`QueryServer` mirrors the continuous decode server in
``xpacks/llm/llms.py`` (lock + deque + wake event + daemon loop with a
failure sweep) and the ingest ``StageWorker`` contract in
``engine/async_runtime.py`` (bounded admission, blocking backpressure):

* ``submit`` enqueues a retrieve or retrieve-rerank request and returns a
  handle; ``queue_bound`` admission blocks when the server is saturated.
* the loop coalesces everything that arrived within one ``tick_ms``
  window (or up to ``max_batch``, whichever first) and issues one batched
  device dispatch per ``(kind, k)`` group — homogeneous load is exactly
  one dispatch per tick.
* results resolve back per request; ``stats()`` reports ticks, the
  batch-size histogram and the coalescing rate.

The server is opt-in: code that never constructs one keeps today's
per-call query path byte-for-byte.
"""

from __future__ import annotations

import threading
import time
from collections import deque

from pathway_tpu.analysis.annotations import guarded_by
from pathway_tpu.analysis.runtime import make_lock
from pathway_tpu.internals.config import pathway_config


class QueryRequest:
    """One in-flight query. ``done`` fires once ``result`` / ``error`` is
    set; ``submitted_at`` (``time.monotonic()``) opens the tick's window,
    and the request's span carries its latency."""

    __slots__ = (
        "kind", "text", "k", "done", "result", "error",
        "submitted_at", "span",
    )

    def __init__(self, kind: str, text: str, k: int):
        from pathway_tpu.engine import tracing

        self.kind = kind                # "retrieve" | "rerank"
        self.text = text
        self.k = k
        self.done = threading.Event()
        self.result = None
        self.error: BaseException | None = None
        self.submitted_at = time.monotonic()
        self.span = tracing.NULL_SPAN  # replaced by QueryServer.submit

    def wait(self, timeout: float | None = None):
        if not self.done.wait(timeout):
            raise TimeoutError("query did not complete in time")
        if self.error is not None:
            raise self.error
        return self.result


@guarded_by(
    _queue="_cond", _stop="_cond", failed="_cond",
    _ticks="_stats_lock", _dispatches="_stats_lock",
    _requests="_stats_lock", _batch_hist="_stats_lock",
    _restarts="_stats_lock", _group_failures="_stats_lock",
    _leaked_thread="_stats_lock",
)
class QueryServer:
    """Coalesces concurrent retrieve / retrieve-rerank requests into
    batched fused dispatches (one per ``(kind, k)`` group per tick)."""

    def __init__(self, pipeline, *, tick_ms: float | None = None,
                 max_batch: int | None = None,
                 queue_bound: int | None = None):
        cfg = pathway_config
        self._pipe = pipeline
        self.tick_s = (cfg.query_tick_ms if tick_ms is None else tick_ms) / 1e3
        self.max_batch = max_batch or cfg.query_max_batch
        self.queue_bound = queue_bound or cfg.query_queue
        self._cond = threading.Condition(make_lock("query_server.cond"))
        self._queue: deque[QueryRequest] = deque()
        self._stop = False
        self.failed: BaseException | None = None
        self._stats_lock = make_lock("query_server.stats")
        self._ticks = 0
        self._dispatches = 0
        self._requests = 0
        self._batch_hist: dict[int, int] = {}
        self._restarts = 0
        self._group_failures = 0
        self._leaked_thread = 0
        # fault-tolerance knobs, read once (kill switches): budget == 0
        # keeps the historical latch-on-first-error behavior exactly
        from pathway_tpu.engine import chaos

        self._restart_budget = int(cfg.serve_restarts)
        self._supervised = self._restart_budget > 0
        self._restarts_left = self._restart_budget
        self._chaos_tick = chaos.site("query.tick")
        # tags this server's request spans in the global trace ring
        self._trace_tag = f"query:{id(self):x}"
        self._thread = threading.Thread(
            target=self._loop, name="query-server", daemon=True
        )
        self._thread.start()

    def recent_traces(self, n: int | None = None) -> list[dict]:
        """Completed per-request spans of THIS server (oldest first),
        from the bounded global trace ring (``PATHWAY_TPU_TRACE_RING``).
        Empty under ``PATHWAY_TPU_METRICS=0``."""
        from pathway_tpu.engine import tracing

        return tracing.recent_traces(server=self._trace_tag, n=n)

    # ------------------------------------------------------------ submit
    def submit(self, text: str, k: int, *, rerank: bool = False) -> QueryRequest:
        """Enqueue a query; blocks (backpressure) while ``queue_bound``
        requests already wait. Returns a handle to ``wait()`` on."""
        kind = "rerank" if rerank else "retrieve"
        if rerank and self._pipe.reranker is None:
            raise ValueError("pipeline has no reranker")
        from pathway_tpu.engine import tracing

        req = QueryRequest(kind, text, k)
        req.span = tracing.start_span(
            "query", server=self._trace_tag, query_kind=kind, k=k,
        )
        with self._cond:
            while (
                len(self._queue) >= self.queue_bound
                and not self._stop and self.failed is None
            ):
                self._cond.wait(timeout=0.1)
            if self.failed is not None:
                raise RuntimeError("query server failed") from self.failed
            if self._stop:
                raise RuntimeError("query server is shut down")
            self._queue.append(req)
            self._cond.notify_all()
        return req

    def query(self, text: str, k: int, *, rerank: bool = False,
              timeout: float | None = 60.0):
        """Synchronous convenience: submit + wait."""
        return self.submit(text, k, rerank=rerank).wait(timeout)

    # -------------------------------------------------------------- loop
    def _drain_tick(self) -> list[QueryRequest]:
        """Block until work exists, then hold the tick window open so
        concurrent arrivals coalesce; returns up to ``max_batch``."""
        with self._cond:
            while not self._queue and not self._stop:
                self._cond.wait()
            if self._stop and not self._queue:
                return []
            deadline = self._queue[0].submitted_at + self.tick_s
            while (
                len(self._queue) < self.max_batch and not self._stop
            ):
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    break
                self._cond.wait(timeout=remaining)
            batch = [
                self._queue.popleft()
                for _ in range(min(len(self._queue), self.max_batch))
            ]
            self._cond.notify_all()  # unblock backpressured submitters
            return batch

    def _loop(self) -> None:
        while True:
            batch = self._drain_tick()
            if not batch:
                with self._cond:
                    stopping = self._stop
                if stopping:
                    return
                continue
            try:
                self._serve(batch)
            except BaseException as exc:  # noqa: BLE001 - sweep to callers
                for req in batch:
                    req.error = exc
                    req.span.finish(error=True)
                    req.done.set()
                if self._supervised and self._restarts_left > 0:
                    # supervised restart: the crashed tick's batch failed
                    # above, but queued/future requests keep being served
                    # until the budget runs out — then latch as before
                    self._restarts_left -= 1
                    from pathway_tpu.engine import probes
                    from pathway_tpu.internals.errors import (
                        get_global_error_log,
                    )

                    get_global_error_log().log(
                        f"query server tick crashed "
                        f"({type(exc).__name__}: {exc}); supervised restart"
                    )
                    probes.REGISTRY.counter_add(
                        "serve_restarts", server=self._trace_tag
                    )
                    with self._stats_lock:
                        self._restarts += 1
                    continue
                with self._cond:
                    self.failed = exc
                    self._stop = True
                    pending = list(self._queue)
                    self._queue.clear()
                    self._cond.notify_all()
                for req in pending:
                    req.error = exc
                    req.span.finish(error=True)
                    req.done.set()
                return

    def _serve(self, batch: list[QueryRequest]) -> None:
        # one batched dispatch per (kind, k) group — requests for the same
        # k share candidates semantics with the per-call path, so batching
        # never changes a request's result
        groups: dict[tuple[str, int], list[QueryRequest]] = {}
        for req in batch:
            req.span.event("admit", batch=len(batch))
            groups.setdefault((req.kind, req.k), []).append(req)
        failed_groups = 0
        for (kind, k), reqs in groups.items():
            try:
                if self._chaos_tick is not None:
                    self._chaos_tick.maybe_fail()
                texts = [r.text for r in reqs]
                if kind == "rerank":
                    results = self._pipe.retrieve_rerank_batch(texts, k)
                else:
                    results = self._pipe.retrieve(texts, k)
            except BaseException as exc:  # noqa: BLE001 - group isolation
                if not self._supervised:
                    raise
                # group-scoped isolation: only THIS (kind, k) group's
                # requests fail; sibling groups in the same tick — and
                # everything queued — keep serving
                from pathway_tpu.engine import probes

                for req in reqs:
                    req.error = exc
                    req.span.finish(error=True)
                    req.done.set()
                probes.REGISTRY.counter_add(
                    "requests_isolated", float(len(reqs)),
                    outcome="failed",
                )
                failed_groups += 1
                continue
            for req, res in zip(reqs, results):
                req.result = res
                req.span.event("drain", group=len(reqs))
                req.span.finish()
                req.done.set()
        with self._stats_lock:
            self._ticks += 1
            self._dispatches += len(groups)
            self._requests += len(batch)
            self._group_failures += failed_groups
            n = len(batch)
            self._batch_hist[n] = self._batch_hist.get(n, 0) + 1

    # ------------------------------------------------------------- stats
    def stats(self) -> dict:
        with self._cond:
            failed = self.failed is not None
        with self._stats_lock:
            ticks = self._ticks
            reqs = self._requests
            return {
                "ticks": ticks,
                "requests": reqs,
                "dispatches": self._dispatches,
                "batch_hist": dict(sorted(self._batch_hist.items())),
                "mean_batch": round(reqs / ticks, 3) if ticks else 0.0,
                "failed": failed,
                "restarts": self._restarts,
                "group_failures": self._group_failures,
                "leaked_thread": self._leaked_thread,
            }

    def shutdown(self, timeout: float = 10.0) -> None:
        with self._cond:
            self._stop = True
            self._cond.notify_all()
        self._thread.join(timeout)
        if self._thread.is_alive():
            from pathway_tpu.internals.errors import get_global_error_log

            with self._stats_lock:
                self._leaked_thread += 1
            get_global_error_log().log(
                f"query server thread still alive {timeout}s after "
                f"shutdown join"
            )
        with self._cond:
            pending = list(self._queue)
            self._queue.clear()
        for req in pending:
            if not req.done.is_set():
                req.error = RuntimeError("query server shut down")
                req.span.finish(error=True)
                req.done.set()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.shutdown()
        return False
