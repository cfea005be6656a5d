"""Per-request spans and host regions on the device profiler's clock.

Two instruments, one module. A :func:`region` is for work that stays on
one thread: a nested interval that IS a ``jax.profiler.TraceAnnotation``
named ``pw.<layer>.<what>``, so inside a profiler session (the
benchmark's traced slice, an operator's ``GET /debug/profile?ms=N``) it
lands in the trace's host plane on the same time base as the device's
``XLA Ops``, and outside one it records nothing. A :class:`Span` is for
work that crosses threads.

A :class:`Span` is one request's timeline through a serving loop: a
monotonic start plus timestamped events — ``enqueue`` (implicit, at
construction), ``admit``, ``prefix_match``, ``prefill_chunk``,
``spec_cycle``, ``decode_chunk``, ``first_token``, ``drain`` — attached
by the continuous decoder server (``xpacks/llm/llms.py``), the
``QueryServer`` micro-batcher, the embed pipeline, the engine's outer
pump (kind ``epoch``: first injection for a time → the epoch begins →
its ``on_time_end`` sweep is done) and the REST connector (kind
``rest``: arrival → commit → its epoch begins → resolve → reply
returned). :meth:`Span.finish`
derives the SLO metrics the histograms in ``engine/probes.py`` serve
(queue-wait = admit − enqueue, TTFT = first_token − enqueue, TPOT =
(drain − first_token)/(tokens − 1), e2e = drain − enqueue), feeds them
into the registry with the span's ``kind`` as the ``phase`` label, and
hands the serialized span to three sinks:

* a bounded in-process ring buffer behind :func:`recent_traces`: the
  last ``PATHWAY_TPU_TRACE_RING`` spans OF EACH KIND (oldest evicted),
  so a burst of ``embed`` spans cannot evict the ``epoch`` ones;
* an optional JSONL flight recorder (``PATHWAY_TPU_TRACE_DIR``), one
  line per span, append-only per pid, through a persistent buffered
  handle flushed every :data:`_JSONL_FLUSH_EVERY` spans and drained by
  :func:`flush_traces` on server shutdown (and atexit);
* the OTel exporter in ``internals/telemetry.py`` when a collector
  endpoint is configured (``PATHWAY_MONITORING_SERVER``) — a no-op stub
  otherwise.

``PATHWAY_TPU_METRICS=0`` makes :func:`start_span` return the shared
:data:`NULL_SPAN`, so instrumented hot loops pay one attribute lookup
and nothing else; spans never touch compute, so token streams are
byte-identical either way.
"""

from __future__ import annotations

import atexit
import gc
import heapq
import itertools
import json
import os
import time
from collections import deque

from jax.profiler import TraceAnnotation

from pathway_tpu.analysis.runtime import make_lock
from pathway_tpu.engine import probes

__all__ = [
    "Span", "NULL_SPAN", "start_span", "recent_traces", "reset_traces",
    "flush_traces", "region",
]

# lock-discipline declaration for module globals (enforced by
# `python -m pathway_tpu.analysis check`, rule GL401): the span ring,
# the flight recorder's file-handle state and the lazy telemetry
# singleton may only be touched under their locks.
_GUARDED_BY = {
    "_rings": "_ring_lock",
    "_jsonl_file": "_jsonl_lock",
    "_jsonl_path": "_jsonl_lock",
    "_jsonl_unflushed": "_jsonl_lock",
    "_telemetry": "_telemetry_lock",
}


class _NullSpan:
    """Kill-switch stand-in: every span method is a no-op."""

    __slots__ = ()

    def event(self, name: str, at: float | None = None, **attrs) -> None:
        pass

    def finish(self, **attrs) -> None:
        return None


NULL_SPAN = _NullSpan()

_ids = itertools.count(1)
_ring_lock = make_lock("tracing.ring")
# kind -> deque of (sequence number, span dict): one ring a kind
_rings: dict[str, deque] = {}
_seq = itertools.count()
_jsonl_lock = make_lock("tracing.jsonl")
_telemetry = None
_telemetry_lock = make_lock("tracing.telemetry")


class Span:
    """One request's event timeline. Event methods are thread-safe in
    the way the serving loops need: a single producer thread appends at
    a time (submit thread hands off to the loop thread at admission),
    and :meth:`finish` is idempotent."""

    __slots__ = (
        "kind", "request_id", "server", "attrs", "t0", "wall0",
        "events", "_finished",
    )

    def __init__(self, kind: str, request_id, server: str | None, attrs: dict):
        self.kind = kind
        self.request_id = request_id
        self.server = server
        self.attrs = attrs
        self.t0 = time.perf_counter()
        self.wall0 = time.time()
        self.events: list = [("enqueue", self.t0, None)]
        self._finished = False

    def event(self, name: str, at: float | None = None, **attrs) -> None:
        """Stamp ``name`` now, or at ``at`` (a ``perf_counter`` reading
        another thread took, e.g. the scheduler's of an epoch's start)."""
        self.events.append(
            (name, time.perf_counter() if at is None else at, attrs or None)
        )

    def first_t(self, name: str) -> float | None:
        for n, t, _ in self.events:
            if n == name:
                return t
        return None

    def finish(self, **attrs) -> dict | None:
        """Close the span: derive the SLO metrics, feed the registry
        histograms (phase = span kind) and record the serialized span.
        Idempotent — the failure sweep and the drain path may race to
        close a request; only the first wins."""
        if self._finished:
            return None
        self._finished = True
        if attrs:
            self.attrs = {**self.attrs, **attrs}
        end = self.events[-1][1]
        t_admit = t_first = t_drain = t_migrate = None  # first occurrence
        for n, t, _ in self.events:
            if n == "admit":
                if t_admit is None:
                    t_admit = t
            elif n == "first_token":
                if t_first is None:
                    t_first = t
            elif n == "drain" and t_drain is None:
                t_drain = t
            elif n == "migrate" and t_migrate is None:
                t_migrate = t
        if t_drain is None:
            t_drain = end
        tokens = self.attrs.get("tokens")

        metrics: dict = {"e2e_ms": round((t_drain - self.t0) * 1e3, 3)}
        probes.observe_latency("e2e_seconds", t_drain - self.t0, self.kind)
        if t_admit is not None:
            metrics["queue_wait_ms"] = round((t_admit - self.t0) * 1e3, 3)
            probes.observe_latency(
                "queue_wait_seconds", t_admit - self.t0, self.kind
            )
        if t_admit is not None and t_migrate is not None:
            # disagg lane handoff: prefill residency from admission to the
            # KV migration edge (decode lane takes over from here)
            metrics["prefill_ms"] = round((t_migrate - t_admit) * 1e3, 3)
        if t_first is not None:
            metrics["ttft_ms"] = round((t_first - self.t0) * 1e3, 3)
            probes.observe_latency(
                "ttft_seconds", t_first - self.t0, self.kind
            )
            if isinstance(tokens, int) and tokens > 1:
                tpot = (t_drain - t_first) / (tokens - 1)
                metrics["tpot_ms"] = round(tpot * 1e3, 3)
                probes.observe_latency("tpot_seconds", tpot, self.kind)

        span_dict = {
            "kind": self.kind,
            "id": self.request_id,
            "server": self.server,
            "start_unix": round(self.wall0, 6),
            "attrs": self.attrs,
            "metrics": metrics,
            "events": [
                {"name": n, "t_ms": round((t - self.t0) * 1e3, 3),
                 **(a or {})}
                for n, t, a in self.events
            ],
        }
        _record(span_dict)
        return span_dict


def start_span(kind: str, request_id=None, server: str | None = None,
               **attrs):
    """A live :class:`Span` (enqueue stamped now), or :data:`NULL_SPAN`
    when ``PATHWAY_TPU_METRICS=0``. ``kind`` becomes the histogram
    ``phase`` label (``decode`` / ``query`` / ``embed``); ``server``
    tags the span for :func:`recent_traces` filtering."""
    if not probes.REGISTRY.enabled:
        return NULL_SPAN
    if request_id is None:
        request_id = next(_ids)
    return Span(kind, request_id, server, dict(attrs))


def recent_traces(server: str | None = None, kind: str | None = None,
                  n: int | None = None) -> list[dict]:
    """Most recent completed spans (oldest first), optionally filtered
    by the ``server`` tag and/or span ``kind``, truncated to the last
    ``n``."""
    with _ring_lock:
        if kind is not None:
            rings = [list(_rings.get(kind, ()))]
        else:
            rings = [list(r) for r in _rings.values()]
    spans = [s for _seq, s in heapq.merge(*rings, key=lambda e: e[0])]
    if server is not None:
        spans = [s for s in spans if s.get("server") == server]
    return spans[-n:] if n else spans


def reset_traces() -> None:
    with _ring_lock:
        _rings.clear()


def _record(span_dict: dict) -> None:
    from pathway_tpu.internals.config import pathway_config

    limit = max(1, pathway_config.trace_ring)
    with _ring_lock:
        ring = _rings.get(span_dict["kind"])
        if ring is None:
            ring = _rings[span_dict["kind"]] = deque()
        ring.append((next(_seq), span_dict))
        while len(ring) > limit:
            ring.popleft()
    trace_dir = pathway_config.trace_dir
    if trace_dir:
        _write_jsonl(trace_dir, span_dict)
    _export_otel(span_dict)


# --------------------------------------------------------------------- #
# host regions on the profiler's clock
#
# Placing one: at a layer boundary, never inside a per-row loop; ids are
# small scalars (t, rows, op, queries); an ``async def`` body never holds
# one open across an ``await`` (a TraceMe belongs to its thread), which
# is why REST requests are spans and not regions.


def region(name: str, **ids):
    """``with region("pw.engine.epoch", t=t, rows=n): ...`` — a nested,
    thread-local interval named ``pw.<layer>.<what>`` with its ids as
    stats. The profiler session is the only switch: with none running
    (every production minute) the TraceMe records nothing; inside one the
    region lands in the ``.xplane.pb`` host plane beside the device's
    ops. (The decoder server's ``pw.decode.prefill`` and
    ``pw.decode.chunk`` carry ``passes``: how many times the dispatch runs
    the layer stack for each of its tokens, 1 but for a looped stack.)"""
    return TraceAnnotation(name, **ids)


# full collections hold the interpreter for a quarter of a second and
# more once millions of rows are tracked: a ``pw.gc`` region on whichever
# thread triggered one, so that an idle gap under it has a name. The
# collector runs one collection at a time, so one slot does.
_gc_region = None


def _on_gc(phase: str, info: dict) -> None:
    global _gc_region
    if info["generation"] != 2:
        return
    if phase == "start":
        _gc_region = region("pw.gc")
        _gc_region.__enter__()
    elif _gc_region is not None:
        _gc_region.__exit__(None, None, None)
        _gc_region = None


gc.callbacks.append(_on_gc)


# flight-recorder file state: ONE persistent buffered append handle per
# process (re-opened if PATHWAY_TPU_TRACE_DIR changes, e.g. across
# tests) instead of an open/close per span. Buffered writes are flushed
# every _JSONL_FLUSH_EVERY spans — bounding what an abrupt kill can
# drop — and drained completely by flush_traces() on server shutdown.
_JSONL_FLUSH_EVERY = 32
_jsonl_file = None
_jsonl_path: str | None = None
_jsonl_unflushed = 0


def _write_jsonl(trace_dir: str, span_dict: dict) -> None:
    global _jsonl_file, _jsonl_path, _jsonl_unflushed
    try:
        line = json.dumps(span_dict, default=str)
        path = os.path.join(trace_dir, f"trace-{os.getpid()}.jsonl")
        with _jsonl_lock:
            if _jsonl_file is None or _jsonl_path != path:
                if _jsonl_file is not None:
                    try:
                        _jsonl_file.close()
                    except Exception:  # noqa: BLE001
                        pass
                os.makedirs(trace_dir, exist_ok=True)
                _jsonl_file = open(path, "a", encoding="utf-8")
                _jsonl_path = path
                _jsonl_unflushed = 0
            _jsonl_file.write(line + "\n")
            _jsonl_unflushed += 1
            if _jsonl_unflushed >= _JSONL_FLUSH_EVERY:
                _jsonl_file.flush()
                _jsonl_unflushed = 0
    except Exception:  # noqa: BLE001 - the recorder must never break serving
        pass


def flush_traces(close: bool = True) -> None:
    """Drain the flight recorder's buffered JSONL lines to disk; with
    ``close`` (the default) also release the file handle so a finished
    server leaves nothing open. Safe to call any number of times, from
    any thread, recorder configured or not — server shutdown paths
    (``_ContinuousServer.shutdown``, ``GraphRunner.run`` teardown,
    ``BaseRestServer.run``) and ``atexit`` all call it."""
    global _jsonl_file, _jsonl_path, _jsonl_unflushed
    with _jsonl_lock:
        f = _jsonl_file
        if f is None:
            return
        try:
            f.flush()
        except Exception:  # noqa: BLE001 - never break shutdown
            pass
        _jsonl_unflushed = 0
        if close:
            try:
                f.close()
            except Exception:  # noqa: BLE001
                pass
            _jsonl_file = None
            _jsonl_path = None


atexit.register(flush_traces)


def _get_telemetry():
    """Lazy per-endpoint ``Telemetry``; rebuilt if the configured
    collector endpoint changes. None when no endpoint is set."""
    global _telemetry
    from pathway_tpu.internals.config import pathway_config

    endpoint = pathway_config.monitoring_server
    if not endpoint:
        return None
    with _telemetry_lock:
        if _telemetry is None or _telemetry.endpoint != endpoint:
            from pathway_tpu.internals.telemetry import Telemetry

            _telemetry = Telemetry(endpoint)
        return _telemetry


def _export_otel(span_dict: dict) -> None:
    tel = _get_telemetry()
    if tel is None or not tel.enabled:
        return
    try:
        attributes = {
            "pathway_tpu.request_id": str(span_dict["id"]),
            "pathway_tpu.server": str(span_dict.get("server")),
            **{f"pathway_tpu.{k}": v
               for k, v in span_dict["metrics"].items()},
        }
        with tel.span(f"pathway_tpu.{span_dict['kind']}", attributes):
            for e in span_dict["events"]:
                tel.event(e["name"], {"t_ms": e["t_ms"]})
    except Exception:  # noqa: BLE001 - export must never break serving
        pass
