"""HTTP connectors: REST request/response inside the dataflow + streaming
HTTP reader.

Reference parity: ``python/pathway/io/http`` — ``PathwayWebserver``
(aiohttp, ``_server.py:329``), ``rest_connector`` (``_server.py:624``): each
HTTP request becomes a row of the query table; the caller wires a response
table back, and the pending request resolves when the row's answer arrives
(as-of-now join through the dataflow).
"""

from __future__ import annotations

import asyncio
import json
import threading
import time
import uuid
from typing import Any

from pathway_tpu.engine import tracing
from pathway_tpu.engine.batch import Batch
from pathway_tpu.engine.operators.core import InputNode
from pathway_tpu.engine.operators.output import SubscribeNode
from pathway_tpu.engine.value import Pointer, hash_values
from pathway_tpu.internals import dtype as dt
from pathway_tpu.io.python import ConnectorSubject as _PyConnectorSubject
from pathway_tpu.internals import schema as schema_mod
from pathway_tpu.internals.json import Json, unwrap_json
from pathway_tpu.internals.parse_graph import G
from pathway_tpu.internals.table import Table
from pathway_tpu.internals.universe import Universe
from pathway_tpu.io._streams import BaseConnector, next_commit_time
from pathway_tpu.io._utils import (
    format_value_for_output,
    parse_record_fields,
    parse_stream_record,
    parse_value,
)


class EndpointExamples:
    """Named request examples for endpoint documentation (reference
    ``io/http/_server.py:89``)."""

    def __init__(self):
        self.examples_by_id: dict = {}

    def add_example(self, id, summary, values):  # noqa: A002
        if id in self.examples_by_id:
            raise ValueError(f"duplicate example id {id!r}")
        self.examples_by_id[id] = {"summary": summary, "value": values}
        return None


class EndpointDocumentation:
    """OpenAPI-style endpoint docs (reference ``EndpointDocumentation:126``)."""

    def __init__(self, summary: str = "", description: str = "", tags=(), method_types=("POST",)):
        self.summary = summary
        self.description = description
        self.tags = list(tags)
        self.method_types = list(method_types)


class RestApiError(Exception):
    """A structured HTTP failure a handler wants returned verbatim:
    ``status`` + JSON ``payload`` (+ optional ``Retry-After``), instead of
    the generic 500 wrapper. Raised by ``_RestConnector._handle`` when the
    resolved result carries the ``_pw_http_error`` envelope that the
    serving layers use to ship typed failures through the dataflow."""

    def __init__(self, status: int, payload: dict,
                 retry_after: float | None = None):
        super().__init__(payload.get("error", "request failed"))
        self.status = int(status)
        self.payload = payload
        self.retry_after = retry_after


class PathwayWebserver:
    """Shared aiohttp server hosting one or more rest_connector routes."""

    def __init__(self, host: str, port: int, with_cors: bool = False, with_schema_endpoint: bool = True):
        self.host = host
        self.port = port
        self._routes: dict[tuple[str, str], Any] = {}
        self._loop: asyncio.AbstractEventLoop | None = None
        self._thread: threading.Thread | None = None
        self._started = threading.Event()
        self._runner = None

    def _register(self, route: str, methods: list[str], handler) -> None:
        for m in methods:
            self._routes[(m.upper(), route)] = handler

    def start(self) -> None:
        if self._thread is not None:
            return
        self._thread = threading.Thread(target=self._serve, daemon=True)
        self._thread.start()
        self._started.wait(timeout=10)

    def _serve(self):
        from aiohttp import web

        async def dispatch(request: "web.Request"):
            handler = self._routes.get((request.method, request.path))
            if handler is None:
                return web.json_response({"error": "no such endpoint"}, status=404)
            try:
                if request.method in ("POST", "PUT", "PATCH"):
                    try:
                        payload = await request.json()
                    except json.JSONDecodeError:
                        payload = {}
                else:
                    payload = dict(request.query)
                result = await handler(payload)
                # handlers carrying _raw_content_type return preformatted
                # text (e.g. the /metrics OpenMetrics exposition) instead
                # of a JSON document
                raw_ct = getattr(handler, "_raw_content_type", None)
                if raw_ct is not None:
                    return web.Response(text=result, content_type=raw_ct)
                return web.json_response(result)
            except RestApiError as exc:
                headers = {}
                if exc.retry_after is not None:
                    headers["Retry-After"] = str(
                        max(1, int(round(exc.retry_after)))
                    )
                return web.json_response(
                    exc.payload, status=exc.status, headers=headers
                )
            except Exception as exc:  # noqa: BLE001
                return web.json_response({"error": str(exc)}, status=500)

        async def main():
            app = web.Application()
            app.router.add_route("*", "/{tail:.*}", dispatch)
            runner = web.AppRunner(app)
            await runner.setup()
            site = web.TCPSite(runner, self.host, self.port)
            await site.start()
            self._runner = runner
            if self.port == 0 and runner.addresses:
                # ephemeral port requested: record what the OS picked so
                # callers (and tests) can reach the server
                self.port = runner.addresses[0][1]
            self._started.set()
            while True:
                await asyncio.sleep(3600)

        self._loop = asyncio.new_event_loop()
        asyncio.set_event_loop(self._loop)
        try:
            self._loop.run_until_complete(main())
        except Exception:
            self._started.set()


class _RestConnector(BaseConnector):
    heartbeat_ms = 500

    def __init__(self, node, schema, webserver: PathwayWebserver, route: str, methods, delete_completed_queries: bool):
        super().__init__(node)
        self.schema = schema
        self.webserver = webserver
        self.route = route
        self.methods = methods
        self.delete_completed = delete_completed_queries
        self._pending: dict[int, asyncio.Future] = {}
        self._pending_lock = threading.Lock()

    def _emit_commit(self, rows, request_id) -> int:
        """A request (or the retraction of a completed one) joins the time
        this connector opened last while the pump has not taken it: what
        arrives while the engine is busy rides ONE epoch, and a lone
        request at an idle engine opens a time that is taken at once. No
        timer and no window: the batch is whatever the engine kept
        waiting."""
        if self._snapshot_writer is not None:
            # a snapshot advances once a commit time
            return super()._emit_commit(rows, request_id)
        fresh = next_commit_time()
        t = self._sched.inject_open(
            self.node, fresh,
            Batch.from_rows(self.node.column_names, rows), request_id)
        if t == fresh:
            # a joined time lies below this frontier already (the opening
            # commit and every heartbeat since advanced past it)
            self.advance(t + 1)
        return t

    async def _handle(self, payload: dict):
        cols = list(self.node.column_names)
        dtypes = {n: c.dtype for n, c in self.schema.__columns__.items()}
        values = parse_record_fields(payload, cols, dtypes, self.schema)
        key = hash_values(str(uuid.uuid4()))
        # a request crosses threads (this loop, the engine, back), so it
        # is a span and not a region; every event is stamped from here
        span = tracing.start_span("rest", request_id=key, server=self.route)
        loop = asyncio.get_event_loop()
        fut: asyncio.Future = loop.create_future()
        with self._pending_lock:
            self._pending[key] = (fut, loop)
        row = tuple(values[c] for c in cols)
        t = self.commit_rows([(key, row, 1)], request_id=key)
        t_commit = time.perf_counter()
        span.event("commit", at=t_commit, t=t)
        t_resolved = None
        try:
            result, t_resolved = await fut
            if self.delete_completed:
                self.commit_rows([(key, row, -1)])
        finally:
            # `admit`: the request's own epoch began (never before the
            # commit it carries). The reply may come from a later epoch
            # and from the subscriber's formatter thread.
            sched = self._sched
            t_admit = sched.admitted_at(key) if sched is not None else None
            if t_admit is not None:
                span.event("admit", at=max(t_admit, t_commit))
            if t_resolved is not None:
                span.event("resolve", at=t_resolved)
            span.event("drain")
            span.finish()
        if isinstance(result, dict) and "_pw_http_error" in result:
            # typed failure envelope from the serving layers (see
            # xpacks/llm/servers.map_serving_errors): surface it as the
            # HTTP status it names instead of a 200 with an error body
            err = result["_pw_http_error"]
            raise RestApiError(
                int(err.get("status", 500)),
                {"error": err.get("error", "request failed"),
                 "reason": err.get("reason", "error")},
                retry_after=err.get("retry_after"),
            )
        return result

    def resolve(self, key: int, result: Any) -> None:
        with self._pending_lock:
            entry = self._pending.pop(key, None)
        if entry is None:
            return
        fut, loop = entry
        reply = (result, time.perf_counter())
        loop.call_soon_threadsafe(
            lambda: fut.set_result(reply) if not fut.done() else None
        )

    def run(self):
        self.webserver._register(self.route, self.methods, self._handle)
        self.webserver.start()
        # stay alive until stopped; frontier stays open (live service)
        self._stop.wait()


class RestServerResponseWriter:
    def __init__(self, connector: _RestConnector):
        self._connector = connector

    def __call__(self, response_table: Table) -> None:
        conn = self._connector
        cols = list(response_table.column_names())

        def on_change(key, row, time, is_addition):
            if not is_addition:
                return
            if "result" in row:
                result = format_value_for_output(row["result"])
            else:
                result = {
                    c: format_value_for_output(v) for c, v in row.items()
                }
            conn.resolve(key.value, unwrap_json(result))

        node = SubscribeNode(
            G.engine_graph,
            response_table._node,
            on_change=lambda key, row, time, is_addition: on_change(
                key, row, time, is_addition
            ),
            skip_errors=False,
        )
        G.register_sink(node)


def rest_connector(
    host: str | None = None,
    port: int | None = None,
    *,
    webserver: PathwayWebserver | None = None,
    route: str = "/",
    schema: Any | None = None,
    methods: tuple = ("POST",),
    autocommit_duration_ms: int | None = 1500,
    keep_queries: bool | None = None,
    delete_completed_queries: bool = True,
    request_validator=None,
    documentation: EndpointDocumentation | None = None,
) -> tuple[Table, RestServerResponseWriter]:
    """Expose an HTTP endpoint as a (query_table, response_writer) pair."""
    if webserver is None:
        webserver = PathwayWebserver(host or "0.0.0.0", 8080 if port is None else port)  # noqa: S104
    if schema is None:
        schema = schema_mod.schema_from_types(query=str)
    cols = list(schema.column_names())
    node = InputNode(G.engine_graph, cols, name=f"rest({route})")
    conn = _RestConnector(
        node, schema, webserver, route, list(methods), delete_completed_queries
    )
    G.register_connector(conn)
    table = Table(node, schema, Universe())
    return table, RestServerResponseWriter(conn)


class RetryPolicy:
    """Exponential-backoff retry schedule (reference ``io/http`` RetryPolicy)."""

    def __init__(self, first_delay_ms: int = 1000, backoff_factor: float = 2.0,
                 jitter_ms: int = 0):
        self.first_delay_ms = first_delay_ms
        self.backoff_factor = backoff_factor
        self.jitter_ms = jitter_ms

    @classmethod
    def default(cls) -> "RetryPolicy":
        return cls()

    def delays_s(self, n_retries: int):
        delay = self.first_delay_ms
        for _ in range(n_retries):
            yield delay / 1000.0
            delay = delay * self.backoff_factor + self.jitter_ms


def _urllib_sender(method: str, headers: dict, connect_timeout_ms: int | None,
                   request_timeout_ms: int | None):
    import urllib.request

    timeout = (request_timeout_ms or connect_timeout_ms or 30000) / 1000.0

    def send(url: str, payload: bytes) -> int:
        req = urllib.request.Request(url, data=payload, method=method,
                                     headers=headers)
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status

    return send


class _HttpStreamConnector(BaseConnector):
    """Streaming HTTP reader: consumes a line-delimited (jsonlines / SSE
    ``data:`` lines / plaintext / raw) response body as a live stream
    (reference ``io/http`` streaming reader). Tracks the BYTE offset of
    consumed lines: a reconnect (EOF in streaming mode) skips what was
    already ingested, so servers that re-serve the full body never
    double-count, and persistence replay seeks the same way."""

    heartbeat_ms = 500

    def __init__(self, node, url: str, schema, fmt: str, headers: dict,
                 opener, mode: str, reconnect_delay_s: float = 1.0,
                 resume_with_offset: bool | None = None, sse: bool = False):
        super().__init__(node)
        self.url = url
        self.schema = schema
        self.fmt = fmt
        self.headers = headers
        self.opener = opener
        self.mode = mode
        self.reconnect_delay_s = reconnect_delay_s
        # growing-log/finite bodies re-serve consumed bytes on reconnect:
        # skip them (no double counting). Live-tail endpoints (SSE, chunked
        # push streams) send only NEW data per connection: skipping there
        # silently swallows fresh records. None = decide per connection from
        # the response: resume only for bodies with a known finite length.
        self.resume_with_offset = resume_with_offset
        self.sse = sse  # strip SSE 'data:' framing only when asked:
        # unconditional stripping would corrupt payloads that legitimately
        # start with 'data:'
        self._counter = 0
        self._byte_offset = 0

    # persistence: (consumed byte offset, row counter)
    def current_offset(self):
        return (self._byte_offset, self._counter)

    def seek_offset(self, offset) -> None:
        if isinstance(offset, (tuple, list)) and len(offset) == 2:
            self._byte_offset, self._counter = int(offset[0]), int(offset[1])

    def _row_of(self, line: bytes, cols, dtypes, pk):
        payload = line.rstrip(b"\r\n")
        if self.sse:
            if payload.startswith(b"data:"):
                payload = payload[len(b"data:"):].strip()
            elif self.fmt != "raw":
                payload = payload.strip()
        if not payload.strip():
            return None
        if self.fmt == "plaintext":
            values = {"data": payload.decode("utf-8", errors="replace").strip()}
        else:
            # raw/json share THE stream-record parse with the kafka reader
            values = parse_stream_record(
                payload if self.fmt == "raw" else payload.strip(),
                self.fmt, self.schema, cols, dtypes,
            )
            if values is None:
                from pathway_tpu.internals.errors import (
                    get_global_error_log,
                )

                get_global_error_log().log(
                    f"http read: skipping undecodable line from {self.url}"
                )
                return None
        if pk:
            key = hash_values(*[values[c] for c in pk])
        else:
            key = hash_values(self.url, self._counter)
            self._counter += 1
        return (key, tuple(values[c] for c in cols), 1)

    def _should_resume(self, resp) -> bool:
        """Skip already-consumed bytes on this connection? Explicit setting
        wins; in auto mode resume only when the body is finite/re-served —
        a Content-Length header, or a plain file-like with no HTTP headers
        at all (injected readers, file URLs). A header-bearing response
        WITHOUT Content-Length is a chunked live tail: each connection
        carries only new data, so skipping would drop records."""
        if self.resume_with_offset is not None:
            return self.resume_with_offset
        if self.sse:
            return False
        headers = getattr(resp, "headers", None)
        if headers is None:
            getheader = getattr(resp, "getheader", None)
            if getheader is None:
                return True  # bare file-like: the body is the whole log
            return getheader("Content-Length") is not None
        return headers.get("Content-Length") is not None

    def _skip_consumed(self, resp) -> bool:
        """Skip bytes already ingested in a previous connection; False when
        the body is shorter than the recorded offset (nothing new)."""
        remaining = self._byte_offset
        while remaining > 0:
            chunk = resp.read(min(remaining, 65536))
            if not chunk:
                return False
            remaining -= len(chunk)
        return True

    def run(self):
        import time as time_mod

        cols = list(self.node.column_names)
        dtypes = {n: c.dtype for n, c in self.schema.__columns__.items()}
        pk = self.schema.primary_key_columns()
        while not self.should_stop():
            try:
                resp = self.opener(self.url, self.headers)
            except Exception as exc:  # noqa: BLE001
                from pathway_tpu.internals.errors import get_global_error_log

                get_global_error_log().log(f"http read connect failed: {exc!r}")
                if self.mode == "static":
                    return
                time_mod.sleep(self.reconnect_delay_s)
                continue
            try:
                try:
                    skipped_ok = (
                        not self._should_resume(resp)
                        or self._skip_consumed(resp)
                    )
                except Exception as exc:  # noqa: BLE001 - blip mid-skip
                    from pathway_tpu.internals.errors import (
                        get_global_error_log,
                    )

                    get_global_error_log().log(
                        f"http read disconnected while resuming: {exc!r}"
                    )
                    skipped_ok = False
                if not skipped_ok:
                    # log rotated/truncated below the stored offset: nothing
                    # new — back off instead of hammering the server
                    if self.mode == "static":
                        return
                    time_mod.sleep(self.reconnect_delay_s)
                    continue
                pending: list = []
                while not self.should_stop():
                    try:
                        line = resp.readline()
                    except Exception as exc:  # noqa: BLE001 - network blip
                        from pathway_tpu.internals.errors import (
                            get_global_error_log,
                        )

                        get_global_error_log().log(
                            f"http read disconnected: {exc!r}"
                        )
                        break  # reconnect (streaming) / finish (static)
                    if not line:
                        break  # EOF
                    if self.mode != "static" and not line.endswith(b"\n"):
                        # partial final line (connection cut mid-record):
                        # do NOT consume it — the reconnect re-reads the
                        # whole record instead of splitting it in half
                        break
                    self._byte_offset += len(line)
                    row = self._row_of(line, cols, dtypes, pk)
                    if row is not None:
                        pending.append(row)
                    if self.mode != "static" and pending:
                        # live stream: each arrived line commits promptly
                        self.commit_rows(pending)
                        pending = []
                if pending:  # static bulk body: ONE commit for all lines
                    self.commit_rows(pending)
            finally:
                close = getattr(resp, "close", None)
                if close is not None:
                    close()
            if self.mode == "static":
                return
            time_mod.sleep(self.reconnect_delay_s)


def _default_opener(url: str, headers: dict, timeout_s: float | None = None):
    import urllib.request

    req = urllib.request.Request(url, headers=headers or {})
    return urllib.request.urlopen(req, timeout=timeout_s)  # noqa: S310


def read(
    url: str,
    *,
    schema=None,
    format: str = "raw",  # noqa: A002 — reference keyword
    mode: str = "streaming",
    headers: dict | None = None,
    persistent_id: str | None = None,
    connect_timeout_ms: int | None = None,
    resume_with_offset: bool | None = None,
    sse: bool = False,
    _opener=None,
    **kwargs,
) -> Table:
    """Stream a line-delimited HTTP response (jsonlines, SSE ``data:``
    lines, plaintext, or raw bytes) into a table; reconnects on EOF in
    streaming mode. ``resume_with_offset`` controls whether a reconnect
    skips already-consumed bytes: leave it ``None`` (default) to decide per
    connection — finite/re-served bodies (Content-Length) resume, live-tail
    endpoints (SSE, chunked push streams, which send only NEW data per
    connection) do not, so fresh records are never swallowed as "already
    ingested". Pass an explicit bool to override both ways.
    ``connect_timeout_ms`` is a blanket socket
    timeout — it also bounds idle gaps BETWEEN streamed lines, so leave it
    unset for quiet live streams. ``_opener(url, headers) -> file-like``
    is injectable for offline tests."""
    if format not in ("raw", "plaintext", "json"):
        raise ValueError(
            f"unsupported HTTP read format {format!r}: raw/plaintext/json"
        )
    if format in ("raw", "plaintext") and schema is not None:
        raise ValueError(
            f"schema is ignored by format={format!r}; pass format='json' "
            "to parse records into schema columns"
        )
    if format == "raw":
        schema = schema_mod.schema_from_types(data=bytes)
    elif format == "plaintext":
        schema = schema_mod.schema_from_types(data=str)
    elif schema is None:
        raise ValueError("schema is required for json-format HTTP reads")
    cols = list(schema.column_names())
    node = InputNode(G.engine_graph, cols, name=f"http({url})")
    if _opener is None:
        timeout_s = (
            connect_timeout_ms / 1000.0 if connect_timeout_ms else None
        )

        def opener(u, h):
            return _default_opener(u, h, timeout_s)

    else:
        opener = _opener
    conn = _HttpStreamConnector(
        node, url, schema, format, headers or {}, opener, mode,
        resume_with_offset=resume_with_offset, sse=sse,
    )
    G.register_connector(conn)
    table = Table(node, schema, Universe())
    if persistent_id is not None:
        from pathway_tpu.persistence import register_persistent_source

        register_persistent_source(persistent_id, conn)
    return table


def write(
    table: Table,
    url: str,
    *,
    method: str = "POST",
    format: str = "json",  # noqa: A002 — reference keyword
    n_retries: int = 0,
    retry_policy: RetryPolicy | None = None,
    connect_timeout_ms: int | None = None,
    request_timeout_ms: int | None = None,
    headers: dict | None = None,
    _sender=None,
) -> None:
    """POST each change of ``table`` to ``url`` as JSON (row fields plus
    ``time``/``diff``), with retry/backoff — reference ``pw.io.http.write``.
    ``_sender(url, payload) -> status`` is injectable for offline tests."""
    from pathway_tpu.engine.operators.output import SinkNode

    if format != "json":
        raise ValueError("pw.io.http.write supports format='json'")
    policy = retry_policy or RetryPolicy.default()
    hdrs = {"Content-Type": "application/json", **(headers or {})}
    sender = _sender or _urllib_sender(
        method, hdrs, connect_timeout_ms, request_timeout_ms
    )
    cols = table.column_names()

    class _QueuedHttpWriter:
        """Sends on a dedicated thread so retry backoff never stalls the
        scheduler epoch loop (the reference runs writers on output joiner
        threads, dataflow.rs:3579-3617). The first send failure (after
        retries) is re-raised into the dataflow on the next batch or at
        end-of-run flush."""

        def __init__(self):
            import queue

            self._queue: queue.Queue = queue.Queue(maxsize=1024)
            self._error: Exception | None = None
            self._thread = threading.Thread(
                target=self._loop, name=f"pathway-tpu:http-sink", daemon=True
            )
            self._thread.start()

        def _loop(self):
            while True:
                body = self._queue.get()
                if body is None:
                    return
                delays = policy.delays_s(n_retries)
                while True:
                    try:
                        sender(url, body)
                        break
                    except Exception as exc:
                        delay = next(delays, None)
                        if delay is None:
                            if self._error is None:
                                self._error = exc
                            break
                        import time as time_mod

                        time_mod.sleep(delay)

        def _check(self):
            if self._error is not None:
                exc, self._error = self._error, None
                raise exc

        def __call__(self, time, batch):
            self._check()
            for _key, row, diff in batch.rows():
                payload = {
                    c: format_value_for_output(v) for c, v in zip(cols, row)
                }
                payload["time"] = time
                payload["diff"] = diff
                self._queue.put(json.dumps(payload).encode())

        def finish(self):
            self._queue.put(None)
            self._thread.join(timeout=60)
            self._check()

    node = SinkNode(
        G.engine_graph, table._node, _QueuedHttpWriter(), name=f"http({url})"
    )
    G.register_sink(node)



class HttpStreamingSubject(_PyConnectorSubject):
    """Streams a long-lived HTTP response line by line into a table
    (reference ``io/http/_streaming.py:13``).  Instantiate and pass to
    ``pw.io.python.read``; subclass and override ``run`` for custom
    protocols."""

    def __init__(self, url, *, sender=None, payload=None, headers=None,
                 delimiter=None, response_mapper=None):
        super().__init__()
        self._url = url
        self._sender = sender
        self._payload = payload
        self._headers = headers
        self._delimiter = delimiter
        self._response_mapper = response_mapper

    def run(self) -> None:
        send = self._sender or _urllib_stream_sender
        for line in send(self._url, headers=self._headers, data=self._payload,
                         delimiter=self._delimiter):
            if self._response_mapper:
                line = self._response_mapper(line)
            self.next_bytes(line if isinstance(line, bytes) else line.encode())
            self.commit()


def _urllib_stream_sender(url, *, headers=None, data=None, delimiter=None):
    import urllib.request

    if isinstance(data, str):
        data = data.encode()
    req = urllib.request.Request(url, headers=headers or {},
                                 data=data, method="GET" if data is None else "POST")
    with urllib.request.urlopen(req) as resp:  # noqa: S310
        sep = delimiter if delimiter is not None else b"\n"
        if isinstance(sep, str):
            sep = sep.encode()
        buf = b""
        while True:
            chunk = resp.read(8192)
            if not chunk:
                break
            buf += chunk
            while sep in buf:
                line, buf = buf.split(sep, 1)
                yield line
        if buf:
            yield buf
