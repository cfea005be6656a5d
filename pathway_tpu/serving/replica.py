"""Replica handles for the fleet router and supervisor.

Two shapes behind one duck type (``replica_id``, ``healthy()``,
``scrape()``, ``stop()``):

* :class:`InProcessReplica` — wraps a continuous-mode
  ``TPUDecoderChat`` living in this process.  This is what the tier-1
  tests use: real decode, real prefix cache,
  no subprocess startup tax.  Supports :meth:`InProcessReplica.submit`
  (the PR-10 two-phase completion protocol).
* :class:`HttpReplica` — a subprocess replica reached over HTTP,
  spawned via :func:`spawn_replica_process` with the
  ``parallel/distributed.py`` env contract (``PATHWAY_PROCESS_ID``,
  ``PATHWAY_FIRST_PORT``, ``PATHWAY_RUN_ID``...).  Health is the pair
  of ``/healthz`` (liveness) + ``/readyz`` (pipeline started) probes
  this PR adds to every REST server; request bodies are forwarded
  verbatim with :meth:`HttpReplica.forward`.

Neither handle owns ring membership or metrics — that is the router's
and fleet manager's job — so a replica object can be constructed,
probed, and torn down in isolation.
"""

from __future__ import annotations

import json
import socket
import subprocess
import urllib.error
import urllib.request


class ReplicaError(RuntimeError):
    """A replica could not accept or complete a request (dead serving
    loop, unreachable process, exhausted candidates)."""


def free_port(host: str = "127.0.0.1") -> int:
    """An OS-assigned ephemeral port, released immediately — the usual
    bind(0) race is acceptable for spawning local replicas."""
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind((host, 0))
        return s.getsockname()[1]


def spawn_replica_process(
    argv: list,
    *,
    replica_index: int,
    port: int,
    run_id: str,
    env: dict | None = None,
) -> subprocess.Popen:
    """Spawn a replica subprocess under the ``parallel/distributed.py``
    env contract — each replica is its own single-process "cluster"
    (``PATHWAY_PROCESSES=1``) on its own first port, sharing only the
    run id, which is exactly how ``cli.py spawn`` lays out workers."""
    from pathway_tpu.internals.config import environ_snapshot

    child = dict(environ_snapshot()) if env is None else dict(env)
    child["PATHWAY_THREADS"] = "1"
    child["PATHWAY_PROCESSES"] = "1"
    child["PATHWAY_PROCESS_ID"] = str(int(replica_index))
    child["PATHWAY_FIRST_PORT"] = str(int(port))
    child["PATHWAY_RUN_ID"] = run_id
    return subprocess.Popen(list(argv), env=child)


class InProcessReplica:
    """A continuous-mode ``TPUDecoderChat`` as a fleet member."""

    kind = "inproc"

    def __init__(self, replica_id: str, chat) -> None:
        self.replica_id = replica_id
        self.chat = chat

    def submit(self, prompt, max_new: int | None = None, *, priority: int = 1):
        """Enqueue one prompt; returns the ``_PendingCompletion`` from
        the PR-10 two-phase protocol (``.done`` event, ``.text``,
        ``.error_reason``).  Raises when the serving loop is dead —
        the router treats that as this replica failing the request."""
        kwargs: dict = {"priority": priority}
        if max_new is not None:
            kwargs["max_new_tokens"] = int(max_new)
        try:
            return self.chat.submit_batch([prompt], **kwargs)[0]
        except RuntimeError as exc:  # dead/stopped serving loop
            raise ReplicaError(str(exc)) from exc

    def healthy(self) -> bool:
        srv = getattr(self.chat, "_server", None)
        if srv is None:
            return False
        return srv.failed is None and srv.thread.is_alive()

    def occupancy(self) -> float:
        srv = getattr(self.chat, "_server", None)
        return srv.occupancy() if srv is not None else 0.0

    def scrape(self) -> dict:
        """Statistics in the ``/v1/statistics`` shape the fleet manager
        consumes — for an in-process replica the SLO watchdog state
        comes straight off the process-local singleton."""
        from pathway_tpu.engine import slo

        srv = getattr(self.chat, "_server", None)
        return {
            "server": dict(srv.stats) if srv is not None else {},
            "lanes": srv.lane_stats() if srv is not None else {},
            "tenants": srv.tenant_depths() if srv is not None else {},
            "slo": slo.get_watchdog().state(),
        }

    def stop(self) -> None:
        self.chat.close()


class HttpReplica:
    """A subprocess replica reached over HTTP on ``base_url``."""

    kind = "http"

    def __init__(
        self,
        replica_id: str,
        base_url: str,
        *,
        proc: subprocess.Popen | None = None,
        probe_timeout_s: float = 2.0,
    ) -> None:
        self.replica_id = replica_id
        self.base_url = base_url.rstrip("/")
        self.proc = proc
        self.probe_timeout_s = float(probe_timeout_s)

    def _get(self, route: str, timeout: float) -> tuple[int, bytes]:
        req = urllib.request.Request(self.base_url + route, method="GET")
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, resp.read()

    def forward(
        self, route: str, body: bytes, *, timeout: float = 60.0
    ) -> tuple[int, bytes, str]:
        """POST ``body`` to this replica verbatim; returns (status,
        payload, content-type).  HTTP error statuses are returned, not
        raised — the router decides whether 5xx means failover.
        Transport errors raise :class:`ReplicaError`."""
        req = urllib.request.Request(
            self.base_url + route,
            data=body,
            headers={"Content-Type": "application/json"},
            method="POST",
        )
        try:
            with urllib.request.urlopen(req, timeout=timeout) as resp:
                ctype = resp.headers.get("Content-Type", "application/json")
                return resp.status, resp.read(), ctype
        except urllib.error.HTTPError as exc:
            ctype = exc.headers.get("Content-Type", "application/json")
            return exc.code, exc.read(), ctype
        except (urllib.error.URLError, OSError, TimeoutError) as exc:
            raise ReplicaError(
                f"replica {self.replica_id} unreachable at "
                f"{self.base_url}{route}: {exc}"
            ) from exc

    def healthy(self) -> bool:
        """Liveness AND readiness: a replica that answers ``/healthz``
        but not ``/readyz`` (pipeline still starting) is not routable
        yet, and the supervisor must not respawn-storm it either — the
        fleet manager grants a readiness grace period separately."""
        if self.proc is not None and self.proc.poll() is not None:
            return False
        try:
            live, _ = self._get("/healthz", self.probe_timeout_s)
            ready, _ = self._get("/readyz", self.probe_timeout_s)
        except (urllib.error.URLError, OSError, TimeoutError):
            return False
        return live == 200 and ready == 200

    def scrape(self) -> dict:
        try:
            status, payload = self._get("/v1/statistics", self.probe_timeout_s)
        except (urllib.error.URLError, OSError, TimeoutError) as exc:
            raise ReplicaError(
                f"replica {self.replica_id} statistics scrape failed: {exc}"
            ) from exc
        if status != 200:
            raise ReplicaError(
                f"replica {self.replica_id} statistics scrape: HTTP {status}"
            )
        try:
            return json.loads(payload.decode("utf-8"))
        except ValueError as exc:
            raise ReplicaError(
                f"replica {self.replica_id} statistics not JSON: {exc}"
            ) from exc

    def stop(self, timeout: float = 5.0) -> None:
        if self.proc is None:
            return
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=timeout)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=timeout)
