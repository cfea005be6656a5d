"""Fleet supervisor: health checks, drain/respawn, SLO elasticity.

:class:`FleetManager` owns the replica lifecycle around a
:class:`~pathway_tpu.serving.router.FleetRouter`:

* **Health tick** — every ``PATHWAY_TPU_FLEET_HEALTH_MS`` it probes
  each member (``replica.healthy()``; the ``replica.health`` chaos
  site injects probe failures to prove the drain path).  A member that
  has never probed healthy keeps a ``boot_grace_s`` window first —
  subprocess replicas spend seconds in jax import + first jit before
  they listen, and draining a booting replica is a respawn storm, not
  supervision.  After that, a replica
  failing ``fail_threshold`` consecutive probes is *drained*: removed
  from the ring (its arcs move, in-flight requests requeue through the
  PR-10 retry path inside ``FleetCompletion.wait``), stopped, and
  respawned through ``ExponentialBackoffRetryStrategy`` — bounded
  backoff, bounded attempts, never a tight respawn storm.
* **Elasticity** — each tick scrapes every replica's ``/v1/statistics``
  and reduces the SLO watchdog burn signals
  (:func:`pathway_tpu.engine.slo.max_burn`: an objective counts only
  when BOTH its fast and slow windows burn, mirroring the alert rule).
  Sustained burn ≥ 1 scales up toward ``PATHWAY_TPU_FLEET_MAX``;
  quiescence scales down toward ``PATHWAY_TPU_FLEET_MIN``, one step
  per cooldown so the fleet never flaps.

The manager is clock/sleep-injectable so the whole policy is testable
without wall time, and usable tick-by-tick (no thread).
"""

from __future__ import annotations

import threading

from pathway_tpu.analysis.annotations import guarded_by
from pathway_tpu.analysis.runtime import make_lock
from pathway_tpu.engine import chaos as chaos_mod
from pathway_tpu.engine import slo as slo_mod
from pathway_tpu.internals.udfs.retries import ExponentialBackoffRetryStrategy
from pathway_tpu.serving.router import FleetRouter


@guarded_by(_fail_counts="_lock", _seq="_lock", _events="_lock",
            _respawns="_lock", _last_scale_at="_lock", _last_burn="_lock",
            _spawned_at="_lock", _ever_ready="_lock",
            _burn_signal_seen="_lock")
class FleetManager:
    """Supervises ``factory(replica_id) -> replica`` instances."""

    def __init__(
        self,
        factory,
        *,
        router: FleetRouter | None = None,
        replicas: int | None = None,
        min_replicas: int | None = None,
        max_replicas: int | None = None,
        health_interval_s: float | None = None,
        boot_grace_s: float = 0.0,
        fail_threshold: int = 1,
        burn_up_threshold: float = 1.0,
        burn_down_threshold: float = 0.25,
        scale_cooldown_s: float = 5.0,
        respawn: ExponentialBackoffRetryStrategy | None = None,
        clock=None,
        sleep=None,
    ) -> None:
        import time as time_mod

        from pathway_tpu.internals.config import pathway_config

        self.factory = factory
        self.router = router if router is not None else FleetRouter()
        self.initial_replicas = (
            pathway_config.fleet_replicas if replicas is None else int(replicas)
        )
        self.min_replicas = (
            pathway_config.fleet_min if min_replicas is None else int(min_replicas)
        )
        self.max_replicas = (
            pathway_config.fleet_max if max_replicas is None else int(max_replicas)
        )
        self.max_replicas = max(self.max_replicas, self.min_replicas)
        self.initial_replicas = min(
            max(self.initial_replicas, self.min_replicas), self.max_replicas
        )
        self.health_interval_s = (
            pathway_config.fleet_health_ms / 1000.0
            if health_interval_s is None
            else float(health_interval_s)
        )
        # a subprocess replica needs seconds (jax import + first jit)
        # before it listens — failed probes inside the grace window of a
        # member that was NEVER ready yet don't count, or the supervisor
        # drains every boot into an endless respawn churn
        self.boot_grace_s = max(0.0, float(boot_grace_s))
        self.fail_threshold = max(1, int(fail_threshold))
        self.burn_up_threshold = float(burn_up_threshold)
        self.burn_down_threshold = float(burn_down_threshold)
        self.scale_cooldown_s = float(scale_cooldown_s)
        # respawn backoff: bounded attempts, capped delay — a replica
        # that cannot come back leaves the fleet degraded (and the gap
        # visible in replica_up) rather than burning the supervisor
        self.respawn = respawn if respawn is not None else (
            ExponentialBackoffRetryStrategy(
                max_retries=3, initial_delay=50, backoff_factor=2.0,
                jitter_ms=0, max_delay_ms=1000,
            )
        )
        self._clock = clock if clock is not None else time_mod.monotonic
        self._sleep = sleep if sleep is not None else time_mod.sleep
        self._lock = make_lock("serving.fleet")
        self._fail_counts: dict = {}
        self._spawned_at: dict = {}
        self._ever_ready: set = set()
        self._seq = 0
        self._events: list = []  # (kind, replica_id) scale/drain audit trail
        self._respawns = 0
        self._last_scale_at = float("-inf")
        self._last_burn = 0.0
        self._burn_signal_seen = False
        self._chaos_health = chaos_mod.site("replica.health")
        self._stop_evt = threading.Event()
        self._thread: threading.Thread | None = None

    # ------ lifecycle --------------------------------------------------
    def _next_id(self) -> str:
        with self._lock:
            rid = f"replica-{self._seq}"
            self._seq += 1
            return rid

    def spawn_one(self) -> str:
        """Create one replica through the factory and join it to the
        ring; the factory raising propagates (callers wrap in the
        respawn backoff where that matters)."""
        rid = self._next_id()
        replica = self.factory(rid)
        self.router.add_replica(replica)
        with self._lock:
            self._spawned_at[rid] = self._clock()
            self._events.append(("spawn", rid))
        return rid

    def start(self) -> "FleetManager":
        """Bring the fleet to its initial size (no supervisor thread —
        call :meth:`run_in_thread` or :meth:`tick` explicitly)."""
        while len(self.router) < self.initial_replicas:
            self.spawn_one()
        return self

    def stop_one(self, replica_id: str, *, kind: str = "scale_down") -> None:
        replica = self.router.remove_replica(replica_id)
        with self._lock:
            self._fail_counts.pop(replica_id, None)
            self._spawned_at.pop(replica_id, None)
            self._ever_ready.discard(replica_id)
            self._events.append((kind, replica_id))
        if replica is not None:
            try:
                replica.stop()
            except Exception:
                pass  # already-dead processes may refuse teardown

    def shutdown(self) -> None:
        self._stop_evt.set()
        if self._thread is not None:
            self._thread.join(timeout=10.0)
            self._thread = None
        for rid in list(self.router.replicas()):
            self.stop_one(rid, kind="shutdown")

    # ------ supervision ------------------------------------------------
    def _probe(self, replica) -> bool:
        if self._chaos_health is not None:
            self._chaos_health.maybe_fail()
        return bool(replica.healthy())

    def health_pass(self) -> list:
        """One probe sweep; drains + respawns dead members. Returns the
        replica ids drained this pass."""
        drained = []
        now = self._clock()
        for rid, replica in self.router.replicas().items():
            try:
                ok = self._probe(replica)
            except Exception:  # InjectedFault or a probe transport error
                ok = False
            with self._lock:
                if ok:
                    self._fail_counts[rid] = 0
                    self._ever_ready.add(rid)
                    continue
                booting = (
                    rid not in self._ever_ready
                    and now - self._spawned_at.get(rid, float("-inf"))
                    < self.boot_grace_s
                )
                if booting:  # still compiling/binding — not a failure yet
                    continue
                self._fail_counts[rid] = self._fail_counts.get(rid, 0) + 1
                dead = self._fail_counts[rid] >= self.fail_threshold
            if dead:
                self.stop_one(rid, kind="drain")
                drained.append(rid)
                self._respawn_replica()
        return drained

    def _respawn_replica(self) -> str | None:
        """Replace a drained replica, honoring max size, with bounded
        exponential backoff between factory attempts."""
        if len(self.router) >= self.max_replicas:
            return None
        try:
            rid = self.respawn.invoke_sync(self.spawn_one, sleep=self._sleep)
        except Exception:
            with self._lock:
                self._events.append(("respawn_failed", None))
            return None
        with self._lock:
            self._respawns += 1
            # spawn_one logged ("spawn", rid); relabel as a respawn
            if self._events and self._events[-1] == ("spawn", rid):
                self._events[-1] = ("respawn", rid)
        return rid

    # ------ elasticity -------------------------------------------------
    def burn(self) -> float:
        """Fleet-wide scale pressure: max over replicas of the reduced
        SLO burn signal from each ``/v1/statistics`` scrape. Returns the
        scalar; whether any replica reported objectives at all is kept
        separately (no objectives ⇒ no signal ⇒ elasticity stays inert —
        a fleet without SLOs must not collapse to ``min`` just because
        0.0 reads as 'healthy')."""
        worst = 0.0
        seen = False
        for replica in self.router.replicas().values():
            try:
                snap = replica.scrape() or {}
            except Exception:
                continue  # unreachable replicas are the health pass's job
            slo_state = snap.get("slo") or {}
            seen = seen or bool(slo_mod.burn_signals(slo_state))
            worst = max(worst, slo_mod.max_burn(slo_state))
        with self._lock:
            self._last_burn = worst
            self._burn_signal_seen = seen
        return worst

    def elasticity_pass(self) -> str | None:
        """Scale one step per cooldown window off the burn signal."""
        burn = self.burn()
        with self._lock:
            has_signal = self._burn_signal_seen
        if not has_signal:
            return None  # no objectives anywhere: nothing to scale on
        now = self._clock()
        n = len(self.router)
        with self._lock:
            in_cooldown = now - self._last_scale_at < self.scale_cooldown_s
        if in_cooldown:
            return None
        action = None
        if burn >= self.burn_up_threshold and n < self.max_replicas:
            self.spawn_one()
            action = "scale_up"
        elif burn <= self.burn_down_threshold and n > self.min_replicas:
            # drop the newest member: oldest replicas hold the warmest
            # prefix caches, so they are the last to go
            members = self.router.ring.members()
            victim = max(
                members, key=lambda r: int(r.rsplit("-", 1)[-1])
                if r.rsplit("-", 1)[-1].isdigit() else -1,
            )
            self.stop_one(victim, kind="scale_down")
            action = "scale_down"
        if action is not None:
            with self._lock:
                self._last_scale_at = now
        return action

    def tick(self) -> dict:
        """One supervisor iteration: health sweep then elasticity."""
        drained = self.health_pass()
        action = self.elasticity_pass()
        return {"drained": drained, "scale": action, "size": len(self.router)}

    # ------ reporting / loop -------------------------------------------
    def state(self) -> dict:
        with self._lock:
            events = list(self._events)
            respawns = self._respawns
            burn = self._last_burn
            fails = dict(self._fail_counts)
        return {
            "replicas": {
                rid: {
                    "kind": getattr(r, "kind", "?"),
                    "consecutive_failures": fails.get(rid, 0),
                }
                for rid, r in self.router.replicas().items()
            },
            "size": len(self.router),
            "min": self.min_replicas,
            "max": self.max_replicas,
            "burn": burn,
            "respawns": respawns,
            "events": events[-50:],
            "ring_members": self.router.ring.members(),
        }

    def run_in_thread(self) -> "FleetManager":
        if self._thread is not None:
            return self
        self._stop_evt.clear()

        def loop() -> None:
            while not self._stop_evt.wait(self.health_interval_s):
                try:
                    self.tick()
                except Exception:
                    continue  # a failed sweep must not kill supervision

        self._thread = threading.Thread(
            target=loop, name="fleet-supervisor", daemon=True
        )
        self._thread.start()
        return self
