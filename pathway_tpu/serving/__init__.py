"""Replicated serving fleet (ROADMAP item 3, horizontal axis).

One chip cannot serve millions of users no matter how fast decode gets:
PRs 5-11 made a single ``_ContinuousServer`` fast, observable, and
fault-tolerant, but every ``/v1/pw_ai_answer`` still landed on one
replica.  This package adds the horizontal layer:

* :mod:`~pathway_tpu.serving.hashring` — consistent-hash ring with
  virtual nodes, keyed on the *prompt-head token blocks* (same block
  size as the radix prefix cache, ``PATHWAY_TPU_PREFIX_BLOCK``), so
  shared RAG prefixes keep landing on the replica whose cache already
  holds them.
* :mod:`~pathway_tpu.serving.replica` — replica handles: in-process
  (a ``TPUDecoderChat`` continuous server, used by tests) and
  subprocess-over-HTTP (spawned via the ``parallel/distributed.py``
  env contract, health-checked through ``/healthz`` + ``/readyz``).
* :mod:`~pathway_tpu.serving.router` — :class:`FleetRouter` picks the
  affinity replica off the ring with ordered fallback; failed
  submissions are requeued on the next candidate through the PR-10
  retry semantics.  :class:`RouterServer` is the HTTP front-end that
  forwards ``/v1/pw_ai_answer`` and ``/v1/retrieve`` bodies.
* :mod:`~pathway_tpu.serving.fleet` — :class:`FleetManager`
  supervises the replica set: health ticks, drain + respawn with
  bounded backoff on death, and SLO-burn-driven elasticity between
  ``PATHWAY_TPU_FLEET_MIN`` and ``PATHWAY_TPU_FLEET_MAX``.

Kill switch: ``PATHWAY_TPU_FLEET`` (default off).  :func:`build_fleet`
is the single choke point — with the flag off it returns ``None``
without constructing a ring, router, or manager, so the single-server
path stays byte-identical (pinned by ``tests/test_fleet.py``).
"""

from __future__ import annotations

from pathway_tpu.serving.fleet import FleetManager
from pathway_tpu.serving.hashring import HashRing, head_block_key
from pathway_tpu.serving.replica import (
    HttpReplica,
    InProcessReplica,
    ReplicaError,
)
from pathway_tpu.serving.router import FleetCompletion, FleetRouter, RouterServer


def fleet_enabled() -> bool:
    """The fleet kill switch, read through the flag registry."""
    from pathway_tpu.internals.config import pathway_config

    return bool(pathway_config.fleet)


def build_fleet(factory, **kwargs):
    """Construct and start a :class:`FleetManager`, or ``None`` when the
    ``PATHWAY_TPU_FLEET`` kill switch is off.

    This is the only entry point product code should use: with the flag
    off *nothing* is constructed — no ring, no router, no supervisor
    thread — so disabling the fleet is byte-identical to the pre-fleet
    single-server path (``tests/test_fleet.py`` pins this).
    """
    if not fleet_enabled():
        return None
    manager = FleetManager(factory, **kwargs)
    manager.start()
    return manager


__all__ = [
    "FleetCompletion",
    "FleetManager",
    "FleetRouter",
    "HashRing",
    "HttpReplica",
    "InProcessReplica",
    "ReplicaError",
    "RouterServer",
    "build_fleet",
    "fleet_enabled",
    "head_block_key",
]
