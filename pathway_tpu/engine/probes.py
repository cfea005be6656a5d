"""Operator probes — per-node runtime statistics and device-dispatch
counters.

The analog of the reference's prober machinery (`src/engine/graph.rs:533`
``ProberStats``/``OperatorStats``, ``src/engine/progress_reporter.rs:17-90``):
the scheduler times every operator step and counts rows; snapshots feed the
console dashboard (``internals/monitoring.py``), the Prometheus endpoint
(``internals/http_server.py``) and ``pw.run``'s final summary.

One addition beyond the reference:

* **device-dispatch counters** — kernels (``models/embedder.py``,
  ``ops/knn.py``) call :func:`record_device_dispatch` on every accelerator
  round trip; counts accumulate globally per kind and, when the dispatch
  happens inside an operator ``step``, per operator.

Since the observability PR every ledger is a thin shim over ONE
:class:`MetricsRegistry` (``REGISTRY``): a thread-safe store of named
counters, gauges and log-bucketed histograms with label sets. The shims
keep the historical ``record_*`` / ``*_stats`` / ``reset_*`` signatures
and return shapes byte-for-byte, so every existing call site (kernels,
tests) keeps working, while the registry adds what the ledgers
never had: per-request latency histograms (TTFT / TPOT / queue-wait /
e2e, fed by ``engine/tracing.py`` spans), one consistent
:meth:`MetricsRegistry.snapshot` dict, and an OpenMetrics export path
(``internals/http_server.py``). ``PATHWAY_TPU_METRICS=0`` is the master
kill switch — record calls become no-ops, outputs stay byte-identical.
"""

from __future__ import annotations

import bisect
import dataclasses
import threading
import time

from pathway_tpu.analysis.annotations import guarded_by
from pathway_tpu.analysis.runtime import make_lock

# --------------------------------------------------------------------- #
# the unified metrics registry

# log-bucketed (factor 2) latency bounds: 100us .. ~105s, 21 buckets +
# one +Inf overflow. Wide enough for queued-request TTFTs, fine enough that
# interpolated p50/p95 stay within a 2x bucket of the truth.
_DEFAULT_HIST_BOUNDS = tuple(1e-4 * (2.0 ** i) for i in range(21))

# every family the package emits, so exporters can render HELP/TYPE
# lines even before the first sample (a scrape during warm-up still
# shows the full surface): name -> (type, label, help)
METRIC_FAMILIES: dict[str, tuple[str, str | None, str]] = {
    "device_dispatch": (
        "counter", "kind", "Accelerator round trips by dispatch kind"),
    "knn_search_queries": (
        "counter", "padded", "Queries per brute-force search dispatch: "
        "as asked (padded=0) and as searched, rounded up to the pow2 "
        "bucket (padded=1); over device_dispatch{kind=knn_search}"),
    "consolidate_rows": (
        "counter", "content", "Rows consolidated after an operator's step: "
        "decided by their keys alone (content=0) or by examining their "
        "content, because they share a key (content=1)"),
    "compiles": (
        "counter", None, "XLA backend compilations in this process"),
    "compile_seconds": (
        "counter", None, "Seconds JAX spent tracing, lowering and "
        "compiling in this process"),
    "cascade_pairs": (
        "counter", "stage", "Rerank pairs scored per cascade stage"),
    "cascade_flops": (
        "counter", "stage", "Model FLOPs paid per cascade stage"),
    "prefix_events": (
        "counter", "kind", "Prefix-KV-cache events (hit/miss tokens, "
        "requests, inserted/evicted blocks)"),
    "prefix_cached_bytes": (
        "gauge", None, "Resident KV bytes in the prefix arena"),
    "spec_events": (
        "counter", "kind", "Speculative-decode events (drafted/accepted/"
        "emitted tokens, verify/draft steps)"),
    "serving_occupancy": (
        "gauge", "server", "Useful slot-steps / total slot-steps of a "
        "continuous decode server"),
    "ttft_seconds": (
        "histogram", "phase", "Time from request enqueue to first "
        "drained token"),
    "tpot_seconds": (
        "histogram", "phase", "Mean time per output token after the "
        "first (per request)"),
    "queue_wait_seconds": (
        "histogram", "phase", "Time from request enqueue to admission"),
    "e2e_seconds": (
        "histogram", "phase", "Time from request enqueue to completion"),
    "op_step_seconds": (
        "histogram", "operator", "Per-operator epoch-processing latency "
        "(one observation per stepped operator per epoch)"),
    "op_rows": (
        "counter", "operator", "Rows entering (direction=in) and leaving "
        "(direction=out) each operator"),
    "op_held_rows": (
        "gauge", "operator", "Rows currently held back by a stateful "
        "temporal operator (buffer backlog / forget liveness set)"),
    "watermark_lag": (
        "gauge", "operator", "Distance (time-column units) between a "
        "temporal operator's watermark and its oldest held threshold"),
    "engine_backlog": (
        "gauge", "queue", "Dataflow backlog depth (pending injected "
        "epochs, async in-flight batches)"),
    "engine_frontier_lag": (
        "gauge", None, "Epochs the source frontier is ahead of the "
        "scheduler's last processed time"),
    "exchange_rows": (
        "counter", "direction", "Rows routed by the exchange layer "
        "(local / sent / received / broadcast)"),
    "hbm_bytes": (
        "gauge", "component", "Current device-memory ledger bytes per "
        "component (slot_pool / prefix_arena / kv_scales / ...)"),
    "hbm_high_water_bytes": (
        "gauge", "component", "High-water device-memory ledger bytes per "
        "component, plus the 'total' series across all components"),
    "slo_burn_rate": (
        "gauge", "objective", "SLO error-budget burn rate per objective "
        "and window (fast / slow)"),
    "slo_alert": (
        "gauge", "objective", "1 while an SLO objective's multi-window "
        "burn-rate alert is firing, else 0"),
    "slo_breaches": (
        "counter", "objective", "SLO alert activations (ok -> firing "
        "transitions) per objective"),
    "serve_restarts": (
        "counter", "server", "Supervised serving-loop restarts "
        "(crash -> backoff -> re-enter) per server"),
    "requests_shed": (
        "counter", "reason", "Requests shed by admission control "
        "(deadline / queue_full / degraded)"),
    "degradation_level": (
        "gauge", None, "Current SLO-driven degradation ladder level "
        "(0 = full service, 3 = shedding low-priority admissions)"),
    "requests_isolated": (
        "counter", "outcome", "Request-scoped serving errors handled by "
        "per-request isolation (retried / failed)"),
    "kv_fragmentation": (
        "gauge", "server", "Fraction of a serving pool's allocated KV "
        "bytes stranded beyond what active requests can reach "
        "(0 = perfectly packed; dense right-padded slots strand the "
        "whole row tail, paged allocation only the final block's)"),
    "kv_parked_bytes": (
        "gauge", "server", "KV bytes held by preempted requests' parked "
        "block rows (held on purpose for re-admission — classified "
        "apart from kv_fragmentation's stranded bytes)"),
    "lane_occupancy": (
        "gauge", "lane", "Slots per serving lane (prefill = mid-prompt, "
        "decode = emitting) of a continuous decode server"),
    "tenant_queue_depth": (
        "gauge", "tenant", "Queued requests per tenant awaiting "
        "weighted-fair admission"),
    "preemptions": (
        "counter", "tenant", "Over-budget requests preempted out of "
        "their slot (KV parked, request requeued) per tenant"),
    "moe_assignments": (
        "counter", "held", "Token-to-expert assignments the routers made, "
        "by whether the expert is held here (held=1) and by phase; summed "
        "on the device, read with the tokens at drain"),
    "prefill_attn_blocks": (
        "counter", "layer", "Key blocks of a slot's row that the blockwise "
        "attention read of a prefill piece visited (visited=1) or skipped "
        "because no query of the piece can see a key in them (visited=0), "
        "by kind of layer (full | window | latent), summed over its layers"),
    "latent_rows_expanded": (
        "counter", "phase", "Latent cache rows turned into per-head keys "
        "and values for the time of one attention read (phase=prefill: the "
        "rows of the key blocks a piece's blockwise read visited), summed "
        "over the latent layers; host arithmetic, no sync"),
    "loop_passes": (
        "counter", "phase", "Passes of a looped layer stack run for tokens "
        "that were real, by phase (prefill: a piece's real columns; decode: "
        "a chunk's useful slot-steps) and by pass (pass=1..loops: every "
        "token runs pass 1, so the series' sum over pass=1's is the passes a "
        "token); host arithmetic, no sync"),
    "loop_exit_step": (
        "counter", "step", "Emitted tokens by the pass (step=1..loops) the "
        "exit rule of a looped stack took their logits from; all at the "
        "last under a threshold of 1. Summed on the device, read with the "
        "tokens at drain"),
    "kv_migrated_blocks": (
        "counter", "server", "KV blocks handed from the prefill lane to "
        "the decode lane at prompt completion (PATHWAY_TPU_DISAGG)"),
    "requests_routed": (
        "counter", "replica", "Requests forwarded by the fleet router, "
        "per destination replica"),
    "requests_requeued": (
        "counter", None, "Fleet requests re-dispatched to another "
        "replica after their replica died mid-flight"),
    "ring_moves": (
        "counter", None, "Consistent-hash-ring vnode arcs that changed "
        "owner on replica join/leave"),
    "replica_up": (
        "gauge", "replica", "1 while a fleet replica is a ring member, "
        "0 once drained"),
}

LATENCY_HISTOGRAMS = (
    "ttft_seconds", "tpot_seconds", "queue_wait_seconds", "e2e_seconds",
)


@guarded_by(_counters="_lock", _gauges="_lock", _hists="_lock")
class MetricsRegistry:
    """Single thread-safe registry of counters, gauges and log-bucketed
    histograms, each a family of label-keyed series.

    One lock covers every mutation and the whole :meth:`snapshot`, so a
    snapshot is CONSISTENT — no torn reads between families the way the
    five per-ledger locks allowed. Recording is gated on the
    ``PATHWAY_TPU_METRICS`` kill switch (read per call, so tests can
    flip it with ``monkeypatch.setenv``); resets always apply."""

    def __init__(self, hist_bounds: tuple = _DEFAULT_HIST_BOUNDS):
        self._lock = make_lock("probes.registry", rlock=True)
        self.hist_bounds = tuple(float(b) for b in hist_bounds)
        self._counters: dict[str, dict[tuple, float]] = {}
        self._gauges: dict[str, dict[tuple, float]] = {}
        # name -> labelkey -> [bucket counts (len bounds+1), sum, count]
        self._hists: dict[str, dict[tuple, list]] = {}

    _cfg = None  # cached pathway_config; the flag itself is read per call

    @property
    def enabled(self) -> bool:
        cfg = self._cfg
        if cfg is None:
            from pathway_tpu.internals.config import pathway_config

            MetricsRegistry._cfg = cfg = pathway_config
        return bool(cfg.metrics)

    @staticmethod
    def _key(labels: dict) -> tuple:
        return tuple(sorted((str(k), str(v)) for k, v in labels.items()))

    # ------------------------------------------------------------ write
    def counter_add(self, name: str, value: float = 1.0, **labels) -> None:
        if not self.enabled:
            return
        key = self._key(labels)
        with self._lock:
            series = self._counters.setdefault(name, {})
            series[key] = series.get(key, 0.0) + value

    def counter_add_many(self, name: str, label, counts: dict) -> None:
        """Batched :meth:`counter_add` over one label dimension (or, with
        a tuple of label names and tuples as ``counts``' keys, several): a
        single enabled check + lock acquisition for a whole group of
        updates — what serving hot loops (one spec cycle = six counters)
        call."""
        if not self.enabled:
            return
        with self._lock:
            series = self._counters.setdefault(name, {})
            several = isinstance(label, tuple)
            for lv, v in counts.items():
                key = (tuple(sorted(zip(label, map(str, lv)))) if several
                       else ((label, str(lv)),))
                series[key] = series.get(key, 0.0) + v

    def gauge_set(self, name: str, value: float, **labels) -> None:
        if not self.enabled:
            return
        key = self._key(labels)
        with self._lock:
            self._gauges.setdefault(name, {})[key] = float(value)

    def gauge_add(self, name: str, value: float, **labels) -> None:
        if not self.enabled:
            return
        key = self._key(labels)
        with self._lock:
            series = self._gauges.setdefault(name, {})
            series[key] = series.get(key, 0.0) + value

    def observe(self, name: str, value: float, **labels) -> None:
        if not self.enabled:
            return
        key = self._key(labels)
        v = float(value)
        with self._lock:
            series = self._hists.setdefault(name, {})
            rec = series.get(key)
            if rec is None:
                rec = series[key] = [
                    [0] * (len(self.hist_bounds) + 1), 0.0, 0,
                ]
            rec[0][bisect.bisect_left(self.hist_bounds, v)] += 1
            rec[1] += v
            rec[2] += 1

    def gauge_max(self, name: str, value: float, **labels) -> None:
        """Set the gauge to ``max(current, value)`` — the high-water
        primitive the HBM ledger rides. Atomic under the registry lock."""
        if not self.enabled:
            return
        key = self._key(labels)
        v = float(value)
        with self._lock:
            series = self._gauges.setdefault(name, {})
            cur = series.get(key)
            if cur is None or v > cur:
                series[key] = v

    def observe_op_step(
        self, operator: str, seconds: float, rows_in: int, rows_out: int
    ) -> None:
        """One stepped operator epoch: latency histogram observation plus
        rows-in/rows-out counters under a SINGLE enabled check + lock
        acquisition — this sits on the scheduler's per-step hot path."""
        if not self.enabled:
            return
        v = float(seconds)
        hkey = (("operator", operator),)
        with self._lock:
            series = self._hists.setdefault("op_step_seconds", {})
            rec = series.get(hkey)
            if rec is None:
                rec = series[hkey] = [
                    [0] * (len(self.hist_bounds) + 1), 0.0, 0,
                ]
            rec[0][bisect.bisect_left(self.hist_bounds, v)] += 1
            rec[1] += v
            rec[2] += 1
            rows = self._counters.setdefault("op_rows", {})
            if rows_in:
                key = (("direction", "in"), ("operator", operator))
                rows[key] = rows.get(key, 0.0) + rows_in
            if rows_out:
                key = (("direction", "out"), ("operator", operator))
                rows[key] = rows.get(key, 0.0) + rows_out

    # ------------------------------------------------------------- read
    def labelled(self, name: str, label: str,
                 kind: str = "counter") -> dict[str, float]:
        """Series values of ``name`` summed by their ``label`` value."""
        with self._lock:
            store = self._counters if kind == "counter" else self._gauges
            items = list((store.get(name) or {}).items())
        out: dict[str, float] = {}
        for key, v in items:
            lv = dict(key).get(label, "")
            out[lv] = out.get(lv, 0.0) + v
        return out

    def gauge_value(self, name: str, **labels) -> float | None:
        with self._lock:
            series = self._gauges.get(name)
            if not series:
                return None
            if labels:
                return series.get(self._key(labels))
            return sum(series.values())

    def hist_summary(self, name: str, **labels) -> dict | None:
        """Merged bucket summary of every series of ``name`` whose labels
        contain ``labels``; quantiles interpolate inside the matched
        bucket. None before the first observation."""
        want = set(self._key(labels)) if labels else None
        merged = [0] * (len(self.hist_bounds) + 1)
        total, s = 0, 0.0
        with self._lock:
            for key, (counts, ssum, cnt) in (
                self._hists.get(name) or {}
            ).items():
                if want is not None and not want <= set(key):
                    continue
                for i, c in enumerate(counts):
                    merged[i] += c
                s += ssum
                total += cnt
        if not total:
            return None
        return {
            "count": total,
            "sum": s,
            "mean": s / total,
            "p50": self._quantile(merged, 0.5),
            "p95": self._quantile(merged, 0.95),
        }

    def _quantile(self, counts: list, q: float) -> float:
        total = sum(counts)
        if not total:
            return 0.0
        rank = q * total
        cum = 0.0
        for i, c in enumerate(counts):
            if not c:
                continue
            if cum + c >= rank:
                lo = 0.0 if i == 0 else self.hist_bounds[i - 1]
                hi = (
                    self.hist_bounds[i] if i < len(self.hist_bounds)
                    else self.hist_bounds[-1]
                )
                frac = max(0.0, min(1.0, (rank - cum) / c))
                return lo + (hi - lo) * frac
            cum += c
        return self.hist_bounds[-1]

    def remove(self, *names: str) -> None:
        with self._lock:
            for n in names:
                self._counters.pop(n, None)
                self._gauges.pop(n, None)
                self._hists.pop(n, None)

    def reset(self) -> None:
        with self._lock:
            self._counters.clear()
            self._gauges.clear()
            self._hists.clear()

    def snapshot(self) -> dict:
        """One CONSISTENT plain-dict snapshot of every family (single
        lock acquisition), for exporters / the dashboard / JSON."""
        with self._lock:
            counters = {
                n: {"series": [
                    {"labels": dict(k), "value": v}
                    for k, v in sorted(s.items())
                ]}
                for n, s in sorted(self._counters.items())
            }
            gauges = {
                n: {"series": [
                    {"labels": dict(k), "value": v}
                    for k, v in sorted(s.items())
                ]}
                for n, s in sorted(self._gauges.items())
            }
            hists = {
                n: {
                    "bounds": list(self.hist_bounds),
                    "series": [
                        {
                            "labels": dict(k),
                            "buckets": list(rec[0]),
                            "sum": rec[1],
                            "count": rec[2],
                        }
                        for k, rec in sorted(s.items())
                    ],
                }
                for n, s in sorted(self._hists.items())
            }
        return {"counters": counters, "gauges": gauges, "histograms": hists}


REGISTRY = MetricsRegistry()


def observe_latency(name: str, seconds: float, phase: str) -> None:
    """Feed one request-latency observation into a registry histogram
    (``name`` in :data:`LATENCY_HISTOGRAMS`, ``phase`` = decode / query /
    embed). Called by ``engine/tracing.py`` span finish."""
    REGISTRY.observe(name, seconds, phase=phase)


def latency_summary(phase: str | None = None) -> dict:
    """Per-histogram ms summaries (count / p50 / p95 / mean), optionally
    filtered to one phase. Families with no observations are omitted."""
    out: dict = {}
    for name in LATENCY_HISTOGRAMS:
        s = REGISTRY.hist_summary(name, **({"phase": phase} if phase else {}))
        if s is not None:
            out[name] = {
                "count": s["count"],
                "p50_ms": round(s["p50"] * 1e3, 3),
                "p95_ms": round(s["p95"] * 1e3, 3),
                "mean_ms": round(s["mean"] * 1e3, 3),
            }
    return out


def reset_latency_metrics() -> None:
    REGISTRY.remove(*LATENCY_HISTOGRAMS)


def serving_snapshot() -> dict:
    """The serving-side view every consumer shares — ``/v1/statistics``,
    the rich dashboard panel and ``cli stats`` all read THIS, so their keys
    and the scraped metrics cannot drift."""
    return {
        "prefix": prefix_stats(),
        "spec": spec_stats(),
        "cascade": cascade_stats(),
        "dispatch": dispatch_counts(),
        "occupancy": {
            k: round(v, 4)
            for k, v in REGISTRY.labelled(
                "serving_occupancy", "server", kind="gauge"
            ).items()
        },
        "lanes": {
            k: round(v, 4)
            for k, v in REGISTRY.labelled(
                "lane_occupancy", "lane", kind="gauge"
            ).items()
        },
        "tenants": {
            k: round(v, 4)
            for k, v in REGISTRY.labelled(
                "tenant_queue_depth", "tenant", kind="gauge"
            ).items()
        },
        "kv_parked_bytes": {
            k: round(v, 1)
            for k, v in REGISTRY.labelled(
                "kv_parked_bytes", "server", kind="gauge"
            ).items()
        },
        "retrieval": retrieval_backend_stats(),
        "latency": latency_summary(),
    }


def unified_snapshot(scheduler_stats=None) -> dict:
    """Scheduler + serving + engine + device-memory + SLO + raw-registry
    in one dict: the payload of ``/v1/statistics`` and the source of the
    monitoring dashboard."""
    sched = None
    if scheduler_stats is not None:
        sched = (
            scheduler_stats.snapshot()
            if hasattr(scheduler_stats, "snapshot") else scheduler_stats
        )
    from pathway_tpu.engine import slo as slo_mod
    from pathway_tpu.internals.config import tuned_config_snapshot

    return {
        "scheduler": sched,
        "serving": serving_snapshot(),
        "engine": engine_snapshot(),
        "hbm": hbm_stats(),
        "slo": slo_mod.slo_snapshot(),
        "tuning": tuned_config_snapshot(),
        "registry": REGISTRY.snapshot(),
    }


# --------------------------------------------------------------------- #
# per-operator dataflow telemetry (registry-backed)
#
# The scheduler already times every operator step for SchedulerStats;
# since the observability PR the same measurement also lands in the
# registry — `op_step_seconds{operator=}` histograms and
# `op_rows{operator=,direction=}` counters — so latency DISTRIBUTIONS
# (not just totals) are scrapeable per operator. Temporal operators add
# `op_held_rows` / `watermark_lag` gauges, the scheduler an
# `engine_backlog{queue=}` gauge riding `pending_backlog()`, and the
# exchange layer `exchange_rows{direction=}` counters. All of it is
# gated twice: PATHWAY_TPU_METRICS (master, per call inside the
# registry) and PATHWAY_TPU_OP_METRICS (operator-telemetry kill switch,
# read once per scheduler construction so the hot path never touches
# the environment).

def record_op_step(
    operator: str, seconds: float, rows_in: int, rows_out: int
) -> None:
    """Per-operator epoch record: latency observation + row counters in
    one registry transaction. Called by ``Scheduler._step_node``."""
    REGISTRY.observe_op_step(operator, seconds, rows_in, rows_out)


def record_consolidate(rows: int, compared: int) -> None:
    """One node's consolidation: the rows it was decided for by their keys
    alone (``content=0``) and those whose content had to be examined
    (``content=1``: they share a key with another row of the batch). A
    standing aggregation costs what a commit brings while ``content=1``
    stays flat as its state grows."""
    REGISTRY.counter_add_many(
        "consolidate_rows", "content", {0: rows - compared, 1: compared}
    )


def record_prefill_attn_blocks(counts: dict) -> None:
    """One prefill piece's ``{(layer, visited): blocks}``
    (``models.decoder.prefill_blocks_visited``: host arithmetic, no sync):
    how much of a long row the blockwise read touches."""
    REGISTRY.counter_add_many(
        "prefill_attn_blocks", ("layer", "visited"), counts)


def record_latent_rows_expanded(rows: int, phase: str = "prefill") -> None:
    """Latent rows one dispatch expanded into per-head keys and values,
    over its latent layers (``models.decoder.prefill_blocks_visited`` times
    the block's rows: host arithmetic, no sync)."""
    REGISTRY.counter_add("latent_rows_expanded", rows, phase=phase)


def record_loop_passes(phase: str, tokens: int, loops: int) -> None:
    """``tokens`` real tokens went through every one of ``loops`` passes
    of a looped stack (``_ContinuousServer._loop_account``)."""
    REGISTRY.counter_add_many(
        "loop_passes", ("phase", "pass"),
        {(phase, u): tokens for u in range(1, loops + 1)})


def record_loop_exits(by_step: dict) -> None:
    """``{step: tokens}``: emitted tokens by the exit rule's pass."""
    REGISTRY.counter_add_many("loop_exit_step", "step", by_step)


def record_backlog(queue: str, depth: int) -> None:
    """Backlog depth gauge (``queue`` = pending_epochs / async_inflight /
    drain_group). Throttled by callers — gauges only need freshness, not
    every transition."""
    REGISTRY.gauge_set("engine_backlog", depth, queue=queue)


def record_frontier_lag(lag: float) -> None:
    REGISTRY.gauge_set("engine_frontier_lag", max(0.0, float(lag)))


def record_watermark(operator: str, held_rows: int,
                     lag: float | None) -> None:
    """Temporal-operator state: rows currently held back and, when the
    time column is numeric, how far the oldest held threshold trails the
    watermark."""
    REGISTRY.gauge_set("op_held_rows", held_rows, operator=operator)
    if lag is not None:
        REGISTRY.gauge_set(
            "watermark_lag", max(0.0, float(lag)), operator=operator
        )


def record_exchange(**rows: int) -> None:
    """Exchange-layer row accounting by direction (``local`` / ``sent`` /
    ``received`` / ``broadcast``): one lock acquisition per step."""
    REGISTRY.counter_add_many(
        "exchange_rows", "direction", {k: v for k, v in rows.items() if v}
    )


def engine_snapshot() -> dict:
    """Per-operator registry view: latency quantiles + row counters per
    operator, backlog gauges, watermark lag, exchange counters. The
    'engine' section of :func:`unified_snapshot` and the source of the
    per-operator dashboard panel."""
    snap = REGISTRY.snapshot()
    ops: dict[str, dict] = {}
    for series in snap["histograms"].get("op_step_seconds", {}).get(
        "series", []
    ):
        name = series["labels"].get("operator", "")
        s = REGISTRY.hist_summary("op_step_seconds", operator=name)
        if s is None:
            continue
        ops[name] = {
            "steps": s["count"],
            "p50_ms": round(s["p50"] * 1e3, 3),
            "p95_ms": round(s["p95"] * 1e3, 3),
            "mean_ms": round(s["mean"] * 1e3, 3),
            "rows_in": 0,
            "rows_out": 0,
        }
    for series in snap["counters"].get("op_rows", {}).get("series", []):
        labels = series["labels"]
        op = ops.setdefault(labels.get("operator", ""), {
            "steps": 0, "p50_ms": 0.0, "p95_ms": 0.0, "mean_ms": 0.0,
            "rows_in": 0, "rows_out": 0,
        })
        key = "rows_in" if labels.get("direction") == "in" else "rows_out"
        op[key] = int(series["value"])
    backlog = {
        k: int(v)
        for k, v in REGISTRY.labelled(
            "engine_backlog", "queue", kind="gauge"
        ).items()
    }
    held = {
        k: int(v)
        for k, v in REGISTRY.labelled(
            "op_held_rows", "operator", kind="gauge"
        ).items()
    }
    lag = REGISTRY.labelled("watermark_lag", "operator", kind="gauge")
    frontier = REGISTRY.gauge_value("engine_frontier_lag")
    out: dict = {
        "operators": {k: ops[k] for k in sorted(ops)},
        "backlog": backlog,
        "held_rows": held,
        "watermark_lag": {k: round(v, 6) for k, v in sorted(lag.items())},
        "exchange": {
            k: int(v)
            for k, v in REGISTRY.labelled(
                "exchange_rows", "direction"
            ).items()
        },
    }
    if frontier is not None:
        out["frontier_lag"] = frontier
    summaries = [o["p50_ms"] for o in ops.values() if o.get("steps")]
    out["op_latency_p50_ms"] = (
        round(sum(summaries) / len(summaries), 3) if summaries else 0.0
    )
    return out


def reset_engine_stats() -> None:
    REGISTRY.remove(
        "op_step_seconds", "op_rows", "op_held_rows", "watermark_lag",
        "engine_backlog", "engine_frontier_lag", "exchange_rows",
        "consolidate_rows",
    )


# --------------------------------------------------------------------- #
# HBM ledger
#
# models/decoder.py `pool_bytes` knows how big ONE pool is the moment it
# is built; the ledger keeps that knowledge live and cumulative:
# per-component current bytes (`hbm_bytes{component=}`), per-component
# high-water, and a `total` high-water across all components — the
# number a capacity planner actually wants. Components re-record freely
# (pool rebuilds overwrite current, high-water is monotone). State lives
# in a module dict so the total high-water is computed atomically even
# though the registry only sees per-series writes.
#
# Under a serving mesh (PATHWAY_TPU_MESH) the ledger is PER DEVICE:
# callers pass the device id a shard lives on and each (component,
# device) cell tracks its own current + high-water, with
# `hbm_bytes{component=,device=}` series alongside the
# device-aggregated `hbm_bytes{component=}` the existing dashboards
# read. Single-chip callers omit the label and land on device "0", so
# every pre-mesh key and gauge keeps its exact value — capacity
# planning against the TIGHTEST device reads `per_device_*`.

_hbm_lock = make_lock("probes.hbm")
_hbm_current: dict[tuple[str, str], int] = {}  # (component, device)
_hbm_high_water: dict[str, int] = {}           # component (+ "total")
_hbm_dev_high_water: dict[str, int] = {}       # device total

_GUARDED_BY = {
    "_hbm_current": "_hbm_lock",
    "_hbm_high_water": "_hbm_lock",
    "_hbm_dev_high_water": "_hbm_lock",
    "_retrieval_backends": "_hbm_lock",
}


def record_hbm(component: str, nbytes: int, device: str = "0") -> None:
    """Record ``component``'s current device-memory footprint (bytes)
    on ``device`` (a device id; "0" for single-chip callers). Updates
    the per-(component, device) current gauge, the device-aggregated
    per-component gauge + high-water, the cross-component ``total``
    high-water, and the per-device total high-water. Called at
    pool/arena build time — never on the per-token path."""
    if not REGISTRY.enabled:
        return
    n = int(nbytes)
    dev = str(device)
    with _hbm_lock:
        _hbm_current[(component, dev)] = n
        comp_total = sum(
            v for (c, _), v in _hbm_current.items() if c == component
        )
        if comp_total > _hbm_high_water.get(component, -1):
            _hbm_high_water[component] = comp_total
        total = sum(_hbm_current.values())
        if total > _hbm_high_water.get("total", -1):
            _hbm_high_water["total"] = total
        dev_total = sum(
            v for (_, d), v in _hbm_current.items() if d == dev
        )
        if dev_total > _hbm_dev_high_water.get(dev, -1):
            _hbm_dev_high_water[dev] = dev_total
        high = dict(_hbm_high_water)
        dev_high = dict(_hbm_dev_high_water)
    REGISTRY.gauge_set("hbm_bytes", n, component=component, device=dev)
    REGISTRY.gauge_set("hbm_bytes", comp_total, component=component)
    for comp, hw in high.items():
        REGISTRY.gauge_max("hbm_high_water_bytes", hw, component=comp)
    for d, hw in dev_high.items():
        REGISTRY.gauge_max("hbm_high_water_bytes", hw, component="total",
                           device=d)


def hbm_stats() -> dict:
    """Snapshot: current bytes per component (aggregated over devices),
    per-component high-water, the total high-water across components,
    and the per-device breakdown (``per_device_bytes`` /
    ``per_device_high_water_bytes``, plus ``device_bytes`` nesting
    component rows per device for `cli stats`). Single-chip all
    per-device views carry the one key "0"."""
    with _hbm_lock:
        current = dict(_hbm_current)
        high = dict(_hbm_high_water)
        dev_high = dict(_hbm_dev_high_water)
    comp_cur: dict[str, int] = {}
    dev_cur: dict[str, int] = {}
    dev_comp: dict[str, dict[str, int]] = {}
    for (c, d), v in current.items():
        comp_cur[c] = comp_cur.get(c, 0) + v
        dev_cur[d] = dev_cur.get(d, 0) + v
        dev_comp.setdefault(d, {})[c] = dev_comp.get(d, {}).get(c, 0) + v
    total_high = high.pop("total", sum(comp_cur.values()))
    return {
        "current_bytes": {k: comp_cur[k] for k in sorted(comp_cur)},
        "high_water_bytes": {k: high[k] for k in sorted(high)},
        "current_total_bytes": sum(comp_cur.values()),
        "high_water_total_bytes": total_high,
        "per_device_bytes": {k: dev_cur[k] for k in sorted(dev_cur)},
        "per_device_high_water_bytes": {
            k: dev_high[k] for k in sorted(dev_high)
        },
        "device_bytes": {
            d: {c: dev_comp[d][c] for c in sorted(dev_comp[d])}
            for d in sorted(dev_comp)
        },
    }


def reset_hbm_stats() -> None:
    with _hbm_lock:
        _hbm_current.clear()
        _hbm_high_water.clear()
        _hbm_dev_high_water.clear()
    REGISTRY.remove("hbm_bytes", "hbm_high_water_bytes")


def record_kv_fragmentation(value: float, server: str = "decoder") -> None:
    """Set the ``kv_fragmentation{server=}`` gauge: the fraction of the
    serving pool's allocated KV bytes that no active request can reach
    (1 - reachable/allocated over admitted slots; 0.0 when idle). The
    dense right-padded pool strands every slot's row tail beyond its
    prompt+budget, so short requests push this past 0.3; paged
    allocation strands at most the final partial block per request.
    Updated by ``_ContinuousServer`` at every admission and drain."""
    REGISTRY.gauge_set("kv_fragmentation", value, server=server)


def kv_fragmentation_value(server: str = "decoder"):
    """Current ``kv_fragmentation`` gauge for ``server`` (None before
    the first admission)."""
    return REGISTRY.labelled(
        "kv_fragmentation", "server", kind="gauge"
    ).get(server)


def record_kv_parked(nbytes: float, server: str = "decoder") -> None:
    """Set the ``kv_parked_bytes{server=}`` gauge: device KV bytes held
    by PREEMPTED requests' parked block rows. Parked blocks are held ON
    PURPOSE — re-admission reuses their computed prompt KV by table
    edit — so they are classified apart from ``kv_fragmentation``:
    counting them as stranded would make the fragmentation signal lie
    under budget preemption."""
    REGISTRY.gauge_set("kv_parked_bytes", nbytes, server=server)


def kv_parked_value(server: str = "decoder"):
    """Current ``kv_parked_bytes`` gauge for ``server`` (None before the
    first preemption)."""
    return REGISTRY.labelled(
        "kv_parked_bytes", "server", kind="gauge"
    ).get(server)


# --------------------------------------------------------------------- #
# device-dispatch counters (registry shim)

_current_op = threading.local()  # set by Scheduler._step_node


def record_device_dispatch(kind: str, n: int = 1) -> None:
    """Count ``n`` accelerator round trips of ``kind`` (e.g. ``embed_submit``,
    ``knn_append``). Cheap and thread-safe: called from kernel wrappers on
    every dispatch. When a scheduler step is on the stack the count is also
    attributed to the stepping operator (always — operator attribution is
    scheduler accounting, not registry telemetry, so the kill switch does
    not gate it)."""
    REGISTRY.counter_add("device_dispatch", n, kind=kind)
    op = getattr(_current_op, "stats", None)
    if op is not None:
        op.dispatches += n


def record_knn_search(queries: int, bucket: int) -> None:
    """One ``knn_search`` dispatch: the queries asked for (``padded=0``)
    and the rows of the pow2 bucket searched for them (``padded=1``: one
    query is searched as sixteen)."""
    record_device_dispatch("knn_search")
    REGISTRY.counter_add_many(
        "knn_search_queries", "padded", {0: queries, 1: bucket}
    )


def dispatch_counts() -> dict[str, int]:
    return {
        k: int(v)
        for k, v in REGISTRY.labelled("device_dispatch", "kind").items()
    }


def reset_dispatch_counts() -> None:
    REGISTRY.remove("device_dispatch")


# --------------------------------------------------------------------- #
# compilations (ROADMAP S8): a compile inside a serving window is a stall
# an operator has to be able to see

def _on_jax_duration(event: str, duration: float, **_kw) -> None:
    if event.startswith("/jax/core/compile/"):
        REGISTRY.counter_add("compile_seconds", duration)
        if event.endswith("backend_compile_duration"):
            REGISTRY.counter_add("compiles")


def watch_compiles() -> None:
    """Count every XLA backend compilation, and the seconds JAX spends
    tracing, lowering and compiling, into the registry. Called once, by
    ``import pathway_tpu``."""
    import jax

    jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)


# --------------------------------------------------------------------- #
# retrieval-backend ledger (PATHWAY_TPU_MESH)
#
# Which index answered retrieval queries: ``dense`` (single-device
# brute force / IVF) or ``sharded_ivf`` (mesh-resident, one shard per
# device). Tests assert that mesh serving actually routed
# queries through the sharded index rather than silently falling back.

_retrieval_backends: dict[str, int] = {}  # backend -> queries served


def record_retrieval_backend(backend: str, n: int = 1) -> None:
    """Count ``n`` retrieval queries answered by ``backend``
    (``dense`` | ``ivf`` | ``sharded_ivf``). Thread-safe."""
    REGISTRY.counter_add("retrieval_queries", n, backend=backend)
    with _hbm_lock:
        _retrieval_backends[backend] = _retrieval_backends.get(backend, 0) + n


def retrieval_backend_stats() -> dict[str, int]:
    """``{backend: queries}`` since the last reset (metrics-off safe:
    the host dict is kept even when the registry is disabled)."""
    with _hbm_lock:
        return dict(_retrieval_backends)


def reset_retrieval_backend_stats() -> None:
    with _hbm_lock:
        _retrieval_backends.clear()
    REGISTRY.remove("retrieval_queries")


# --------------------------------------------------------------------- #
# cascade-rerank ledger
#
# The cascade's whole point is skipped compute, so the ledger counts what
# each stage actually paid: pairs scored and model FLOPs per stage
# (``cheap`` = truncated-depth pass over all k candidates, ``maxsim`` =
# late-interaction MaxSim over the ingest-time token banks, ``full`` =
# full-depth pass over survivors only). ``cascade_stats()['survivor_rate']``
# is the fraction of candidates that reached the full pass — the knob the
# quality/latency trade hangs on — and the per-stage FLOPs expose the
# cheap-stage pair-FLOPs collapse when MaxSim replaces the encoder pass.

def record_cascade(stage: str, pairs: int, flops: float = 0.0) -> None:
    """Account ``pairs`` scored (and model ``flops`` paid) by cascade
    ``stage`` (``cheap`` / ``maxsim`` / ``full``). Thread-safe; called
    per dispatch by the fused query path."""
    REGISTRY.counter_add("cascade_pairs", pairs, stage=stage)
    if flops:
        REGISTRY.counter_add("cascade_flops", flops, stage=stage)


def cascade_stats() -> dict:
    """Snapshot: per-stage pairs + FLOPs, and the survivor rate (full-pass
    pairs / first-stage pairs, with ``cheap`` and ``maxsim`` both counting
    as a first stage; 1.0 when the cascade never ran — every candidate
    'survived' into the only pass there was)."""
    pairs = {
        k: int(v) for k, v in REGISTRY.labelled("cascade_pairs", "stage").items()
    }
    flops = REGISTRY.labelled("cascade_flops", "stage")
    cheap = pairs.get("cheap", 0) + pairs.get("maxsim", 0)
    full = pairs.get("full", 0)
    rate = (full / cheap) if cheap else 1.0
    return {
        "pairs": pairs,
        "gflops": {k: round(v / 1e9, 3) for k, v in flops.items()},
        "survivor_rate": round(rate, 4),
    }


def reset_cascade_stats() -> None:
    REGISTRY.remove("cascade_pairs", "cascade_flops")


# --------------------------------------------------------------------- #
# prefix-KV-cache ledger
#
# Like the cascade ledger, the prefix cache's whole point is SKIPPED
# compute: ``hit_tokens`` counts prompt tokens whose KV was reused from
# the arena instead of re-prefilled (== prefill tokens saved),
# ``miss_tokens`` the tokens that still paid prefill. ``cached_bytes``
# tracks the arena's resident KV bytes (insert adds, evict subtracts),
# so the HBM budget is observable, not just enforced.

def record_prefix(kind: str, n: float = 1) -> None:
    """Account ``n`` of ``kind`` (``hit_tokens`` / ``miss_tokens`` /
    ``requests`` / ``hit_requests`` / ``inserted_blocks`` /
    ``evicted_blocks`` / ``copy_bytes`` / ``cached_bytes`` — the last is
    a running delta, negative on eviction, stored as a gauge).
    ``copy_bytes`` counts HBM bytes physically DUPLICATED to serve a
    hit: the dense pool's arena->slot block copies. A cache hit that
    copies still saves the prefill compute, but the "tokens saved" claim
    costs those bytes twice — under the paged pool hits pin shared
    blocks instead, so the counter staying at zero is the copy-on-write
    proof. Thread-safe; called by the serving loop and
    :class:`pathway_tpu.engine.prefix_cache.PrefixCache`."""
    if kind == "cached_bytes":
        REGISTRY.gauge_add("prefix_cached_bytes", n)
    else:
        REGISTRY.counter_add("prefix_events", n, kind=kind)


def prefix_stats() -> dict:
    """Snapshot: raw counters plus the token-level ``hit_rate``
    (hit_tokens / (hit_tokens + miss_tokens); 0.0 when the cache never
    saw a prompt) and ``prefill_tokens_saved`` (== hit_tokens)."""
    c = REGISTRY.labelled("prefix_events", "kind")
    cached = REGISTRY.gauge_value("prefix_cached_bytes")
    if cached is not None:
        c["cached_bytes"] = cached
    hit = c.get("hit_tokens", 0)
    miss = c.get("miss_tokens", 0)
    total = hit + miss
    t2_l = c.get("t2_lookups", 0)
    t2_h = c.get("t2_hits", 0)
    return {
        "counts": {k: (int(v) if float(v).is_integer() else v)
                   for k, v in c.items()},
        "hit_rate": round(hit / total, 4) if total else 0.0,
        "prefill_tokens_saved": int(hit),
        "evicted_blocks": int(c.get("evicted_blocks", 0)),
        "cached_bytes": int(c.get("cached_bytes", 0)),
        "copy_bytes": int(c.get("copy_bytes", 0)),
        # tier-2 (host-RAM) store: lookups past a tier-1 match, hits
        # (demoted edges recovered for promotion) and the block-level
        # demote/promote traffic
        "hit_rate_t2": round(t2_h / t2_l, 4) if t2_l else 0.0,
        "t2_lookups": int(t2_l),
        "t2_hits": int(t2_h),
        "t2_hit_blocks": int(c.get("t2_hit_blocks", 0)),
        "t2_demoted_blocks": int(c.get("t2_demoted_blocks", 0)),
        "t2_promoted_blocks": int(c.get("t2_promoted_blocks", 0)),
    }


def reset_prefix_stats() -> None:
    REGISTRY.remove("prefix_events", "prefix_cached_bytes")


# --------------------------------------------------------------------- #
# speculative-decode ledger
#
# Spec decode trades cheap shallow draft steps for multi-token
# full-model verifies; whether that wins depends entirely on the
# acceptance rate, so the ledger's job is to make it observable.
# ``drafted`` counts draft tokens proposed, ``accepted`` the ones the
# verify pass kept, ``emitted`` the total tokens produced (accepted +
# one certain token per lane-cycle), ``verify_steps`` the full-model
# lane-cycles paid (the unit a plain decode step would also cost) and
# ``draft_steps`` the shallow lane-steps paid. ``kv_bytes_saved`` is the
# HBM the int8 pool did NOT allocate vs bf16 (recorded once at pool
# init). tokens_per_dispatch = emitted / verify_steps is the headline:
# 1.0 is plain decode, anything above is amortized weight streaming.

def record_spec(kind: str, n: float = 1) -> None:
    """Account ``n`` of ``kind`` (``drafted`` / ``accepted`` /
    ``emitted`` / ``verify_steps`` / ``draft_steps`` / ``dispatches`` /
    ``kv_bytes_saved``). Thread-safe; called by the continuous server's
    drain (token accounting) and pool init (KV bytes)."""
    REGISTRY.counter_add("spec_events", n, kind=kind)


def record_spec_many(**counts: float) -> None:
    """Batched :func:`record_spec`: one lock acquisition for a whole spec
    cycle's counters — the drain path records six kinds per dispatch and
    sits on the decode critical path."""
    REGISTRY.counter_add_many("spec_events", "kind", counts)


def spec_stats() -> dict:
    """Snapshot: raw counters plus ``acceptance_rate`` (accepted /
    drafted; 0.0 before any draft ran) and ``tokens_per_dispatch``
    (emitted / verify_steps; 1.0 is the plain-decode baseline)."""
    c = REGISTRY.labelled("spec_events", "kind")
    drafted = c.get("drafted", 0)
    accepted = c.get("accepted", 0)
    emitted = c.get("emitted", 0)
    verify = c.get("verify_steps", 0)
    return {
        "counts": {k: int(v) for k, v in c.items()},
        "acceptance_rate": round(accepted / drafted, 4) if drafted else 0.0,
        "tokens_per_dispatch": round(emitted / verify, 4) if verify else 0.0,
        "kv_bytes_saved": int(c.get("kv_bytes_saved", 0)),
    }


def reset_spec_stats() -> None:
    REGISTRY.remove("spec_events")


@dataclasses.dataclass
class OperatorStats:
    name: str
    rows_in: int = 0
    rows_out: int = 0
    epochs: int = 0
    total_time_s: float = 0.0
    last_active_time: float = 0.0
    dispatches: int = 0

    @property
    def lag_s(self) -> float:
        return max(0.0, time.time() - self.last_active_time)


@dataclasses.dataclass
class ConnectorStats:
    name: str
    rows_read: int = 0
    commits: int = 0
    finished: bool = False


@guarded_by(operators="_lock", connectors="_lock", steps_skipped="_lock")
class SchedulerStats:
    """Thread-safe stats registry attached to a live scheduler.

    Only the collections (and the skip counter) are guarded:
    ``current_time`` / ``epochs_total`` / ``finished`` / ``fused_*`` are
    written by the single scheduler thread before workers start or after
    they stop, so declaring them guarded would be a lie the analyzer
    rightly rejects."""

    def __init__(self) -> None:
        self._lock = make_lock("probes.scheduler_stats")
        self.operators: dict[int, OperatorStats] = {}
        # keyed by connector node id (names may collide across connectors)
        self.connectors: dict[int, ConnectorStats] = {}
        self.current_time: int = -1
        self.epochs_total: int = 0
        self.started_at: float = time.time()
        self.finished: bool = False
        # chain-fusion plan summary (set by the scheduler after fuse_chains)
        self.fused_chains: int = 0
        self.fused_nodes: int = 0
        # epochs where a node's step was skipped (no input deltas, no
        # injection) — the sparse-stepping win made countable
        self.steps_skipped: int = 0

    def operator(self, node_id: int, name: str) -> OperatorStats:
        with self._lock:
            stats = self.operators.get(node_id)
            if stats is None:
                stats = self.operators[node_id] = OperatorStats(name=name)
            return stats

    def connector(self, node_id: int, name: str) -> ConnectorStats:
        with self._lock:
            stats = self.connectors.get(node_id)
            if stats is None:
                stats = self.connectors[node_id] = ConnectorStats(name=name)
            return stats

    def record_connector_commit(self, node_id: int, name: str, n_rows: int) -> None:
        stats = self.connector(node_id, name)
        with self._lock:
            stats.rows_read += n_rows
            stats.commits += 1

    def connector_finished(self, node_id: int, name: str) -> None:
        self.connector(node_id, name).finished = True

    def record_skip(self) -> None:
        with self._lock:
            self.steps_skipped += 1

    def record_step(
        self, node_id: int, name: str, rows_in: int, rows_out: int, dt: float
    ) -> None:
        stats = self.operator(node_id, name)
        with self._lock:
            stats.rows_in += rows_in
            stats.rows_out += rows_out
            stats.epochs += 1
            stats.total_time_s += dt
            stats.last_active_time = time.time()

    def snapshot(self) -> dict:
        """Plain-dict snapshot for renderers/exporters."""
        with self._lock:
            return {
                "current_time": self.current_time,
                "epochs_total": self.epochs_total,
                "uptime_s": time.time() - self.started_at,
                "finished": self.finished,
                "fused_chains": self.fused_chains,
                "fused_nodes": self.fused_nodes,
                "steps_skipped": self.steps_skipped,
                "operators": [dataclasses.asdict(s) for s in self.operators.values()],
                "connectors": [dataclasses.asdict(s) for s in self.connectors.values()],
            }
