"""Readers of per-layer metrics. A metric's file
(``benchmarks/metrics/<metric>.json``) names one of these and gives its
parameters; a metric that needs other code ships ``<metric>.py`` with a
``read(ctx, params)`` beside its json. A reader that finds nothing to read
returns ``None`` and the metric is left out of the line — never 0 for a
share. A share of a roofline or of a peak above 100 % is a fault of the
count: the reader raises, and the run fails, rather than print it.

A roofline's work is named in the metric's file (``params.work``) and found
by ``manifest.resolve``: one of :data:`WORK`, or ``work(ctx, runs)`` of a
``<path>/work/<name>.py``.

``ctx``: ``manifest``, ``trace`` (:class:`harness.trace.TraceSummary` or
None), ``counters`` (whole window), ``slice_counters`` (the traced slice),
``lifetime_counters`` (since the process began),
``spans`` (name -> list of ms), ``peaks``, ``config`` (with ``layouts``:
role -> its model's layout), ``traffic``, ``window_s``, ``facts`` (readers
may add what they learned: which bound).
"""

from __future__ import annotations

import statistics

from . import manifest as M
from . import work


class ShareAbove100(ValueError):
    pass


def _share(name: str, value: float) -> float:
    if value > 100.0:
        raise ShareAbove100(
            f"{name} = {value:.2f} % of a roofline or peak: the work is "
            f"counted too high, or the time leaves out part of it")
    return value


def trace_idle(ctx, params):
    t = ctx["trace"]
    if t is None:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)


def span_median(ctx, params):
    values = ctx["spans"].get(params["span"])
    return statistics.median(values) if values else None


def counter_ratio(ctx, params):
    c = ctx["counters"]
    num, den = c.get(params["numerator"]), c.get(params["denominator"])
    if num is None or not den:
        return None
    value = params.get("scale", 1.0) * num / den
    return _share(params["numerator"], value) if params.get("share") else value


def _per_run(ctx, what: str, runs_counter: str) -> float:
    """How much of ``what`` one dispatch carried, on average over the whole
    life of the process (both are the program's own counters; a ratio over
    the traced slice alone would be off by a burst at either edge)."""
    life = ctx["lifetime_counters"]
    return life.get(what, 0) / max(life.get(runs_counter, 0), 1)


def _embed_step(ctx, runs):
    """Each run of the embed executable carried the embedder's documents
    per dispatch, at the tokens a document needs (padding is not work)."""
    model = ctx["config"]["models"]["embedder"]
    layout = ctx["config"]["layouts"]["embedder"]
    docs = _per_run(ctx, "embed_dedup_misses", "dispatch_embed_dispatch")
    tokens = ctx["config"]["deployment"]["doc_words"] + 2
    ctx["facts"]["embed_docs_per_dispatch"] = docs
    return (runs * docs * layout.encoder_flops(model, tokens),
            runs * layout.encoder_bytes(model, docs, tokens))


def _knn_search(ctx, runs):
    """Every run reads the whole capacity once; the queries of the slice
    are spread over the runs."""
    dep = ctx["config"]["deployment"]
    cap, dim = dep["index_capacity"], dep["index_dimensions"]
    queries = ctx["slice_counters"].get("requests_completed", 0)
    return (work.knn_scan_flops(queries, cap, dim),
            runs * work.knn_scan_bytes(cap, dim))


WORK = {"embed_step": _embed_step, "knn_search": _knn_search}


def trace_module_roofline(ctx, params):
    """Least time for the work the slice needed (from shapes and counts)
    over the device time of every run of the named executables (found by
    XLA module name; layout copies XLA puts inside them are in that
    time)."""
    t = ctx["trace"]
    if t is None:
        return None
    runs, seconds = t.module_seconds(params["modules"])
    if not runs or seconds <= 0:
        return None
    flops, nbytes = M.resolve(ctx["manifest"], "work", params["work"])(
        ctx, runs)
    if flops <= 0 and nbytes <= 0:
        return None
    least, bound = work.least_seconds(flops, nbytes, ctx["peaks"])
    ctx["facts"].setdefault("roofline", {})[params["work"]] = {
        "bound": bound, "runs": runs, "device_s": seconds,
        "least_s": least, "flops": flops, "bytes": nbytes}
    return _share(params["work"], 100.0 * least / seconds)


READERS = {"trace_idle": trace_idle, "span_median": span_median,
           "counter_ratio": counter_ratio,
           "trace_module_roofline": trace_module_roofline}
