"""What the PROGRAM says of a run, for the per-layer metrics that read it:
its ``pw.`` regions in the traced slice's host plane (the program writes
them as ``jax.profiler.TraceAnnotation``s, so they share the device's time
base), its request spans (``engine/tracing.py``'s ring) and its counters
(``engine/probes.py``'s registry).

``ctx`` holds neither the trace's path nor the window's edges, so the slice's
file is found as ``run.py`` leaves it (``<checkout>/.bench_trace/*``, still
there when readers run) and clipped to the summary's ``lo`` / ``hi``. Spans
and counters cover the process's life, as ``readers._per_run`` does. A
program that has no such region, span kind or counter (the parent of the PR
that added them) gives every reader here nothing to read: it returns
``None`` and the metric is left out of the line.

The reduction works on plain lists — :func:`leaf_segments` and
:func:`attribute_idle` take ``(thread, name, start_ns, duration_ns, stats)``
tuples — so a small recorded list checks it without a chip.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import statistics

from . import trace as T
from .manifest import ROOT

# thread, name, start_ns, duration_ns, stats
Region = tuple[int, str, int, int, dict]

ENGINE = "pw.engine."
WAIT = "pw.engine.wait_ready"
EPOCH = "pw.engine.epoch"
OP = "pw.engine.op"
UNATTRIBUTED = "unattributed"


def slice_xplane() -> str | None:
    found = glob.glob(os.path.join(
        ROOT, ".bench_trace", "*", "plugins", "profile", "*", "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def load_host_regions(path: str) -> list[Region]:
    """Every ``pw.`` event of the host planes; a thread is one line."""
    from jax.profiler import ProfileData

    out: list[Region] = []
    thread = 0
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            thread += 1
            for ev in line.events:
                if ev.name.startswith("pw."):
                    out.append((thread, ev.name, int(ev.start_ns),
                                int(ev.duration_ns), dict(ev.stats)))
    return out


def slice_regions(ctx) -> list[Region]:
    """The slice's regions, clipped to the summary's edges; loaded once a
    run (kept in ``ctx``)."""
    if "host_regions" not in ctx:
        summary, path = ctx.get("trace"), slice_xplane()
        clipped = []
        if summary is not None and path:
            for thread, name, start, dur, stats in load_host_regions(path):
                a, b = max(start, summary.lo), min(start + dur, summary.hi)
                if b > a:
                    clipped.append((thread, name, a, b - a, stats))
        ctx["host_regions"] = clipped
    return ctx["host_regions"]


def leaf_segments(regions: list[Region]) -> list[tuple[int, int, tuple]]:
    """One thread's nested regions, flattened: ``(start, end, stack)`` in
    time order and never overlapping, ``stack`` the ``(name, stats)`` of
    every region open there, the deepest last."""
    out: list[tuple[int, int, tuple]] = []
    stack: list[tuple[int, str, dict]] = []    # end, name, stats
    cursor = 0

    def emit(upto: int) -> None:
        nonlocal cursor
        if stack and upto > cursor:
            out.append((cursor, upto, tuple((n, s) for _e, n, s in stack)))
        cursor = max(cursor, upto)

    for _th, name, start, dur, stats in sorted(
            regions, key=lambda r: (r[2], -r[3])):
        while stack and stack[-1][0] <= start:
            emit(stack[-1][0])
            stack.pop()
        emit(start)
        # a child never outlives its parent (clock jitter at the edges)
        end = min(start + dur, stack[-1][0]) if stack else start + dur
        stack.append((end, name, stats))
    while stack:
        emit(stack[-1][0])
        stack.pop()
    return out


def _overlapping(segments, starts, a: int, b: int):
    i = max(bisect.bisect_right(starts, a) - 1, 0)
    while i < len(segments) and segments[i][0] < b:
        s, e, stack = segments[i]
        if e > a:
            yield max(s, a), min(e, b), stack
        i += 1


def _subtract(intervals: list[tuple[int, int]], a: int, b: int):
    out = []
    for s, e in intervals:
        if e <= a or s >= b:
            out.append((s, e))
            continue
        if s < a:
            out.append((s, a))
        if e > b:
            out.append((b, e))
    return out


def attribute_idle(gaps: list[tuple[int, int]], regions: list[Region]):
    """Split every idle gap ``(start_ns, duration_ns)`` of the device, by
    time, among the deepest regions open on the thread that runs
    ``pw.engine.epoch``. While that thread waits for a ready time the
    cause is the deepest region of any other thread, else the wait itself;
    time under no region is ``unattributed``. Returns ``(nanoseconds by
    region name, nanoseconds by operator under pw.engine.op)``, or
    ``None`` where no thread ran the engine.

    The profiler keeps a region only if it began AND ended inside the
    session, and a slice of 3 s holds one whole epoch of cell 3 as a rule
    (6 of 8 slices; in 1 of 13 none: my chip runs, PR 27). So where no
    epoch is whole the engine's thread is the one with the most
    ``pw.engine.`` regions (its operators and waits are whole), and the
    epoch's own time outside them reads ``unattributed``."""
    threads: dict[int, list[Region]] = {}
    for r in regions:
        threads.setdefault(r[0], []).append(r)
    engine = max(threads, default=None, key=lambda th: (
        sum(r[1] == EPOCH for r in threads[th]),
        sum(r[1].startswith(ENGINE) for r in threads[th])))
    if engine is None or not any(r[1].startswith(ENGINE)
                                 for r in threads[engine]):
        return None
    segments = {th: leaf_segments(rs) for th, rs in threads.items()}
    starts = {th: [s[0] for s in segs] for th, segs in segments.items()}
    others = sorted(th for th in threads if th != engine)
    by_region: dict[str, int] = {}
    by_op: dict[str, int] = {}

    def add(into: dict, key: str, ns: int) -> None:
        if ns > 0:
            into[key] = into.get(key, 0) + ns

    for g_start, g_dur in gaps:
        a, b = g_start, g_start + g_dur
        named = 0
        for s, e, stack in _overlapping(segments[engine], starts[engine],
                                        a, b):
            named += e - s
            leaf = stack[-1][0]
            for name, stats in reversed(stack):
                if name == OP:
                    add(by_op, str(stats.get("op", "?")), e - s)
                    break
            if leaf != WAIT:
                add(by_region, leaf, e - s)
                continue
            left = [(s, e)]
            for th in others:
                for os_, oe, ostack in _overlapping(segments[th], starts[th],
                                                    s, e):
                    for ls, le in left:
                        add(by_region, ostack[-1][0],
                            min(le, oe) - max(ls, os_))
                    left = _subtract(left, os_, oe)
            add(by_region, WAIT, sum(le - ls for ls, le in left))
        if b - a > named:
            add(by_region, UNATTRIBUTED, b - a - named)
    return by_region, by_op


def region_seconds(regions: list[Region]) -> tuple[dict, dict]:
    """Seconds under each region name (inclusive of its children) and
    under ``pw.engine.op`` by operator, over every thread."""
    by_name: dict[str, float] = {}
    by_op: dict[str, float] = {}
    for _th, name, _start, dur, stats in regions:
        by_name[name] = by_name.get(name, 0.0) + dur / 1e9
        if name == OP:
            op = str(stats.get("op", "?"))
            by_op[op] = by_op.get(op, 0.0) + dur / 1e9
    return by_name, by_op


def _top(table: dict, n: int = 12) -> dict:
    rows = sorted(table.items(), key=lambda kv: -kv[1])[:n]
    return {k: round(v, 6) for k, v in rows}


def idle_attributed_pct(ctx, params):
    """Share of the slice's device-idle time under a named region; prints
    the ``host_attribution`` line (idle seconds by region and by operator,
    and all seconds by region and by operator)."""
    summary = ctx.get("trace")
    regions = slice_regions(ctx)
    if summary is None or not regions:
        return None
    first = next(iter(summary.devices.values()))
    events = T.clip(first["ops"] or first["modules"], summary.lo, summary.hi)
    gaps = T.gaps(events, summary.lo, summary.hi)
    split = attribute_idle(gaps, regions)
    idle_ns = sum(d for _s, d in gaps)
    if split is None or idle_ns <= 0:
        return None
    by_region, by_op = split
    seconds, op_seconds = region_seconds(regions)
    print(json.dumps({
        "phase": "host_attribution", "idle_s": idle_ns / 1e9,
        "idle_by_region_s": _top({k: v / 1e9 for k, v in by_region.items()}),
        "idle_by_op_s": _top({k: v / 1e9 for k, v in by_op.items()}),
        "region_s": _top(seconds, 24), "op_s": _top(op_seconds),
        "regions": len(regions),
    }), flush=True)
    return 100.0 * (idle_ns - by_region.get(UNATTRIBUTED, 0)) / idle_ns


def program_spans(kind: str) -> list[dict]:
    """Finished spans of ``kind`` still in the program's ring."""
    from pathway_tpu.engine import tracing

    return [s for s in tracing.recent_traces(kind=kind)
            if not s["attrs"].get("error")]


def span_metric_median(ctx, params):
    """Median over the ``kind`` spans of ``metric``, less ``minus`` where
    the file names one (``e2e_ms`` less ``queue_wait_ms``: the part of an
    epoch in which the engine worked); over the last ``counters[last]``
    spans where it names a window counter."""
    names = [params["metric"]] + ([params["minus"]] if "minus" in params
                                  else [])
    spans = program_spans(params["kind"])
    if "last" in params:
        # as many as the generator counted in the window: set-up's spans,
        # which the ring still holds, are another population
        n = int(ctx["counters"].get(params["last"], 0))
        spans = spans[-n:] if n else spans
    values = []
    for s in spans:
        got = [s["metrics"].get(n) for n in names]
        if None not in got:
            values.append(got[0] - sum(got[1:]))
    return statistics.median(values) if values else None


def requests_per_epoch(ctx, params):
    """Mean number of REST requests among the ``epoch`` spans that carried
    any (today every request is a commit, and an epoch, of its own)."""
    counts = [len(s["attrs"]["requests"]) for s in program_spans("epoch")
              if s["attrs"].get("requests")]
    return sum(counts) / len(counts) if counts else None


def registry_ratio(ctx, params):
    """One registry counter's series over another's (``[family, label,
    value]`` each), since the process began."""
    from pathway_tpu.engine import probes

    def series(family, label, value):
        return probes.REGISTRY.labelled(family, label).get(str(value))

    num, den = series(*params["numerator"]), series(*params["denominator"])
    if num is None or not den:
        return None
    return num / den
