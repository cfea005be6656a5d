"""The device's time in a traced slice by the program's own named scopes.

The program registers each executable its servers build
(``engine/tracing.py:register_executable``): its module name, what one run
carries, the HLO shapes of its arguments and results. The trace is the one
source of the scopes: a profiler session keeps the compiled HLO of every
program that ran on its ``/host:metadata`` plane, keyed by the program id
its runs carry (``jit_piece(<id>)`` on a device's ``XLA Modules`` line), and
each instruction keeps its ``op_name``. An op is named by the innermost
named scope (``<layer>.<what>``) of its OWN instruction's ``op_name``: a
fusion by the fusion's, which is the compiler's naming of it (a TPU trace's
``tf_op`` stat shows the same); an instruction with none is ``unscoped``.

A program is the ONE registered executable :func:`match` gives: the first,
middle and last pieces and the grouped piece are all ``jit_piece``, and
decode chunks of 16, 8 and 4 steps are all ``jit_chunk``, so the module name
alone tells none of them apart; their shapes do. An op's SELF time is its
duration less what the ops nested in it cover (a ``while`` spans its body).
Only runs wholly inside the slice count, and each carries what its
registration says. A program that registers nothing (the parent of the PR
that added the registry) gives every reader nothing to read.
"""

from __future__ import annotations

import json
import re
import time
from collections import Counter

from . import program_trace
from . import trace as T

UNSCOPED = "unscoped"
_MODULE_RE = re.compile(r"^(.*)\((\d+)\)$")
_DEVICE_RE = re.compile(r"^/device:(TPU|GPU):\d+$")
# a named scope of the program: a dotted name, ``<layer>.<what>``
_SCOPE_RE = re.compile(r"^[a-z][a-z0-9_]*(?:\.[a-z0-9_]+)+$")
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%([^\s=]+) = ")
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_SHAPE_RE = re.compile(r"\b([a-z][a-z0-9]*)\[([0-9,]*)\]")
_LAYOUT = "entry_computation_layout={"


# ------------------------------------------- the trace file's metadata plane


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        byte = buf[i]
        i += 1
        out |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return out, i


def _fields(buf: bytes, lo: int = 0, hi: int | None = None):
    """``(field, value)`` of a protobuf message in ``buf[lo:hi]``: a varint
    as an int, a length-delimited value as its ``(start, end)``."""
    i, hi = lo, len(buf) if hi is None else hi
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire in (1, 5):
            value, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, value


def program_hlo(path: str, modules: set[str]) -> dict[int, tuple[str, str]]:
    """Program id -> ``(module name, compiled HLO text)`` of every program
    of ``modules`` on the trace's ``/host:metadata`` plane (``XSpace.planes``
    → ``XPlane.event_metadata``, whose stat ``Hlo Proto`` is an
    ``HloProto``; ``jax.profiler.ProfileData`` does not read it)."""
    from jax._src.lib import _jax

    with open(path, "rb") as f:
        buf = f.read()
    out: dict[int, tuple[str, str]] = {}
    for field, plane in _fields(buf):
        if field != 1:
            continue
        name, entries, stat_names = None, [], {}
        for f2, v in _fields(buf, *plane):
            if f2 == 2:
                name = buf[v[0]:v[1]].decode(errors="replace")
            elif f2 in (4, 5) and name == "/host:metadata":
                value = dict(_fields(buf, *v)).get(2)
                if f2 == 4:
                    entries.append(value)
                else:
                    meta = dict(_fields(buf, *value))
                    if 2 in meta:
                        stat_names[meta.get(1, 0)] = buf[
                            meta[2][0]:meta[2][1]].decode()
        if name != "/host:metadata":
            continue
        for value in entries:
            program, proto = "", None
            for f3, w in _fields(buf, *value):
                if f3 == 2:
                    program = buf[w[0]:w[1]].decode(errors="replace")
                elif f3 == 5:
                    stat = dict(_fields(buf, *w))
                    if stat_names.get(stat.get(1, 0)) == "Hlo Proto" \
                            and 6 in stat:
                        proto = stat[6]
            m = _MODULE_RE.match(program)
            if not m or m.group(1) not in modules or proto is None:
                continue
            module = dict(_fields(buf, *proto)).get(1)  # HloProto.hlo_module
            if module is not None:
                out[int(m.group(2))] = (m.group(1), _jax.HloModule
                                        .from_serialized_hlo_module_proto(
                                            buf[module[0]:module[1]])
                                        .to_string())
    return out


# ------------------------------------------------- a program's HLO text


def hlo_scopes(text: str) -> dict[str, str | None]:
    """Instruction name -> the innermost named scope of its own
    ``op_name`` (``None``: unscoped), over a compiled module's text."""
    out: dict[str, str | None] = {}
    for line in text.splitlines():
        m = _INSTR_RE.match(line)
        if not m:
            continue
        scope = None
        op = _OP_NAME_RE.search(line)
        if op:
            for part in reversed(op.group(1).split("/")):
                if _SCOPE_RE.match(part):
                    scope = part
                    break
        out[m.group(1)] = scope
    return out


def entry_shapes(text: str) -> tuple[tuple[str, ...], tuple[str, ...]]:
    """The shapes of a compiled module's arguments and results, as its
    header's ``entry_computation_layout`` gives them, without layouts:
    ``(("bf16[16,512]", ...), ("f32[4]",))``."""
    head = text.split("\n", 1)[0]
    start = head.find(_LAYOUT)
    if start < 0:
        return (), ()
    i = start + len(_LAYOUT)
    depth = 1
    for end in range(i, len(head)):
        depth += (head[end] == "{") - (head[end] == "}")
        if depth == 0:
            break
    ins, _, outs = head[i:end].partition(")->")

    def shapes(s):
        return tuple(f"{t}[{d}]" for t, d in _SHAPE_RE.findall(s))

    return shapes(ins), shapes(outs)


def _gap(a: tuple, b: tuple) -> int:
    ca, cb = Counter(a), Counter(b)
    return ((ca - cb) + (cb - ca)).total()


def match(table: list[dict], module: str, params: tuple, results: tuple):
    """``(entry, gap)``: the ONE registered executable a program of
    ``module`` whose entry has these shapes is, and by how many shapes they
    differ. Of the registrations of that module name, the ones whose
    shapes differ least from the entry's (a compile drops an argument it
    never reads; a typed key is no HLO type) — entries alike in what a run
    carries (first, middle and last pieces) are one answer. ``(None,
    gap)`` where none is registered or the closest differ in what a run
    carries."""
    found = [(e, _gap(e["params"], params) + _gap(e["results"], results))
             for e in table if e["module"] == module]
    if not found:
        return None, None
    best = min(g for _e, g in found)
    closest = [e for e, g in found if g == best]
    if len({tuple(sorted(e["carries"].items())) for e in closest}) != 1:
        return None, best
    return closest[0], best


# ------------------------------------------------------ runs and self time


def _op_name(event_name: str) -> str:
    """``%fusion.2 = f32[..] fusion(..)`` (a TPU op) or ``fusion.2`` (a CPU
    op's own name) -> ``fusion.2``."""
    if event_name.startswith("%"):
        return event_name[1:event_name.find(" = ")]
    return event_name


def _whole_between(modules: list, ops: list, lo, hi) -> tuple:
    """The open interval a device run must lie in to be whole: the profiler
    cuts the runs the session began or ended inside of at its edges, so a
    run that touches the device line's first or last event is a part."""
    edges = [(s, e) for _n, s, e in modules] + [
        (s, s + d) for _n, s, d in ops]
    if not edges:
        return lo, hi
    return (max(lo, min(s for s, _e in edges)),
            min(hi, max(e for _s, e in edges)))


def device_runs(path: str, lo: int | None = None,
                hi: int | None = None) -> list[tuple]:
    """``(module, program, start, end, ops)`` of every module run of the
    first device plane strictly inside ``(lo, hi)`` and the line's own first
    and last events (:func:`_whole_between`), ``ops`` as ``[(line, name,
    start, duration)]`` (a TPU op is named by its instruction's text).
    Raises where the trace holds no device plane."""
    from jax.profiler import ProfileData

    lo = -1 if lo is None else lo
    hi = float("inf") if hi is None else hi
    device = sorted((p for p in ProfileData.from_file(path).planes
                     if _DEVICE_RE.match(p.name)), key=lambda p: p.name)
    if not device:
        raise ValueError(f"{path}: no device plane")
    lines = {ln.name: ln for ln in device[0].lines}

    def events(line):
        return sorted(((ev.name, int(ev.start_ns), int(ev.duration_ns))
                       for ev in (lines[line].events if line in lines
                                  else ())), key=lambda e: e[1])

    ops = events("XLA Ops")
    modules = [(n, s, s + d) for n, s, d in events("XLA Modules")]
    lo, hi = _whole_between(modules, ops, lo, hi)
    runs, i = [], 0
    for module_name, start, end in modules:
        while i < len(ops) and ops[i][1] < start:
            i += 1
        inside, j = [], i
        while j < len(ops) and ops[j][1] < end:
            inside.append((0,) + ops[j])
            j += 1
        m = _MODULE_RE.match(module_name)
        if m and lo < start and end < hi:
            runs.append((m.group(1), int(m.group(2)), start, end, inside))
    return runs


def host_runs(path: str) -> list[tuple]:
    """The same from a CPU trace, whose runs have no event of their own:
    the host threads' op events (stats ``hlo_op``, ``hlo_module``,
    ``program_id``, ``run_id``), a run the ops of one ``run_id``."""
    from jax.profiler import ProfileData

    found: dict = {}
    line_no = 0
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            line_no += 1
            for ev in ln.events:
                st = dict(ev.stats)
                if "hlo_op" in st and "hlo_module" in st:
                    found.setdefault(
                        (st["hlo_module"], int(st.get("program_id", -1)),
                         st.get("run_id")), []).append(
                            (line_no, st["hlo_op"], int(ev.start_ns),
                             int(ev.duration_ns)))
    runs = [(module, program, min(o[2] for o in ops),
             max(o[2] + o[3] for o in ops), ops)
            for (module, program, _run), ops in found.items()]
    return sorted(runs, key=lambda r: r[2])


def self_times(ops: list[tuple]) -> list[int]:
    """Each op's self time: its duration less what the ops nested in it on
    its line cover. ``ops`` as :func:`device_runs` gives them."""
    out = [d for _l, _n, _s, d in ops]
    by_line: dict = {}
    for k, (line, _n, start, dur) in enumerate(ops):
        by_line.setdefault(line, []).append((start, -dur, k))
    for items in by_line.values():
        stack: list[tuple[int, int]] = []   # (end, index) of open ops
        for start, neg, k in sorted(items):
            while stack and stack[-1][0] <= start:
                stack.pop()
            if stack:
                end, parent = stack[-1]
                out[parent] -= min(start - neg, end) - start
            stack.append((start - neg, k))
    return out


def _run_ns(run: tuple, on_device: bool) -> int:
    """A run's own time: on a device, its module event's duration (ops the
    trace dropped leave a gap); on the CPU, the union of its ops' intervals,
    line by line."""
    if on_device:
        return run[3] - run[2]
    by_line: dict = {}
    for line, name, start, dur in run[4]:
        by_line.setdefault(line, []).append((name, start, dur))
    return sum(T.union_ns(evs) for evs in by_line.values())


# ------------------------------------------------------------ the split


def split_runs(runs: list[tuple], hlo: dict, table: list[dict],
               on_device: bool = True) -> dict:
    """The runs' time by registered executable and by scope. ``hlo``:
    program id -> ``(module, text)`` (:func:`program_hlo`).
    ``executables``: one row for each kind of run some program matched
    (``module``, ``carries``, ``runs``, ``ns``: the runs' own time,
    ``by_scope_ns``, ``gap``: the largest share by which a run's self times
    missed its own time, ``shape_gap``, ``unnamed_ns``: ops its HLO does not
    name); ``unmatched_ns`` (registered module names: no HLO, or no one
    registration) and ``other_ns`` (modules nobody registered), by module."""
    registered = {e["module"] for e in table}
    programs: dict = {}
    for run in runs:
        programs.setdefault((run[0], run[1]), []).append(run)
    rows: dict = {}
    unmatched: dict = {}
    other: dict = {}
    for (module, pid), prun in programs.items():
        own = [_run_ns(r, on_device) for r in prun]
        if module not in registered:
            other[module] = other.get(module, 0) + sum(own)
            continue
        text = hlo.get(pid, (None, None))[1]
        entry, shape_gap = match(table, module, *entry_shapes(text)) \
            if text else (None, None)
        if entry is None:
            unmatched[module] = unmatched.get(module, 0) + sum(own)
            continue
        scopes = hlo_scopes(text)
        row = rows.setdefault((module, tuple(sorted(
                entry["carries"].items()))), {
            "module": module, "carries": dict(entry["carries"]), "runs": 0,
            "ns": 0, "by_scope_ns": {}, "gap": 0.0, "shape_gap": 0,
            "unnamed_ns": 0})
        row["shape_gap"] = max(row["shape_gap"], shape_gap)
        for r, run_ns in zip(prun, own):
            total = 0
            for (_l, name, _s, _d), ns in zip(r[4], self_times(r[4])):
                instruction = _op_name(name)
                if instruction not in scopes:
                    row["unnamed_ns"] += ns
                scope = scopes.get(instruction) or UNSCOPED
                row["by_scope_ns"][scope] = row["by_scope_ns"].get(
                    scope, 0) + ns
                total += ns
            row["runs"] += 1
            row["ns"] += run_ns
            if run_ns:
                row["gap"] = max(row["gap"], abs(total - run_ns) / run_ns)
    return {"executables": list(rows.values()), "unmatched_ns": unmatched,
            "other_ns": other, "table": len(table)}


def split_trace(path: str, table: list[dict], lo: int | None = None,
                hi: int | None = None, on_device: bool = True) -> dict:
    """:func:`split_runs` over a trace file: a device's runs inside the
    slice, or (``on_device`` false) a CPU trace's whole."""
    runs = device_runs(path, lo, hi) if on_device else host_runs(path)
    return split_runs(runs, program_hlo(path, {e["module"] for e in table}),
                      table, on_device)


def _seconds(table: dict, top: int = 12) -> dict:
    rows = sorted(table.items(), key=lambda kv: -kv[1])[:top]
    return {k: v / 1e9 for k, v in rows}


def split(ctx) -> dict | None:
    """The slice's split, made once a run (kept in ``ctx``), and printed as
    ``{"phase": "device_scopes", ...}`` before the last line; a trace it
    cannot read prints its error there and leaves every reader out."""
    if "device_scopes" in ctx:
        return ctx["device_scopes"]
    ctx["device_scopes"] = None
    summary, path = ctx.get("trace"), program_trace.slice_xplane()
    if summary is None or not path:
        return None
    try:
        from pathway_tpu.engine.tracing import executable_table
    except ImportError:
        return None
    table = executable_table()
    if not table:
        return None
    t0 = time.perf_counter()
    try:
        got = split_trace(path, table, summary.lo, summary.hi)
    except Exception as exc:  # noqa: BLE001 - a trace the reader cannot read
        print(json.dumps({"phase": "device_scopes",
                          "error": f"{type(exc).__name__}: {exc}"[:300]}),
              flush=True)
        return None
    got["split_s"] = time.perf_counter() - t0
    ctx["device_scopes"] = got
    matched = sum(r["ns"] for r in got["executables"])
    mine = matched + sum(got["unmatched_ns"].values())
    scopes: dict = {}
    for r in got["executables"]:
        for s, ns in r["by_scope_ns"].items():
            scopes[s] = scopes.get(s, 0) + ns
    print(json.dumps({
        "phase": "device_scopes", "split_s": got["split_s"],
        "executables": [{
            "module": r["module"], "carries": r["carries"],
            "runs": r["runs"], "device_s": r["ns"] / 1e9,
            "unscoped_pct": 100.0 * r["by_scope_ns"].get(UNSCOPED, 0)
            / max(r["ns"], 1),
            "run_gap_pct": 100.0 * r["gap"], "shape_gap": r["shape_gap"],
            "unnamed_s": r["unnamed_ns"] / 1e9,
            "scope_s": _seconds(r["by_scope_ns"], 16)}
            for r in sorted(got["executables"], key=lambda r: -r["ns"])],
        "scope_s": _seconds(scopes, 24),
        "matched_pct": 100.0 * matched / mine if mine else None,
        "unmatched_s": _seconds(got["unmatched_ns"]),
        "other_s": _seconds(got["other_ns"], 8),
        "table": got["table"],
    }), flush=True)
    return got


# ------------------------------------------------------------ readers


def _in(scope: str, wanted: list[str]) -> bool:
    """``scope`` is one of ``wanted``, where ``decoder.attn.`` (a trailing
    dot) stands for every scope under it."""
    return any(scope == w or (w.endswith(".") and scope.startswith(w))
               for w in wanted)


def _rows(ctx, params):
    got = split(ctx)
    if got is None:
        return []
    rx = re.compile(params["modules"])
    return [r for r in got["executables"] if rx.search(r["module"])]


def _under(rows, params) -> int | None:
    """Self time of ``rows`` under ``scopes``; None where none of those
    scopes ran at all (a program that names its ops otherwise: nothing to
    read, not a zero)."""
    ns = [v for r in rows for s, v in r["by_scope_ns"].items()
          if _in(s, params["scopes"])]
    return sum(ns) if ns else None


def ms_per_carry(ctx, params):
    """Self time of the matched runs of ``modules`` under ``scopes``, in ms,
    over what those runs carried of ``per`` (``pieces``: a lane of a group
    is one; ``steps``)."""
    rows = _rows(ctx, params)
    ns = _under(rows, params)
    carried = sum(r["runs"] * r["carries"].get(params["per"], 0)
                  for r in rows)
    return ns / 1e6 / carried if ns is not None and carried else None


def scope_share_pct(ctx, params):
    """Share of the matched runs' self time of ``modules`` under
    ``scopes``."""
    rows = _rows(ctx, params)
    ns = _under(rows, params)
    total = sum(v for r in rows for v in r["by_scope_ns"].values())
    return 100.0 * ns / total if ns is not None and total else None
