"""The reduction from a profiler trace to numbers. This exists nowhere in
the program (``internals/profiling.py`` captures and does not reduce), so
every PR reads the same trace the same way.

A trace is reduced in two steps: :func:`load_device_events` turns the
``.xplane.pb`` the JAX profiler wrote into plain lists of
``(name, start_ns, duration_ns)`` for each device — one list of XLA MODULE
runs (whole executables) and one of XLA OPS — and the functions below work
on those lists alone, so a small recorded list (``tests/benchmark``'s
fixture) checks them without a chip.
"""

from __future__ import annotations

import glob
import os
import re

Event = tuple[str, int, int]  # name, start_ns, duration_ns

_MODULE_LINES = ("XLA Modules",)
_OP_LINES = ("XLA Ops",)


def newest_xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)


def load_device_events(path: str) -> dict:
    """``{"devices": {plane_name: {"modules": [Event], "ops": [Event]}},
    "lines": {plane_name: [line names]}}`` for every accelerator plane
    (``/device:TPU:<n>``; host planes are left out)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict = {}
    lines: dict = {}
    for plane in data.planes:
        lines[plane.name] = [ln.name for ln in plane.lines][:12]
        if not re.match(r"^/device:(TPU|GPU):\d+$", plane.name):
            continue
        dev = {"modules": [], "ops": []}
        for ln in plane.lines:
            if ln.name in _MODULE_LINES:
                into = dev["modules"]
            elif ln.name in _OP_LINES:
                into = dev["ops"]
            else:
                continue
            for ev in ln.events:
                into.append((ev.name, int(ev.start_ns), int(ev.duration_ns)))
        devices[plane.name] = dev
    return {"devices": devices, "lines": lines}


def clip(events: list[Event], lo: int, hi: int) -> list[Event]:
    """The parts of ``events`` inside ``[lo, hi)``."""
    out = []
    for name, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            out.append((name, a, b - a))
    return out


def union_ns(events: list[Event]) -> int:
    """Nanoseconds covered by at least one event (overlaps count once)."""
    total, end = 0, None
    for _name, start, dur in sorted(events, key=lambda e: e[1]):
        stop = start + dur
        if end is None or start > end:
            total += dur
            end = stop
        elif stop > end:
            total += stop - end
            end = stop
    return total


def gaps(events: list[Event], lo: int, hi: int) -> list[tuple[int, int]]:
    """``(start_ns, duration_ns)`` of every stretch of ``[lo, hi)`` in which
    no event runs, longest first."""
    out, end = [], lo
    for _name, start, dur in sorted(events, key=lambda e: e[1]):
        if start > end:
            out.append((end, min(start, hi) - end))
        end = max(end, start + dur)
        if end >= hi:
            break
    if end < hi:
        out.append((end, hi - end))
    return sorted((g for g in out if g[1] > 0), key=lambda g: -g[1])


def base_name(name: str) -> str:
    """``jit__search_kernel(1234567)`` -> ``jit__search_kernel``: the
    module's name without the run's program id."""
    return re.sub(r"\(\d+\)$", "", name)


def by_name(events: list[Event]) -> dict[str, tuple[int, int]]:
    """``name -> (runs, total_ns)``, by :func:`base_name`."""
    out: dict[str, list[int]] = {}
    for name, _start, dur in events:
        row = out.setdefault(base_name(name), [0, 0])
        row[0] += 1
        row[1] += dur
    return {k: (v[0], v[1]) for k, v in out.items()}


def matching(events: list[Event], pattern: str) -> tuple[int, int]:
    """Runs and total nanoseconds of the events whose name matches the
    regular expression ``pattern`` (searched, not anchored)."""
    rx = re.compile(pattern)
    runs = total = 0
    for name, _start, dur in events:
        if rx.search(name):
            runs += 1
            total += dur
    return runs, total


class TraceSummary:
    """What the readers and the result line take from one traced slice.

    ``busy_s`` is the union of the XLA-op intervals (of the module
    intervals where a device plane has no op line), averaged over the
    devices used; ``window_s`` the length of the traced window."""

    def __init__(self, loaded: dict, lo: int | None = None,
                 hi: int | None = None, spans: list | None = None,
                 window_s: float | None = None):
        self.devices = loaded["devices"]
        if not self.devices:
            raise ValueError(
                f"the trace holds no device plane; planes and lines: "
                f"{loaded['lines']}")
        every = [e for d in self.devices.values()
                 for e in d["modules"] + d["ops"]]
        if not every:
            raise ValueError("no operation ran on any device in the trace")
        self.lo = min(e[1] for e in every) if lo is None else lo
        self.hi = max(e[1] + e[2] for e in every) if hi is None else hi
        self.spans = spans or []
        busy = []
        self.modules: list[Event] = []
        self.ops: list[Event] = []
        for d in self.devices.values():
            mods = clip(d["modules"], self.lo, self.hi)
            ops = clip(d["ops"], self.lo, self.hi)
            busy.append(union_ns(ops or mods))
            self.modules += mods
            self.ops += ops
        # the length of the traced window is the host's (start_trace to
        # stop_trace) where the caller has it: the device may sit idle at
        # both ends, and from first to last event would leave that out
        self.window_s = (self.hi - self.lo) / 1e9 if window_s is None \
            else max(window_s, (self.hi - self.lo) / 1e9)
        self.busy_s = sum(busy) / len(busy) / 1e9
        self.n_devices = len(self.devices)

    def module_seconds(self, pattern: str) -> tuple[int, float]:
        runs, ns = matching(self.modules, pattern)
        return runs, ns / 1e9 / self.n_devices

    def breakdown(self, top: int = 10, longest: int = 5) -> dict:
        """The executables that took most device time, by module name, and
        the longest idle gaps with the benchmark span open at their start
        (``unattributed`` where none was)."""
        mods = sorted(by_name(self.modules).items(), key=lambda kv: -kv[1][1])
        first = next(iter(self.devices.values()))
        events = clip(first["ops"] or first["modules"], self.lo, self.hi)
        idle = []
        for start, dur in gaps(events, self.lo, self.hi)[:longest]:
            open_ = [n for n, a, b in self.spans if a <= start < b]
            idle.append([open_[0] if open_ else "unattributed", dur / 1e9])
        return {
            "device_ops": [[n, ns / 1e9 / self.n_devices]
                           for n, (_runs, ns) in mods[:top]],
            "idle_gaps": idle,
        }
