#!/usr/bin/env python3
"""One run of one benchmark cell.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process: it needs a TPU (no fallback, no platform set), builds the
cell's system from the seed, warms only that cell's shapes, measures for
``--seconds`` and prints, as the last line of standard output, one JSON
object with ``correct``, ``attempted``, ``failed``, ``metrics`` and
``device`` (with ``--trace 1``: the per-layer metrics, ``busy_s`` /
``window_s`` and ``breakdown``). Earlier lines are facts of the run, each
stamped with the device. What was compared, beside its limits, is the last
lines of standard error and the last key of the result line.

The cell, its configuration, its traffic mix and its per-layer metrics are
DATA, found by name from ``BENCHMARK.json`` (``harness/manifest.py``); the
code they name (builder, generator, check, model layouts, a roofline's
work, a metric's reader) is found by name too: a built-in of the harness or
a file under ``paths`` (``manifest.resolve``).
"""

from __future__ import annotations

import argparse
import faulthandler
import json
import os
import shutil
import sys
import time

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))  # the checkout: pathway_tpu

from harness import manifest as M  # noqa: E402


def emit(device: dict, **fields) -> None:
    print(json.dumps({"device": device, **fields}), flush=True)


def per_layer_metrics(man: dict, cell: dict, ctx: dict) -> dict:
    from harness.readers import READERS

    out = {}
    ctx = dict(ctx, manifest=man)
    for m in cell["per_layer"]:
        spec = M.load_json_named(man, "metrics", m["name"])
        own = M.load_reader_module(man, m["name"])
        reader = own.read if own is not None else READERS[spec["reader"]]
        value = reader(ctx, spec.get("params", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


class Tracer:
    """A profiler trace of a steady slice in the middle of the window."""

    def __init__(self, system, trace_dir: str, after: float, seconds: float):
        self.system, self.dir = system, trace_dir
        self.after_s, self.seconds = after, seconds
        self.state = 0
        self.before = self.after = None
        self.t_on = self.t_off = 0.0

    def tick(self, now: float, opened: float) -> None:
        """``opened``: when the window opened (0.0 while it has not)."""
        import jax

        if self.state == 0 and opened and now >= opened + self.after_s:
            shutil.rmtree(self.dir, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            self.before = self.system.counters()
            jax.profiler.start_trace(self.dir, profiler_options=options)
            self.t_on = time.perf_counter()
            self.state = 1
        elif self.state == 1 and now >= self.t_on + self.seconds:
            self.t_off = time.perf_counter()
            jax.profiler.stop_trace()
            self.after = self.system.counters()
            self.state = 2

    def finish(self) -> None:
        if self.state == 1:
            self.tick(float("inf"), 1.0)


def run_cell(man: dict, name: str, seed: int, seconds: float, trace: bool,
             control: bool, device: dict, t_start: float = T_START) -> dict:
    from harness import trace as T
    from harness.checks import judge
    from harness.generators import HostWatch
    from harness.peaks import peaks_for
    from harness.system import peak_bytes

    cell = M.cell(man, name)
    config, traffic = cell["config"], cell["traffic"]
    peaks = peaks_for(device["kind"]) if trace else None

    system = M.resolve(man, "builders", config["builder"])(
        config, traffic, seed)
    gen = M.resolve(man, "generators", traffic["generator"])(traffic)
    check = M.resolve(man, "checks", traffic["check"])(config, traffic, seed)
    gen.prepare(system)
    system.step_done("generator_prepare")
    emit(device, phase="setup", steps=system.setup_steps,
         before_generator_s=time.perf_counter() - t_start,
         compile_s=system.clock.seconds, compiles=system.clock.compiles,
         peak_bytes_in_use=peak_bytes())

    tracer = None
    if trace:
        slice_s = min(traffic.get("trace_seconds", 3.0), seconds / 2)
        tracer = Tracer(system, os.path.join(
            os.path.dirname(HERE), ".bench_trace", name),
            (seconds - slice_s) / 2, slice_s)
    with HostWatch() as watch:
        window = gen.run(system, seconds, tracer.tick if tracer else None)
    if tracer:
        tracer.finish()
    # set-up ends where the window opens: the generator's ramp to its first
    # completion (every commit or client then in flight) is set-up too
    if not window.t0:
        raise RuntimeError(f"the window never opened: {window.problems}")
    setup_s = window.t0 - t_start
    window.facts.update(watch.facts(window.t0, window.t1))
    after = system.counters()
    opened, closed = window.counters_open, window.counters_close or after
    counters = {k: closed[k] - opened.get(k, 0) for k in closed}
    counters.update(window.counters)

    numbers = {"failed_requests": window.failed,
               "generator_problems": len(window.problems),
               "compiles_in_window": counters["compiles"]}
    numbers.update(check.exact(system, window))
    got = check.collect(system, window)
    memory_peak = peak_bytes()
    occupancy = system.chat._server.occupancy() if system.chat else None
    emit(device, phase="window", seconds=seconds, setup_s=setup_s,
         compiles_in_window=counters["compiles"], counters=counters,
         peak_bytes_in_use=memory_peak, decoder_occupancy=occupancy,
         problems=window.problems, **window.facts)
    params = system.params
    spans = dict(system.spans)
    system.close()

    t_ref = time.perf_counter()
    more, ctrl = check.compare(got, params, control)
    numbers.update(more)
    correct, compared = judge(numbers, config["limits"])
    emit(device, phase="reference", seconds=time.perf_counter() - t_ref)

    metrics = dict(window.end_to_end, setup_s=setup_s)
    units = {m["name"]: m["unit"] for m in cell["end_to_end"]}
    missing = sorted(set(units) - set(metrics))
    if missing:
        raise RuntimeError(f"the window gave no {missing}")
    result = {
        "correct": bool(correct), "attempted": int(window.attempted),
        "failed": int(window.failed),
        "metrics": {k: {"value": float(metrics[k]), "unit": units[k]}
                    for k in units},
        "device": dict(device, memory_peak_bytes=memory_peak),
    }
    if trace:
        if tracer.state != 2:
            raise RuntimeError("the window ended before the trace began")
        loaded = T.load_device_events(T.newest_xplane(tracer.dir))
        marks = window.span_marks
        summary = T.TraceSummary(loaded,
                                 window_s=tracer.t_off - tracer.t_on)
        # the benchmark's spans on the trace's clock: the slice began at
        # t_on on the host and at summary.lo in the trace
        shift = summary.lo - int(tracer.t_on * 1e9)
        summary.spans = [(n, int(a * 1e9) + shift, int(b * 1e9) + shift)
                         for n, a, b in marks]
        slice_counters = {k: tracer.after[k] - tracer.before[k]
                          for k in tracer.after}
        slice_counters["requests_completed"] = sum(
            1 for n, _a, b in marks
            if n.startswith("request") and tracer.t_on <= b <= tracer.t_off)
        spans.update(window.spans)
        counters["needed_flops"] = check.needed_flops(window)
        counters["window_s"] = window.t1 - window.t0
        counters["window_peak_flops"] = (window.t1 - window.t0) \
            * peaks["bf16_flops_per_s"] * device["count"]
        ctx = {"trace": summary, "counters": counters,
               "slice_counters": slice_counters, "lifetime_counters": after,
               "spans": spans,
               "peaks": peaks, "config": config, "traffic": traffic,
               "window_s": seconds, "facts": dict(window.facts)}
        result["metrics"] = per_layer_metrics(man, cell, ctx)
        result["device"].update(busy_s=summary.busy_s,
                                window_s=summary.window_s)
        result["breakdown"] = summary.breakdown()
        emit(device, phase="trace", lines=loaded["lines"],
             slice_counters=slice_counters,
             roofline=ctx["facts"].get("roofline"))
        shutil.rmtree(tracer.dir, ignore_errors=True)
    if control:
        # the control goes through the SAME comparison: it has to come out
        # as not correct, failing at least one number of the cell
        ok, table = judge(ctrl, config["limits"])
        result["control_correct"] = bool(ok)
        result["control_compared"] = table
        for k, v in table.items():
            print(f"control {k}: {v['value']} (limit {v['limit']})",
                  file=sys.stderr, flush=True)
    result["compared"] = compared
    for k, v in compared.items():
        print(f"compared {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr, flush=True)
    return result


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also judge, against the same limits, the fp8 "
                         "reference put in the program's place: it has to "
                         "come out not correct (the builder's control "
                         "runs; the driver never passes it)")
    args = ap.parse_args()
    man = M.load_manifest()
    cell = M.cell(man, args.workload)
    from harness.system import require_tpu

    device = require_tpu(cell["cell"]["chips"])
    # a run that hangs must not hold the chip past its allowance
    faulthandler.dump_traceback_later(1150, exit=True)
    result = run_cell(man, args.workload, args.seed, args.seconds,
                      bool(args.trace), bool(args.control), device)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
