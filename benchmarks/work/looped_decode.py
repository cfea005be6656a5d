"""Work of the decoder server's decode executable (``jit_chunk``) over a
traced slice, for a model whose layout counts a step (``ouro``: the layer
stack read once a PASS, a cache layer for every pass of every layer).

The reader hands over only the NUMBER of runs, and runs differ in steps (a
chunk is 16, 8 or 4 of them) and are dispatched up to ``pipeline_depth``
chunks before the device runs them, so neither ``runs x chunk_steps`` nor
the host's regions of the slice say how many steps the traced runs held.
The DEVICE's own op line does: inside the runs of ``jit_chunk``, an
operation of a layer's body ran once for every layer of every pass of every
step, and a layer's body is where most of the executable's distinct
operations are. So the steps the slice holds are the count that most
operation names share, over ``total_ut_steps x num_hidden_layers`` (a run
cut by the slice's edge counts for the part of it that is inside, as its
time does). Each step needs the layout's ``decode_step_flops`` and
``decode_step_bytes`` for the USEFUL lanes (``stats``: useful slot-steps a
step dispatched, since the process began) at the live columns of an answer
half written: the prompt plus half the new tokens."""

import bisect
import re

MODULES = r"^jit_chunk\b"      # as the metric's file names them


def steps_in_runs(trace, pattern: str, per_step: int) -> float:
    """Decode steps inside the runs of the modules matching ``pattern``,
    counted from the device's operations (the module's text)."""
    rx = re.compile(pattern)
    runs = sorted((start, start + dur) for name, start, dur in trace.modules
                  if rx.search(name))
    if not runs or not trace.ops:
        return 0.0
    starts = [a for a, _b in runs]
    counts: dict = {}
    for name, start, _dur in trace.ops:
        i = bisect.bisect_right(starts, start) - 1
        if i >= 0 and start < runs[i][1]:
            counts[name] = counts.get(name, 0) + 1
    if not counts:
        return 0.0
    # the count that most of the runs' operations share (a layer's body is
    # where nearly all of them are), to within a run's ragged edge
    values = sorted(counts.values())
    best, most, i = 0.0, 0, 0
    while i < len(values):
        j = i
        while j < len(values) and values[j] <= values[i] * 1.02 + 1:
            j += 1
        if sum(values[i:j]) > most:
            most, best = sum(values[i:j]), values[(i + j) // 2]
        i = j
    return best / per_step


def work(ctx, runs):
    model = ctx["config"]["models"]["decoder"]
    layout = ctx["config"]["layouts"]["decoder"]
    srv = ctx["config"]["deployment"]["decoder_server"]
    prompt = ctx["facts"].get("prompt_tokens_median")
    life = ctx["lifetime_counters"]
    if not prompt or not runs or not life.get("decoder_slot_steps_total"):
        return 0.0, 0.0
    per_step = model["total_ut_steps"] * model["num_hidden_layers"]
    steps = steps_in_runs(ctx["trace"], MODULES, per_step)
    lanes = srv["n_slots"] * life["decoder_steps"] \
        / life["decoder_slot_steps_total"]
    live = lanes * (prompt + srv["max_new_tokens"] / 2.0)
    ctx["facts"]["decode_steps_traced"] = steps
    ctx["facts"]["decode_useful_lanes"] = lanes
    return (steps * layout.decode_step_flops(model, lanes, live),
            steps * layout.decode_step_bytes(model, live, batch=lanes))
