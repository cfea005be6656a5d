"""Least work of the decode chunks' gated delta-rule recurrence (the scope
``kda.step`` of ``jit_chunk``) over the steps of a traced slice, for a model
whose layout counts it (``kimi_linear``).

A step of a useful lane carries every KDA layer's state in once and out
once (float32, ``heads x d x d`` a layer), and reads its float32 q, k, g,
v and beta; it needs ``recurrence_flops`` a layer. Useful lanes a step come
from the program's lifetime counters (slot-steps that advanced a request
over slot-steps dispatched, times the slots); the steps from the executable
table (what each run of ``jit_chunk`` carries)."""


def work(ctx, steps):
    model = ctx["config"]["models"]["decoder"]
    layout = ctx["config"]["layouts"]["decoder"]
    srv = ctx["config"]["deployment"]["decoder_server"]
    life = ctx["lifetime_counters"]
    if not steps or not life.get("decoder_slot_steps_total"):
        return 0.0, 0.0
    lanes = srv["n_slots"] * life["decoder_steps"] \
        / life["decoder_slot_steps_total"]
    ctx["facts"]["kda_steps_traced"] = steps
    ctx["facts"]["kda_useful_lanes"] = lanes
    layers = layout.kda_layers(model)
    return (steps * lanes * layers * layout.recurrence_flops(model),
            steps * lanes * layout.recurrence_bytes(model))
