"""Share of its roofline that the decode chunks' recurrence reaches: the
least time of the traced steps' state read once and written once for each
useful lane, with its q, k, v, g and beta (work ``kda_decode``, against
``peaks.json``), over the device's self time under the scope ``kda.step``
in the runs of ``jit_chunk``; the steps are what the executable table says
those runs carried (``harness/device_scopes.py``). Nothing to read where no
op of the slice is under the scope."""

from harness import device_scopes
from harness import manifest as M
from harness import work as W
from harness.readers import _share


def read(ctx, params):
    rows = device_scopes._rows(ctx, params)
    ns = device_scopes._under(rows, params)
    steps = sum(r["runs"] * r["carries"].get("steps", 0) for r in rows)
    if not ns or not steps:
        return None
    flops, nbytes = M.resolve(ctx["manifest"], "work", params["work"])(
        ctx, steps)
    if flops <= 0 and nbytes <= 0:
        return None
    least, bound = W.least_seconds(flops, nbytes, ctx["peaks"])
    ctx["facts"].setdefault("roofline", {})[params["work"]] = {
        "bound": bound, "steps": steps, "device_s": ns / 1e9,
        "least_s": least, "flops": flops, "bytes": nbytes}
    return _share(params["work"], 100.0 * least / (ns / 1e9))
