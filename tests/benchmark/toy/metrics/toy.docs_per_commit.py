"""A metric with a reader of its own: what a later PR ships beside the
metric's json when no reader of ``harness/readers.py`` fits."""


def read(ctx, params):
    landed = ctx["counters"].get("commits_landed")
    if not landed:
        return None
    return params["scale"] * ctx["counters"]["docs_landed"] / landed
