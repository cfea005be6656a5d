"""Share of the routers' token-to-expert assignments that fell on the
experts HELD here (the program's ``moe_assignments{held=}``, summed on the
device since the process began); an eighth where routing is even."""


def read(ctx, params):
    from pathway_tpu.engine import probes

    series = probes.REGISTRY.labelled(params["family"], params["label"])
    every = sum(series.values())
    if not every:
        return None
    return series.get(str(params["value"]), 0.0) / every
