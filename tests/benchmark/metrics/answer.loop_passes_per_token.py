"""Passes of the layer stack a token went through, over every real prompt
token prefilled and every token emitted since the process began (the
program's ``loop_passes{phase, pass}``: a token's first pass counts it, and
the series' sum is the passes run): 4.0 where a stack of four passes skips
none, less where a batch's tokens leave the loop at different passes."""


def read(ctx, params):
    from pathway_tpu.engine import probes

    by_pass = probes.REGISTRY.labelled(params["family"], params["per"])
    tokens = by_pass.get("1")
    if not tokens:
        return None
    return sum(by_pass.values()) / tokens
