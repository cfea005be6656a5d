"""Latent cache rows that a prefill turned into per-head keys and values,
a layer, for every prompt column it prefilled (the program's
``latent_rows_expanded{phase=prefill}``, summed over the latent layers on
the host, over the server's ``prefill_chunks`` pieces of ``prefill_chunk``
columns each; both since the process began: warm-up dispatches count in
neither). 1 if no row is expanded twice; about half the number of pieces a
prompt where every piece expands all the rows its queries see."""


def read(ctx, params):
    from pathway_tpu.engine import probes

    rows = probes.REGISTRY.labelled(params["family"], params["label"]).get(
        params["value"])
    pieces = ctx.get("lifetime_counters", {}).get(params["pieces"])
    if not rows or not pieces:
        return None
    model = ctx["config"]["models"]["decoder"]
    srv = ctx["config"]["deployment"]["decoder_server"]
    return rows / (model["num_hidden_layers"] * pieces * srv["prefill_chunk"])
