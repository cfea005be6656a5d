"""A roofline's work made of new files only: each run of the decoder's step
executable carried one token for every slot over the slots' live cache, by
the counts of the decoder's own layout."""


def work(ctx, runs):
    model = ctx["config"]["models"]["decoder"]
    layout = ctx["config"]["layouts"]["decoder"]
    srv = ctx["config"]["deployment"]["decoder_server"]
    live = srv["n_slots"] * srv["max_prompt_tokens"]
    return (runs * layout.decode_step_flops(model, srv["n_slots"], live),
            runs * layout.decode_step_bytes(model, live))
