"""Work of the decoder server's chunked-prefill executable (``jit_piece``)
over a traced slice, counted from the shapes by the ``afmoe`` layout: every
run is one piece of ``prefill_chunk`` columns of one prompt. What a prompt
NEEDS is one causal forward over its real tokens (window layers read at
most the window; of the routed experts a token multiplies only its picks
that fall on the experts held); a piece reads every matrix the layers hold
once, and writes and reads its keys and values."""


def work(ctx, runs):
    model = ctx["config"]["models"]["decoder"]
    layout = ctx["config"]["layouts"]["decoder"]
    srv = ctx["config"]["deployment"]["decoder_server"]
    prompt = ctx["facts"].get("prompt_tokens_median")
    if not prompt or not runs:
        return 0.0, 0.0
    pieces = -(-int(prompt) // srv["prefill_chunk"])    # with a real token
    prompts = runs / pieces
    flops = prompts * (layout.prefill_flops(model, int(prompt))
                       - 2.0 * model["vocab_size"] * model["hidden_size"])
    kv = layout.kv_tokens(model, prompt) \
        * layout.kv_bytes_per_token_layer(model)
    nbytes = runs * layout.param_bytes(model) + prompts * 2 * kv
    ctx["facts"]["prefill_pieces_per_prompt"] = pieces
    return flops, nbytes
