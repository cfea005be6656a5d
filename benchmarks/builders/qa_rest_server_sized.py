"""``qa_rest_server`` for a generator whose configuration SIZES its server:
``BaseRAGQuestionAnswerer`` behind ``QARestServer`` as the built-in builds
it, with the decoder server's ``prefill_chunk`` and ``chunk_steps`` taken
from the configuration's ``deployment.decoder_server`` (the built-in passes
neither). Every other option of the server is its default; the server
builds, before it serves, every executable its loop can dispatch.

The weights are made leaf by leaf, the largest stack first: one jitted call
for a tree of 8.6 GB would hold its float32 draws beside the result.
"""

from harness import weights as W
from harness.corpus import WordTokenizer
from harness.system import System, _finish_setup


def _make_leaf_by_leaf(seed: int, stream: int, spec: dict) -> dict:
    """``weights.make_params`` over ``spec``, one call per leaf."""
    def size(item) -> int:
        if isinstance(item, dict):
            return sum(size(v) for v in item.values())
        n = 1
        for d in item[0]:
            n *= d
        return n

    out: dict = {}
    for n, (name, item) in enumerate(
            sorted(spec.items(), key=lambda kv: -size(kv[1]))):
        if isinstance(item, dict):
            out[name] = _make_leaf_by_leaf(seed, stream * 131 + n + 7, item)
        else:
            out[name] = W.make_params(seed, stream * 131 + n + 7,
                                      {name: item})[name]
    return out


def build_decoder(system: System) -> None:
    from pathway_tpu.xpacks.llm.llms import TPUDecoderChat

    model = system.config["models"]["decoder"]
    layout = system.config["layouts"]["decoder"]
    srv = system.dep["decoder_server"]
    # first: a program that lacks the block fails here, at once
    cfg = layout.program_config(model)
    system.params["decoder"] = _make_leaf_by_leaf(
        system.seed, W.STREAM_DECODER, layout.weight_spec(model, "decoder"))
    system.step_done("decoder_weights")
    system.tokenizer = WordTokenizer(cfg.vocab_size, system.seed)
    system.chat = TPUDecoderChat(
        params=system.params["decoder"], cfg=cfg,
        tokenizer=system.tokenizer, max_new_tokens=srv["max_new_tokens"],
        temperature=srv["temperature"],
        max_prompt_tokens=srv["max_prompt_tokens"],
        continuous=True, deferred=True, n_slots=srv["n_slots"],
        prefill_chunk=srv["prefill_chunk"], chunk_steps=srv["chunk_steps"],
    )
    system.setup_steps["decoder_warm"] = round(
        system.chat._server.warm_seconds, 3)


def build(config: dict, traffic: dict, seed: int) -> System:
    from pathway_tpu.xpacks.llm.question_answering import (
        BaseRAGQuestionAnswerer,
    )
    from pathway_tpu.xpacks.llm.servers import QARestServer

    system = System(config, seed)
    system.step_done("corpus")
    build_decoder(system)
    system.step_done("decoder")
    system.build_encoders()
    system.step_done("encoders")
    store = system.build_store()
    qa = BaseRAGQuestionAnswerer(
        llm=system.chat, search_topk=system.dep["search_topk"], indexer=store)
    system.start(QARestServer("127.0.0.1", 0, qa))
    system.step_done("graph_and_server")
    _finish_setup(system, traffic)
    return system
