"""The two built-in model layouts are a re-routing, not a rewrite: ``bert``
and ``gpt2`` give, bit for bit, the weight trees, the reference outputs and
the counts of the functions of ``weights.py``, ``reference.py`` and
``work.py`` that they wrap, on one seed at the toy's size; and a layout made
of new files only, under other key names, reads the same model the same."""

import jax
import numpy as np
import pytest

from bench_paths import TOY_MANIFEST

from harness import manifest as M, reference as R, weights as W, work
from harness.corpus import Corpus
from harness.layouts import LAYOUTS

SEED = 2 ** 31 + 27
ENC = {"hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
       "intermediate_size": 128, "vocab_size": 30522,
       "max_position_embeddings": 512, "type_vocab_size": 2,
       "layer_norm_eps": 1e-12, "torch_dtype": "bfloat16"}
DEC = {"n_embd": 64, "n_layer": 2, "n_head": 4, "n_inner": None,
       "n_positions": 256, "vocab_size": 503, "layer_norm_epsilon": 1e-5}
RENAMED = {"hidden_size": 64, "num_hidden_layers": 2,
           "num_attention_heads": 4, "intermediate_size": None,
           "max_position_embeddings": 256, "vocab_size": 503,
           "layer_norm_eps": 1e-5}
IDS = [int(i) for i in np.random.default_rng(SEED).integers(1, 503, 40)]


def same_tree(a, b) -> None:
    assert jax.tree.structure(a) == jax.tree.structure(b)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert x.dtype == y.dtype and np.array_equal(np.asarray(x),
                                                     np.asarray(y))


@pytest.mark.parametrize("role,head", [("embedder", False),
                                       ("reranker", True)])
def test_bert_layout_gives_the_weight_tree_it_wraps(role, head):
    spec = LAYOUTS["bert"].weight_spec(ENC, role)
    assert spec == W.encoder_spec(ENC, head=head)
    same_tree(W.make_params(SEED, W.STREAM_EMBEDDER, spec),
              W.make_params(SEED, W.STREAM_EMBEDDER,
                            W.encoder_spec(ENC, head=head)))


@pytest.mark.parametrize("precision", ["f32", "fp8"])
def test_bert_layout_gives_the_reference_outputs_it_wraps(precision):
    bert = LAYOUTS["bert"]
    params = W.make_params(SEED, W.STREAM_RERANKER,
                           bert.weight_spec(ENC, "reranker"))
    corpus = Corpus(SEED, 20)
    texts = corpus.documents(6)
    pairs = [(q, texts[d]) for d, q in corpus.queries(4, 20)]
    assert np.array_equal(bert.embed(params, ENC, texts, 32, precision),
                          R.embed_texts(params, ENC, texts, 32, precision))
    assert np.array_equal(bert.score(params, ENC, pairs, 64, precision),
                          R.score_pairs(params, ENC, pairs, 64, precision))


def test_bert_layout_gives_the_counts_it_wraps():
    bert = LAYOUTS["bert"]
    assert bert.encoder_flops(ENC, 22) == work.encoder_flops(ENC, 22)
    assert bert.encoder_bytes(ENC, 32, 22) == work.encoder_bytes(ENC, 32, 22)


def test_bert_layout_refuses_a_type_the_cells_do_not_state():
    with pytest.raises(ValueError, match="bfloat16"):
        LAYOUTS["bert"].program_config(dict(ENC, torch_dtype="float32"))
    cfg = LAYOUTS["bert"].program_config(ENC)
    assert (cfg.hidden, cfg.layers, cfg.heads, cfg.intermediate) == (
        64, 2, 4, 128)


def test_gpt2_layout_gives_the_weight_tree_it_wraps():
    spec = LAYOUTS["gpt2"].weight_spec(DEC, "decoder")
    assert spec == W.decoder_spec(DEC)
    same_tree(W.make_params(SEED, W.STREAM_DECODER, spec),
              W.make_params(SEED, W.STREAM_DECODER, W.decoder_spec(DEC)))
    cfg = LAYOUTS["gpt2"].program_config(DEC)
    assert (cfg.hidden, cfg.layers, cfg.heads, cfg.intermediate,
            cfg.max_position, cfg.vocab_size) == (64, 2, 4, 256, 256, 503)


@pytest.mark.parametrize("precision", ["f32", "fp8"])
def test_gpt2_layout_gives_the_reference_outputs_it_wraps(precision):
    gpt2 = LAYOUTS["gpt2"]
    params = W.make_params(SEED, W.STREAM_DECODER, W.decoder_spec(DEC))
    same_tree(gpt2.prepare(params, precision),
              R.prepare_decoder(params, precision))
    prepared = gpt2.prepare(params, precision)
    got = gpt2.logits(prepared, DEC, IDS, 30, precision)
    assert got.shape == (10, 503)
    assert np.array_equal(got, R.gpt2_logits(prepared, DEC, IDS, 30,
                                             precision))


def test_gpt2_layout_gives_the_counts_it_wraps():
    gpt2 = LAYOUTS["gpt2"]
    assert gpt2.matmul_param_count(DEC) == work.decoder_matmul_param_count(DEC)
    assert gpt2.param_bytes(DEC) == work.decoder_param_bytes(DEC)
    assert gpt2.kv_bytes_per_token(DEC) == work.kv_bytes_per_token(DEC)
    assert gpt2.prefill_flops(DEC, 45) == work.prefill_flops(DEC, 45)
    assert gpt2.decode_step_flops(DEC, 4, 300.0) == work.decode_step_flops(
        DEC, 4, 300.0)
    assert gpt2.decode_step_bytes(DEC, 300.0) == work.decode_step_bytes(
        DEC, 300.0)
    assert gpt2.answer_flops(DEC, 45, 8) == work.answer_flops(DEC, 45, 8)
    assert gpt2.answer_flops(DEC, 45, 1) == work.prefill_flops(DEC, 45)


def test_a_model_that_names_no_layout_gets_its_roles_present_one():
    man = M.load_manifest(TOY_MANIFEST)
    config = {"models": {"embedder": ENC, "reranker": ENC, "decoder": DEC}}
    assert M.layouts_for(man, config) == {
        "embedder": LAYOUTS["bert"], "reranker": LAYOUTS["bert"],
        "decoder": LAYOUTS["gpt2"]}
    with pytest.raises(M.ManifestError, match="names no layout"):
        M.layouts_for(man, {"models": {"speech": {}}})


def test_a_layout_of_new_files_reads_the_same_model_under_other_names():
    """``toy_renamed`` brings its own spec, reference and counts; on the
    model GPT-2's file describes it has to agree with ``gpt2``: the same
    tree (so the same weights from a seed), logits to float32 rounding,
    the same counts."""
    man = M.load_manifest(TOY_MANIFEST)
    own, gpt2 = M.resolve(man, "layouts", "toy_renamed"), LAYOUTS["gpt2"]
    assert own.weight_spec(RENAMED, "decoder") == gpt2.weight_spec(
        DEC, "decoder")
    assert own.program_config(RENAMED) == gpt2.program_config(DEC)
    params = W.make_params(SEED, W.STREAM_DECODER,
                           own.weight_spec(RENAMED, "decoder"))
    for precision, tol in (("f32", 1e-5), ("fp8", 0.0)):
        mine = own.logits(own.prepare(params, precision), RENAMED, IDS, 30,
                          precision)
        theirs = gpt2.logits(gpt2.prepare(params, precision), DEC, IDS, 30,
                             precision)
        if tol:
            assert np.abs(mine - theirs).max() < tol
        else:   # the control only has to be as far off as the built-in's
            exact = gpt2.logits(gpt2.prepare(params, "f32"), DEC, IDS, 30)
            assert np.abs(mine - exact).max() > 0.3 * np.abs(
                theirs - exact).max() > 0
    for count, args in (("matmul_param_count", ()), ("param_bytes", ()),
                        ("kv_bytes_per_token", ()), ("prefill_flops", (45,)),
                        ("decode_step_flops", (4, 300.0)),
                        ("decode_step_bytes", (300.0,)),
                        ("answer_flops", (45, 8))):
        assert getattr(own, count)(RENAMED, *args) == getattr(gpt2, count)(
            DEC, *args), count
