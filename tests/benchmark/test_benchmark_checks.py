"""The comparison that decides ``correct``, at a size a test run can hold:
the control (the reference at fp8 in the program's place) comes out as NOT
correct under limits that the float32 reference passes, and each fault a
serving cell can have (a token or an answer altered where it is produced)
fails a number of its own."""

import copy

import numpy as np
import pytest

import bench_paths  # noqa: F401 - sets sys.path

from harness import manifest as M, reference as R, weights as W
from harness.checks import AnswerCheck, IngestCheck, RetrieveCheck, judge
from harness.corpus import Corpus, WordTokenizer

ENC = {"hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
       "intermediate_size": 128, "vocab_size": 30522,
       "max_position_embeddings": 512, "type_vocab_size": 2,
       "layer_norm_eps": 1e-12}
DEC = {"n_embd": 64, "n_layer": 2, "n_head": 4, "n_inner": None,
       "n_positions": 256, "vocab_size": 503, "layer_norm_epsilon": 1e-5}
CONFIG = {
    "models": {"embedder": ENC, "reranker": ENC, "decoder": DEC},
    "deployment": {"doc_words": 20, "commit_docs": 16, "setup_commits": 2,
                   "embed_max_length": 32, "rerank_max_length": 64,
                   "rerank_candidates": 4, "search_topk": 2,
                   "index_warm_rows": 0,
                   "decoder_server": {"max_prompt_tokens": 128,
                                      "max_new_tokens": 8, "n_slots": 4}},
}
# the layouts a cell's configuration gets from ``manifest.cell``: here the
# roles' present ones (``bert``, ``gpt2``), as the models name none
CONFIG["layouts"] = M.layouts_for(M.load_manifest(), CONFIG)
TRAFFIC = {"query_words": 20, "body": {"k": 2}, "check_requests": 4,
           "check_answers": 4, "check_docs": 8}
SEED = 2 ** 31 + 9


@pytest.fixture(scope="module")
def world():
    """What a sound program would have produced, made with the reference
    itself: documents, queries, replies, greedy tokens."""
    params = {
        "embedder": W.make_params(SEED, W.STREAM_EMBEDDER,
                                  W.encoder_spec(ENC, head=False)),
        "reranker": W.make_params(SEED, W.STREAM_RERANKER,
                                  W.encoder_spec(ENC, head=True)),
        "decoder": W.make_params(SEED, W.STREAM_DECODER, W.decoder_spec(DEC)),
    }
    corpus = Corpus(SEED, 20)
    texts = corpus.documents(32)
    queries = corpus.queries(4, 20)
    check = RetrieveCheck(CONFIG, TRAFFIC, SEED)
    qv = check._embed(params, [q for _d, q in queries])
    dv = check._embed(params, texts)
    cos = qv @ dv.T
    sample = []
    for qi, (_src, q) in enumerate(queries):
        cand = np.argsort(-cos[qi])[:4]
        scores = check._score(params, [(q, texts[d]) for d in cand])
        best = np.argsort(-scores)[:2]
        sample.append({"query": q, "docs": [
            (int(cand[b]), float(-cos[qi, cand[b]]), float(scores[b]))
            for b in best]})
    # greedy answers by the float32 reference over seeded prompts
    tok = WordTokenizer(DEC["vocab_size"], SEED)
    p32 = R.prepare_decoder(params["decoder"])
    answers = []
    for _src, q in queries:
        ids = tok.encode("Question: " + q)
        toks: list[int] = []
        for _ in range(8):
            lg = R.gpt2_logits(p32, DEC, ids + toks, len(ids + toks) - 1)
            toks.append(int(lg[-1].argmax()))
        answers.append((ids, toks))
    got = {"sample": sample, "doc_texts": texts, "answers": answers,
           "prompt_mismatch": 0}
    return params, got


LIMITS = {"knn_dist_err": 1e-5, "knn_rank_gap": 1e-5,
          "rerank_score_err": 1e-4, "token_logit_gap": 1e-4,
          "prompt_context_mismatch": 0}


def test_the_float32_reference_passes_and_the_fp8_control_fails(world):
    params, got = world
    numbers, control = AnswerCheck(CONFIG, TRAFFIC, SEED).compare(
        got, params, control=True)
    ok, table = judge(numbers, LIMITS)
    assert ok, table
    assert set(control) == {"knn_dist_err", "knn_rank_gap",
                            "rerank_score_err", "token_logit_gap"}
    # the control goes through the same comparison as the program does
    ctrl_ok, ctrl_table = judge(control, LIMITS)
    assert not ctrl_ok
    failed = [k for k, v in ctrl_table.items() if v["value"] > v["limit"]]
    # the control has to fail one of the cell's numbers, not each
    assert "rerank_score_err" in failed and "knn_dist_err" in failed
    assert control["rerank_score_err"] > 3 * numbers["rerank_score_err"]


@pytest.mark.parametrize("fault,number", [
    ("token", "token_logit_gap"),
    ("score", "rerank_score_err"),
    ("dist", "knn_dist_err"),
    ("wrong_doc", "knn_rank_gap"),
])
def test_an_answer_altered_where_it_is_produced_fails_its_number(
        world, fault, number):
    params, got = world
    got = copy.deepcopy(got)
    if fault == "token":
        prompt, toks = got["answers"][1]
        worst = R.gpt2_logits(R.prepare_decoder(params["decoder"]), DEC,
                              prompt + toks[:-1], len(prompt) - 1)[3].argmin()
        toks[3] = int(worst)
    else:
        d, dist, score = got["sample"][2]["docs"][0]
        if fault == "score":
            score += 0.01
        elif fault == "dist":
            dist += 0.01
        else:
            check = RetrieveCheck(CONFIG, TRAFFIC, SEED)
            query = got["sample"][2]["query"]
            cos = check._embed(params, [query]) @ check._embed(
                params, got["doc_texts"]).T
            d = int(np.argmin(cos[0]))          # the farthest document
            dist = float(-cos[0, d])
            score = float(check._score(
                params, [(query, got["doc_texts"][d])])[0])
        got["sample"][2]["docs"][0] = (d, dist, score)
    numbers, _ = AnswerCheck(CONFIG, TRAFFIC, SEED).compare(
        got, params, control=False)
    ok, table = judge(numbers, LIMITS)
    assert not ok
    assert table[number]["value"] > table[number]["limit"]
    sound = [k for k, v in table.items()
             if k != number and v["value"] > v["limit"]]
    assert sound == [], f"the fault also moved {sound}"


def test_ingest_vectors_against_the_reference_and_the_control(world):
    params, got = world
    check = IngestCheck(CONFIG, TRAFFIC, SEED)
    texts = got["doc_texts"][:8]
    served = check._embed(params, texts).astype(np.float32)
    numbers, control = check.compare(
        {"texts": texts, "rows": [served], "not_found": 0}, params, True)
    assert numbers["embed_cos_gap"] < 1e-6
    assert control["embed_cos_gap"] > 3e-6
    # a vector that belongs to another document
    broken = served.copy()
    broken[3] = served[4]
    numbers, _ = check.compare(
        {"texts": texts, "rows": [broken], "not_found": 0}, params, False)
    assert numbers["embed_cos_gap"] > 1e-4
    # nothing collected is a failure, never a pass
    numbers, _ = check.compare(
        {"texts": [], "rows": [], "not_found": 0}, params, False)
    assert numbers["embed_cos_gap"] == 1.0


def test_a_number_without_a_limit_is_a_fault_of_the_configuration():
    with pytest.raises(KeyError):
        judge({"new_number": 0.0}, {})
    ok, table = judge({"a": 0, "b": 2}, {"a": 0, "b": 1})
    assert not ok and table["b"] == {"value": 2, "limit": 1}
