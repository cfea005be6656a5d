"""The ``afmoe`` layout, its configuration ``trinity-large-ep8-rag`` and its
cell ``trinity_rag_answer_closed16``: the counts against hand-reckoned
figures at the published widths, the files as the manifest names them, and
a small-width copy of the cell (``tests/benchmark/afmoe_small``: the same
layout, builder, generator and check) through ``run_cell`` on the CPU —
``correct``, its control not. These check the harness's side and measure
nothing."""

import json
import os
import time

import numpy as np
import pytest

from bench_paths import BENCH, REPO

import run as bench_run
from harness import manifest as M
from harness.system import device_stamp

CELL = "trinity_rag_answer_closed16"
SMALL = os.path.join(REPO, "tests", "benchmark", "afmoe_small",
                     "BENCHMARK.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def cell():
    return M.cell(M.load_manifest(), CELL)


def test_the_configuration_and_the_cell_resolve(cell):
    man = M.load_manifest()
    assert M.problems(man) == []
    assert M.unresolved(man, CELL) == []
    assert cell["cell"] == {
        "name": CELL, "config": "trinity-large-ep8-rag", "traffic": CELL,
        "chips": 1, "why": cell["cell"]["why"]}
    assert {m["name"] for m in cell["end_to_end"]} == {
        "requests_per_s", "request_p50_ms", "request_p95_ms", "setup_s"}
    # nine of its own; four of the retrieval cell's read something here too
    shared = {"retrieve.request_floor_ms", "retrieve.rest_queue_wait_ms_p50",
              "retrieve.requests_per_epoch",
              "retrieve.queries_per_search_dispatch"}
    own = [m for m in cell["per_layer"] if m["name"] not in shared]
    assert len(own) == 9 and len(cell["per_layer"]) == 13
    assert all(m["workloads"] == [CELL] for m in own)
    assert cell["config_entry"]["reduced"] == [
        "num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size",
        "index_warm_rows"]
    traffic = cell["traffic"]
    assert (traffic["generator"], traffic["check"]) == (
        "closed_loop_posts", "answer_moe")
    assert (traffic["clients"], traffic["query_words"],
            traffic["pool_queries"], traffic["check_requests"],
            traffic["check_answers"], traffic["trace_seconds"]) == (
                16, 20, 4096, 3, 3, 3)
    assert traffic["body"] == {"return_context_docs": True}
    srv = cell["config"]["deployment"]["decoder_server"]
    assert traffic["clients"] == srv["n_slots"]     # clients follow slots
    assert (srv["max_prompt_tokens"], srv["max_new_tokens"],
            srv["temperature"]) == (8192, 32, 0)
    dep = cell["config"]["deployment"]
    assert dep["search_topk"] == 56 <= dep["rerank_candidates"]
    assert dep["index_capacity"] * dep["index_dimensions"] * 2 \
        == 402653184                                    # 0.40 GB an instance
    limits = cell["config"]["limits"]
    for exact in ("compiles_in_window", "answers_short_of_tokens",
                  "prompts_truncated", "prompt_context_mismatch",
                  "failed_requests"):
        assert limits[exact] == 0
    # the encoders and their limits are the accepted configuration's
    with open(os.path.join(BENCH, "configs",
                           "minilm-l6-wiki21m-quarter.json")) as f:
        accepted = json.load(f)
    for role in ("embedder", "reranker"):
        assert cell["config"]["models"][role] == accepted["models"][role]
    for name, limit in accepted["limits"].items():
        assert limits[name] == limit


def test_the_file_states_the_published_config_and_its_cut(cell):
    """Every published key verbatim, at the top level of the file and in
    the decoder's entry; only the keys listed under ``reduced`` differ."""
    config = cell["config"]
    model = config["models"]["decoder"]
    reduced = set(cell["config_entry"]["reduced"])
    assert set(config["reduced"]) == reduced
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            rows = [json.loads(line) for line in f]
        row = [r for r in rows if r["name"] == "Trinity-Large-Preview"][0]
        assert cell["config_entry"]["source"] == row["source_url"]
        for key, value in row["config"].items():
            for holder in (config, model):
                if key in reduced:
                    assert holder[key] != value
                else:
                    assert holder[key] == value, key
    for key in ("num_hidden_layers", "num_dense_layers", "num_experts",
                "vocab_size", "hidden_size", "head_dim", "sliding_window",
                "num_experts_per_tok", "moe_intermediate_size"):
        assert config[key] == model[key]
    assert (model["num_hidden_layers"], model["num_dense_layers"],
            model["num_experts"], model["vocab_size"]) == (5, 1, 32, 25024)
    assert (model["num_hidden_layers_published"],
            model["num_dense_layers_published"],
            model["num_experts_published"],
            model["vocab_size_published"]) == (60, 6, 256, 200192)
    assert model["layers_kept"] == [0, 6, 7, 8, 9]
    assert [model["layer_types"][i] for i in model["layers_kept"]] == [
        "sliding_attention", "sliding_attention", "full_attention",
        "sliding_attention", "sliding_attention"]
    # the guide's floors: a whole period and four layers after the dense
    # ones, at least 8 experts, at least an eighth of the vocabulary
    assert model["num_hidden_layers"] - model["num_dense_layers"] >= 4
    assert model["num_experts"] >= 8
    assert model["vocab_size"] * 8 >= model["vocab_size_published"]
    assert model["num_experts"] * config["deployment"][
        "chips_sharing_a_layer"] == model["num_experts_published"]


def test_the_counts_at_published_widths(cell):
    """Hand-reckoned (the issue's figures): attention 62.9M a layer, an
    expert 28.3M, an expert layer with 32 held 998.0M, a dense layer
    176.2M, an eighth of the vocabulary twice 153.7M: 8.64 GB in bfloat16;
    4 KB of keys and values a token a layer; a window layer's never more
    than 4,096 tokens."""
    layout, model = cell["config"]["layouts"]["decoder"], \
        cell["config"]["models"]["decoder"]
    h, hd = 3072, 128
    attention = h * 48 * hd * 3 + 2 * h * 8 * hd      # q, gate, o; k, v
    assert layout.attention_params(model) == attention == 62_914_560
    assert layout.expert_params(model) == 3 * h * 3072 == 28_311_552
    moe_layer = attention + h * 256 + 33 * 28_311_552
    assert layout.layer_params(model, ("window", "moe")) == moe_layer
    assert moe_layer == pytest.approx(997.6e6, rel=1e-3)
    dense_layer = attention + 3 * h * 12288
    assert layout.layer_params(model, ("window", "dense")) == dense_layer
    assert dense_layer == pytest.approx(176.2e6, rel=1e-3)
    total = dense_layer + 4 * moe_layer + 2 * 25024 * h
    assert layout.matmul_param_count(model) == total
    assert layout.param_bytes(model) == pytest.approx(8.64e9, rel=2e-3)
    assert layout.kv_bytes_per_token_layer(model) == 4096
    assert layout.kv_bytes_per_token(model) == 5 * 4096
    assert layout.kv_tokens(model, 1000) == 5 * 1000
    assert layout.kv_tokens(model, 5700) == 4 * 4096 + 5700
    # a token multiplies 4 x 32/256 = 0.5 routed experts and the shared one
    per_token = layout.layer_params_per_token(model, ("full", "moe"))
    assert per_token == attention + h * 256 + 1.5 * 28_311_552
    n = 5700
    window_pairs = 4096 * 4097 / 2 + (n - 4096) * 4096
    want = 2.0 * n * (dense_layer + 4 * per_token) \
        + 4.0 * 48 * hd * (4 * window_pairs + n * (n + 1) / 2) \
        + 2.0 * 25024 * h
    assert layout.prefill_flops(model, n) == pytest.approx(want, rel=1e-12)
    assert 7.5e12 < want < 9.5e12       # the issue reckoned 8.6 TFLOP
    step = layout.decode_step_bytes(model, 16 * 5700.0, experts_touched=8,
                                    batch=16.0)
    assert 4.0e9 < step < 5.0e9         # "a decode step reads about 4.5 GB"
    assert layout.decode_step_bytes(model, 5700.0) \
        > layout.decode_step_bytes(model, 5700.0, experts_touched=8)
    assert layout.answer_flops(model, n, 32) > want
    # the program's own pool holds what the layout counts
    import jax

    from pathway_tpu.models import decoder as D

    cfg = layout.program_config(model)
    pool = jax.eval_shape(lambda: D.pool_init(None, cfg, 16, 8304))
    comp = D.pool_component_bytes(pool)
    assert comp["slot_pool"] == 16 * 8304 * 4096              # one full layer
    assert comp["slot_pool_window"] == 4 * 16 * (4096 + 256) * 4096
    assert sum(comp.values()) == pytest.approx(1.69e9, rel=0.02)
    spec = layout.weight_spec(model, "decoder")

    def size(tree):
        return sum(size(v) if isinstance(v, dict)
                   else int(np.prod(v[0]))
                   for v in tree.values())

    assert size(spec) * 2 == layout.param_bytes(model)


def test_every_new_file_is_named_by_the_manifest(cell):
    man = M.load_manifest()
    assert callable(M.resolve(man, "work", "afmoe_prefill"))
    assert cell["traffic"]["check"] == "answer_moe"
    assert issubclass(M.resolve(man, "checks", "answer_moe"),
                      M.resolve(man, "checks", "answer"))
    assert callable(M.resolve(man, "builders", "qa_rest_server_sized"))
    assert M.resolve(man, "layouts", "afmoe") is cell["config"]["layouts"][
        "decoder"]
    own_readers = {"answer.idle_attributed_pct", "answer.moe_held_share",
                   "answer.admit_wait_ms_p50", "answer.ttft_ms_p50"}
    for m in cell["per_layer"]:
        if m["workloads"] != [CELL]:
            continue        # the retrieval cell's, with this cell appended
        spec = M.load_json_named(man, "metrics", m["name"])
        reader = M.load_reader_module(man, m["name"])
        assert (reader is not None) == (m["name"] in own_readers)
        if reader is not None:
            path = M._find(man, "metrics", m["name"], ".py")
            assert os.path.relpath(path, REPO).startswith(
                os.path.join("tests", "benchmark", "metrics"))
        else:
            assert spec["reader"] in ("trace_idle", "counter_ratio",
                                      "trace_module_roofline")
    # a program that records none of it gives every reader nothing to read
    ctx = {"trace": None, "counters": {}, "slice_counters": {},
           "lifetime_counters": {}, "spans": {}, "facts": {},
           "config": cell["config"], "traffic": cell["traffic"]}
    from pathway_tpu.engine import probes, tracing

    for family in ("moe_assignments", "knn_search_queries",
                   "device_dispatch"):
        probes.REGISTRY.remove(family)
    tracing.reset_traces()
    assert bench_run.per_layer_metrics(man, cell, ctx) == {}


def test_the_work_counts_read_the_programs_counters(cell):
    man = M.load_manifest()
    from pathway_tpu.engine import probes

    probes.REGISTRY.remove("moe_assignments")
    probes.REGISTRY.counter_add("moe_assignments", 100, held=1, phase="decode")
    probes.REGISTRY.counter_add("moe_assignments", 700, held=0, phase="decode")
    ctx = {"config": cell["config"],
           "facts": {"prompt_tokens_median": 5651.0}}
    try:
        flops, nbytes = M.resolve(man, "work", "afmoe_prefill")(ctx, 24)
        layout, model = cell["config"]["layouts"]["decoder"], \
            cell["config"]["models"]["decoder"]
        assert ctx["facts"]["prefill_pieces_per_prompt"] == 12
        assert flops == pytest.approx(
            2 * layout.prefill_flops(model, 5651), rel=1e-3)
        assert nbytes > 24 * 8.6e9
        assert M.resolve(man, "work", "afmoe_prefill")(ctx, 0) == (0.0, 0.0)
        reader = M.load_reader_module(man, "answer.moe_held_share")
        assert reader.read(ctx, {"family": "moe_assignments",
                                 "label": "held", "value": 1}) == 0.125
    finally:
        probes.REGISTRY.remove("moe_assignments")


def test_a_small_width_answer_cell_runs_and_its_control_fails():
    """The cell's own layout, builder (sized decoder server, warmed), mix
    generator and check at small widths on the CPU: ``correct`` with no
    compile in the window, every answer admitted once; the control not."""
    man = M.load_manifest(SMALL)
    assert M.problems(man) == []
    small = M.cell(man, "afmoe_small_answer")
    assert small["config"]["builder"] == "qa_rest_server_sized"
    assert small["config"]["models"]["decoder"]["layout"] == "afmoe"
    result = bench_run.run_cell(man, "afmoe_small_answer", 2 ** 31 + 17, 2.0,
                                False, True, device_stamp(),
                                time.perf_counter())
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 0
    compared = result["compared"]
    assert compared["compiles_in_window"] == {"value": 0, "limit": 0}
    assert compared["answers_short_of_tokens"]["value"] == 0
    assert compared["prompts_truncated"]["value"] == 0
    assert compared["token_logit_gap"]["value"] < compared[
        "token_logit_gap"]["limit"]
    assert compared["token_positions_near_tie"]["value"] < 0.5
    assert result["control_correct"] is False, result["control_compared"]
    failing = {k for k, v in result["control_compared"].items()
               if v["value"] > v["limit"]}
    # the decoder's own number refuses the lower precision, not only the
    # index's and the reranker's
    assert {"token_logit_gap", "knn_dist_err", "rerank_score_err"} <= failing
    assert set(result["metrics"]) == {"requests_per_s", "request_p50_ms",
                                      "request_p95_ms", "setup_s"}
