"""The ``deepseek_v2`` layout, its configuration ``deepseek-v2-ep8-rag`` and
its cell ``deepseek_v2_rag_long_closed8``: the counts against hand-reckoned
figures at the published widths, the files as the manifest names them, the
pool the program builds for the cell, and a small-width copy of the cell
(``tests/benchmark/deepseek_small``: the same layout, builder, generator and
check) through ``run_cell`` on the CPU — ``correct``, its control not. These
check the harness's side and measure nothing."""

import json
import os
import time

import numpy as np
import pytest

from bench_paths import BENCH, REPO

import run as bench_run
from harness import manifest as M
from harness.system import device_stamp

CELL = "deepseek_v2_rag_long_closed8"
ANSWER = "trinity_rag_answer_closed16"
SMALL = os.path.join(REPO, "tests", "benchmark", "deepseek_small",
                     "BENCHMARK.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = "answer.latent_rows_expanded_per_token"


@pytest.fixture(scope="module")
def cell():
    return M.cell(M.load_manifest(), CELL)


def test_the_configuration_and_the_cell_resolve(cell):
    man = M.load_manifest()
    assert M.problems(man) == []
    assert M.unresolved(man, CELL) == []
    assert cell["cell"] == {
        "name": CELL, "config": "deepseek-v2-ep8-rag", "traffic": CELL,
        "chips": 1, "why": cell["cell"]["why"]}
    assert {m["name"] for m in cell["end_to_end"]} == {
        "requests_per_s", "request_p50_ms", "request_p95_ms", "setup_s"}
    # every per-layer metric the accepted answer cell reports, and one more
    accepted = M.cell(man, ANSWER)
    assert [m["name"] for m in cell["per_layer"]] \
        == [m["name"] for m in accepted["per_layer"]] + [NEW]
    assert cell["per_layer"][-1] == {
        "name": NEW, "unit": "rows/token", "better": "lower",
        "source": "program_counter", "layer": "model steps",
        "moves": "requests_per_s", "workloads": [CELL]}
    # the new entries stand at the end of their lists
    assert man["configs"][-1]["name"] == "deepseek-v2-ep8-rag"
    assert man["workloads"][-1]["name"] == CELL
    assert man["per_layer"][-1]["name"] == NEW
    assert all(m["workloads"][-1] == CELL for m in man["per_layer"]
               if ANSWER in m["workloads"])
    assert cell["config_entry"]["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size",
        "index_warm_rows"]
    traffic = cell["traffic"]
    assert (traffic["generator"], traffic["check"], traffic["route"]) == (
        "closed_loop_posts", "answer_moe", "/v2/answer")
    assert (traffic["clients"], traffic["query_words"],
            traffic["pool_queries"], traffic["warm_rounds"],
            traffic["trace_seconds"]) == (8, 20, 4096, 1, 3)
    srv = cell["config"]["deployment"]["decoder_server"]
    assert traffic["clients"] == srv["n_slots"]     # clients follow slots
    assert (srv["max_prompt_tokens"], srv["max_new_tokens"],
            srv["temperature"], srv["chunk_steps"]) == (16384, 64, 0, 16)
    dep = cell["config"]["deployment"]
    assert dep["search_topk"] == 40 <= dep["rerank_candidates"] == 48
    # nothing is truncated on its way through the encoders: the harness's
    # needed-FLOP counts and references take whole passages
    assert dep["doc_words"] == 400 and dep["doc_words"] % 10 == 0
    assert dep["doc_words"] + 2 <= dep["embed_max_length"] == 512
    assert dep["doc_words"] + traffic["query_words"] + 3 \
        <= dep["rerank_max_length"] == 512
    # a prompt: 40 passages and the question inside the bucket
    assert 40 * 400 + 20 < srv["max_prompt_tokens"]
    assert (dep["index_warm_rows"], dep["index_capacity"]) == (
        125000, 131072)
    limits = cell["config"]["limits"]
    assert set(limits) == set(M.cell(man, ANSWER)["config"]["limits"])
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
    for line in (cell["cell"]["why"], cell["config_entry"]["why"]):
        assert len(line) <= 200


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
        row = [r for r in rows if r["name"] == "DeepSeek-V2"][0]
        assert cell["config_entry"]["source"] == row["source_url"]
        for key, value in row["config"].items():
            for holder in (config, model):
                if key in reduced:
                    assert holder[key] != value
                else:
                    assert holder[key] == value, key
    assert (model["num_hidden_layers"], model["n_routed_experts"],
            model["vocab_size"]) == (6, 20, 12800)
    assert (model["num_hidden_layers_published"],
            model["n_routed_experts_published"],
            model["vocab_size_published"]) == (60, 160, 102400)
    assert model["layers_kept"] == [0, 1, 2, 3, 4, 5]
    # every width as published
    assert (model["hidden_size"], model["num_attention_heads"],
            model["q_lora_rank"], model["kv_lora_rank"],
            model["qk_nope_head_dim"], model["qk_rope_head_dim"],
            model["v_head_dim"], model["intermediate_size"],
            model["moe_intermediate_size"], model["num_experts_per_tok"],
            model["n_group"], model["topk_group"]) == (
                5120, 128, 1536, 512, 128, 64, 128, 12288, 1536, 6, 8, 3)
    # the guide's floors: a whole period and four layers after the dense
    # one, at least 8 experts, at least an eighth of the vocabulary
    assert model["num_hidden_layers"] - model["first_k_dense_replace"] >= 4
    assert model["n_routed_experts"] >= 8
    assert model["vocab_size"] * 8 >= model["vocab_size_published"]
    # the share is ONE of the model's own device groups
    shared_by = config["deployment"]["chips_sharing_a_layer"]
    assert shared_by == model["n_group"] == 8
    assert model["n_routed_experts"] * shared_by \
        == model["n_routed_experts_published"]
    assert model["experts_held_first"] % model["n_routed_experts"] == 0
    assert any("576 values a token a layer" in g
               for g in config["guarantees"])


def test_the_counts_at_published_widths(cell):
    """Hand-reckoned (the issue's figures): attention 149.2M a layer (W_DQ
    7.86M, W_UQ 37.75M, W_DKV 2.95M, W_UKV 16.78M, W_O 83.89M), an expert
    23.59M, an expert layer with 20 held and 2 shared 669.0M, the dense
    layer 337.9M, an eighth of the vocabulary twice 131.1M: 3,814M, 7.63 GB
    in bfloat16; 1,152 B of cache a token a layer."""
    layout, model = cell["config"]["layouts"]["decoder"], \
        cell["config"]["models"]["decoder"]
    h = 5120
    parts = (h * 1536, 1536 * 128 * 192, h * 576, 512 * 128 * 256,
             128 * 128 * h)
    assert [round(p / 1e6, 2) for p in parts] == [
        7.86, 37.75, 2.95, 16.78, 83.89]
    attention = sum(parts)
    assert layout.attention_params(model) == attention == 149_225_472
    assert layout.expert_params(model) == 3 * h * 1536 == 23_592_960
    moe_layer = attention + h * 160 + 22 * 23_592_960
    assert layout.layer_params(model, "moe") == moe_layer
    assert moe_layer == pytest.approx(669.0e6, rel=1e-3)
    dense_layer = attention + 3 * h * 12288
    assert layout.layer_params(model, "dense") == dense_layer
    assert dense_layer == pytest.approx(337.9e6, rel=1e-3)
    total = dense_layer + 5 * moe_layer + 2 * 12800 * h
    assert layout.matmul_param_count(model) == total
    assert total == pytest.approx(3814e6, rel=1e-3)
    assert layout.param_bytes(model) == pytest.approx(7.63e9, rel=1e-3)
    assert layout.kv_bytes_per_token_layer(model) == 1152
    assert layout.kv_bytes_per_token(model) == 6 * 1152
    assert layout.kv_tokens(model, 16050) == 6 * 16050
    # per-head keys and values would be 57 times as much
    assert 128 * (128 + 128) * 2 == 65536 and 65536 // 1152 == 56
    # a token multiplies 6 x 20/160 = 0.75 routed experts and the 2 shared
    per_token = layout.layer_params_per_token(model, "moe")
    assert per_token == attention + h * 160 + 2.75 * 23_592_960
    n = 16050
    want = 2.0 * n * (dense_layer + 5 * per_token) \
        + 2.0 * 128 * (192 + 128) * 6 * n * (n + 1) / 2 + 2.0 * 12800 * h
    assert layout.prefill_flops(model, n) == pytest.approx(want, rel=1e-12)
    # the read is well over half of a long prompt's operations
    read = 2.0 * 128 * 320 * 6 * n * (n + 1) / 2
    assert 0.55 < read / want < 0.65 and 1.0e14 < want < 1.2e14
    # a step reads the weights it multiplies and every slot's latent rows
    step = layout.decode_step_bytes(model, 8 * 16050.0, experts_touched=8,
                                    batch=8.0)
    assert 5.0e9 < step < 6.0e9
    assert step - layout.decode_step_bytes(model, 0.0, experts_touched=8,
                                           batch=8.0) \
        == pytest.approx(8 * 16050 * 6 * 1152)
    assert layout.decode_step_flops(model, 1, 16050.0) \
        - layout.decode_step_flops(model, 1, 0.0) \
        == pytest.approx(2.0 * 128 * (2 * 512 + 64) * 6 * 16050)
    assert layout.answer_flops(model, n, 64) > want
    # the work of the accepted roofline reads every count from THIS layout
    ctx = {"config": cell["config"],
           "facts": {"prompt_tokens_median": 16050.0}}
    flops, nbytes = M.resolve(M.load_manifest(), "work", "afmoe_prefill")(
        ctx, 64)
    assert ctx["facts"]["prefill_pieces_per_prompt"] == 32
    assert flops == pytest.approx(
        2 * (want - 2.0 * 12800 * h), rel=1e-9)
    assert nbytes == pytest.approx(
        64 * layout.param_bytes(model) + 2 * 2 * 6 * 16050 * 1152)
    spec = layout.weight_spec(model, "decoder")

    def size(tree):
        return sum(size(v) if isinstance(v, dict)
                   else int(np.prod(v[0]))
                   for v in tree.values())

    assert size(spec) * 2 == layout.param_bytes(model)


def test_the_cells_pool_holds_latent_rows_and_nothing_per_head(cell):
    """The program's own pool at the cell's sizes: 8 slots of 16,384 + 64 +
    5 x 16 columns, 6 layers of 1,152 B a token: 0.91 GB (per-head keys and
    values would be 52 GB), in one array a run of like layers and its arena
    blocks; no K, no V."""
    import jax

    from pathway_tpu.models import decoder as D

    layout, model = cell["config"]["layouts"]["decoder"], \
        cell["config"]["models"]["decoder"]
    cfg = layout.program_config(model)
    assert cfg.runs() == ((("latent", "rotary", "dense"), 0, 1),
                          (("latent", "rotary", "moe"), 1, 5))
    assert (cfg.moe.held, cfg.moe.experts, cfg.moe.groups,
            cfg.moe.groups_per_token, cfg.moe.per_token) == (
                (0, 20), 160, 8, 3, 6)
    columns = 16384 + 64 + 5 * 16
    pool = jax.eval_shape(lambda: D.pool_init(
        None, cfg, 8, columns, arena_blocks=18, arena_block=512))
    kv = {n: a.shape for n, a in pool.items() if D._is_kv(n)}
    assert kv == {"cl0": (1, 8, 1, columns, 576),
                  "cl1": (5, 8, 1, columns, 576)}
    assert not any(n[:1] in "kv" or n.startswith(("arena_k", "arena_v"))
                   for n in pool)
    comp = D.pool_component_bytes(pool)
    assert comp["slot_pool_latent"] == 8 * columns * 6 * 1152
    assert comp["slot_pool_latent"] == pytest.approx(
        8 * 16448 * 6 * 1152, rel=0.005)            # within the slack columns
    assert comp["prefix_arena"] == 18 * 512 * 6 * 1152
    assert set(comp) == {"slot_pool_latent", "prefix_arena"}
    assert 64 * (1 << 20) // (512 * D.kv_token_bytes(cfg, 2)) == 18


def test_every_new_file_is_named_by_the_manifest(cell):
    man = M.load_manifest()
    assert M.resolve(man, "layouts", "deepseek_v2") is cell["config"][
        "layouts"]["decoder"]
    assert cell["config"]["builder"] == "qa_rest_server_sized"
    spec = M.load_json_named(man, "metrics", NEW)
    reader = M.load_reader_module(man, NEW)
    assert reader is not None and spec["params"]["family"] \
        == "latent_rows_expanded"
    path = M._find(man, "metrics", NEW, ".py")
    assert os.path.relpath(path, REPO).startswith(
        os.path.join("tests", "benchmark", "metrics"))
    from pathway_tpu.engine import probes, tracing

    # a program that records none of it gives every reader nothing to read
    ctx = {"trace": None, "counters": {}, "slice_counters": {},
           "lifetime_counters": {}, "spans": {}, "facts": {},
           "config": cell["config"], "traffic": cell["traffic"]}
    for family in ("moe_assignments", "knn_search_queries",
                   "device_dispatch", "latent_rows_expanded"):
        probes.REGISTRY.remove(family)
    tracing.reset_traces()
    assert bench_run.per_layer_metrics(man, cell, ctx) == {}
    # 32 pieces of 512 that each expand every block of 512 rows they see:
    # 1 + 2 + ... + 32 blocks a layer, over 32 x 512 columns: 16.5
    try:
        probes.REGISTRY.counter_add(
            "latent_rows_expanded", 6 * 512 * sum(range(1, 33)),
            phase="prefill")
        ctx["lifetime_counters"] = {"decoder_prefill_chunks": 32}
        assert reader.read(ctx, spec["params"]) == 16.5
        ctx["lifetime_counters"] = {}
        assert reader.read(ctx, spec["params"]) is None
    finally:
        probes.REGISTRY.remove("latent_rows_expanded")


def test_a_small_width_answer_cell_runs_and_its_control_fails():
    """The cell's own layout, builder (sized decoder server, warmed), mix
    generator and check at small widths on the CPU: ``correct`` with no
    compile in the window, every answer admitted once; the control not."""
    man = M.load_manifest(SMALL)
    assert M.problems(man) == []
    small = M.cell(man, "deepseek_small_answer")
    assert small["config"]["builder"] == "qa_rest_server_sized"
    assert small["config"]["models"]["decoder"]["layout"] == "deepseek_v2"
    result = bench_run.run_cell(man, "deepseek_small_answer", 2 ** 31 + 17,
                                2.0, False, True, device_stamp(),
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
