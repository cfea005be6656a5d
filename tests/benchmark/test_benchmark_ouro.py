"""The ``ouro`` layout, its configuration ``ouro-2.6b-rag`` and its cell
``ouro_rag_reason_closed4``: the counts against hand-reckoned figures at the
published entry, the files as the manifest names them, the pool the program
builds for the cell, the decode roofline's work against a hand count, and a
small-width copy of the cell (``tests/benchmark/ouro_small``: the same
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

CELL = "ouro_rag_reason_closed4"
ANSWER = "trinity_rag_answer_closed16"
SMALL = os.path.join(REPO, "tests", "benchmark", "ouro_small",
                     "BENCHMARK.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PASSES, ROOFLINE = "answer.loop_passes_per_token", "decoder_decode_roofline"
APPENDED = [
    "answer.model_mfu", "answer.device_idle_pct",
    "answer.idle_attributed_pct", "answer.slot_occupancy_pct",
    "answer.admissions_per_request", "answer.admit_wait_ms_p50",
    "answer.ttft_ms_p50", "retrieve.request_floor_ms",
    "retrieve.rest_queue_wait_ms_p50", "retrieve.requests_per_epoch",
    "retrieve.queries_per_search_dispatch"]


@pytest.fixture(scope="module")
def cell():
    return M.cell(M.load_manifest(), CELL)


def test_the_configuration_and_the_cell_resolve(cell):
    man = M.load_manifest()
    assert M.problems(man) == []
    assert M.unresolved(man, CELL) == []
    assert cell["cell"] == {
        "name": CELL, "config": "ouro-2.6b-rag", "traffic": CELL,
        "chips": 1, "why": cell["cell"]["why"]}
    # not ``request_p95_ms``: of 60 replies in groups of four it is the
    # slowest round but one, and read 3,851 to 4,599 ms over six seeds (a
    # spread of 10.5 %, twice what a new cell may show: PERF.md section 2)
    assert {m["name"] for m in cell["end_to_end"]} == {
        "requests_per_s", "request_p50_ms", "setup_s"}
    # the accepted metrics that read something here, and two of its own;
    # not the routers', the latent rows' nor the prefill roofline
    assert sorted(m["name"] for m in cell["per_layer"]) \
        == sorted(APPENDED + [PASSES, ROOFLINE])
    assert [m["name"] for m in man["per_layer"][-2:]] == [PASSES, ROOFLINE]
    assert man["per_layer"][-2] == {
        "name": PASSES, "unit": "passes/token", "better": "lower",
        "source": "program_counter", "layer": "model steps",
        "moves": "requests_per_s", "workloads": [CELL]}
    assert man["per_layer"][-1] == {
        "name": ROOFLINE, "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "kernels",
        "moves": "requests_per_s", "workloads": [CELL]}
    # the new entries stand at the end of their lists
    assert man["configs"][-1]["name"] == "ouro-2.6b-rag"
    assert man["workloads"][-1]["name"] == CELL
    assert all(m["workloads"][-1] == CELL
               for m in man["per_layer"] + man["end_to_end"]
               if CELL in m.get("workloads", []))
    assert cell["config_entry"]["reduced"] == ["index_warm_rows"]
    assert list(cell["config"]["reduced"]) == ["index_warm_rows"]
    traffic = cell["traffic"]
    assert (traffic["generator"], traffic["check"], traffic["route"]) == (
        "closed_loop_posts", "answer", "/v2/answer")
    assert (traffic["clients"], traffic["query_words"],
            traffic["pool_queries"], traffic["warm_rounds"],
            traffic["floor_probes"], traffic["timeout_s"],
            traffic["check_requests"], traffic["check_answers"],
            traffic["trace_seconds"]) == (4, 20, 4096, 1, 6, 300, 12, 12, 3)
    srv = cell["config"]["deployment"]["decoder_server"]
    assert traffic["clients"] == srv["n_slots"] == 4
    assert srv == {"n_slots": 4, "max_prompt_tokens": 512,
                   "max_new_tokens": 64, "temperature": 0,
                   "prefill_chunk": 256, "chunk_steps": 16}
    dep = cell["config"]["deployment"]
    assert dep["search_topk"] == 4 <= dep["rerank_candidates"] == 8
    # nothing is truncated on its way through the encoders
    assert dep["doc_words"] + 2 <= dep["embed_max_length"] == 128
    assert dep["doc_words"] + traffic["query_words"] + 3 \
        <= dep["rerank_max_length"] == 256
    # a prompt: 4 passages and the question inside the bucket, two pieces
    assert 256 < 4 * 100 + 20 < srv["max_prompt_tokens"]
    assert (dep["index_warm_rows"], dep["index_capacity"],
            dep["commit_docs"], dep["setup_commits"]) == (
                125000, 131072, 2048, 2)
    limits = cell["config"]["limits"]
    accepted = M.cell(man, ANSWER)["config"]["limits"]
    assert set(limits) == set(accepted) - {"token_positions_near_tie"}
    assert set(cell["config"]["limits_why"]) == {"token_logit_gap"}
    for name, limit in accepted.items():
        if name not in ("token_logit_gap", "token_positions_near_tie"):
            assert limits[name] == limit
    # the encoders are the accepted configuration's
    with open(os.path.join(BENCH, "configs",
                           "minilm-l6-wiki21m-quarter.json")) as f:
        quarter = json.load(f)
    for role in ("embedder", "reranker"):
        assert cell["config"]["models"][role] == quarter["models"][role]
    for line in (cell["cell"]["why"], cell["config_entry"]["why"]):
        assert len(line) <= 200


def test_the_file_states_the_published_config_uncut(cell):
    """Every published key verbatim, at the top level of the file and in
    the decoder's entry; nothing of the model is listed under ``reduced``;
    every placement ``config.json`` does not give is listed under
    ``assumed``."""
    config = cell["config"]
    model = config["models"]["decoder"]
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            rows = [json.loads(line) for line in f]
        row = [r for r in rows if r["name"] == "Ouro-2.6B"][0]
        assert cell["config_entry"]["source"] == row["source_url"] \
            == config["source"] == model["_source"]
        for key, value in row["config"].items():
            assert config[key] == value and model[key] == value, key
    assert (model["num_hidden_layers"], model["hidden_size"],
            model["num_attention_heads"], model["num_key_value_heads"],
            model["head_dim"], model["intermediate_size"],
            model["vocab_size"], model["total_ut_steps"],
            model["early_exit_threshold"], model["rope_theta"],
            model["max_position_embeddings"]) == (
                48, 2048, 16, 16, 128, 5632, 49152, 4, 1, 1000000, 65536)
    assert model["layout"] == "ouro" and model["torch_dtype"] == "bfloat16"
    assumed = " ".join(config["assumed"])
    for said in ("four RMSNorms", "after EVERY pass", "Linear(2,048 -> 1)",
                 "every (pass, layer)", "no attention biases",
                 "half-split", "word-level tokenizer", "synthetic passages",
                 "the encoders' torch_dtype"):
        assert said in assumed, said
    guarantees = " ".join(config["guarantees"])
    for said in ("exactly 64 tokens", "all four passes", "192 cache layers",
                 "none averaged", "no prompt truncated", "exact search",
                 "bfloat16 as served"):
        assert said in guarantees, said
    assert "whole on each chip" in config["deployment"]["stands_for"]


def test_the_counts_at_the_published_entry(cell):
    """Hand-reckoned (the issue's figures): attention 16.78M, SwiGLU 34.60M,
    four gains 8,192: a layer 51.39M, 48 layers 2,466.6M; embedding and head
    201.3M; final norm and gate 4,097: 2,668.0M parameters, 5.34 GB in
    bfloat16. 8,192 B of cache a token a cache layer, 192 of them:
    1,572,864 B a token."""
    layout, model = cell["config"]["layouts"]["decoder"], \
        cell["config"]["models"]["decoder"]
    h, i, v = 2048, 5632, 49152
    assert layout.attention_params(model) == 4 * h * h == 16_777_216
    assert layout.layer_params(model) == 4 * h * h + 3 * h * i == 51_380_224
    layers = 48 * (51_380_224 + 8192)
    assert layers == 2_466_643_968
    assert layout.matmul_param_count(model) == 48 * 51_380_224 + 2 * v * h
    total = layers + 2 * v * h + 4097
    assert layout.param_count(model) == total == 2_667_974_657
    assert round(total / 1e6, 1) == 2668.0
    assert layout.param_bytes(model) == 2 * total
    assert round(2 * total / 1e9, 2) == 5.34
    assert layout.kv_bytes_per_token_layer(model) == 8192
    assert layout.kv_bytes_per_token(model) == 1_572_864 == 192 * 8192
    assert layout.kv_tokens(model, 470) == 192 * 470
    # 4 slots of 656 columns: the pool the issue reckons
    assert 4 * 656 * 1_572_864 == 4_127_195_136
    # a step: the layers four times and the head once, and the live cache
    step = layout.decode_step_bytes(model, 4 * 470.0, batch=4.0)
    weights = 2 * (4 * layers + v * h + 2 * h + 1 + 4 * h)
    assert step == weights + 4 * 470 * 1_572_864
    assert 19.9e9 < weights < 20.0e9 and 22.8e9 < step < 23.0e9
    flops = layout.decode_step_flops(model, 4.0, 4 * 470.0)
    assert flops == 2.0 * 4 * (4 * 48 * 51_380_224 + v * h + 4 * h) \
        + 4.0 * h * 192 * 4 * 470
    # a piece of 256: 4 passes x 2 x 2.47 G x 256 = 5.1 TFLOP
    n = 425
    want = 2.0 * n * (4 * 48 * 51_380_224 + 4 * h) \
        + 4.0 * h * 192 * n * (n + 1) / 2 + 2.0 * v * h
    assert layout.prefill_flops(model, n) == pytest.approx(want, rel=1e-12)
    assert 5.0e12 < 2.0 * 256 * 4 * 48 * 51_380_224 < 5.1e12
    assert layout.answer_flops(model, n, 64) > want \
        + 63 * 2.0 * (4 * 48 * 51_380_224)
    spec = layout.weight_spec(model, "decoder")

    def size(tree):
        return sum(size(t) if isinstance(t, dict) else int(np.prod(t[0]))
                   for t in tree.values())

    assert size(spec) == total


def test_the_cells_pool_keeps_a_cache_layer_a_pass(cell):
    """The program's own pool at the cell's sizes: 4 slots of 512 + 64 + 5
    x 16 columns, 192 cache layers of 8,192 B a token: 4,127,195,136 B; no
    prefix arena (a block of 256 tokens is 384 MiB, over the server's 64
    MB)."""
    import jax

    from pathway_tpu.models import decoder as D

    layout, model = cell["config"]["layouts"]["decoder"], \
        cell["config"]["models"]["decoder"]
    cfg = layout.program_config(model)
    assert (cfg.loops, cfg.layers, cfg.exit_gate, cfg.exit_threshold) == (
        4, 48, True, 1.0)
    assert cfg.runs() == ((("full", "rotary", "dense"), 0, 48),)
    columns = 512 + 64 + 5 * 16
    pool = jax.eval_shape(lambda: D.pool_init(None, cfg, 4, columns))
    kv = {n: a.shape for n, a in pool.items() if D._is_kv(n)}
    assert kv == {"k": (192, 4, 16, columns, 128),
                  "v": (192, 4, 16, columns, 128)}
    assert D.pool_component_bytes(pool) == {"slot_pool": 4_127_195_136}
    assert D.kv_token_bytes(cfg, 2) == 1_572_864
    assert 64 * (1 << 20) // (256 * D.kv_token_bytes(cfg, 2)) == 0
    params = jax.eval_shape(
        lambda: D.init_params(jax.random.PRNGKey(0), cfg))
    assert D.count_params(params) == layout.param_count(model)


def _trace(steps_by_run, passes=4, layers=48, edge=0):
    """A device trace as the work function reads it: a run of ``jit_piece``
    and runs of ``jit_chunk`` whose every step holds 3 operations of its
    own, 2 a pass and 7 a layer of a pass; the last ``edge`` operations
    fall outside the slice."""
    from harness.trace import TraceSummary

    modules, ops, t = [("jit_piece(1)", 0, 50)], [("fusion.9", 10, 5)], 100
    for steps in steps_by_run:
        start = t
        for _step in range(steps):
            names = [f"step.{k}" for k in range(3)]
            for _u in range(passes):
                for _l in range(layers):
                    names += [f"layer.{k}" for k in range(7)]
                names += ["pass.0", "pass.1"]
            for name in names:
                ops.append((name, t, 1))
                t += 1
        modules.append((f"jit_chunk({17 + steps})", start, t - start))
        t += 10
    return TraceSummary({"devices": {"/device:TPU:0": {
        "modules": modules, "ops": ops[:len(ops) - edge]}}, "lines": {}})


def test_the_decode_rooflines_work_is_a_hand_count(cell):
    """``looped_decode``: the steps the traced runs of ``jit_chunk`` held,
    read off the device's own operations (a layer-body operation runs once
    for every layer of every pass of every step: 16 + 8 + 4 steps here,
    never runs x 16), times the layout's step at the useful lanes and the
    live columns of an answer half written."""
    man = M.load_manifest()
    work = M.resolve(man, "work", "looped_decode")
    spec = M.load_json_named(man, "metrics", ROOFLINE)
    assert spec == {"reader": "trace_module_roofline",
                    "params": {"modules": "^jit_chunk\\b",
                               "work": "looped_decode"}}
    layout, model = cell["config"]["layouts"]["decoder"], \
        cell["config"]["models"]["decoder"]
    ctx = {"config": cell["config"], "trace": _trace([16, 8, 4]),
           "facts": {"prompt_tokens_median": 425.0},
           "lifetime_counters": {"decoder_steps": 3 * 64 * 4,
                                 "decoder_slot_steps_total": 4 * 64 * 4}}
    flops, nbytes = work(ctx, 3)
    assert ctx["facts"]["decode_steps_traced"] == 28.0
    assert ctx["facts"]["decode_useful_lanes"] == 3.0
    live = 3.0 * (425 + 32)
    assert flops == 28 * layout.decode_step_flops(model, 3.0, live)
    assert nbytes == 28 * layout.decode_step_bytes(model, live, batch=3.0)
    assert nbytes == pytest.approx(
        28 * (19.94e9 + 3 * 457 * 1_572_864), rel=1e-3)
    # a run cut by the slice's edge counts for the part inside
    ctx["trace"] = _trace([16, 16], edge=4 * (4 * (48 * 7 + 2) + 3))
    work(ctx, 2)
    assert 27.9 < ctx["facts"]["decode_steps_traced"] < 28.1
    # nothing to read: no prompt seen, no run, a program that counts none
    assert work(dict(ctx, facts={}), 3) == (0.0, 0.0)
    assert work(ctx, 0) == (0.0, 0.0)
    assert work(dict(ctx, lifetime_counters={}), 3) == (0.0, 0.0)
    # through the accepted reader: a share of the chip's roofline
    from harness.readers import trace_module_roofline

    ctx = dict(ctx, manifest=man, trace=_trace([16, 8, 4]),
               peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
               facts={"prompt_tokens_median": 425.0})
    runs, seconds = ctx["trace"].module_seconds(spec["params"]["modules"])
    assert runs == 3
    share = None
    try:
        share = trace_module_roofline(ctx, spec["params"])
    except ValueError as exc:       # the toy trace's runs last microseconds
        assert "roofline" in str(exc)
    assert share is None or share > 0
    assert ctx["facts"]["roofline"]["looped_decode"]["bound"] == "memory"


def test_every_new_file_is_named_by_the_manifest(cell):
    man = M.load_manifest()
    assert M.resolve(man, "layouts", "ouro") is cell["config"]["layouts"][
        "decoder"]
    assert cell["config"]["builder"] == "qa_rest_server_sized"
    spec = M.load_json_named(man, "metrics", PASSES)
    reader = M.load_reader_module(man, PASSES)
    assert reader is not None and spec["params"] == {
        "family": "loop_passes", "label": "phase", "value": "decode",
        "per": "pass"}
    path = M._find(man, "metrics", PASSES, ".py")
    assert os.path.relpath(path, REPO).startswith(
        os.path.join("tests", "benchmark", "metrics"))
    from pathway_tpu.engine import probes, tracing

    # a program that records none of it gives every reader nothing to read
    ctx = {"trace": None, "counters": {}, "slice_counters": {},
           "lifetime_counters": {}, "spans": {}, "facts": {},
           "config": cell["config"], "traffic": cell["traffic"]}
    for family in ("moe_assignments", "knn_search_queries",
                   "device_dispatch", "loop_passes", "loop_exit_step"):
        probes.REGISTRY.remove(family)
    tracing.reset_traces()
    assert bench_run.per_layer_metrics(man, cell, ctx) == {}
    try:
        probes.record_loop_passes("prefill", 425, 4)
        probes.record_loop_passes("decode", 64, 4)
        assert reader.read(ctx, spec["params"]) == 4.0
        # tokens of one batch leaving the loop early would read less
        probes.REGISTRY.counter_add_many(
            "loop_passes", ("phase", "pass"),
            {("decode", 1): 11, ("decode", 2): 11})
        assert reader.read(ctx, spec["params"]) == pytest.approx(
            (4 * 489 + 22) / 500)
    finally:
        probes.REGISTRY.remove("loop_passes")
    assert reader.read(ctx, spec["params"]) is None


def test_a_small_width_answer_cell_runs_and_its_control_fails():
    """The cell's own layout, builder (sized decoder server, warmed), mix
    generator and check at small widths on the CPU: ``correct`` with no
    compile in the window, four passes a token to the digit; the control
    not."""
    from pathway_tpu.engine import probes

    man = M.load_manifest(SMALL)
    assert M.problems(man) == []
    small = M.cell(man, "ouro_small_answer")
    assert small["config"]["builder"] == "qa_rest_server_sized"
    assert small["config"]["models"]["decoder"]["layout"] == "ouro"
    assert small["traffic"]["check"] == "answer"
    probes.REGISTRY.remove("loop_passes", "loop_exit_step")
    result = bench_run.run_cell(man, "ouro_small_answer", 2 ** 31 + 17,
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
    assert result["control_correct"] is False, result["control_compared"]
    failing = {k for k, v in result["control_compared"].items()
               if v["value"] > v["limit"]}
    # the decoder's own number refuses the lower precision, not only the
    # index's and the reranker's
    assert {"token_logit_gap", "knn_dist_err", "rerank_score_err"} <= failing
    assert set(result["metrics"]) == {"requests_per_s", "request_p50_ms",
                                      "request_p95_ms", "setup_s"}
    reader = M.load_reader_module(man, PASSES)
    spec = M.load_json_named(man, "metrics", PASSES)
    assert reader.read({}, spec["params"]) == 4.0
    exits = probes.REGISTRY.labelled("loop_exit_step", "step")
    assert set(exits) == {"4"} and exits["4"] > 0
