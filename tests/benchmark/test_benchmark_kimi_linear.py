"""The ``kimi_linear`` layout, its configuration ``kimi-linear-48b-ep8-rag``
and its cell ``kimi_linear_rag_decode_closed64``: the counts against
hand-reckoned figures at the published widths, the files as the manifest
names them, the pool the program builds for the cell, the two KDA metrics'
readers and work against a hand count, and a small-width copy of the cell
(``tests/benchmark/kimi_small``: the same layout, builder, generator and
check) through ``run_cell`` on the CPU — ``correct``, its control not. These
check the harness's side and measure nothing."""

import json
import os
import time

import numpy as np
import pytest

from bench_paths import REPO

import run as bench_run
from harness import manifest as M
from harness.system import device_stamp

CELL = "kimi_linear_rag_decode_closed64"
CONFIG = "kimi-linear-48b-ep8-rag"
ANSWER = "trinity_rag_answer_closed16"
SMALL = os.path.join(REPO, "tests", "benchmark", "kimi_small",
                     "BENCHMARK.json")
# the published-config catalog, a JSON line a model, where one is given
CATALOG = os.environ.get("MODEL_CONFIG_CATALOG", "")
ROOFLINE, PER_STEP = "kda_decode_roofline", "answer.kda_ms_per_step"
APPENDED = [
    "retrieve.request_floor_ms", "retrieve.rest_queue_wait_ms_p50",
    "retrieve.requests_per_epoch", "retrieve.queries_per_search_dispatch",
    "answer.device_idle_pct", "answer.idle_attributed_pct",
    "answer.model_mfu", "answer.moe_held_share",
    "answer.slot_occupancy_pct", "answer.admissions_per_request",
    "answer.admit_wait_ms_p50", "answer.ttft_ms_p50",
    "answer.decode_attn_ms_per_step"]


@pytest.fixture(scope="module")
def cell():
    return M.cell(M.load_manifest(), CELL)


def test_the_configuration_and_the_cell_are_on_the_list(cell):
    man = M.load_manifest()
    assert M.problems(man) == []
    assert M.unresolved(man, CELL) == []
    assert cell["cell"] == {
        "name": CELL, "config": CONFIG, "traffic": CELL, "chips": 1,
        "why": cell["cell"]["why"]}
    assert CELL in [w["name"] for w in man["workloads"]]
    assert CONFIG in [c["name"] for c in man["configs"]]
    # not ``request_p95_ms``: 64 replies a round come as one burst whose
    # tail is the round's last prefill
    assert {m["name"] for m in cell["end_to_end"]} == {
        "requests_per_s", "request_p50_ms", "setup_s"}
    assert [m["name"] for m in cell["per_layer"]] \
        == APPENDED + [ROOFLINE, PER_STEP]
    mine = {m["name"]: m for m in man["per_layer"]}
    assert mine[ROOFLINE] == {
        "name": ROOFLINE, "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "kernels",
        "moves": "requests_per_s", "workloads": [CELL]}
    assert mine[PER_STEP] == {
        "name": PER_STEP, "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "model steps",
        "moves": "requests_per_s", "workloads": [CELL]}
    # appended: where the cell stands in a list, it stands last
    assert all(m["workloads"][-1] == CELL
               for m in man["per_layer"] + man["end_to_end"]
               if CELL in m.get("workloads", []))
    assert cell["config_entry"]["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size", "index_warm_rows"]
    assert list(cell["config"]["reduced"]) \
        == cell["config_entry"]["reduced"]
    traffic = cell["traffic"]
    assert (traffic["generator"], traffic["check"], traffic["route"]) == (
        "closed_loop_posts", "answer_moe", "/v2/answer")
    assert (traffic["clients"], traffic["query_words"],
            traffic["pool_queries"], traffic["warm_rounds"],
            traffic["timeout_s"], traffic["check_answers"],
            traffic["trace_seconds"]) == (64, 20, 4096, 1, 300, 4, 3)
    srv = cell["config"]["deployment"]["decoder_server"]
    assert traffic["clients"] == srv["n_slots"] == 64
    assert srv == {"n_slots": 64, "max_prompt_tokens": 1024,
                   "max_new_tokens": 2048, "temperature": 0,
                   "prefill_chunk": 512, "chunk_steps": 16}
    dep = cell["config"]["deployment"]
    assert dep["search_topk"] == 2 <= dep["rerank_candidates"]
    # nothing is truncated on its way through the encoders; a prompt of two
    # passages and the question lies inside the 1,024 bucket, two pieces
    assert dep["doc_words"] + 2 <= dep["embed_max_length"] == 512
    assert dep["doc_words"] + traffic["query_words"] + 3 \
        <= dep["rerank_max_length"] == 512
    assert 512 < 2 * (dep["doc_words"] + 2) + 20 < srv["max_prompt_tokens"]
    limits = cell["config"]["limits"]
    accepted = M.cell(man, ANSWER)["config"]["limits"]
    assert set(limits) == set(accepted)
    assert set(cell["config"]["limits_why"]) == {
        "token_logit_gap", "token_positions_near_tie"}
    for name, limit in accepted.items():
        if name not in ("token_logit_gap", "token_positions_near_tie"):
            assert limits[name] == limit
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "minilm-l6-wiki21m-quarter.json")) as f:
        quarter = json.load(f)
    for role in ("embedder", "reranker"):
        assert cell["config"]["models"][role] == quarter["models"][role]
    for line in (cell["cell"]["why"], cell["config_entry"]["why"]):
        assert len(line) <= 200


def test_the_file_states_the_published_config_and_its_cut(cell):
    """Every published key verbatim at the top level of the file and in the
    decoder's entry but the three cut, each cut listed under ``reduced``
    with the published count beside it; every size ``config.json`` does not
    give listed under ``assumed``."""
    config = cell["config"]
    model = config["models"]["decoder"]
    cut = {"num_hidden_layers": 8, "num_experts": 32, "vocab_size": 20480}
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            rows = [json.loads(line) for line in f]
        row = [r for r in rows
               if r["name"] == "Kimi-Linear-48B-A3B-Instruct"][0]
        assert cell["config_entry"]["source"] == row["source_url"] \
            == config["source"] == model["_source"]
        for key, value in row["config"].items():
            want = cut.get(key, value)
            assert config[key] == want and model[key] == want, key
    assert (model["num_hidden_layers_published"],
            model["num_experts_published"], model["vocab_size_published"],
            model["experts_held_first"], model["layers_kept"]) == (
                27, 256, 163840, 0, list(range(8)))
    assert model["layout"] == "kimi_linear" \
        and model["torch_dtype"] == "bfloat16"
    lin = model["linear_attn_config"]
    assert (lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"],
            lin["full_attn_layers"]) == (32, 128, 4, [4, 8, 12, 16, 20, 24,
                                                      27])
    assumed = " ".join(config["assumed"])
    for said in ("state in float32", "no bias", "eps", "A_log", "dt_bias",
                 "head_dim 72", "word-level tokenizer"):
        assert said in assumed, said
    assert config["deployment"]["chips_sharing_a_layer"] == 8
    assert "layers 0-7" in config["deployment"]["stands_for"]


def test_the_counts_at_the_published_entry(cell):
    """Hand-reckoned figures: KDA attention 37.75M + 1.77M, MLA
    29.11M, an expert layer's share 234.16M, the dense SwiGLU 63.70M,
    embedding and head 94.37M: 2,093M, 4.19 GB; 1,152 B a token an MLA
    layer; 2 MiB and 72 KiB a KDA layer a slot."""
    layout, model = cell["config"]["layouts"]["decoder"], \
        cell["config"]["models"]["decoder"]
    h, n, d = 2304, 32, 128
    kda = layout.attention_params(model, "kda")
    assert kda == 4 * h * n * d + 2 * (h * d + d * n * d) + h * n
    assert round(4 * h * n * d / 1e6, 2) == 37.75
    gates = kda - 4 * h * n * d + 4 * 3 * n * d + n + n * d + d
    assert round(gates / 1e6, 2) == 1.77
    mla = layout.attention_params(model, "latent")
    assert mla == h * n * 192 + h * 576 + 512 * n * 256 + n * 128 * h
    assert round(mla / 1e6, 2) == 29.11
    held = 32 * 3 * h * 1024 + 3 * h * 1024 + h * 256
    assert round(held / 1e6, 2) == 234.16
    assert layout.layer_params(model, ("kda", "dense")) \
        == kda + 3 * h * 9216
    total = layout.matmul_param_count(model)
    assert total == 6 * kda + 2 * mla + 7 * held + 3 * h * 9216 \
        + 2 * 20480 * h
    assert round(total / 1e6, 1) == 2092.2
    assert 4.18e9 < layout.param_bytes(model) < 4.20e9
    assert layout.kv_bytes_per_token_layer(model) == 1152
    assert layout.kv_bytes_per_token(model) == 2 * 1152
    assert layout.kda_layers(model) == 6
    per_slot = layout.state_bytes_per_slot(model)
    assert per_slot == 6 * (n * d * d * 4 + 3 * 3 * n * d * 2)
    # the pool at 64 slots of 1,024 + 2,048 + 5 x 16 columns: 1.30 GB
    assert 64 * 3152 * 2 * 1152 + 64 * per_slot == 1_298_399_232
    # a step of 64 lanes carries 1.61 GB of state in and out, and reads it
    rec = layout.recurrence_bytes(model)
    assert rec == 6 * (2 * n * d * d + 4 * n * d + n) * 4
    assert 64 * 2 * 6 * n * d * d * 4 == 1_610_612_736
    step = layout.decode_step_bytes(model, 64 * 1500.0, batch=64.0,
                                    experts_touched=28)
    assert 5.4e9 < step < 5.8e9
    assert layout.recurrence_flops(model) == 7.0 * n * d * d
    flops = layout.decode_step_flops(model, 64.0, 64 * 1500.0)
    assert flops > 64 * 6 * layout.recurrence_flops(model)
    assert layout.prefill_flops(model, 870) > 2.0 * 870 * 6 * kda
    spec = layout.weight_spec(model, "decoder")

    def size(tree):
        return sum(size(t) if isinstance(t, dict) else int(np.prod(t[0]))
                   for t in tree.values())

    small = size(spec) - total
    assert 0 < small < 1e6          # the gains, the taps, the decay's bias
    assert round(size(spec) / 1e6) == 2093


def test_the_cells_pool_keeps_a_state_a_slot(cell):
    """The program's own pool at the cell's sizes: the latent runs' rows and
    the KDA runs' states and tails, 1,298,399,232 B (1.30 GB); no prefix
    arena."""
    import jax

    from pathway_tpu.models import decoder as D

    layout, model = cell["config"]["layouts"]["decoder"], \
        cell["config"]["models"]["decoder"]
    cfg = layout.program_config(model)
    assert [(k[0], n) for k, _f, n in cfg.runs()] == [
        ("kda", 1), ("kda", 2), ("latent", 1), ("kda", 3), ("latent", 1)]
    assert cfg.positions == "none" and cfg.has_state
    pool = jax.eval_shape(lambda: D.pool_init(None, cfg, 64, 3152))
    comp = D.pool_component_bytes(pool)
    assert comp == {"slot_pool_latent": 64 * 3152 * 2 * 1152,
                    "slot_state": 64 * layout.state_bytes_per_slot(model)}
    assert sum(comp.values()) == 1_298_399_232
    assert D.kv_token_bytes(cfg, 2) == layout.kv_bytes_per_token(model)
    assert D.state_slot_bytes(cfg, 2) == layout.state_bytes_per_slot(model)
    params = jax.eval_shape(
        lambda: D.init_params(jax.random.PRNGKey(0), cfg))
    spec = layout.weight_spec(model, "decoder")
    assert jax.tree.map(lambda a: a.shape, params) == jax.tree.map(
        lambda t: tuple(t[0]), spec, is_leaf=lambda t: isinstance(t, tuple))


def _split(steps_a_run, kda_ns):
    """The device-scope split a traced slice would give: runs of
    ``jit_chunk`` carrying ``steps`` each, with ``kda_ns`` under
    ``kda.step`` and some under the other KDA scopes."""
    return {"executables": [
        {"module": "jit_chunk", "carries": {"steps": s, "passes": 1},
         "runs": 2, "ns": 10 * kda_ns,
         "by_scope_ns": {"kda.step": kda_ns, "kda.conv": kda_ns // 2,
                         "mla.absorb": kda_ns, "unscoped": kda_ns}}
        for s in steps_a_run] + [
        {"module": "jit_piece", "carries": {"pieces": 1, "passes": 1},
         "runs": 1, "ns": 5, "by_scope_ns": {"kda.chunk": 5}}],
        "unmatched_ns": {}, "other_ns": {}, "table": 3}


def test_the_kda_readers_and_their_work_are_a_hand_count(cell):
    """``kda_decode_roofline``: the least time of the traced steps' states
    read once and written once for each useful lane, over the self time
    under ``kda.step``; ``answer.kda_ms_per_step``: the self time under
    every ``kda.*`` scope of the chunks over their steps. A program that
    names no such scope gives both nothing to read."""
    man = M.load_manifest()
    work = M.resolve(man, "work", "kda_decode")
    layout, model = cell["config"]["layouts"]["decoder"], \
        cell["config"]["models"]["decoder"]
    life = {"decoder_steps": 48 * 100, "decoder_slot_steps_total": 64 * 100}
    ctx = {"config": cell["config"], "facts": {}, "lifetime_counters": life,
           "manifest": man, "trace": object(),
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
           "device_scopes": _split([16, 8], 4_000_000_000)}
    flops, nbytes = work(ctx, 48)
    assert ctx["facts"]["kda_useful_lanes"] == 48.0
    assert nbytes == 48 * 48 * layout.recurrence_bytes(model)
    assert flops == 48 * 48 * 6 * layout.recurrence_flops(model)
    assert work(dict(ctx, lifetime_counters={}), 48) == (0.0, 0.0)
    assert work(ctx, 0) == (0.0, 0.0)
    roof = M.load_reader_module(man, ROOFLINE)
    spec = M.load_json_named(man, "metrics", ROOFLINE)
    assert spec["params"] == {"modules": "^jit_chunk\\b",
                              "scopes": ["kda.step"], "work": "kda_decode"}
    share = roof.read(ctx, spec["params"])
    steps = 2 * 16 + 2 * 8
    least = steps * 48 * layout.recurrence_bytes(model) / 819e9
    assert share == pytest.approx(100 * least / (2 * 4.0))
    assert ctx["facts"]["roofline"]["kda_decode"]["bound"] == "memory"
    per = M.load_reader_module(man, PER_STEP)
    spec = M.load_json_named(man, "metrics", PER_STEP)
    assert spec["params"] == {"modules": "^jit_chunk\\b",
                              "scopes": ["kda."], "per": "steps"}
    assert per.read(ctx, spec["params"]) == pytest.approx(
        2 * (4000.0 + 2000.0) / steps)
    for name, reader in ((ROOFLINE, roof), (PER_STEP, per)):
        path = M._find(man, "metrics", name, ".py")
        assert os.path.relpath(path, REPO).startswith(
            os.path.join("tests", "benchmark", "metrics"))
        params = M.load_json_named(man, "metrics", name)["params"]
        empty = dict(ctx, device_scopes=_split([16], 0))
        empty["device_scopes"]["executables"][0]["by_scope_ns"] = {
            "decoder.attn.full": 7}
        assert reader.read(empty, params) is None
        assert reader.read(dict(ctx, device_scopes=None), params) is None


def test_a_small_width_answer_cell_runs_and_its_control_fails():
    """The cell's own layout, builder (sized decoder server, warmed), mix
    generator and check at small widths on the CPU: ``correct`` with no
    compile in the window; the control not, by the decoder's own number
    among others; the state's resets are counted, one an admission."""
    from pathway_tpu.engine import probes

    man = M.load_manifest(SMALL)
    assert M.problems(man) == []
    small = M.cell(man, "kimi_small_answer")
    assert small["config"]["builder"] == "qa_rest_server_sized"
    assert small["config"]["models"]["decoder"]["layout"] == "kimi_linear"
    assert small["traffic"]["check"] == "answer_moe"
    probes.REGISTRY.remove("recurrent_state_resets")
    result = bench_run.run_cell(man, "kimi_small_answer", 2 ** 31 + 17,
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
    assert {"token_logit_gap", "knn_dist_err", "rerank_score_err"} <= failing
    assert set(result["metrics"]) == {"requests_per_s", "request_p50_ms",
                                      "setup_s"}
    resets = probes.REGISTRY.labelled("recurrent_state_resets", "cause")
    assert resets.get("admit", 0) >= result["attempted"]
