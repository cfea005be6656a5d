"""Cells made ONLY of new files (``tests/benchmark/toy``: configurations,
mixes, metrics and a manifest that names them; for ``toy_answer_byname``
also its own builder, generator, check, model layout and roofline work,
found by name) run through
the same harness with no edit to any file of ``benchmarks/``; a run whose
timed path is broken underneath comes out as not ``correct``; and so does
the control (the fp8 reference put in the program's place), by the run's own
comparison. The toy's limits lie between what the program and what the
control read at the toy's size, as the cells' do at theirs.

These skip the harness's look for a chip and drive the rest of a run on the
CPU at a toy size: they check the harness, and measure nothing."""

import json
import os
import subprocess
import sys
import time

import pytest

from bench_paths import BENCH, REPO, TOY_MANIFEST

import run as bench_run
from harness import manifest as M
from harness.generators import GENERATORS
from harness.system import device_stamp


def drive(cell: str, control: bool = False, seconds: float = 2.0,
          seed: int = 2 ** 31 + 17) -> dict:
    man = M.load_manifest(TOY_MANIFEST)
    return bench_run.run_cell(man, cell, seed, seconds, False, control,
                              device_stamp(), time.perf_counter())


BYNAME = [("builders", "toy_qa"), ("generators", "toy_posts"),
          ("checks", "toy_answer"), ("layouts", "toy_renamed"),
          ("work", "toy_step")]


def failing(table: dict) -> set:
    return {k for k, v in table.items() if v["value"] > v["limit"]}


def test_the_toy_cell_is_made_of_new_files_only():
    man = M.load_manifest(TOY_MANIFEST)
    toy = os.path.join(REPO, "tests", "benchmark", "toy")
    assert os.path.exists(os.path.join(toy, "configs", "toy-tiny.json"))
    assert os.path.exists(os.path.join(toy, "traffic", "toy_ingest.json"))
    assert os.path.exists(os.path.join(toy, "metrics",
                                       "toy.commit_ms_p50.json"))
    assert os.path.exists(os.path.join(toy, "metrics",
                                       "toy.docs_per_commit.py"))
    for kind, name in BYNAME:
        assert os.path.exists(os.path.join(toy, kind, name + ".py"))
        assert not os.path.exists(os.path.join(BENCH, kind, name + ".py"))
    for root, _dirs, files in os.walk(BENCH):
        for name in files:
            if name.endswith((".py", ".json")):
                with open(os.path.join(root, name)) as f:
                    assert "toy" not in f.read(), f"{name} knows the toy"
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        assert "toy" not in f.read()
    assert [w["name"] for w in man["workloads"]][0] == "toy_ingest"


def test_the_toy_cell_runs_and_is_correct_and_its_control_is_not():
    result = drive("toy_ingest", control=True)
    assert list(result)[:5] == ["correct", "attempted", "failed", "metrics",
                                "device"]
    assert list(result)[-1] == "compared"
    assert result["correct"] is True, result["compared"]
    assert result["control_correct"] is False, result["control_compared"]
    assert failing(result["control_compared"]) == {"embed_cos_gap"}
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"ingest_docs_per_s", "setup_s"}
    assert result["metrics"]["ingest_docs_per_s"]["value"] > 0
    assert result["metrics"]["ingest_docs_per_s"]["unit"] == "docs/s"
    assert result["device"]["platform"] == "cpu"   # stamped, whatever it is
    for row in result["compared"].values():
        assert set(row) == {"value", "limit"}
    json.dumps(result)
    # its own per-layer metrics: one read by the reader its file names, one
    # by the reader it ships beside its file; with nothing to read, nothing
    man = M.load_manifest(TOY_MANIFEST)
    ctx = {"trace": None, "counters": {"docs_landed": 96, "commits_landed": 3},
           "spans": {"commit_ms": [5.0, 7.0, 9.0]}}
    got = bench_run.per_layer_metrics(man, M.cell(man, "toy_ingest"), ctx)
    assert got == {"toy.commit_ms_p50": {"value": 7.0, "unit": "ms"},
                   "toy.docs_per_commit": {"value": 32.0, "unit": "docs"}}
    ctx = {"trace": None, "counters": {}, "spans": {}}
    assert bench_run.per_layer_metrics(
        man, M.cell(man, "toy_ingest"), ctx) == {}


def test_a_document_lost_on_the_way_makes_the_run_incorrect(monkeypatch):
    from pathway_tpu.ops.knn import BruteForceKnnIndex

    armed = {"on": False}
    real_append = BruteForceKnnIndex._append
    real_run = GENERATORS["commit_feeder"].run

    def lossy_append(self, keys, v, normalize):
        if armed["on"] and len(keys) > 1:
            keys, v = keys[:-1], v[:-1]     # one row of the batch left out
        return real_append(self, keys, v, normalize)

    def armed_run(self, *args, **kw):
        armed["on"] = True
        try:
            return real_run(self, *args, **kw)
        finally:
            armed["on"] = False

    monkeypatch.setattr(BruteForceKnnIndex, "_append", lossy_append)
    monkeypatch.setattr(GENERATORS["commit_feeder"], "run", armed_run)
    result = drive("toy_ingest")
    assert result["correct"] is False
    assert result["compared"]["docs_lost_or_duplicated"]["value"] > 0


def test_a_score_altered_where_it_is_produced_makes_the_run_incorrect(
        monkeypatch):
    from pathway_tpu.models.cross_encoder import CrossEncoderModel

    real = CrossEncoderModel.score_submit

    def shifted(self, pairs):
        out, n = real(self, pairs)
        return out + 0.25, n

    monkeypatch.setattr(CrossEncoderModel, "score_submit", shifted)
    result = drive("toy_retrieve")
    assert result["correct"] is False
    row = result["compared"]["rerank_score_err"]
    assert row["value"] > row["limit"]
    assert result["compared"]["malformed_replies"]["value"] == 0
    assert {"requests_per_s", "request_p50_ms", "request_p95_ms",
            "setup_s"} == set(result["metrics"])


def test_the_answer_path_runs_and_is_correct_and_its_control_is_not():
    result = drive("toy_answer", control=True)
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert {"answers_short_of_tokens", "prompts_truncated",
            "prompt_context_mismatch", "token_logit_gap", "knn_dist_err",
            "rerank_score_err"} <= set(result["compared"])
    # the control has to fail one of the cell's numbers, not each
    assert result["control_correct"] is False, result["control_compared"]
    assert {"knn_dist_err", "rerank_score_err"} <= failing(
        result["control_compared"])
    assert "control_correct" not in drive("toy_retrieve")


def test_a_cell_whose_code_is_found_by_name_runs_and_its_control_fails(
        monkeypatch):
    """``toy_answer_byname``: builder, generator, check, the decoder's
    layout (other key names than GPT-2's) and a roofline's work are files
    under ``tests/benchmark/toy`` that the harness has never heard of."""
    man = M.load_manifest(TOY_MANIFEST)
    cell = M.cell(man, "toy_answer_byname")
    assert "n_embd" not in cell["config"]["models"]["decoder"]
    loaded = {kind: M.load_named_module(man, kind, name)
              for kind, name in BYNAME}
    ran = []
    real_build = loaded["builders"].build
    real_payload = loaded["generators"].Generator.payload
    real_logits = type(loaded["layouts"].layout).logits
    monkeypatch.setattr(loaded["builders"], "build", lambda *a: (
        ran.append("builder"), real_build(*a))[1])
    monkeypatch.setattr(loaded["generators"].Generator, "payload",
                        lambda self, q: (ran.append("generator"),
                                         real_payload(self, q))[1])
    monkeypatch.setattr(type(loaded["layouts"].layout), "logits",
                        lambda self, *a: (ran.append("layout"),
                                          real_logits(self, *a))[1])
    result = drive("toy_answer_byname", control=True)
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert {"builder", "generator", "layout"} <= set(ran)
    # the check's own number, beside the limit its configuration gives it
    assert result["compared"]["answers_not_compared"] == {"value": 0,
                                                          "limit": 0}
    assert "compiles_in_window" in result["compared"]
    assert result["compared"]["token_logit_gap"]["value"] < 0.05
    assert result["control_correct"] is False, result["control_compared"]
    assert {"knn_dist_err", "rerank_score_err"} <= failing(
        result["control_compared"])
    # its roofline reads the work its file names, by the layout's counts
    from harness.peaks import peaks_for

    class Slice:
        def module_seconds(self, patterns):
            assert patterns == "^jit_pool_decode"
            return 10, 1e-3

    layout, model = cell["config"]["layouts"]["decoder"], cell["config"][
        "models"]["decoder"]
    ctx = {"trace": Slice(), "peaks": peaks_for("TPU v5 lite"),
           "config": cell["config"], "facts": {}, "counters": {},
           "spans": {}}
    got = bench_run.per_layer_metrics(man, cell, ctx)
    fact = ctx["facts"]["roofline"]["toy_step"]
    assert fact["flops"] == 10 * layout.decode_step_flops(model, 4, 512)
    assert fact["bytes"] == 10 * layout.decode_step_bytes(model, 512)
    assert got["toy.decode_step_roofline"]["value"] == pytest.approx(
        100 * fact["least_s"] / 1e-3)
    ctx["trace"] = None         # nothing to read: nothing in the line
    assert "toy.decode_step_roofline" not in bench_run.per_layer_metrics(
        man, cell, ctx)


def test_a_compile_inside_the_window_makes_the_run_incorrect(monkeypatch):
    """A shape the set-up did not warm compiles in the window: that is a
    number compared, with the limit 0, and no longer a line to overlook."""
    real_run = GENERATORS["commit_feeder"].run

    def run_and_compile(self, system, seconds, on_tick=None):
        import jax
        import jax.numpy as jnp

        def tick(now, opened):
            if opened and not tick.done:
                tick.done = True
                jax.jit(lambda x: x * 3 + now)(jnp.ones((7, 3)))
            if on_tick is not None:
                on_tick(now, opened)

        tick.done = False
        return real_run(self, system, seconds, tick)

    monkeypatch.setattr(GENERATORS["commit_feeder"], "run", run_and_compile)
    result = drive("toy_ingest")
    assert result["correct"] is False
    assert failing(result["compared"]) == {"compiles_in_window"}


def test_a_token_altered_where_it_is_produced_makes_the_run_incorrect(
        monkeypatch):
    from pathway_tpu.models import decoder as D

    real_chunk, real_spec = D.pool_decode_chunk, D.pool_decode_spec

    def chunk(params, pool, active, key, cfg, n_steps, **kw):
        pool, toks = real_chunk(params, pool, active, key, cfg, n_steps, **kw)
        return pool, toks.at[0].set((toks[0] + 1) % cfg.vocab_size)

    def spec(params, pool, active, cfg, n_cycles, **kw):
        pool, toks, emit = real_spec(params, pool, active, cfg, n_cycles,
                                     **kw)
        return pool, toks.at[:, :, 0].set(
            (toks[:, :, 0] + 1) % cfg.vocab_size), emit

    monkeypatch.setattr(D, "pool_decode_chunk", chunk)
    monkeypatch.setattr(D, "pool_decode_spec", spec)
    result = drive("toy_answer")
    assert result["correct"] is False
    assert failing(result["compared"]) == {"token_logit_gap"}


def test_without_a_tpu_the_command_fails_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "ingest_saturated", "--seed", "3000000000", "--seconds", "1",
         "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert "correct" not in p.stdout
