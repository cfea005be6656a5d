"""The device's time by the program's own named scopes, on the CPU.

A toy decoder server and a toy search run inside a profiler session; the
executables they registered (``tracing.register_executable``) give the
table (``tracing.executable_table``), the session's own record of each
program's compiled HLO gives the scopes, and the benchmark's reduction
(``benchmarks/harness/device_scopes.py``) splits the session's op events by
them: every run matched to one executable, self times that add up to each
run's own time, and what the runs carried as the server dispatched it. The
same reduction runs over an excerpt of a TPU trace. Names and counts only:
a CPU time is never a device metric."""

import functools
import glob
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.benchmark.bench_paths import BENCH  # noqa: F401 - sets sys.path
from harness import device_scopes as S
from harness import manifest, program_trace

from pathway_tpu.engine import tracing

NEW = {
    "answer.prefill_experts_ms_per_piece": "decoder",
    "answer.prefill_attn_ms_per_piece": "decoder",
    "answer.decode_attn_ms_per_step": "decoder",
    "retrieve.search_topk_pct": "search",
}
N_SLOTS, PIECE, CHUNK = 2, 8, 8


class _Ids:
    def encode(self, text):
        return [int(t) for t in text.split()]

    def decode(self, ids):
        return " ".join(str(i) for i in ids)


def _clear_registry():
    with tracing._executables_lock:
        tracing._executables.clear()


@pytest.fixture(scope="module")
def fresh_compiles():
    """The persistent compile cache off: a CPU trace's ops name their
    program by the id of the module that runs them, which is the one its
    session's HLO record gives only where the process compiled it (a TPU
    trace names a program by one id in both, cache or not)."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _traced(tmp, run):
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(tmp, profiler_options=options)
    try:
        out = run()
    finally:
        jax.profiler.stop_trace()
    return max(glob.glob(os.path.join(
        tmp, "plugins", "profile", "*", "*.xplane.pb"))), out


@pytest.fixture(scope="module")
def decoder(tmp_path_factory, fresh_compiles):
    """A routed-expert toy server built INSIDE a profiler session (its warm
    start runs every executable it registers once), then two prompts of two
    pieces served together: the trace, the table read after the server
    closed (as a benchmark run reads it), whether reading it compiled
    anything, and what the server dispatched."""
    from pathway_tpu.models import decoder as D
    from pathway_tpu.models.moe import MoEConfig
    from pathway_tpu.xpacks.llm.llms import TPUDecoderChat

    cfg = D.DecoderConfig(
        vocab_size=64, hidden=16, layers=1, heads=2, intermediate=32,
        max_position=64, dtype=jnp.float32, mlp="swiglu", bias=False,
        moe=MoEConfig(experts=4, per_token=2, width=8, held=(0, 2)))
    _clear_registry()

    def run():
        chat = TPUDecoderChat(
            params=D.init_params(jax.random.PRNGKey(0), cfg), cfg=cfg,
            tokenizer=_Ids(), max_new_tokens=4, temperature=0.0,
            max_prompt_tokens=16, continuous=True, n_slots=N_SLOTS,
            chunk_steps=CHUNK, prefill_chunk=PIECE)
        server = chat._server
        try:
            reqs = [server.submit(list(range(1 + k, 12 + k)), 4)
                    for k in range(2)]
            assert all(r.done.wait(timeout=300) for r in reqs)
            return (dict(server.stats), server.prefill_group,
                    list(server._step_cands),
                    sum(len(k) == 4 for k in server._prefill_fns))
        finally:
            chat.close()

    path, (stats, group, cands, one_wide) = _traced(
        str(tmp_path_factory.mktemp("scopes")), run)
    compiles = []

    def listen(event, _seconds, **_kw):
        if event.endswith("backend_compile_duration"):
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        table = tracing.executable_table()
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    return {"path": path, "table": table, "stats": stats, "group": group,
            "cands": cands, "one_wide": one_wide,
            "table_compiles": len(compiles)}


@pytest.fixture(scope="module")
def search(tmp_path_factory, fresh_compiles):
    """A toy exact index searched at both buckets inside a session."""
    from pathway_tpu.ops.knn import BruteForceKnnIndex

    _clear_registry()
    index = BruteForceKnnIndex(8, reserved_space=64)
    rows = np.random.default_rng(0).normal(size=(40, 8)).astype(np.float32)
    index.add(list(range(40)), rows)

    def run():
        index.search(rows[:3], 2)       # bucket 16, and both compiled
        index.search(rows[:20], 2)      # bucket 32

    path, _ = _traced(str(tmp_path_factory.mktemp("search")), run)
    return {"path": path, "table": tracing.executable_table()}


def _split(run):
    return S.split_trace(run["path"], run["table"], on_device=False)


def test_every_decoder_run_matches_one_registered_executable(decoder):
    got = _split(decoder)
    assert not set(got["unmatched_ns"]) & {"jit_piece", "jit_chunk"}
    rows = {(r["module"], tuple(sorted(r["carries"].items()))): r
            for r in got["executables"]}
    G = decoder["group"]
    assert G == N_SLOTS
    # the 1-wide pieces and the grouped one share a module name and are
    # told apart: the group ran once in the warm start and once for each of
    # the two pieces the two prompts had
    assert rows[("jit_piece", (("passes", 1), ("pieces", G)))]["runs"] == 3
    # each 1-wide variant the warm start built (first, middle, last and,
    # with the prefix cache, the last with its column traced) ran once
    assert rows[("jit_piece", (("passes", 1), ("pieces", 1)))]["runs"] \
        == decoder["one_wide"] == 4
    # a chunk of 8 steps and one of 4: told apart by their results' shapes
    steps = {r["carries"]["steps"] for r in got["executables"]
             if r["module"] == "jit_chunk"}
    assert steps == set(decoder["cands"]) == {8, 4}


def test_the_runs_carried_what_the_server_dispatched(decoder):
    got = _split(decoder)
    stats = decoder["stats"]
    pieces = sum(r["runs"] * r["carries"]["pieces"]
                 for r in got["executables"] if r["module"] == "jit_piece")
    # the warm start's 1-wide variants and one group, then the loop's
    assert pieces == decoder["one_wide"] + decoder["group"] \
        + stats["prefill_chunks"]
    steps = sum(r["runs"] * r["carries"]["steps"]
                for r in got["executables"] if r["module"] == "jit_chunk")
    assert steps == sum(decoder["cands"]) \
        + stats["slot_steps_total"] // N_SLOTS


def test_self_times_by_scope_add_up_to_each_runs_own_time(decoder):
    got = _split(decoder)
    for r in got["executables"]:
        assert r["gap"] < 0.01, r
        assert r["unnamed_ns"] == 0, r       # every op is in its HLO
        assert sum(r["by_scope_ns"].values()) == pytest.approx(
            r["ns"], rel=0.01)
    scopes = {s for r in got["executables"] for s in r["by_scope_ns"]}
    assert {"moe.experts", "moe.route", "decoder.attn.full",
            S.UNSCOPED} <= scopes


def test_the_table_is_names_and_shapes_and_compiles_nothing(decoder):
    assert decoder["table_compiles"] == 0
    # the 1-wide variants whose shapes are alike are registered once
    kinds = sorted((e["module"], tuple(sorted(e["carries"].items())))
                   for e in decoder["table"])
    assert sorted(set(kinds)) == [
        ("jit_chunk", (("passes", 1), ("steps", 4))),
        ("jit_chunk", (("passes", 1), ("steps", 8))),
        ("jit_piece", (("passes", 1), ("pieces", 1))),
        ("jit_piece", (("passes", 1), ("pieces", N_SLOTS)))]
    # what tells the group from a piece alone, and the chunks apart
    assert len({(e["params"], e["results"]) for e in decoder["table"]
                if e["carries"].get("pieces") != 1}) == 3


def test_a_registration_is_kept_once_and_holds_no_callable():
    @functools.partial(jax.jit, static_argnums=1)
    def step(x, k):
        return x * k

    _clear_registry()
    x = jnp.ones((4, 2), jnp.bfloat16)
    for _ in range(2):
        tracing.register_executable(step, (x, 3), step(x, 3), steps=4)
    (entry,) = tracing.executable_table()
    assert entry == {"module": "jit_step", "carries": {"steps": 4},
                     "params": ("bf16[4,2]",), "results": ("bf16[4,2]",)}
    _clear_registry()


def test_a_search_at_two_buckets_shows_both_scopes(search):
    got = _split(search)
    rows = {r["carries"]["queries"]: r for r in got["executables"]}
    assert set(rows) == {16, 32} and not got["unmatched_ns"]
    for r in rows.values():
        assert r["by_scope_ns"]["knn.scores"] > 0
        assert r["by_scope_ns"]["knn.top_k"] > 0


class _Slice:
    lo, hi = 0, 2 ** 63


def _ctx(monkeypatch, path):
    """A traced run's context over a CPU trace: the split reads the host
    threads' ops where a benchmark run reads a device's."""
    monkeypatch.setattr(program_trace, "slice_xplane", lambda: path)
    monkeypatch.setattr(S, "split_trace", functools.partial(
        _SPLIT_TRACE, on_device=False))
    return {"trace": _Slice()}


_SPLIT_TRACE = S.split_trace


def _reader(name):
    return manifest.load_reader_module(manifest.load_manifest(), name).read


def _params(name):
    return manifest.load_json_named(
        manifest.load_manifest(), "metrics", name)["params"]


@pytest.mark.parametrize("name", sorted(NEW))
def test_each_reader_reads_its_toy(name, decoder, search, monkeypatch,
                                   capsys):
    run = decoder if NEW[name] == "decoder" else search
    _clear_registry()
    monkeypatch.setattr(tracing, "executable_table", lambda: run["table"])
    value = _reader(name)(_ctx(monkeypatch, run["path"]), _params(name))
    assert value is not None and value > 0
    if name.endswith("_pct"):
        assert value < 100.0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["phase"] == "device_scopes" and line["matched_pct"] == 100.0


@pytest.mark.parametrize("name", sorted(NEW))
def test_each_reader_is_left_out_on_a_program_that_registered_nothing(
        name, decoder, monkeypatch, capsys):
    _clear_registry()
    assert _reader(name)(_ctx(monkeypatch, decoder["path"]),
                         _params(name)) is None
    # the parent: no table at all
    monkeypatch.delattr(tracing, "executable_table")
    assert _reader(name)(_ctx(monkeypatch, decoder["path"]),
                         _params(name)) is None
    # an untraced run, or a slice the run left nowhere
    assert _reader(name)({"trace": None}, _params(name)) is None
    assert "device_scopes" not in capsys.readouterr().out


def test_the_split_is_printed_once_a_run(decoder, monkeypatch, capsys):
    monkeypatch.setattr(tracing, "executable_table",
                        lambda: decoder["table"])
    ctx = _ctx(monkeypatch, decoder["path"])
    for name in ("answer.prefill_experts_ms_per_piece",
                 "answer.decode_attn_ms_per_step"):
        assert _reader(name)(ctx, _params(name)) > 0
    lines = [json.loads(line) for line in
             capsys.readouterr().out.strip().splitlines()]
    assert [line["phase"] for line in lines] == ["device_scopes"]
    got = lines[0]
    assert {r["module"] for r in got["executables"]} == {"jit_piece",
                                                         "jit_chunk"}
    assert all(r["run_gap_pct"] < 1.0 for r in got["executables"])
    assert got["scope_s"]["moe.experts"] > 0
    assert got["table"] == len(decoder["table"])


# ----------------------------------------------- the reduction, by hand


HLO = """HloModule jit_step, is_scheduled=true, entry_computation_layout={(f32[4]{0:T(128)}, /*index=1*/s32[4,128]{1,0:T(4,128)})->(f32[4]{0}, s32[8,4]{1,0})}

%fused_computation (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  %exp.1 = f32[4]{0} exponential(%param_0), metadata={op_name="jit(step)/moe.experts/exp"}
  ROOT %mul.3 = f32[4]{0} multiply(%exp.1, %exp.1), metadata={op_name="jit(step)/moe.route/mul"}
}

ENTRY %main (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %fusion = f32[4]{0} fusion(%p), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/moe.experts/exp"}
  %fusion.1 = f32[4]{0} fusion(%fusion), kind=kLoop, calls=%fused_computation
  %custom-call = f32[4]{0} custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/decoder.pass/decoder.attn.latent/mla.expand/pallas_call"}
  ROOT %copy = f32[4]{0} copy(%custom-call)
}
"""


def test_an_instruction_takes_the_innermost_scope_of_its_own_op_name():
    """A fusion is named as the compiler named it, by its own ``op_name``
    (the TPU trace's ``tf_op`` of the same op), not by what it fused."""
    scopes = S.hlo_scopes(HLO)
    assert scopes["exp.1"] == "moe.experts" and scopes["mul.3"] == "moe.route"
    assert scopes["fusion"] == "moe.experts"
    assert scopes["fusion.1"] is None
    assert scopes["custom-call"] == "mla.expand"
    assert scopes["copy"] is None and scopes["p"] is None


def test_the_entry_shapes_are_read_without_their_layouts():
    assert S.entry_shapes(HLO) == (("f32[4]", "s32[4,128]"),
                                   ("f32[4]", "s32[8,4]"))
    assert S.entry_shapes("HloModule jit_step") == ((), ())


def _entry(module, carries, params, results=()):
    return {"module": module, "carries": carries, "params": tuple(params),
            "results": tuple(results)}


def test_a_program_is_told_apart_by_its_shapes():
    """A group is a piece alone but for its lanes, and a chunk of 8 steps
    one of 4 but for its tokens: their shapes tell them apart; of the
    pieces alike in what a run carries (first, middle, last), any is the
    answer; an argument the compile dropped costs every candidate alike."""
    w = "f32[64,64]"
    first = _entry("jit_piece", {"pieces": 1}, [w, "s32[1,8]"], [w])
    last = _entry("jit_piece", {"pieces": 1}, [w, "s32[1,8]", "s32[]"], [w])
    group = _entry("jit_piece", {"pieces": 4}, [w, "s32[4,8]", "s32[4]"],
                   [w])
    chunks = [_entry("jit_chunk", {"steps": n}, [w], [w, f"s32[{n},2]"])
              for n in (8, 4)]
    table = [first, last, group] + chunks
    assert S.match(table, "jit_piece", (w, "s32[4,8]", "s32[4]"), (w,)) \
        == (group, 0)
    # the weights' argument dropped: still the group
    assert S.match(table, "jit_piece", ("s32[4,8]", "s32[4]"), (w,)) \
        == (group, 1)
    assert S.match(table, "jit_piece", (w, "s32[1,8]"), (w,))[0][
        "carries"] == {"pieces": 1}
    assert S.match(table, "jit_chunk", (w,), (w, "s32[4,2]")) \
        == (chunks[1], 0)
    # nothing of the module registered, or a tie between kinds of run
    assert S.match(table, "jit_spec", (w,), (w,)) == (None, None)
    tie = [_entry("jit_chunk", {"steps": n}, [w], [w]) for n in (8, 4)]
    assert S.match(tie, "jit_chunk", (w,), (w,)) == (None, 0)


def test_a_device_run_the_session_cut_is_left_out():
    """The profiler cuts a run the session began or ended inside of at the
    session's edge: a run that touches the line's first or last event is a
    part (a decode chunk of 16 steps read as 3 of them, on the chip)."""
    modules = [("jit_chunk(1)", 0, 10), ("jit_chunk(1)", 20, 30),
               ("jit_chunk(2)", 40, 50)]
    ops = [("%a = f32[] add()", 0, 5), ("%b = f32[] add()", 45, 5)]
    lo, hi = S._whole_between(modules, ops, -1, float("inf"))
    assert [s for _n, s, e in modules if lo < s and e < hi] == [20]
    assert S._whole_between(modules, ops, 25, 45) == (25, 45)


def test_self_time_leaves_out_what_nested_ops_cover():
    # a loop (0-100) whose body holds two ops, and an op after it; a second
    # line's op overlapping the first line is its own
    ops = [(1, "while", 0, 100), (1, "a", 10, 30), (1, "b", 50, 40),
           (1, "c", 100, 20), (2, "d", 0, 60)]
    assert S.self_times(ops) == [30, 30, 40, 20, 60]
    run = ("jit_step", 7, 0, 125, ops)
    assert sum(S.self_times(ops)) == S._run_ns(run, False) == 180
    # on a device the module event is the run's time: 5 ns no op covered
    assert S._run_ns(run, True) == 125


def test_a_trace_without_a_device_plane_is_not_read_as_one(search):
    with pytest.raises(ValueError, match="no device plane"):
        S.device_runs(search["path"])


def test_a_program_whose_ops_name_no_scope_reads_nothing(search, monkeypatch,
                                                          capsys):
    """An executable compiled by a build without the scopes (one the
    persistent cache returned: its key leaves debug info out) runs under
    that build's op names: matched, all of it unscoped, and a reader whose
    scopes did not run leaves its metric out: not a zero."""
    hlo = S.program_hlo

    def unnamed(path, modules):
        return {pid: (m, re.sub(r'op_name="[^"]*"', 'op_name="x"', t))
                for pid, (m, t) in hlo(path, modules).items()}

    monkeypatch.setattr(S, "program_hlo", unnamed)
    monkeypatch.setattr(tracing, "executable_table", lambda: search["table"])
    name = "retrieve.search_topk_pct"
    assert _reader(name)(_ctx(monkeypatch, search["path"]),
                         _params(name)) is None
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["matched_pct"] == 100.0
    assert set(line["scope_s"]) == {S.UNSCOPED}


# ------------------------------------------------- a TPU trace's excerpt

with open(os.path.join(os.path.dirname(__file__), "data",
                       "device_scopes_tpu_v5e.json"), encoding="utf-8") as _f:
    TPU = json.load(_f)


def _tpu_table():
    return [dict(e, params=tuple(e["params"]), results=tuple(e["results"]))
            for e in TPU["registrations"]]


@pytest.mark.parametrize("program", TPU["programs"],
                         ids=[p["program"] for p in TPU["programs"]])
def test_each_tpu_program_is_the_registration_its_shapes_say(program):
    """The headers of the compiled modules a TPU trace's metadata plane
    held (tiled layouts, memory spaces, index comments) against the toy's
    registrations: each is ONE kind of run, and the one its own shapes
    say — a search's queries, a chunk's tokens (steps, slots), a group's
    lanes of 128 columns."""
    params, results = S.entry_shapes(program["header"])
    entry, gap = S.match(_tpu_table(), program["module"], params, results)
    assert entry is not None and gap <= 3
    carries = entry["carries"]
    if "queries" in carries:
        assert f"f32[{carries['queries']},64]" in params
    if "steps" in carries:
        assert f"s32[{carries['steps']},4]" in results
    if "pieces" in carries:
        assert ("s32[4,128]" in params) == (carries["pieces"] == 4)


def test_a_tpu_search_splits_by_its_own_scopes():
    """The toy search at bucket 16 as the chip ran it: its op events (a
    TPU op is named by its instruction's text), its HLO from the trace."""
    search = TPU["search"]
    pid = int(search["program"])
    runs = [("jit__search_kernel", pid, r["start"], r["end"],
             [(0, name, start, dur) for name, start, dur in r["ops"]])
            for r in search["runs"]]
    assert runs[0][4][1][1].startswith("%multiply_reduce_fusion = f32[16]")
    got = S.split_runs(runs, {pid: ("jit__search_kernel", search["hlo"])},
                       _tpu_table())
    (row,) = got["executables"]
    assert row["carries"] == {"queries": 16} and row["runs"] == 2
    assert row["unnamed_ns"] == 0 and row["gap"] < 0.01
    by = row["by_scope_ns"]
    assert by["knn.scores"] > 0 and by["knn.top_k"] > 0.9 * row["ns"]


# the parent's lowered text (debug info left out) of the search kernel at
# the retrieval cell's capacity: the scopes are metadata and change no byte
SEARCH_LOWERED = {16: "12d7acc04c68b3a8", 32: "218eec0676a5a9a9"}


@pytest.mark.parametrize("bucket", sorted(SEARCH_LOWERED))
def test_the_search_kernels_lowered_text_is_the_parents(bucket):
    import hashlib

    from pathway_tpu.ops import knn as K

    S_ = jax.ShapeDtypeStruct
    text = K._search_kernel.lower(
        S_((8388608, 384), jnp.bfloat16), S_((8388608,), jnp.bool_),
        S_((bucket, 384), jnp.float32), 32, "cos", normalize=True,
        f32_scores=False).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == SEARCH_LOWERED[bucket]
    assert "knn.top_k" in K._search_kernel.lower(
        S_((64, 8), jnp.bfloat16), S_((64,), jnp.bool_),
        S_((bucket, 8), jnp.float32), 2, "cos").as_text(debug_info=True)
