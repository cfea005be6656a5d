"""What the benchmark reads from the program, by name.

``benchmarks/`` finds the program's device modules by a pattern over
``"jit_" + f.__name__``, its counters by family and label, its request spans
by kind and metric, and the decoder server's work by ``stats`` field. A
rename on the program's side breaks none of the program's own tests: it
shows a PR later, as a ``null`` on the ledger. The cases here are read FROM
the benchmark's own files (never edited here), and each runs the program
at toy size and then the benchmark's own reader: CPU only, names and
counts, never a time."""

import ast
import glob
import json
import os
import re
import threading
import urllib.request

import jax
import jax.numpy as jnp
import pytest

import pathway_tpu as pw
from pathway_tpu.engine import probes, tracing
from tests.benchmark.bench_paths import BENCH, REPO
from harness import manifest, program_trace

def _load(path):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


METRICS = {
    os.path.basename(path)[:-len(".json")]: _load(path)
    for path in sorted(glob.glob(os.path.join(BENCH, "metrics", "*.json")))
}


def _params(key):
    return sorted((name, m["params"]) for name, m in METRICS.items()
                  if key in m.get("params", {}))


def _ids(cases):
    return [name for name, _ in cases]


# ------------------------------------------------------- device modules


def _jitted_names():
    """``__name__`` of every function the package hands to ``jax.jit``:
    decorated with it (bare or through ``functools.partial``), or passed to
    it by name."""

    def is_jit(node):
        return (isinstance(node, ast.Attribute) and node.attr == "jit") or (
            isinstance(node, ast.Name) and node.id == "jit")

    names = set()
    for path in glob.glob(os.path.join(REPO, "pathway_tpu", "**", "*.py"),
                          recursive=True):
        with open(path, encoding="utf-8") as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            if isinstance(node, ast.FunctionDef):
                for dec in node.decorator_list:
                    call = dec.func if isinstance(dec, ast.Call) else dec
                    args = dec.args if isinstance(dec, ast.Call) else []
                    if is_jit(call) or any(is_jit(a) for a in args):
                        names.add(node.name)
            elif (isinstance(node, ast.Call) and is_jit(node.func)
                  and node.args and isinstance(node.args[0], ast.Name)):
                names.add(node.args[0].id)
    return names


MODULE_PATTERNS = _params("modules")
# device ops that PERF.md's breakdowns and the `host_attribution` line quote
# by name, beside the three a roofline metric matches by pattern
QUOTED_MODULES = ("jit__append_kernel", "jit_score_fn", "jit_chunk")


@pytest.fixture(scope="module")
def jitted():
    return {"jit_" + name for name in _jitted_names()}


@pytest.mark.parametrize("name,params", MODULE_PATTERNS,
                         ids=_ids(MODULE_PATTERNS))
def test_a_roofline_metrics_pattern_finds_a_jitted_function(
        name, params, jitted):
    assert any(re.search(params["modules"], module) for module in jitted)


@pytest.mark.parametrize("module", QUOTED_MODULES)
def test_a_quoted_device_module_is_a_jitted_function(module, jitted):
    assert module in jitted


# ------------------------------------------------- the decoder's toy run


class _Ids:
    def encode(self, text):
        return [int(t) for t in text.split()]

    def decode(self, ids):
        return " ".join(str(i) for i in ids)


@pytest.fixture(scope="module")
def decoder_run():
    """One request through a continuous server whose block routes experts
    and holds half of them; the server's ``stats`` once it is answered."""
    from pathway_tpu.models import decoder as D
    from pathway_tpu.models.moe import MoEConfig
    from pathway_tpu.xpacks.llm.llms import TPUDecoderChat

    cfg = D.DecoderConfig(
        vocab_size=64, hidden=16, layers=1, heads=2, intermediate=32,
        max_position=64, dtype=jnp.float32, mlp="swiglu", bias=False,
        moe=MoEConfig(experts=4, per_token=2, width=8, held=(0, 2)))
    tracing.reset_traces()
    probes.REGISTRY.remove("moe_assignments")
    chat = TPUDecoderChat(
        params=D.init_params(jax.random.PRNGKey(0), cfg), cfg=cfg,
        tokenizer=_Ids(), max_new_tokens=4, temperature=0.0,
        max_prompt_tokens=16, continuous=True, n_slots=2, chunk_steps=4,
        prefill_chunk=8)
    try:
        request = chat._server.submit(list(range(1, 12)), 4)
        assert request.done.wait(timeout=300)
        return dict(chat._server.stats)
    finally:
        chat.close()


LATENT_METRIC = "answer.latent_rows_expanded_per_token"


def _latent_cfg():
    from pathway_tpu.models import decoder as D

    return D.DecoderConfig(
        vocab_size=64, hidden=16, layers=2, heads=2, intermediate=32,
        max_position=64, dtype=jnp.float32, norm="rmsnorm",
        positions="rotary", mlp="swiglu", bias=False, tied_head=False,
        q_rank=8, kv_rank=8, nope_size=6, rope_size=2, v_size=6,
        rope_factor=40.0, rope_original=16, rope_mscale=0.707,
        rope_mscale_all_dim=0.707)


def _kda_cfg():
    """Gated delta-rule layers beside a NoPE latent one (Kimi-Linear's
    block at toy widths)."""
    from pathway_tpu.models import decoder as D

    return D.DecoderConfig(
        vocab_size=64, hidden=16, layers=2, heads=2, intermediate=32,
        max_position=64, dtype=jnp.float32, norm="rmsnorm",
        positions="none", mlp="swiglu", bias=False, tied_head=False,
        layer_types=("kda", "latent"), kv_rank=8, nope_size=6, rope_size=2,
        v_size=6, kda_heads=2, kda_head=4)


@pytest.fixture(scope="module")
def latent_run():
    """One request through a continuous server whose block is latent
    attention, the shape rule's threshold at zero so that its prefill
    pieces read blockwise as a long row's do: the server's ``stats`` once
    it is answered, and the context the new metric's reader is given."""
    from pathway_tpu.models import decoder as D
    from pathway_tpu.xpacks.llm.llms import TPUDecoderChat

    cfg = _latent_cfg()
    probes.REGISTRY.remove("latent_rows_expanded", "prefill_attn_blocks")
    was, D._DENSE_SCORE_BYTES = D._DENSE_SCORE_BYTES, 0
    chat = TPUDecoderChat(
        params=D.init_params(jax.random.PRNGKey(0), cfg), cfg=cfg,
        tokenizer=_Ids(), max_new_tokens=4, temperature=0.0,
        max_prompt_tokens=16, continuous=True, n_slots=2, chunk_steps=4,
        prefill_chunk=8)
    try:
        request = chat._server.submit(list(range(1, 12)), 4)
        assert request.done.wait(timeout=300)
        stats = dict(chat._server.stats)
    finally:
        chat.close()
        D._DENSE_SCORE_BYTES = was
    ctx = {"lifetime_counters": {"decoder_" + k: v
                                 for k, v in stats.items()
                                 if isinstance(v, (int, float))},
           "config": {"models": {"decoder": {"num_hidden_layers": 2}},
                      "deployment": {"decoder_server": {
                          "prefill_chunk": 8}}}}
    return stats, ctx


def test_the_latent_blocks_scopes_and_counters_are_what_the_metric_reads(
        latent_run):
    """The named scopes are in the executables' text, the counter series
    under the names the benchmark's files give, and the new metric is their
    ratio: two pieces of 8 over a row that is one block, so every column
    prefilled costs the whole row's expansion twice over."""
    from pathway_tpu.models import decoder as D

    stats, ctx = latent_run
    assert stats["prefill_chunks"] == 2
    params = METRICS[LATENT_METRIC]["params"]
    assert (params["family"], params["label"], params["value"],
            params["pieces"]) == ("latent_rows_expanded", "phase",
                                  "prefill", "decoder_prefill_chunks")
    blocks = probes.REGISTRY.labelled("prefill_attn_blocks", "layer")
    assert set(blocks) == {"latent"} and blocks["latent"] == 2 * 2
    rows = probes.REGISTRY.labelled("latent_rows_expanded", "phase")
    cache_len = 16 + 4 + 5 * 4
    assert rows == {"prefill": float(2 * 2 * cache_len)}
    assert _reader(LATENT_METRIC)(ctx, params) == cache_len / 8
    assert _reader(LATENT_METRIC)({"lifetime_counters": {}}, params) is None
    cfg = _latent_cfg()
    p = jax.eval_shape(lambda: D.init_params(jax.random.PRNGKey(0), cfg))
    pool = jax.eval_shape(lambda: D.pool_init(None, cfg, 2, cache_len))
    ids = jax.ShapeDtypeStruct((1, 256), jnp.int32)
    one = jax.ShapeDtypeStruct((), jnp.int32)
    piece = jax.jit(lambda p, i, pl, s: D.pool_prefill_chunk(
        p, i, i, i, pl, s, s, s[None], cfg, first=False, last=False)
    ).lower(p, ids, jax.eval_shape(
        lambda: D.pool_init(None, cfg, 2, 512)), one).as_text(
            debug_info=True)
    step = jax.jit(lambda p, pl, a, k: D.pool_decode_chunk(
        p, pl, a, k, cfg, 2)).lower(
            p, pool, jax.ShapeDtypeStruct((2,), jnp.bool_),
            jax.ShapeDtypeStruct((2,), jnp.uint32)).as_text(debug_info=True)
    assert "decoder.attn.latent" in piece and "mla.expand" in piece
    assert "decoder.attn.latent" in step and "mla.absorb" in step
    assert "mla.expand" not in step      # a step never expands a row


SCOPE_METRICS = _params("scopes")


@pytest.fixture(scope="module")
def toy_scopes(tmp_path_factory):
    """Module name -> every named scope a traced session's own record of
    the compiled HLO gives toy executables under the names the metrics
    read: a routed-expert server's and a latent server's pieces and chunks
    and a search; and a latent piece over a row of 512 columns, whose
    expanded read is a scope of its own as a long row's is. Compiled with
    the persistent cache off: it would hand back an executable another
    build named (its key leaves debug info out)."""
    import numpy as np
    from jax.experimental.compilation_cache import compilation_cache

    from harness import device_scopes
    from pathway_tpu.models import decoder as D
    from pathway_tpu.models.moe import MoEConfig
    from pathway_tpu.ops.knn import BruteForceKnnIndex
    from pathway_tpu.xpacks.llm.llms import TPUDecoderChat

    moe = D.DecoderConfig(
        vocab_size=64, hidden=16, layers=1, heads=2, intermediate=32,
        max_position=64, dtype=jnp.float32, mlp="swiglu", bias=False,
        moe=MoEConfig(experts=4, per_token=2, width=8, held=(0, 2)))
    trace = str(tmp_path_factory.mktemp("toy_scopes"))
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.profiler.start_trace(trace)
    try:
        for cfg in (moe, _latent_cfg(), _kda_cfg()):
            chat = TPUDecoderChat(
                params=D.init_params(jax.random.PRNGKey(0), cfg), cfg=cfg,
                tokenizer=_Ids(), max_new_tokens=4, temperature=0.0,
                max_prompt_tokens=16, continuous=True, n_slots=2,
                chunk_steps=4, prefill_chunk=8)
            try:
                request = chat._server.submit(list(range(1, 12)), 4)
                assert request.done.wait(timeout=300)
            finally:
                chat.close()
        index = BruteForceKnnIndex(12, reserved_space=16)
        rows = np.eye(12, dtype=np.float32)
        index.add(list(range(12)), rows)
        index.search(rows[:3], 2)
    finally:
        jax.profiler.stop_trace()
    cfg = _latent_cfg()

    def piece(p, i, pl, s):
        return D.pool_prefill_chunk(p, i, i, i, pl, s, s, s[None], cfg,
                                    first=False, last=False)

    try:
        texts = [("jit_piece", jax.jit(piece).lower(
            jax.eval_shape(lambda: D.init_params(jax.random.PRNGKey(0), cfg)),
            jax.ShapeDtypeStruct((1, 256), jnp.int32),
            jax.eval_shape(lambda: D.pool_init(None, cfg, 2, 512)),
            jax.ShapeDtypeStruct((), jnp.int32)).compile().as_text())]
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    (path,) = glob.glob(os.path.join(trace, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    texts += device_scopes.program_hlo(path, {
        "jit_piece", "jit_chunk", "jit__search_kernel"}).values()
    out: dict = {}
    for module, text in texts:
        out.setdefault(module, set()).update(
            s for s in device_scopes.hlo_scopes(text).values() if s)
    return out, device_scopes


@pytest.mark.parametrize("name,params", SCOPE_METRICS,
                         ids=_ids(SCOPE_METRICS))
def test_a_scope_metric_names_scopes_a_toy_executable_has(
        name, params, toy_scopes):
    """Every scope a metric file names (``decoder.attn.`` for all three
    kinds) names ops of a toy executable of the module it reads."""
    scopes, device_scopes = toy_scopes
    have = set().union(*(s for module, s in scopes.items()
                         if re.search(params["modules"], module)))
    for wanted in params["scopes"]:
        assert any(device_scopes._in(s, [wanted]) for s in have), wanted


PASSES_METRIC = "answer.loop_passes_per_token"


@pytest.fixture(scope="module")
def looped_run(tmp_path_factory):
    """One request through a continuous server whose stack runs three times
    (chunked prefill, then decode), inside a profiler session: the server's
    ``stats`` once it is answered, and the session's ``pw.`` regions."""
    from pathway_tpu.models import decoder as D
    from pathway_tpu.xpacks.llm.llms import TPUDecoderChat

    cfg = D.DecoderConfig(
        vocab_size=64, hidden=16, layers=2, heads=2, intermediate=32,
        max_position=64, dtype=jnp.float32, norm="rmsnorm",
        sandwich_norm=True, positions="rotary", mlp="swiglu", bias=False,
        tied_head=False, loops=3, exit_gate=True)
    probes.REGISTRY.remove("loop_passes", "loop_exit_step")
    chat = TPUDecoderChat(
        params=D.init_params(jax.random.PRNGKey(0), cfg), cfg=cfg,
        tokenizer=_Ids(), max_new_tokens=4, temperature=0.0,
        max_prompt_tokens=16, continuous=True, n_slots=2, chunk_steps=4,
        prefill_chunk=8)
    trace_dir = str(tmp_path_factory.mktemp("looped"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        request = chat._server.submit(list(range(1, 12)), 4)
        assert request.done.wait(timeout=300)
        stats = dict(chat._server.stats)
    finally:
        jax.profiler.stop_trace()
        chat.close()
    regions = program_trace.load_host_regions(max(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))))
    return stats, regions


def test_the_loops_counters_and_field_are_what_the_benchmark_reads(
        looped_run):
    """Both counters under the names and labels the metric's file and
    ``/metrics`` give them, the ``passes`` field on the two regions a
    slice's reader would take it from, and the new metric their ratio: 11
    prompt tokens and 4 emitted through three passes each."""
    stats, regions = looped_run
    params = METRICS[PASSES_METRIC]["params"]
    assert params == {"family": "loop_passes", "label": "phase",
                      "value": "decode", "per": "pass"}
    assert probes.METRIC_FAMILIES["loop_passes"][:2] == ("counter", "phase")
    assert probes.METRIC_FAMILIES["loop_exit_step"][:2] == ("counter",
                                                            "step")
    assert stats["prefill_chunks"] == 2 and stats["steps"] == 4
    assert probes.REGISTRY.labelled("loop_passes", "phase") == {
        "prefill": 3.0 * 11, "decode": 3.0 * 4}
    assert probes.REGISTRY.labelled("loop_passes", "pass") == {
        "1": 15.0, "2": 15.0, "3": 15.0}
    assert probes.REGISTRY.labelled("loop_exit_step", "step") == {"3": 4.0}
    assert _reader(PASSES_METRIC)({}, params) == 3.0
    for name in ("pw.decode.prefill", "pw.decode.chunk"):
        found = [stats_ for _th, n, _s, _d, stats_ in regions if n == name]
        assert found and all(int(s["passes"]) == 3 for s in found), name
    chunk = [s for _th, n, _s, _d, s in regions if n == "pw.decode.chunk"]
    assert sum(int(s["steps"]) for s in chunk) == 4
    # a stack run once says so, and counts nothing
    exported = "\n".join(
        line for line in _metrics_text().splitlines() if "loop_" in line)
    assert 'pathway_tpu_loop_passes_total{pass="1",phase="decode"} 4' \
        in exported
    assert 'pathway_tpu_loop_exit_step_total{step="3"} 4' in exported


def test_the_grouped_prefill_keeps_its_names(looped_run, jitted):
    """A group of pieces is still the module ``jit_piece`` (the prefill
    roofline's pattern), its region says how many lanes it carried (one
    here: a lone request's pieces go alone), and the counter of pieces by
    dispatch width is on ``/metrics`` under the name PERF.md gives it."""
    _stats, regions = looped_run
    found = [s for _th, n, _s, _d, s in regions if n == "pw.decode.prefill"]
    assert found and all(int(s["lanes"]) == 1 for s in found)
    with open(os.path.join(REPO, "pathway_tpu", "xpacks", "llm", "llms.py"),
              encoding="utf-8") as f:
        tree = ast.parse(f.read())
    group = next(node for node in ast.walk(tree)
                 if isinstance(node, ast.FunctionDef)
                 and node.name == "_prefill_group_fn")
    assert [call.args[0].id for call in ast.walk(group)
            if isinstance(call, ast.Call)
            and getattr(call.func, "attr", None) == "jit"] == ["piece"]
    assert "jit_piece" in jitted
    assert probes.METRIC_FAMILIES["prefill_group_lanes"][:2] == ("counter",
                                                                 "width")
    assert 'pathway_tpu_prefill_group_lanes_total{width="1"}' \
        in _metrics_text()


@pytest.fixture(scope="module")
def kda_run(tmp_path_factory):
    """Two requests through a continuous server whose layers keep a
    recurrent state, on two slots, inside a profiler session: the session's
    ``pw.`` regions and the server's state a slot."""
    from pathway_tpu.models import decoder as D
    from pathway_tpu.xpacks.llm.llms import TPUDecoderChat

    cfg = _kda_cfg()
    probes.REGISTRY.remove("recurrent_state_resets")
    chat = TPUDecoderChat(
        params=D.init_params(jax.random.PRNGKey(0), cfg), cfg=cfg,
        tokenizer=_Ids(), max_new_tokens=4, temperature=0.0,
        max_prompt_tokens=16, continuous=True, n_slots=2, chunk_steps=4,
        prefill_chunk=8)
    trace_dir = str(tmp_path_factory.mktemp("kda"))
    jax.profiler.start_trace(trace_dir)
    try:
        requests = [chat._server.submit(list(range(1, 12)), 4),
                    chat._server.submit(list(range(3, 9)), 4)]
        for request in requests:
            assert request.done.wait(timeout=300)
        mib = chat._server._state_mib
    finally:
        jax.profiler.stop_trace()
        chat.close()
    regions = program_trace.load_host_regions(max(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))))
    return regions, mib, D.state_slot_bytes(cfg, 4)


def test_the_states_counter_and_field_are_what_the_program_names(kda_run):
    """``recurrent_state_resets{cause=admit}``, one an admission, under the
    name and label PERF.md gives it on ``/metrics``; the ``state_mib`` field
    (the pool's recurrent state, every slot's) on both decode regions."""
    regions, mib, per_slot = kda_run
    assert probes.METRIC_FAMILIES["recurrent_state_resets"][:2] == (
        "counter", "cause")
    assert probes.REGISTRY.labelled("recurrent_state_resets", "cause") == {
        "admit": 2.0}
    assert 'pathway_tpu_recurrent_state_resets_total{cause="admit"} 2' \
        in _metrics_text()
    assert mib == round(2 * per_slot / (1 << 20), 3) > 0
    for name in ("pw.decode.prefill", "pw.decode.chunk"):
        found = [s for _th, n, _s, _d, s in regions if n == name]
        assert found and all(float(s["state_mib"]) == mib for s in found), \
            name


def _metrics_text():
    from pathway_tpu.internals.http_server import registry_text

    return registry_text()


STATS_FIELDS = sorted({
    value[len("decoder_"):]
    for m in METRICS.values() for value in m.get("params", {}).values()
    if isinstance(value, str) and value.startswith("decoder_")})


@pytest.mark.parametrize("field", STATS_FIELDS)
def test_the_decoder_server_counts_a_stats_field_a_metric_names(
        field, decoder_run):
    assert decoder_run[field] > 0


# ----------------------------------------------------------- counters


def _series(params):
    """``[family, label, value]`` of every registry series a metric's
    ``params`` name."""
    out = [params[k] for k in ("numerator", "denominator")
           if isinstance(params.get(k), list)]
    if "family" in params:
        out.append([params["family"], params["label"], params["value"]])
    return out


COUNTER_METRICS = sorted(
    (name, m["params"]) for name, m in METRICS.items()
    if _series(m.get("params", {})))


@pytest.fixture(scope="module")
def search_run():
    """One search of a toy exact index."""
    import numpy as np

    from pathway_tpu.ops.knn import BruteForceKnnIndex

    probes.REGISTRY.remove("knn_search_queries", "device_dispatch")
    index = BruteForceKnnIndex(8, reserved_space=16)
    vectors = np.eye(8, dtype=np.float32)
    index.add(list(range(8)), vectors)
    index.search(vectors[:3], 2)


@pytest.mark.parametrize("name,params", COUNTER_METRICS,
                         ids=_ids(COUNTER_METRICS))
def test_a_counter_metric_reads_what_the_program_registered(
        name, params, decoder_run, search_run, latent_run, looped_run):
    for family, label, value in _series(params):
        assert probes.METRIC_FAMILIES[family][1] == label
        assert probes.REGISTRY.labelled(family, label).get(str(value))
    ctx = latent_run[1] if name == LATENT_METRIC else {}
    assert _reader(name)(ctx, params) is not None


def _reader(name):
    """The metric's own reader, as the harness loads it."""
    return manifest.load_reader_module(manifest.load_manifest(), name).read


# -------------------------------------------------------------- spans


class _Query(pw.Schema):
    q: str


@pytest.fixture(scope="module")
def rest_run(decoder_run):
    """Two requests through ``rest_connector`` (after the decoder's run,
    whose spans it leaves in the ring)."""
    from pathway_tpu.io.http import _RestConnector

    pw.clear_graph()
    queries, writer = pw.io.http.rest_connector(
        port=0, schema=_Query, delete_completed_queries=True)
    writer(queries.select(ans=queries.q + "!"))
    conns = list(pw.G.connectors)
    rest = next(c for c in conns if isinstance(c, _RestConnector))
    answers = []

    def client():
        rest.webserver._started.wait(timeout=20)
        try:
            for q in ("hi", "ho"):
                req = urllib.request.Request(
                    f"http://127.0.0.1:{rest.webserver.port}/",
                    data=json.dumps({"q": q}).encode(),
                    headers={"Content-Type": "application/json"})
                answers.append(json.loads(
                    urllib.request.urlopen(req, timeout=15).read()))
        finally:
            for c in conns:
                c._stop.set()
                c.close()

    thread = threading.Thread(target=client, daemon=True)
    thread.start()
    pw.run()
    thread.join(timeout=20)
    pw.clear_graph()
    assert [a["ans"] for a in answers] == ["hi!", "ho!"]


SPAN_METRICS = _params("kind")


@pytest.mark.parametrize("name,params", SPAN_METRICS, ids=_ids(SPAN_METRICS))
def test_a_span_metric_finds_its_kind_and_its_events(
        name, params, decoder_run, rest_run):
    spans = program_trace.program_spans(params["kind"])
    assert spans
    for metric in [params["metric"]] + [params[k] for k in ("minus",)
                                        if k in params]:
        assert all(metric in s["metrics"] for s in spans)
    counters = {params["last"]: len(spans)} if "last" in params else {}
    assert _reader(name)({"counters": counters}, params) is not None


def test_an_epoch_span_lists_the_requests_it_carried(rest_run):
    assert _reader("retrieve.requests_per_epoch")({}, {}) >= 1.0
