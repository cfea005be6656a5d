"""Perf guard: the ENGINE path over a synthetic stream must sustain at
least 0.8x the throughput of a direct Python loop over the same kernel —
the host-side engine tax (operator dispatch, batch plumbing, consolidate)
may cost at most ~25% on top of the actual compute.

It runs with a numpy kernel so it guards the engine's
overhead on any machine, independent of the accelerator. Marked slow: it
needs multi-second measurement windows to be stable, and tier-1 excludes
it (-m 'not slow').
"""

import time

import numpy as np
import pytest

import pathway_tpu as pw
from pathway_tpu.internals import run as run_mod
from tests.utils import _capture_rows

# a kernel heavy enough (~100 us/row) that a well-behaved engine's per-row
# overhead (~tens of us with fusion + sparse stepping) fits in the 25%
# budget, but light enough that the guard finishes in a few seconds
_D_BATCH, _D_IN, _D_OUT = 24, 384, 512
_W = np.random.default_rng(0).standard_normal((_D_IN, _D_OUT)).astype(
    np.float32
)


def _kernel(seed: int) -> float:
    x = np.full((_D_BATCH, _D_IN), (seed % 97) * 0.01, dtype=np.float32)
    return float((x @ _W).sum())


def _build(rows):
    pw.clear_graph()
    t = pw.debug.table_from_rows(
        pw.schema_from_types(v=int), rows, is_stream=True
    )
    s = t.select(t.v, y=pw.apply_with_type(_kernel, float, t.v))
    f = s.filter(s.v >= 0)
    return f.select(f.v, z=f.y + 0.0)


def _stream_rows(n_rows, n_epochs):
    per = n_rows // n_epochs
    return [(i, 2 + 2 * (i // per), 1) for i in range(n_rows)]


@pytest.mark.slow
def test_engine_stream_vs_direct_kernel_loop():
    n_rows, n_epochs = 4000, 20

    # warm-up pass OUTSIDE both timed windows: absorbs one-per-process
    # costs shared by neither side fairly (the native-extension build
    # attempt on first Batch.from_rows, numpy thread-pool spin-up,
    # expression-compile caches)
    _capture_rows(_build(_stream_rows(200, 4)))
    for i in range(50):
        _kernel(i)

    # direct loop: the same kernel called row-by-row, no engine around it
    t0 = time.perf_counter()
    direct_out = [_kernel(i) for i in range(n_rows)]
    direct_s = time.perf_counter() - t0
    assert len(direct_out) == n_rows

    # engine: the same rows streamed over n_epochs commits through a
    # fusable select/filter chain with the kernel as a rowwise UDF
    out = _build(_stream_rows(n_rows, n_epochs))
    t0 = time.perf_counter()
    state, _ = _capture_rows(out)
    engine_s = time.perf_counter() - t0
    assert len(state) == n_rows

    stats = run_mod.LAST_RUN_STATS
    ratio = direct_s / engine_s
    detail = (
        f"direct={direct_s:.3f}s engine={engine_s:.3f}s ratio={ratio:.3f} "
        f"stats={stats.snapshot() if stats else None}"
    )
    assert ratio >= 0.8, f"engine tax exceeded 25% of kernel cost: {detail}"


@pytest.mark.slow
def test_prefix_cache_ttft_not_worse_than_cold():
    """Shared-prefix trace: warm-cache TTFT must not exceed cold-cache
    TTFT (PATHWAY_TPU_PREFIX_CACHE). The cached admission replaces a
    multi-piece prefill of the shared head with one arena copy, so the
    first token of a hit request can only come earlier. Median over a
    sequential request train, warm-up outside both timed windows; 15%
    slack absorbs scheduler jitter on a loaded CI host."""
    import jax
    import jax.numpy as jnp

    from pathway_tpu.models import decoder as D
    from pathway_tpu.xpacks.llm.llms import TPUDecoderChat
    from tests.utils import ToyCharTokenizer

    cfg = D.DecoderConfig(
        vocab_size=128, hidden=64, layers=4, heads=4, intermediate=128,
        max_position=256, dtype=jnp.float32,
    )
    params = D.init_params(jax.random.PRNGKey(0), cfg)
    head = "x" * 56  # 7 blocks cached, 8..16-token suffix per request
    prompts = [head + f"q{k:02d}xxxx" for k in range(12)]

    def ttft_p50(prefix_on: bool) -> float:
        chat = TPUDecoderChat(
            params=params, cfg=cfg, tokenizer=ToyCharTokenizer(128),
            max_new_tokens=8, temperature=0.0, max_prompt_tokens=64,
            continuous=True, n_slots=4, chunk_steps=4, pipeline_depth=2,
            prefill_chunk=8, prefix_cache=prefix_on, prefix_cache_mb=4,
        )
        try:
            # warm-up: compiles every executable on the measured path
            # (including, on the ON arm, the insert -> hit pair)
            for wtail in ("warmAAxx", "warmBBxx"):
                r = chat.submit_batch([head + wtail])[0]
                assert r.done.wait(timeout=120)
            lats = []
            for p in prompts:
                t0 = time.perf_counter()
                r = chat.submit_batch([p])[0]
                assert r.done.wait(timeout=120)
                lats.append(r.first_token_at - t0)
            if prefix_on:
                assert chat._server.stats["prefix_hit_requests"] > 0
            return float(np.percentile(np.asarray(lats), 50))
        finally:
            chat.close()

    warm = ttft_p50(True)
    cold = ttft_p50(False)
    assert warm <= cold * 1.15, (
        f"warm-cache TTFT {warm * 1e3:.1f}ms exceeds cold-cache "
        f"{cold * 1e3:.1f}ms"
    )


@pytest.mark.slow
def test_spec_decode_tok_s_not_worse_than_plain():
    """Greedy shared-head burst: spec-on decode throughput must be at
    least the plain path's (PATHWAY_TPU_SPEC_DECODE). Each verify
    dispatch streams the weights once for up to k+1 emitted tokens, and
    the adaptive latch falls back to plain dispatch if acceptance
    collapses — so spec can only lose to jitter. Warm-up outside both
    timed windows; the guard allows 1.0x (not worse), no speedup bar."""
    import jax
    import jax.numpy as jnp

    from pathway_tpu.models import decoder as D
    from pathway_tpu.xpacks.llm.llms import TPUDecoderChat
    from tests.utils import ToyCharTokenizer

    cfg = D.DecoderConfig(
        vocab_size=128, hidden=64, layers=4, heads=4, intermediate=128,
        max_position=256, dtype=jnp.float32,
    )
    params = D.init_params(jax.random.PRNGKey(0), cfg)
    head = "c" * 40 + "ontext: "
    prompts = [head + f"q{k:02d}tail"[:8].ljust(8, "x") for k in range(8)]

    def tok_s(spec_on: bool) -> float:
        chat = TPUDecoderChat(
            params=params, cfg=cfg, tokenizer=ToyCharTokenizer(128),
            max_new_tokens=24, temperature=0.0, max_prompt_tokens=64,
            continuous=True, n_slots=4, chunk_steps=8, pipeline_depth=2,
            prefill_chunk=8, prefix_cache=False, spec_decode=spec_on,
        )
        try:
            for r in chat.submit_batch([head + "warmAAxx"] * 2):
                assert r.done.wait(timeout=120)
            t0 = time.perf_counter()
            reqs = chat.submit_batch(prompts)
            for r in reqs:
                assert r.done.wait(timeout=120)
            wall = max(r.finished_at for r in reqs) - t0
            if spec_on:
                assert chat._server.stats["spec_dispatches"] > 0
            gen = sum(len(r.tokens) for r in reqs)
            return gen / max(wall, 1e-9)
        finally:
            chat.close()

    spec = tok_s(True)
    plain = tok_s(False)
    assert spec >= plain * 1.0, (
        f"spec decode {spec:.1f} tok/s slower than plain {plain:.1f} tok/s"
    )


@pytest.mark.slow
def test_instrumentation_overhead_under_three_pct(monkeypatch):
    """Metrics + tracing on must sustain >= 0.97x the throughput of the
    PATHWAY_TPU_METRICS=0 kill switch on the same greedy burst, and the
    two arms must emit byte-identical token streams — observability is
    bookkeeping around the serving loop, never inside the computation.
    Warm-up outside both timed windows; 3% slack is the instrumentation
    budget, not jitter allowance (the burst is long enough that host
    jitter stays well under it)."""
    import jax
    import jax.numpy as jnp

    from pathway_tpu.engine import probes, tracing
    from pathway_tpu.models import decoder as D
    from pathway_tpu.xpacks.llm.llms import TPUDecoderChat
    from tests.utils import ToyCharTokenizer

    cfg = D.DecoderConfig(
        vocab_size=128, hidden=64, layers=4, heads=4, intermediate=128,
        max_position=256, dtype=jnp.float32,
    )
    params = D.init_params(jax.random.PRNGKey(0), cfg)
    head = "c" * 40 + "ontext: "
    # 16 requests x 32 tokens: a long enough timed window (~0.3s steady
    # state) that a 3% delta is measurement, not noise
    prompts = [head + f"q{k:02d}tail"[:8].ljust(8, "x") for k in range(16)]

    probes.REGISTRY.reset()
    tracing.reset_traces()
    # ONE server for both arms: the kill switch is read per call, so
    # flipping the env between bursts compares identical compiled
    # executables and thread state — no cold-start confound
    chat = TPUDecoderChat(
        params=params, cfg=cfg, tokenizer=ToyCharTokenizer(128),
        max_new_tokens=32, temperature=0.0, max_prompt_tokens=64,
        continuous=True, n_slots=4, chunk_steps=8, pipeline_depth=2,
        prefill_chunk=8, prefix_cache=False,
    )
    try:
        for r in chat.submit_batch([head + "warmAAxx"] * 2):
            assert r.done.wait(timeout=120)

        def burst(metrics_on: bool):
            monkeypatch.setenv(
                "PATHWAY_TPU_METRICS", "1" if metrics_on else "0"
            )
            t0 = time.perf_counter()
            reqs = chat.submit_batch(prompts)
            for r in reqs:
                assert r.done.wait(timeout=120)
            wall = max(r.finished_at for r in reqs) - t0
            gen = sum(len(r.tokens) for r in reqs)
            return gen / max(wall, 1e-9), [list(r.tokens) for r in reqs]

        on_tok_s, on_toks = burst(True)
        # instrumentation actually ran: 2 warm-up + 16 burst spans
        assert len(chat.recent_traces()) == len(prompts) + 2
        off_tok_s, off_toks = burst(False)
        # kill switch actually killed it: no new spans
        assert len(chat.recent_traces()) == len(prompts) + 2
        assert off_toks == on_toks, "kill switch changed the token streams"
        # a single ~0.2s burst jitters +-5-10% on a loaded CPU host —
        # far above the 3% bar — so the guard compares TWO robust
        # estimators over 12 alternating rounds (order flipped each
        # round, so neither arm systematically lands the warmer slot
        # while CPU frequency ramps):
        #   * the median of per-round on/off ratios — robust to the
        #     occasional GC pause or scheduler hiccup (outliers);
        #   * the ratio of per-arm peaks — burst noise is one-sided
        #     (stalls only slow a burst down), so each arm's max
        #     estimates its clean-host rate.
        # A real instrumentation regression shifts the whole
        # distribution and fails BOTH; host noise rarely sinks both at
        # once, which is what makes a 3% bar decidable at all here.
        def measure():
            ons, offs = [on_tok_s], [off_tok_s]
            for i in range(11):
                first, second = (True, False) if i % 2 else (False, True)
                r1 = burst(first)[0]
                r2 = burst(second)[0]
                on_r, off_r = (r1, r2) if first else (r2, r1)
                ons.append(on_r)
                offs.append(off_r)
            med = float(np.median(np.asarray(ons) / np.asarray(offs)))
            return med, max(ons) / max(offs), ons, offs

        med, edge, ons, offs = measure()
        if max(med, edge) < 0.97:
            # one remeasure before declaring a regression: a co-tenant
            # burning the host for a few seconds sinks every round of
            # one attempt, but a real instrumentation cost fails both
            med, edge, ons, offs = measure()
    finally:
        chat.close()
    assert max(med, edge) >= 0.97, (
        f"instrumentation overhead above 3%: median paired ratio "
        f"{med:.4f}, peak ratio {edge:.4f} over {len(ons)} rounds "
        f"(on={[f'{v:.0f}' for v in ons]}, "
        f"off={[f'{v:.0f}' for v in offs]})"
    )


@pytest.mark.slow
def test_lock_sanitizer_compiled_out(monkeypatch):
    """PATHWAY_TPU_LOCK_SANITIZER is read once per lock CONSTRUCTION, so
    unlike the metrics guard the two arms need separate servers: OFF
    builds plain stdlib locks (asserted by type — the wrapper is
    compiled out, not merely quiet) and its throughput must be unchanged
    (>= 0.97x the ON arm); the ON arm's wrapper bookkeeping must itself
    fit the same 3% budget. Token streams are byte-identical either way,
    and a full continuous-decode burst under the sanitizer produces zero
    reports. Same two robust estimators + remeasure-once policy as
    ``test_instrumentation_overhead_under_three_pct``."""
    import jax
    import jax.numpy as jnp

    from pathway_tpu.analysis import runtime as rt
    from pathway_tpu.models import decoder as D
    from pathway_tpu.xpacks.llm.llms import TPUDecoderChat
    from tests.utils import ToyCharTokenizer

    cfg = D.DecoderConfig(
        vocab_size=128, hidden=64, layers=4, heads=4, intermediate=128,
        max_position=256, dtype=jnp.float32,
    )
    params = D.init_params(jax.random.PRNGKey(0), cfg)
    head = "c" * 40 + "ontext: "
    prompts = [head + f"q{k:02d}tail"[:8].ljust(8, "x") for k in range(16)]

    rt.reset()

    def run_arm(sanitizer_on: bool):
        """One server construction: warm-up, then two timed bursts."""
        monkeypatch.setenv(
            "PATHWAY_TPU_LOCK_SANITIZER", "1" if sanitizer_on else "0"
        )
        chat = TPUDecoderChat(
            params=params, cfg=cfg, tokenizer=ToyCharTokenizer(128),
            max_new_tokens=32, temperature=0.0, max_prompt_tokens=64,
            continuous=True, n_slots=4, chunk_steps=8, pipeline_depth=2,
            prefill_chunk=8, prefix_cache=False,
        )
        try:
            assert isinstance(
                chat._server.lock, rt.SanitizedLock
            ) is sanitizer_on
            for r in chat.submit_batch([head + "warmAAxx"] * 2):
                assert r.done.wait(timeout=120)
            rates, toks = [], None
            for _ in range(2):
                t0 = time.perf_counter()
                reqs = chat.submit_batch(prompts)
                for r in reqs:
                    assert r.done.wait(timeout=120)
                wall = max(r.finished_at for r in reqs) - t0
                gen = sum(len(r.tokens) for r in reqs)
                rates.append(gen / max(wall, 1e-9))
                if toks is None:
                    toks = [list(r.tokens) for r in reqs]
            return rates, toks
        finally:
            chat.close()

    def measure():
        ons, offs = [], []
        on_toks = off_toks = None
        for i in range(4):  # alternate construction order per round
            for s_on in ((True, False) if i % 2 else (False, True)):
                rates, toks = run_arm(s_on)
                if s_on:
                    ons.extend(rates)
                    on_toks = on_toks or toks
                else:
                    offs.extend(rates)
                    off_toks = off_toks or toks
        assert off_toks == on_toks, "sanitizer changed the token streams"
        med = float(np.median(np.asarray(offs) / np.asarray(ons)))
        return med, max(offs) / max(ons), ons, offs

    med, edge, ons, offs = measure()
    if max(med, edge) < 0.97 or max(1 / med, max(ons) / max(offs)) < 0.97:
        med, edge, ons, offs = measure()
    assert rt.reports() == [], rt.reports()
    detail = (
        f"median paired off/on ratio {med:.4f}, peak ratio {edge:.4f} "
        f"(on={[f'{v:.0f}' for v in ons]}, off={[f'{v:.0f}' for v in offs]})"
    )
    assert max(med, edge) >= 0.97, (
        "sanitizer-off arm slower than sanitizer-on — the off-path is "
        "not compiled out: " + detail
    )
    assert max(1 / med, max(ons) / max(offs)) >= 0.97, (
        "lock-sanitizer wrapper overhead above 3%: " + detail
    )


@pytest.mark.slow
def test_paged_kv_tok_s_and_capacity():
    """Paged KV (PATHWAY_TPU_PAGED_KV) on a mixed long/short greedy
    burst: paged serving must sustain >= 0.95x the dense pool's
    throughput at equal batch on an accelerator, where the Pallas kernel
    walks the block table in place; on CPU the reference path pays a
    real gather/scatter materialization per dispatch, so the guard pins
    that tax to a 25% budget instead (>= 0.75x) — it catches pathological
    regressions (quadratic gathers, per-token dispatches) without
    pretending the materialization is free. Token streams must be
    byte-identical either way, and at the dense pool's HBM budget the
    per-request block allocation must admit >= 1.3x the concurrent
    slots (arithmetic over the server's own sizing, no timing). Same
    max-of-alternating-rounds estimator as the other serving guards:
    burst noise is one-sided, each arm's peak estimates its clean-host
    rate."""
    import jax
    import jax.numpy as jnp

    from pathway_tpu.models import decoder as D
    from pathway_tpu.xpacks.llm.llms import TPUDecoderChat
    from tests.utils import ToyCharTokenizer

    cfg = D.DecoderConfig(
        vocab_size=128, hidden=64, layers=4, heads=4, intermediate=128,
        max_position=256, dtype=jnp.float32,
    )
    params = D.init_params(jax.random.PRNGKey(0), cfg)
    head = "c" * 40 + "ontext: "
    # 1-in-4 long prompts: the dense pool sizes every slot for the long
    # ones, the paged pool allocates what each request can reach
    prompts = [
        head + f"q{k:02d}tail"[:8].ljust(8, "x") if k % 4 == 0
        else f"q{k:02d}" + "y" * (2 + k % 5)
        for k in range(16)
    ]
    max_new = 16

    def run_arm(paged: bool):
        chat = TPUDecoderChat(
            params=params, cfg=cfg, tokenizer=ToyCharTokenizer(128),
            max_new_tokens=max_new, temperature=0.0, max_prompt_tokens=64,
            continuous=True, n_slots=4, chunk_steps=8, pipeline_depth=2,
            prefill_chunk=8, prefix_cache=False, paged_kv=paged,
        )
        try:
            for r in chat.submit_batch([head + "warmAAxx", "qWWyyyy"]):
                assert r.done.wait(timeout=120)
            rates, toks = [], None
            for _ in range(2):
                t0 = time.perf_counter()
                reqs = chat.submit_batch(prompts)
                for r in reqs:
                    assert r.done.wait(timeout=120)
                wall = max(r.finished_at for r in reqs) - t0
                gen = sum(len(r.tokens) for r in reqs)
                rates.append(gen / max(wall, 1e-9))
                if toks is None:
                    toks = [list(r.tokens) for r in reqs]
            srv = chat._server
            sizing = (srv.cache_len, srv.paged_block, srv._slack,
                      srv.pipeline_depth)
            return rates, toks, sizing
        finally:
            chat.close()

    ons, offs = [], []
    on_toks = off_toks = None
    sizing = None
    for i in range(3):  # alternate construction order per round
        for paged in ((True, False) if i % 2 else (False, True)):
            rates, toks, sz = run_arm(paged)
            if paged:
                ons.extend(rates)
                on_toks = on_toks or toks
                sizing = sz
            else:
                offs.extend(rates)
                off_toks = off_toks or toks
    assert on_toks == off_toks, "paged pool changed the token streams"

    paged_tok_s, dense_tok_s = max(ons), max(offs)
    bar = 0.95 if jax.default_backend() == "tpu" else 0.75
    assert paged_tok_s >= bar * dense_tok_s, (
        f"paged KV {paged_tok_s:.1f} tok/s below {bar}x dense "
        f"{dense_tok_s:.1f} tok/s "
        f"(on={[f'{v:.0f}' for v in ons]}, off={[f'{v:.0f}' for v in offs]})"
    )

    # capacity at fixed HBM: the dense pool burns n_slots full cache_len
    # rows; paged admission allocates ceil(cover / block) blocks where
    # cover = prompt + budget + pipeline slack (the server's own formula)
    cache_len, block, slack, depth = sizing
    budget_tokens = 4 * cache_len  # the dense pool's KV footprint
    covers = [
        min(cache_len, len(p) + max_new + (depth + 1) * slack)
        for p in prompts
    ]
    alloc = [-(-c // block) * block for c in covers]
    paged_max_slots = int(budget_tokens // np.mean(alloc))
    assert paged_max_slots >= 1.3 * 4, (
        f"paged pool admits {paged_max_slots} slots in the dense budget "
        f"(dense: 4; covers={covers}, block={block})"
    )


@pytest.mark.slow
def test_weight_quant_tok_s_not_worse_than_full_precision():
    """Weight-only int8 (PATHWAY_TPU_WEIGHT_QUANT) on the same greedy
    burst: serving weights as int8 with the dequant fused into the
    matmul read must sustain >= 1.0x the full-precision arm's decode
    throughput on an accelerator — the matmul is HBM-bandwidth-bound
    there, so halving (bf16) or quartering (f32) the weight bytes per
    step cannot lose. On CPU XLA pays a real int8->f32 widening per
    read with no bandwidth win to show for it, so the guard pins that
    tax to a 25% budget instead (>= 0.75x); it catches pathological
    regressions (per-step requantization, dequant outside the fused
    read), not CPU microarchitecture. Greedy top-1 agreement across the
    arms must stay >= 0.99 regardless of backend. Same
    max-of-alternating-rounds estimator as the other serving guards."""
    import jax
    import jax.numpy as jnp

    from pathway_tpu.models import decoder as D
    from pathway_tpu.xpacks.llm.llms import TPUDecoderChat
    from tests.utils import ToyCharTokenizer

    cfg = D.DecoderConfig(
        vocab_size=128, hidden=64, layers=4, heads=4, intermediate=128,
        max_position=256, dtype=jnp.float32,
    )
    params = D.init_params(jax.random.PRNGKey(0), cfg)
    head = "c" * 40 + "ontext: "
    prompts = [head + f"q{k:02d}tail"[:8].ljust(8, "x") for k in range(16)]
    max_new = 16

    def run_arm(wq: str):
        chat = TPUDecoderChat(
            params=params, cfg=cfg, tokenizer=ToyCharTokenizer(128),
            max_new_tokens=max_new, temperature=0.0, max_prompt_tokens=64,
            continuous=True, n_slots=4, chunk_steps=8, pipeline_depth=2,
            prefill_chunk=8, prefix_cache=False, weight_quant=wq,
        )
        try:
            for r in chat.submit_batch([head + "warmAAxx"]):
                assert r.done.wait(timeout=120)
            rates, toks = [], None
            for _ in range(2):
                t0 = time.perf_counter()
                reqs = chat.submit_batch(prompts)
                for r in reqs:
                    assert r.done.wait(timeout=120)
                wall = max(r.finished_at for r in reqs) - t0
                gen = sum(len(r.tokens) for r in reqs)
                rates.append(gen / max(wall, 1e-9))
                if toks is None:
                    toks = [t for r in reqs for t in r.tokens]
            return rates, toks
        finally:
            chat.close()

    ons, offs = [], []
    on_toks = off_toks = None
    for i in range(3):  # alternate construction order per round
        for wq in (("int8", "") if i % 2 else ("", "int8")):
            rates, toks = run_arm(wq)
            if wq:
                ons.extend(rates)
                on_toks = on_toks or toks
            else:
                offs.extend(rates)
                off_toks = off_toks or toks
    agree = sum(
        a == b for a, b in zip(on_toks, off_toks)
    ) / max(len(off_toks), 1)
    assert len(on_toks) == len(off_toks) and agree >= 0.99, (
        f"int8 weights broke greedy agreement: {agree:.3f}"
    )

    quant_tok_s, base_tok_s = max(ons), max(offs)
    bar = 1.0 if jax.default_backend() == "tpu" else 0.75
    assert quant_tok_s >= bar * base_tok_s, (
        f"weight-quant {quant_tok_s:.1f} tok/s below {bar}x full-precision "
        f"{base_tok_s:.1f} tok/s "
        f"(on={[f'{v:.0f}' for v in ons]}, off={[f'{v:.0f}' for v in offs]})"
    )
