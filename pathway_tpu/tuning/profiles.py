"""Workload profiles for the autotuner.

A :class:`WorkloadProfile` is a reusable, seeded trace shape plus the
slice of the flag surface worth searching for it and the SLO objectives a
winning config must hold. ``run_trial`` plays one profile against the REAL
serving/ingest stack in-process (a continuous ``TPUDecoderChat`` server
or a pipelined ``SentenceEmbedderModel``), with the candidate flags
applied through :func:`pathway_tpu.internals.config.flag_overrides`
(``construction=True`` — every consuming object is built inside the
scope), and scores it off the PR-7 metrics registry: tok/s, TTFT/e2e
p95, occupancy, prefix hit rate, shed/restart counts.

Trials are deterministic given ``(profile, scale, seed)``: arrivals and
prompt tails come from a profile-keyed ``np.random.default_rng``, and
decoding is greedy.
"""

from __future__ import annotations

import time
import zlib
from dataclasses import dataclass, field

import numpy as np

from pathway_tpu.internals.config import flag_overrides

_REQ_TIMEOUT_S = 120.0


class _CharTok:
    """1-token-per-char toy tokenizer (the bench serving traces' shape):
    keeps trial prompts byte-countable and vocab tiny."""

    eos_id = None  # budget-bounded: every request costs max_new tokens

    def encode(self, text):
        return [(ord(c) % 96) + 1 for c in text]

    def decode(self, ids):
        return "".join(chr((int(i) % 96) + 32) for i in ids)


@dataclass(frozen=True)
class WorkloadProfile:
    """One named trace shape + its searchable flag slice.

    ``headline``/``direction`` name the metric a trial is ranked by
    (``"max"`` throughput-like, ``"min"`` latency-like). ``tunables``
    are the registry env names the tuner may vary — each must carry a
    ``Tunable`` spec. ``base_flags`` pin the scenario itself (e.g. the
    tenant scheduler ON for the burst profile) and apply to every arm,
    including the all-defaults baseline. ``slo`` arms the PR-9 watchdog
    objectives for the validation leg; ``chaos_sites`` names the sites
    the chaos drill arms (empty = no serving fault surface, skip the
    drill)."""

    name: str
    doc: str
    headline: str
    direction: str  # "max" | "min"
    tunables: tuple[str, ...]
    base_flags: dict = field(default_factory=dict)
    slo: dict = field(default_factory=dict)
    # the drill arms the request-scoped admission site only: dispatch
    # faults kill the whole serving loop and burn the restart budget,
    # which is a fleet-level recovery story, not a per-config one
    chaos_sites: str = "decode.admit"
    kind: str = "serving"  # "serving" | "ingest"
    # trace shape (serving)
    nreq: int = 24
    max_new: int = 12
    n_slots: int = 4
    chunk_steps: int = 4
    lam: float = 40.0  # Poisson arrival rate, requests/s
    head_len: int = 48
    tail_len: int = 8
    prompt_cap: int = 64
    burst: int = 0  # >0: arrivals come in back-to-back bursts this size
    tenants: tuple[str, ...] = ()
    # trace shape (ingest)
    rows: int = 96
    dup_rate: float = 0.5


PROFILES: dict[str, WorkloadProfile] = {
    p.name: p
    for p in [
        WorkloadProfile(
            name="long_doc_rag",
            doc="Long-document RAG: distinct ~88-token prompts, short "
                "answers — admission cost dominates, so chunked-prefill "
                "shape and the disagg prefill lane set the TTFT tail.",
            headline="ttft_p95_ms", direction="min",
            tunables=(
                "PATHWAY_TPU_PREFILL_CHUNK",
                "PATHWAY_TPU_CHUNKED_PREFILL",
                "PATHWAY_TPU_PREFILL_OVERLAP",
                "PATHWAY_TPU_DISAGG",
                "PATHWAY_TPU_DISAGG_PREFILL_BUDGET",
            ),
            slo={"PATHWAY_TPU_SLO_E2E_P95_MS": "30000"},
            nreq=20, max_new=8, n_slots=4, chunk_steps=4, lam=30.0,
            head_len=80, tail_len=8, prompt_cap=96,
        ),
        WorkloadProfile(
            name="shared_prefix_chat",
            doc="Chat/RAG serving with a shared system-prompt head and "
                "short distinct tails — the prefix KV cache, speculative "
                "depth and admission batching set steady-state tok/s.",
            headline="tok_s", direction="max",
            tunables=(
                "PATHWAY_TPU_PREFIX_CACHE",
                "PATHWAY_TPU_PREFIX_CACHE_MB",
                "PATHWAY_TPU_PREFIX_BLOCK",
                "PATHWAY_TPU_SPEC_DECODE",
                "PATHWAY_TPU_SPEC_DECODE_K",
                "PATHWAY_TPU_CHUNK_AUTOTUNE",
                "PATHWAY_TPU_BATCH_ADMIT",
            ),
            slo={"PATHWAY_TPU_SLO_E2E_P95_MS": "30000"},
            nreq=24, max_new=16, n_slots=4, chunk_steps=4, lam=40.0,
            head_len=48, tail_len=8, prompt_cap=64,
        ),
        WorkloadProfile(
            name="multi_tenant_burst",
            doc="Two tenants (prod:batch at 3:1 weight), arrivals in "
                "back-to-back bursts — fairness budgets and refill "
                "policy set the end-to-end tail.",
            headline="e2e_p95_ms", direction="min",
            tunables=(
                "PATHWAY_TPU_TENANT_BUDGET",
                "PATHWAY_TPU_EAGER_REFILL",
                "PATHWAY_TPU_BATCH_ADMIT",
                "PATHWAY_TPU_SPEC_DECODE",
            ),
            base_flags={
                "PATHWAY_TPU_TENANT_SCHED": "1",
                "PATHWAY_TPU_TENANT_WEIGHTS": "prod:3,batch:1",
            },
            slo={"PATHWAY_TPU_SLO_E2E_P95_MS": "30000"},
            nreq=24, max_new=12, n_slots=4, chunk_steps=4, lam=60.0,
            head_len=40, tail_len=8, prompt_cap=64, burst=6,
            tenants=("prod", "prod", "prod", "batch"),
        ),
        WorkloadProfile(
            name="retraction_heavy_ingest",
            doc="Churny ingest: half the rows are re-ingested duplicates "
                "of earlier ones — pipeline depth and queue bound set "
                "rows/s through the tokenize→h2d→dispatch stages.",
            headline="rows_per_s", direction="max",
            tunables=(
                "PATHWAY_TPU_PIPELINE_DEPTH",
                "PATHWAY_TPU_PIPELINE_QUEUE",
            ),
            chaos_sites="", kind="ingest",
            rows=96, dup_rate=0.5,
        ),
        WorkloadProfile(
            name="smoke",
            doc="Seconds-scale CI profile (`cli tune smoke --smoke`): a "
                "tiny shared-head trace over one axis, just enough to "
                "keep the search/validate/persist path from rotting.",
            headline="tok_s", direction="max",
            tunables=("PATHWAY_TPU_PREFILL_CHUNK",),
            nreq=6, max_new=8, n_slots=4, chunk_steps=4, lam=50.0,
            head_len=24, tail_len=8, prompt_cap=48,
        ),
    ]
}


def get_profile(profile) -> WorkloadProfile:
    if isinstance(profile, WorkloadProfile):
        return profile
    try:
        return PROFILES[str(profile)]
    except KeyError:
        raise KeyError(
            f"unknown workload profile {profile!r}; "
            f"available: {sorted(PROFILES)}"
        ) from None


# --------------------------------------------------------------------- #
# shared trial resources (built once per process — trials vary FLAGS,
# so the decoder weights can be shared across every candidate)

_DECODER_RES = None


def decoder_resources():
    """(params, cfg, tokenizer) for the serving profiles: a tiny seeded
    decoder, shared process-wide. ``run_trial(..., resources=)`` lets
    a caller substitute its own checkpoint."""
    global _DECODER_RES
    if _DECODER_RES is None:
        import jax
        import jax.numpy as jnp

        from pathway_tpu.models import decoder as D

        cfg = D.DecoderConfig(
            vocab_size=128, hidden=32, layers=4, heads=4, intermediate=64,
            max_position=256, dtype=jnp.float32,
        )
        params = D.init_params(jax.random.PRNGKey(0), cfg)
        _DECODER_RES = (params, cfg, _CharTok())
    return _DECODER_RES


def _profile_rng(profile: WorkloadProfile, seed: int) -> np.random.Generator:
    return np.random.default_rng(
        (zlib.crc32(profile.name.encode()) << 8) ^ (int(seed) & 0xFFFFFFFF)
    )


def _prompts(profile: WorkloadProfile, nreq: int, rng) -> list[str]:
    if profile.head_len >= 40:
        head = "c" * (profile.head_len - 8) + "ontext: "
    else:
        head = "c" * profile.head_len
    out = []
    for k in range(nreq):
        tail = f"q{k:02d}" + "".join(
            chr(97 + int(c)) for c in rng.integers(0, 26, profile.tail_len)
        )
        out.append(head + tail[:profile.tail_len].ljust(profile.tail_len, "x"))
    return out


def _arrivals(profile: WorkloadProfile, nreq: int, rng) -> np.ndarray:
    gaps = rng.exponential(1.0 / profile.lam, nreq)
    if profile.burst > 0:
        # burst arrivals: every request inside a burst lands with its
        # burst head; the exponential gap survives only between bursts
        for k in range(nreq):
            if k % profile.burst:
                gaps[k] = 0.0
    return np.cumsum(gaps)


def _percentile_ms(samples_s: list[float], q: float) -> float:
    if not samples_s:
        return 0.0
    return round(float(np.percentile(np.asarray(samples_s) * 1e3, q)), 2)


def _serving_trial(
    profile: WorkloadProfile, nreq: int, resources, seed: int,
    deadline_s: float | None,
) -> dict:
    from pathway_tpu.engine import probes
    from pathway_tpu.xpacks.llm.llms import TPUDecoderChat

    params, cfg, tok = resources
    rng = _profile_rng(profile, seed)
    prompts = _prompts(profile, nreq, rng)
    arrivals = _arrivals(profile, nreq, rng)
    t_start = time.perf_counter()
    chat = TPUDecoderChat(
        params=params, cfg=cfg, tokenizer=tok,
        max_new_tokens=profile.max_new, temperature=0.0,
        max_prompt_tokens=profile.prompt_cap, continuous=True,
        n_slots=profile.n_slots, chunk_steps=profile.chunk_steps,
    )
    aborted = False
    latched = False
    try:
        # warm the executables outside the timed window
        for r in chat.submit_batch([prompts[0]]):
            r.done.wait(timeout=_REQ_TIMEOUT_S)
        probes.reset_prefix_stats()
        probes.reset_latency_metrics()
        t0 = time.perf_counter()
        reqs = []
        for k in range(nreq):
            if deadline_s is not None and (
                time.perf_counter() - t_start
            ) > deadline_s:
                aborted = True  # obviously-bad trial: stop feeding it
                break
            now = time.perf_counter() - t0
            if arrivals[k] > now:
                time.sleep(arrivals[k] - now)
            kw = {}
            if profile.tenants:
                kw["tenant"] = profile.tenants[k % len(profile.tenants)]
            try:
                reqs.append(chat.submit_batch([prompts[k]], **kw)[0])
            except RuntimeError:
                # serving loop latched dead (e.g. chaos drill exhausted
                # the restart budget): a losing config, not a crash
                latched = True
                break
        ttft, e2e, tokens, failures, terminal_ok = [], [], 0, 0, not latched
        for k, r in enumerate(reqs):
            if not r.done.wait(timeout=_REQ_TIMEOUT_S):
                terminal_ok = False
                continue
            if r.text is None:
                failures += 1
                continue
            tokens += len(r.tokens)
            if r.first_token_at is not None:
                ttft.append(r.first_token_at - t0 - arrivals[k])
            e2e.append(time.perf_counter() - t0 - arrivals[k])
        wall = max(time.perf_counter() - t0, 1e-9)
        st = dict(chat._server.stats)
        lat = probes.latency_summary(phase="decode")
        ps = probes.prefix_stats()
        slot_steps = int(st.get("slot_steps_total", 0))
        steps = int(st.get("steps", 0))
        return {
            "profile": profile.name,
            "requests": len(reqs),
            "tok_s": round(tokens / wall, 2),
            "ttft_p95_ms": _percentile_ms(ttft, 95),
            "ttft_p50_ms": _percentile_ms(ttft, 50),
            "e2e_p95_ms": _percentile_ms(e2e, 95),
            "e2e_p50_ms": (
                (lat.get("e2e_seconds") or {}).get("p50_ms")
                or _percentile_ms(e2e, 50)
            ),
            "occupancy": round(
                slot_steps / max(steps * profile.n_slots, 1), 4
            ),
            "prefix_hit_rate": ps.get("hit_rate", 0.0),
            "shed": int(st.get("shed", 0)),
            "restarts": int(st.get("restarts", 0)),
            "failures": failures,
            "terminal_ok": terminal_ok,
            "aborted": aborted,
            "wall_s": round(wall, 3),
        }
    finally:
        chat.close()


def _ingest_trial(
    profile: WorkloadProfile, rows: int, seed: int,
    deadline_s: float | None,
) -> dict:
    import dataclasses

    from pathway_tpu.models import MINILM_L6, SentenceEmbedderModel

    rng = _profile_rng(profile, seed)
    uniq = max(1, int(rows * (1.0 - profile.dup_rate)))
    texts = [
        "doc %03d " % k + "".join(
            chr(97 + int(c)) for c in rng.integers(0, 26, 24)
        )
        for k in range(uniq)
    ]
    # retraction-heavy stream: re-ingested duplicates interleave with
    # fresh rows, exactly the upsert/remove churn shape
    stream = [texts[int(rng.integers(0, uniq))] for _ in range(rows)]
    cfg = dataclasses.replace(
        MINILM_L6, layers=2, hidden=32, heads=4, intermediate=64,
        vocab_size=500, max_position=32,
    )
    model = SentenceEmbedderModel(cfg=cfg, max_length=16)
    aborted = False
    t_start = time.perf_counter()
    try:
        # warm (compile) outside the timed window
        model.embed_batch(stream[:4])
        t0 = time.perf_counter()
        handles, done = [], 0
        batch = 8
        for i in range(0, len(stream), batch):
            if deadline_s is not None and (
                time.perf_counter() - t_start
            ) > deadline_s:
                aborted = True
                break
            handles.append(model.embed_submit(stream[i:i + batch]))
            done += len(stream[i:i + batch])
        outs = model.embed_resolve(handles)
        wall = max(time.perf_counter() - t0, 1e-9)
        n_rows = int(sum(o.shape[0] for o in outs))
        return {
            "profile": profile.name,
            "requests": done,
            "rows_per_s": round(n_rows / wall, 2),
            "tok_s": 0.0,
            "shed": 0,
            "restarts": 0,
            "failures": 0,
            "terminal_ok": n_rows == done,
            "aborted": aborted,
            "wall_s": round(wall, 3),
        }
    finally:
        model.close()


def run_trial(
    profile,
    flags: dict,
    *,
    scale: float = 1.0,
    seed: int = 0,
    deadline_s: float | None = None,
    resources=None,
    arm_slo: bool = False,
) -> dict:
    """Play one profile trace under ``flags`` and return its metrics.

    ``flags`` (env name → raw value) apply via ``flag_overrides``
    on top of the profile's ``base_flags``, with ``construction=True``
    — the server/model/watchdog are all built inside the scope, so
    construction-read knobs really take effect and ``os.environ`` is
    never touched. ``scale`` multiplies the request count (successive
    halving re-runs survivors at larger scales); ``deadline_s`` is the
    early-abort budget — a trial past it stops submitting and comes
    back with ``aborted=True`` (the search scores it -inf).

    ``arm_slo=True`` additionally resets + constructs the PR-9 watchdog
    inside the scope (the profile's ``slo`` objectives must be part of
    ``flags``), force-ticks it after the trace, and reports
    ``slo_alerting`` / ``slo_breaches`` — the validation leg."""
    profile = get_profile(profile)
    merged = dict(profile.base_flags)
    merged.update(flags)
    with flag_overrides(merged, construction=True):
        if arm_slo:
            from pathway_tpu.engine import slo as slo_mod

            slo_mod.reset_watchdog()
        try:
            if profile.kind == "ingest":
                rows = max(16, int(round(profile.rows * scale)))
                metrics = _ingest_trial(profile, rows, seed, deadline_s)
            else:
                nreq = max(4, int(round(profile.nreq * scale)))
                metrics = _serving_trial(
                    profile, nreq,
                    resources or decoder_resources(), seed, deadline_s,
                )
            if arm_slo:
                wd = slo_mod.get_watchdog()
                wd.tick()
                state = wd.state()
                metrics["slo_alerting"] = list(state["alerting"])
                metrics["slo_breaches"] = int(state["breaches"])
            return metrics
        finally:
            if arm_slo:
                slo_mod.reset_watchdog()
