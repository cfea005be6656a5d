#!/usr/bin/env python3
"""First proof that the program starts on the chip: one process drives
ingest -> retrieve -> rerank -> generate and the engine at real size.

    python chip_smoke.py            # one TPU chip (what the driver runs)
    python chip_smoke.py --chips 4  # only the paths that exist across chips

It needs a TPU: where ``jax.devices()[0].platform`` is not ``tpu`` it exits
non-zero at once and prints no result. It sets no platform and no
``interpret``; it starts no child that needs the chip (a chip belongs to one
process at a time). Data and weights are made from ``SEED``; nothing is read
from a network.

Every phase compares its answers with a plain computation of the same
thing, and a failed comparison or an exception anywhere is a non-zero exit.
Each earlier line of standard output is one JSON object of counts and
set-up facts, labelled with the device — wall seconds (ending in a device
barrier), seconds spent compiling, rows / docs / requests / tokens, peak
device bytes. They are NOT benchmark numbers and go into no record as
rates. The last line is exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

A CPU rehearsal calls the phase functions with a small :class:`Sizes` from a
scratch script; the platform check in :func:`main` is never argued away.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import faulthandler
import json
import os
import queue
import shutil
import sys
import tempfile
import threading
import time
import zlib

import numpy as np

SEED = 22
DIM = 384  # MiniLM-L6's embedding width


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Every size a phase uses. The defaults are the real ones."""

    # ingest: a deployment's warm index, then engine windows of raw text
    warm_rows: int = 1 << 20
    windows: int = 3
    window_docs: int = 8192
    doc_words: int = 100          # fills the bench's seq-128 bucket
    embed_batch: int = 256
    topics: int = 16              # queries; each owns `top_k` near-duplicates
    # serve
    candidates: int = 32          # retrieved, then reranked ...
    top_k: int = 10               # ... down to the context the decoder reads
    requests: int = 8
    new_tokens: int = 64
    n_slots: int = 16
    max_prompt_tokens: int = 512
    decoder_cfg: object = None    # None = GPT2_MEDIUM
    logit_prompts: int = 4
    # the engine alone
    wordcount_rows: int = 1_000_000
    wordcount_files: int = 16
    join_orders: int = 400_000
    join_users: int = 20_000
    join_commits: int = 4
    # --chips 4
    shard_rows: int = 1 << 20     # per device


# stated tolerances, each against a float32 `highest`-precision reference
LOGIT_TOL = 0.15     # max |bf16 - f32| over first-token logits (std ~0.6)
RERANK_TOL = 0.05    # max |bf16 - f32| over cross-encoder scores
EMBED_COS = 0.999    # min cosine(bf16 embedding, f32 embedding)
RECALL = 0.99        # top-k ids against the numpy exact search


def emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


class Fail(AssertionError):
    """A phase's comparison did not hold."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise Fail(what)


# ---------------------------------------------------------------- set-up


class Clock:
    """Seconds JAX spent tracing, lowering and compiling, and how many
    backend compiles ran — reported per phase as set-up, apart from wall."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration
            if event.endswith("backend_compile_duration"):
                self.compiles += 1


def device_barrier() -> None:
    """Every computation enqueued so far has finished on every device."""
    import jax
    import jax.numpy as jnp

    for d in jax.local_devices():
        (jax.device_put(jnp.zeros((), jnp.int32), d) + 1).block_until_ready()


@contextlib.contextmanager
def phase(name: str, device: dict, clock: Clock, facts: dict):
    """Time one phase; what the body puts into ``facts`` is printed with
    the wall and compile seconds when — and only when — the body held."""
    import jax

    t0, c0, n0 = time.perf_counter(), clock.seconds, clock.compiles
    yield
    device_barrier()
    stats = jax.local_devices()[0].memory_stats() or {}
    emit(
        phase=name, device=device,
        wall_s=round(time.perf_counter() - t0, 3),
        compile_s=round(clock.seconds - c0, 3),
        compiles=clock.compiles - n0,
        peak_bytes_in_use=stats.get("peak_bytes_in_use"),
        **facts,
    )


def make_vocab(rng, n: int = 5000) -> np.ndarray:
    letters = np.array(list("abcdefghijklmnopqrstuvwxyz"))
    return np.array([
        "".join(rng.choice(letters, rng.integers(3, 9))) for _ in range(n)
    ])


class WordTokenizer:
    """Seeded word-level tokenizer for the decoder: one id per word, no EOS
    (every request spends its whole budget), ids decode to ``t<id>`` so a
    response's token count can be read back."""

    eos_id = None

    def __init__(self, vocab_size: int, seed: int):
        self.vocab_size = vocab_size
        self.seed = seed

    def encode(self, text: str) -> list[int]:
        span = self.vocab_size - 1
        return [
            1 + zlib.crc32(w.encode(), self.seed) % span for w in text.split()
        ]

    def decode(self, ids) -> str:
        return " ".join(f"t{int(i)}" for i in ids)


def build_corpus(sizes: Sizes, rng):
    """Raw-text windows plus the queries that must find their own docs:
    topic ``j`` is a ``doc_words``-word text, and ``top_k`` near-duplicates
    of it (a tenth of the words replaced) are spread over ALL windows, so
    its exact top-k is known to need every window. Returns
    ``(windows: list[list[(doc_id, text)]], queries: list[str])``."""
    vocab = make_vocab(rng)
    n_docs = sizes.windows * sizes.window_docs
    words = rng.choice(vocab, size=(n_docs, sizes.doc_words))
    queries = []
    slots = rng.choice(n_docs, size=sizes.topics * sizes.top_k, replace=False)
    # interleave so each topic's group lands in every window
    slots = np.sort(slots).reshape(sizes.top_k, sizes.topics).T
    swap = max(1, sizes.doc_words // 10)
    for j in range(sizes.topics):
        topic = rng.choice(vocab, sizes.doc_words)
        queries.append(" ".join(topic))
        for slot in slots[j]:
            body = topic.copy()
            at = rng.choice(sizes.doc_words, swap, replace=False)
            body[at] = rng.choice(vocab, swap)
            words[slot] = body
    texts = [" ".join(row) for row in words]
    windows = [
        [(i, texts[i]) for i in range(w * sizes.window_docs,
                                      (w + 1) * sizes.window_docs)]
        for w in range(sizes.windows)
    ]
    return windows, queries


# ------------------------------------------------- ingest + serve (1 chip)


def warm_chunks(sizes: Sizes, chunk: int = 1 << 17):
    """The warm state as seeded device chunks of unit rows, regenerated the
    same for every index instance (the REST server builds one per route)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(key):
        v = jax.random.normal(key, (min(chunk, sizes.warm_rows), DIM))
        return v / jnp.linalg.norm(v, axis=1, keepdims=True)

    base = jax.random.PRNGKey(SEED)
    for start in range(0, sizes.warm_rows, chunk):
        rows = min(chunk, sizes.warm_rows - start)
        yield start, make(jax.random.fold_in(base, start))[:rows]


def warm_factory(sizes: Sizes, embedder, loaded: list):
    """``BruteForceKnnFactory`` whose per-worker index instance starts from
    the deployment's warm state, loaded through the index's OWN add path
    (``add_device``). Warm rows have negative keys: no document row backs
    them, so one reaching a reply would surface as an ``ix`` error."""
    from pathway_tpu.engine.operators.external_index import (
        ExternalIndexFactory,
    )
    from pathway_tpu.stdlib.indexing import (
        BruteForceKnn,
        BruteForceKnnFactory,
    )

    class WarmInstances(ExternalIndexFactory):
        def __init__(self, inner):
            self.inner = inner

        def make_instance(self):
            index = self.inner.make_instance()
            for start, rows in warm_chunks(sizes):
                keys = list(range(-start - 1, -start - 1 - len(rows), -1))
                index.add_device(keys, rows)
            loaded.append(index)
            return index

    class WarmKnn(BruteForceKnn):
        def make_factory(self):
            return WarmInstances(super().make_factory())

    class WarmKnnFactory(BruteForceKnnFactory):
        def build_inner_index(self, data_column, metadata_column=None):
            return WarmKnn(
                data_column, metadata_column, dimensions=self.dimensions,
                reserved_space=self.reserved_space, metric=self.metric,
                embedder=self.embedder,
            )

    return WarmKnnFactory(
        dimensions=DIM, embedder=embedder,
        # the whole run's rows plus one append bucket of headroom, so the
        # capacity-shaped executables compile once
        reserved_space=(sizes.warm_rows
                        + (sizes.windows + 1) * sizes.window_docs),
    )


class RerankingStore:
    """The indexer a user writes to put a cross-encoder between retrieval
    and generation: ask the document store for ``candidates`` rows, score
    every (query, doc) pair with the reranker UDF, keep the best ``k``.
    Each result doc keeps its KNN ``dist`` beside its ``rerank_score``."""

    def __init__(self, store, reranker, candidates: int):
        self.store = store
        self.reranker = reranker
        self.candidates = candidates
        self.statistics_query = store.statistics_query
        self.inputs_query = store.inputs_query

    def retrieve_query(self, queries):
        import pathway_tpu as pw
        from pathway_tpu.internals.json import Json, unwrap_json
        from pathway_tpu.xpacks.llm.rerankers import rerank_topk_filter

        wide = self.store.retrieve_query(
            queries.with_columns(k=self.candidates)
        )

        @pw.udf
        def as_list(result) -> list:
            return [Json(d) for d in unwrap_json(result) or ()]

        @pw.udf
        def text_of(doc) -> str:
            return str(unwrap_json(doc)["text"])

        asked = queries.select(
            qid=queries.id, query=queries.query, k=queries.k,
            doc=as_list(wide.promise_universes_are_equal(queries).result),
        )
        pairs = asked.flatten(asked.doc)
        scored = pairs.select(
            pairs.qid, pairs.k, pairs.doc,
            score=self.reranker(text_of(pairs.doc), pairs.query),
        )
        ranked = scored.groupby(scored.qid).reduce(
            qid=scored.qid, k=pw.reducers.max(scored.k),
            scored=pw.reducers.tuple(pw.make_tuple(scored.score, scored.doc)),
        )

        @pw.udf
        def keep_best(scored_docs, k: int) -> Json:
            docs, scores = rerank_topk_filter.__wrapped__(
                [d for _s, d in scored_docs],
                [float(s) for s, _d in scored_docs], k,
            )
            return Json([
                {**unwrap_json(d), "rerank_score": s}
                for d, s in zip(docs, scores)
            ])

        best = ranked.with_id(ranked.qid).select(
            result=keep_best(pw.this.scored, pw.this.k)
        )
        none = queries.select(result=Json([]))
        return none.update_rows(best.promise_universe_is_subset_of(none))


def logit_prompts(sizes: Sizes, tokenizer, rng):
    """``(prompts, ids, mask)``: equal-length prompts for the logits checks."""
    import jax.numpy as jnp

    vocab = make_vocab(rng, 2000)
    n_tok = min(sizes.max_prompt_tokens, 256)
    prompts = [
        " ".join(rng.choice(vocab, n_tok)) for _ in range(sizes.logit_prompts)
    ]
    ids = jnp.asarray([tokenizer.encode(p) for p in prompts], jnp.int32)
    return prompts, ids, jnp.ones_like(ids)


def first_token_logits(params, ids, mask, cfg, reference: bool = False):
    """Last-position logits of the decoder's ``forward``: as served (the
    config's bf16), or — ``reference`` — the SAME ``forward`` with float32
    parameters and activations at ``highest`` matmul precision."""
    import jax
    import jax.numpy as jnp

    from pathway_tpu.models import decoder as D

    precision = None
    if reference:
        cfg = dataclasses.replace(cfg, dtype=jnp.float32)
        params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        precision = "highest"
    with jax.default_matmul_precision(precision):
        return np.asarray(jax.jit(
            lambda p, i, m: D.forward(p, i, m, cfg)[:, -1, :]
        )(params, ids, mask))


def check_decoder(who: str, logits, texts, ref, sizes: Sizes) -> float:
    """Served logits within ``LOGIT_TOL`` of the reference; every request
    answered with its token budget; and the token each decoded FIRST is one
    the reference also ranks within ``LOGIT_TOL`` of its best — never an
    exact-string test, because random weights put near-ties everywhere.
    Returns the logits' max abs error."""
    check(logits.shape == ref.shape, f"{who}: logits shape {logits.shape}")
    check(bool(np.isfinite(logits).all()), f"{who}: logits are not finite")
    err = float(np.abs(logits - ref).max())
    check(err <= LOGIT_TOL,
          f"{who}: first-token logits off by {err} > {LOGIT_TOL}")
    for row, text in enumerate(texts):
        toks = [int(t[1:]) for t in text.split()]
        check(len(toks) == sizes.new_tokens,
              f"{who} request {row}: {len(toks)} tokens, asked for "
              f"{sizes.new_tokens}")
        gap = float(ref[row].max() - ref[row, toks[0]])
        check(gap <= LOGIT_TOL,
              f"{who} request {row}: served first token is {gap} below the "
              f"reference's best logit")
    return err


def encoder_reference_checks(embedder, reranker, queries, docs,
                             facts: dict) -> None:
    """The embedder and the cross-encoder, as served (bf16), against their
    own forward at float32 ``highest`` precision on a small input."""
    import jax
    import jax.numpy as jnp

    from pathway_tpu.models.cross_encoder import score_fn
    from pathway_tpu.models.embedder import embed_fn
    from pathway_tpu.models.tokenizer import pad_to_buckets

    em = embedder.model
    texts = list(queries[:4]) + list(docs[:4])
    got = em.embed_batch(texts)
    ids, mask = pad_to_buckets(*em.tokenizer(texts, max_length=em.max_length))
    cfg32 = dataclasses.replace(em.cfg, dtype=jnp.float32)
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), em.params)
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(embed_fn(p32, jnp.asarray(ids), jnp.asarray(mask),
                                  cfg32))[:len(texts)]
    cos = float(np.sum(got * ref, axis=1).min())
    facts["embed_min_cos_vs_f32"] = round(cos, 6)
    check(got.shape == (len(texts), em.cfg.hidden), "embedding shape")
    check(cos >= EMBED_COS, f"embedding cosine vs f32 {cos} < {EMBED_COS}")

    rm = reranker.model
    pairs = [(q, d) for q in queries[:2] for d in docs[:4]]
    got = np.asarray(rm.score_batch(pairs))
    ids, mask, types = pad_to_buckets(*rm.tokenizer.encode_pairs(
        pairs, max_length=rm.max_length, return_types=True))
    cfg32 = dataclasses.replace(rm.cfg, dtype=jnp.float32)
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(score_fn(
            rm.params, rm.head, jnp.asarray(ids), jnp.asarray(mask), cfg32,
            jnp.asarray(types)))[:len(pairs)]
    err = float(np.abs(got - ref).max())
    facts.update(rerank_max_abs_err=round(err, 5),
                 rerank_pair_tokens=int(ids.shape[1]))
    check(bool(np.isfinite(got).all()), "rerank scores are not finite")
    check(err <= RERANK_TOL, f"rerank scores off by {err} > {RERANK_TOL}")


def make_window_feeder():
    """Python connector subject: one engine window (one commit) per
    ``windows.put(window)``; ``None`` ends the stream."""
    import pathway_tpu as pw

    class WindowFeeder(pw.io.python.ConnectorSubject):
        def __init__(self):
            super().__init__()
            self.windows: queue.Queue = queue.Queue()

        def run(self) -> None:
            while (window := self.windows.get()) is not None:
                for doc_id, text in window:
                    self.next(data=text, _metadata={"doc_id": int(doc_id)})
                self.commit()

    return WindowFeeder()


def run_ingest_and_serve(sizes: Sizes, device: dict, clock: Clock) -> None:
    """Phases ``decoder``, ``ingest`` and ``serve`` — one ``pw.run()``."""
    import jax

    import pathway_tpu as pw
    from pathway_tpu.internals.errors import get_global_error_log
    from pathway_tpu.models import decoder as D
    from pathway_tpu.xpacks.llm.document_store import DocumentStore
    from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder
    from pathway_tpu.xpacks.llm.llms import TPUDecoderChat
    from pathway_tpu.xpacks.llm.question_answering import (
        BaseRAGQuestionAnswerer,
        send_post_request,
    )
    from pathway_tpu.xpacks.llm.rerankers import CrossEncoderReranker
    from pathway_tpu.xpacks.llm.servers import QARestServer

    rng = np.random.default_rng(SEED)
    windows, queries = build_corpus(sizes, rng)
    all_docs = [t for w in windows for _i, t in w]

    # ---- the decoder, before the indexes take their share of memory
    facts: dict = {}
    with phase("decoder", device, clock, facts):
        cfg = sizes.decoder_cfg or D.GPT2_MEDIUM
        chat = TPUDecoderChat(
            params=D.init_params(jax.random.PRNGKey(SEED), cfg), cfg=cfg,
            tokenizer=WordTokenizer(cfg.vocab_size, SEED),
            max_new_tokens=sizes.new_tokens, temperature=0.0,
            max_prompt_tokens=sizes.max_prompt_tokens,
            continuous=True, deferred=True, n_slots=sizes.n_slots,
        )
        facts.update(
            model=dict(hidden=cfg.hidden, layers=cfg.layers, heads=cfg.heads,
                       head_dim=cfg.head_dim, vocab=cfg.vocab_size,
                       dtype=str(np.dtype(cfg.dtype)), params=D.count_params(
                           chat.params)),
            n_slots=sizes.n_slots,
        )
        prompts, ids, mask = logit_prompts(sizes, chat.tokenizer, rng)
        ref = first_token_logits(chat.params, ids, mask, cfg, reference=True)
        err = check_decoder(
            "direct", first_token_logits(chat.params, ids, mask, cfg),
            chat.__wrapped__(prompts), ref, sizes)
        facts.update(logit_max_abs_err=round(err, 4),
                     logit_ref_std=round(float(ref.std()), 4),
                     direct_requests=len(prompts),
                     direct_tokens=len(prompts) * sizes.new_tokens)

    embedder = SentenceTransformerEmbedder(
        model="minilm-l6", max_batch_size=sizes.embed_batch)
    reranker = CrossEncoderReranker("minilm-l6")

    class DocSchema(pw.Schema):
        data: str
        _metadata: pw.Json

    pw.clear_graph()
    get_global_error_log().clear()
    feeder = make_window_feeder()
    docs = pw.io.python.read(feeder, schema=DocSchema,
                             autocommit_duration_ms=None)
    instances: list = []
    store = DocumentStore(
        docs, retriever_factory=warm_factory(sizes, embedder, instances))
    seen = {"rows": 0, "closed": 0}
    landed = threading.Condition()

    def on_change(key, row, time, is_addition):
        seen["rows"] += 1 if is_addition else -1

    def on_time_end(time):
        with landed:
            seen["closed"] = seen["rows"]
            landed.notify_all()

    pw.io.subscribe(store.chunked_docs, on_change=on_change,
                    on_time_end=on_time_end)
    qa = BaseRAGQuestionAnswerer(
        llm=chat, search_topk=sizes.top_k,
        indexer=RerankingStore(store, reranker, sizes.candidates),
    )
    server = QARestServer("127.0.0.1", 0, qa)
    server.run(threaded=True)
    try:
        check(server.webserver._started.wait(timeout=120),
              "REST server did not start")
        url = f"http://127.0.0.1:{server.webserver.port}"

        # ---- phase 1: warm state + engine windows ------------------------
        facts = {}
        with phase("ingest", device, clock, facts):
            window_s = []
            for w, window in enumerate(windows):
                t0 = time.perf_counter()
                feeder.windows.put(window)
                want = (w + 1) * sizes.window_docs
                with landed:
                    check(landed.wait_for(lambda: seen["closed"] >= want,
                                          timeout=900),
                          f"window {w} did not land: {seen}")
                device_barrier()
                window_s.append(round(time.perf_counter() - t0, 3))
            total = sizes.warm_rows + sizes.windows * sizes.window_docs
            check(len(instances) >= 1, "no index instance was built")
            for ix in instances:
                check(len(ix) == total, f"index holds {len(ix)} of {total}")
            facts.update(
                warm_rows=sizes.warm_rows, windows=sizes.windows,
                window_docs=sizes.window_docs, window_wall_s=window_s,
                docs=sizes.windows * sizes.window_docs,
                index_instances=len(instances), index_rows=total,
                index_capacity=instances[0].capacity,
                embed_batch=sizes.embed_batch,
                embed_seq=embedder.model.max_length,
                embed_dedup=dict(embedder.dedup_stats),
            )

        # ---- phase 2: retrieve -> rerank -> generate over HTTP -----------
        facts = {}
        with phase("serve", device, clock, facts):
            encoder_reference_checks(embedder, reranker, queries, all_docs,
                                     facts)
            # the numpy exact search over the SAME vectors: every warm row
            # and every document embedding, as the embedder hands them to
            # the index
            qvec = embedder.model.embed_batch(queries)
            best = [[] for _ in queries]  # per query: (score, doc_id)
            for start, rows in warm_chunks(sizes):
                sc = qvec @ np.asarray(rows, np.float32).T
                for qi, row in enumerate(sc):
                    top = np.argpartition(-row, sizes.top_k)[:sizes.top_k]
                    best[qi] += [(float(row[t]), -start - 1 - int(t))
                                 for t in top]
            step = sizes.embed_batch  # the shape the ingest compiled
            for start in range(0, len(all_docs), step):
                emb = embedder.model.embed_batch(all_docs[start:start + step])
                for qi, row in enumerate(qvec @ emb.T):
                    best[qi] += [(float(s), start + i)
                                 for i, s in enumerate(row)]
            exact = [
                {d for _s, d in sorted(b, reverse=True)[:sizes.top_k]}
                for b in best
            ]

            hits = 0
            for qi, query in enumerate(queries):
                got = send_post_request(
                    f"{url}/v1/retrieve",
                    {"query": query, "k": sizes.candidates}, timeout=900)
                check(len(got) == sizes.candidates,
                      f"retrieve returned {len(got)} of {sizes.candidates}")
                scores = [d["rerank_score"] for d in got]
                check(bool(np.isfinite(scores).all())
                      and scores == sorted(scores, reverse=True),
                      "reranked docs are not in finite descending order")
                nearest = sorted(got, key=lambda d: d["dist"])[:sizes.top_k]
                hits += len(
                    {d["metadata"]["doc_id"] for d in nearest} & exact[qi])
            recall = hits / (len(queries) * sizes.top_k)
            facts.update(retrieve_queries=len(queries),
                         recall_at_k=round(recall, 4), k=sizes.top_k,
                         rerank_candidates=sizes.candidates)
            check(recall >= RECALL, f"recall@{sizes.top_k} {recall} < {RECALL}")

            # answers: two requests first, the rest while those decode, so
            # admission into a running decode happens
            answers: list = [None] * sizes.requests
            errors: list = []

            def ask(i: int) -> None:
                try:
                    answers[i] = send_post_request(
                        f"{url}/v2/answer",
                        {"prompt": queries[i % len(queries)],
                         "return_context_docs": True}, timeout=900)
                except Exception as exc:  # noqa: BLE001 - raised after join
                    errors.append(repr(exc))

            before = dict(chat._server.stats)
            threads = [threading.Thread(target=ask, args=(i,), daemon=True)
                       for i in range(sizes.requests)]
            for i, th in enumerate(threads):
                if i == 2:
                    time.sleep(0.3)
                th.start()
            for th in threads:
                th.join(timeout=900)
            check(not errors, f"{len(errors)} answers failed: {errors[:1]}")
            for i, ans in enumerate(answers):
                check(ans is not None, f"answer {i} never came back")
                n_tok = len(str(ans["response"]).split())
                check(n_tok == sizes.new_tokens,
                      f"answer {i}: {n_tok} tokens, asked {sizes.new_tokens}")
                check(len(ans["context_docs"]) == sizes.top_k,
                      f"answer {i}: {len(ans['context_docs'])} context docs")
            stats = chat._server.stats
            delta = {k: stats[k] - before[k] for k in
                     ("admitted", "admit_dispatches", "prefill_chunks",
                      "chunks", "steps")}
            # more than `requests` when the engine has already re-derived
            # a completed query's answer to retract it (deferred UDFs do)
            check(delta["admitted"] >= sizes.requests,
                  f"server admitted {delta['admitted']} of {sizes.requests}")
            facts.update(
                answer_requests=sizes.requests,
                answer_tokens=sizes.requests * sizes.new_tokens,
                prompt_tokens_cap=chat.max_prompt_tokens,
                decode_server=delta,
            )
        errs = get_global_error_log().entries
        check(not errs, f"engine logged {len(errs)} errors: {errs[:2]}")
    finally:
        feeder.windows.put(None)
        for c in pw.G.connectors:
            c._stop.set()
            c.close()
        if server._thread is not None:
            server._thread.join(timeout=120)
        chat.close()
        embedder.model.close()
    pw.clear_graph()


# ---------------------------------------------------- the engine alone


def _stop_when(done: threading.Event, timeout: float):
    import pathway_tpu as pw

    def stop():
        done.wait(timeout=timeout)
        for c in pw.G.connectors:
            c._stop.set()
            c.close()

    threading.Thread(target=stop, daemon=True).start()


def run_wordcount(sizes: Sizes, device: dict, clock: Clock) -> None:
    """jsonlines files arriving over time -> groupby/count -> subscriber,
    against a dict over the same rows, exactly."""
    import pathway_tpu as pw

    facts: dict = {}
    with phase("engine_wordcount", device, clock, facts):
        pw.clear_graph()
        per = sizes.wordcount_rows // sizes.wordcount_files
        n_rows = per * sizes.wordcount_files
        # files are staged beside the watched directory and renamed into
        # it whole, so the reader never sees one half-written
        root = tempfile.mkdtemp(prefix="chip_smoke_wc_")
        src, stage = f"{root}/in", f"{root}/stage"
        os.mkdir(src)
        os.mkdir(stage)
        expected: dict = {}
        for i in range(n_rows):
            w = f"w{(i * 7919) % 5000}"
            expected[w] = expected.get(w, 0) + 1

        class S(pw.Schema):
            word: str

        t = pw.io.jsonlines.read(src, schema=S, mode="streaming",
                                 refresh_interval=0.02)
        counts = t.groupby(t.word).reduce(t.word, c=pw.reducers.count())
        totals: dict = {}
        running = [0]
        done = threading.Event()

        def on_counts(key, row, time, is_addition):
            if is_addition:
                running[0] += row["c"] - totals.get(row["word"], 0)
                totals[row["word"]] = row["c"]
                if running[0] >= n_rows:
                    done.set()

        pw.io.subscribe(counts, on_change=on_counts)

        def feeder():
            for fi in range(sizes.wordcount_files):
                blob = b"".join(
                    b'{"word": "w%d"}\n' % ((i * 7919) % 5000)
                    for i in range(fi * per, (fi + 1) * per)
                )
                with open(f"{stage}/f{fi}.jsonl", "wb") as f:
                    f.write(blob)
                os.replace(f"{stage}/f{fi}.jsonl", f"{src}/f{fi}.jsonl")

        threading.Thread(target=feeder, daemon=True).start()
        _stop_when(done, timeout=600)
        try:
            pw.run()
        finally:
            shutil.rmtree(root, ignore_errors=True)
        check(totals == expected,
              f"wordcount differs: {len(totals)} words, "
              f"{sum(totals.values())} of {n_rows} rows")
        facts.update(rows=n_rows, files=sizes.wordcount_files,
                     distinct_words=len(totals))
    pw.clear_graph()


def run_join(sizes: Sizes, device: dict, clock: Clock) -> None:
    """Streaming inner join of orders with users, arriving over a few
    commits, against a loop over the same rows, exactly."""
    import pathway_tpu as pw
    from pathway_tpu.io.kafka import InMemoryKafkaBroker

    facts: dict = {}
    with phase("engine_join", device, clock, facts):
        pw.clear_graph()
        rng = np.random.default_rng(SEED + 1)
        n_orders, n_users = sizes.join_orders, sizes.join_users
        uids = rng.integers(0, n_users, n_orders)
        expected = {
            (i, f"user{int(uids[i])}", float(i % 97)) for i in range(n_orders)
        }
        broker = InMemoryKafkaBroker()

        class OrderS(pw.Schema):
            oid: int
            uid: int
            amount: float

        class UserS(pw.Schema):
            uid: int
            name: str

        orders = pw.io.kafka.read(broker, topic="orders", schema=OrderS)
        users = pw.io.kafka.read(broker, topic="users", schema=UserS)
        joined = orders.join(users, orders.uid == users.uid).select(
            orders.oid, users.name, orders.amount)
        live: set = set()
        times: set = set()
        done = threading.Event()

        def on_change(key, row, time, is_addition):
            item = (row["oid"], row["name"], row["amount"])
            (live.add if is_addition else live.discard)(item)
            times.add(time)
            if len(live) >= n_orders:
                done.set()

        pw.io.subscribe(joined, on_change=on_change)

        def feeder():
            # each wave brings a slice of both sides, so late users meet
            # orders that were already waiting for them
            waves = sizes.join_commits
            for w in range(waves):
                for u in range(w, n_users, waves):
                    broker.produce("users", json.dumps(
                        {"uid": u, "name": f"user{u}"}).encode())
                for i in range(w, n_orders, waves):
                    broker.produce("orders", json.dumps(
                        {"oid": i, "uid": int(uids[i]),
                         "amount": float(i % 97)}).encode())
                time.sleep(0.2)
            broker.close()

        threading.Thread(target=feeder, daemon=True).start()
        _stop_when(done, timeout=600)
        pw.run()
        check(live == expected,
              f"join differs: {len(live)} rows of {n_orders}, "
              f"{len(live ^ expected)} wrong")
        facts.update(orders=n_orders, users=n_users, rows=len(live),
                     commits=len(times))
    pw.clear_graph()


# ------------------------------------------------------------ four chips


def run_sharded_index(sizes: Sizes, device: dict, clock: Clock) -> None:
    """The index factory route users get with the mesh flag on: one IVF
    shard per device, exhaustive probing, against the numpy exact search —
    and every device must hold its share of the rows."""
    import jax

    import pathway_tpu as pw
    from pathway_tpu.parallel.sharded_ivf import ShardedIvfIndex
    from pathway_tpu.stdlib.indexing import BruteForceKnnFactory

    facts: dict = {}
    with phase("sharded_index", device, clock, facts):
        n_dev = len(jax.devices())
        os.environ["PATHWAY_TPU_MESH"] = "1"
        try:
            pw.clear_graph()
            table = pw.debug.table_from_rows(
                pw.schema_from_types(vec=np.ndarray), [])
            index = BruteForceKnnFactory(dimensions=DIM) \
                .build_inner_index(table.vec).make_factory().make_instance()
        finally:
            del os.environ["PATHWAY_TPU_MESH"]
        check(isinstance(index, ShardedIvfIndex),
              f"mesh flag on, {n_dev} devices, but the factory built "
              f"{type(index).__name__}")
        check(index.dp == n_dev, f"{index.dp} shards for {n_dev} devices")
        check(index.nprobe == index.n_cells, "probing is not exhaustive")

        rng = np.random.default_rng(SEED)
        total = sizes.shard_rows * n_dev
        # seeded unit rows, made in bulk on the device and brought to the
        # host, where this index keeps its mirror
        rows = dataclasses.replace(sizes, warm_rows=total)
        vecs = np.concatenate([
            np.asarray(chunk, np.float32) for _s, chunk in warm_chunks(rows)
        ])
        # each query owns ten planted neighbours (cosine ~0.9, against
        # ~0.25 for the best random row), spread over the whole index, so
        # its exact top-10 has a margin that bf16 cells cannot reorder
        n_q = 8
        queries = rng.standard_normal((n_q, DIM), dtype=np.float32)
        queries /= np.linalg.norm(queries, axis=1, keepdims=True)
        planted = rng.choice(total, (n_q, 10), replace=False)
        for qi in range(n_q):
            near = queries[qi] + (0.5 / np.sqrt(DIM)
                                  ) * rng.standard_normal(
                (10, DIM), dtype=np.float32)
            vecs[planted[qi]] = near / np.linalg.norm(
                near, axis=1, keepdims=True)
        index.add_bulk(list(range(total)), vecs)
        check(len(index) == total, f"index holds {len(index)} of {total}")
        got = index.search(queries, 10)
        qn = queries
        scores = vecs @ qn.T                         # the numpy exact search
        hits = 0
        for qi in range(len(queries)):
            exact = np.argpartition(-scores[:, qi], 10)[:10]
            hits += len({k for k, _s in got[qi]} & set(exact.tolist()))
        recall = hits / (len(queries) * 10)
        check(recall >= RECALL, f"sharded recall@10 {recall} < {RECALL}")

        cells = index._device_state()[0]
        per_dev = {}
        for sh in cells.addressable_shards:
            per_dev[str(sh.device.id)] = int(sh.data.nbytes)
        check(len(per_dev) == n_dev,
              f"cells live on {len(per_dev)} of {n_dev} devices")
        share = cells.nbytes // n_dev
        check(all(b == share for b in per_dev.values()),
              f"uneven shards: {per_dev}")
        in_use = {str(d.id): (d.memory_stats() or {}).get("bytes_in_use")
                  for d in jax.devices()}
        rows_dev = [int(c) for c in index._shard_count]
        check(all(c == sizes.shard_rows for c in rows_dev),
              f"rows per shard {rows_dev}")
        for d, b in in_use.items():
            check(b is None or b >= share,
                  f"device {d} holds {b} bytes, less than its {share}-byte "
                  f"shard")
        facts.update(devices=n_dev, rows=total, rows_per_device=rows_dev,
                     cell_capacity=index.cell_cap,
                     recall_at_10=round(recall, 4),
                     shard_bytes_per_device=per_dev,
                     bytes_in_use_per_device=in_use)
    pw.clear_graph()


def run_mesh_decoder(sizes: Sizes, device: dict, clock: Clock) -> None:
    """``TPUDecoderChat`` on a tp mesh over every device against the
    one-device server on the same prompts, and the per-device bytes of the
    parameters and the KV pool against the one-device figure."""
    import jax

    from pathway_tpu.models import decoder as D
    from pathway_tpu.parallel.mesh import MeshShapeError, make_serving_mesh
    from pathway_tpu.xpacks.llm.llms import TPUDecoderChat

    facts: dict = {}
    with phase("mesh_decoder", device, clock, facts):
        n_dev = len(jax.devices())
        mesh = make_serving_mesh(tp=n_dev)
        cfg = sizes.decoder_cfg or D.GPT2_MEDIUM
        if cfg.vocab_size % n_dev:
            # tp shards the tied embedding by vocabulary rows, and 50,257
            # does not divide: the library must say so, typed, at
            # construction — and the run goes on at the same widths with
            # the table padded to the next multiple of 128, as a tp
            # deployment of a GPT-2 pads it
            try:
                D.validate_decoder_mesh(cfg, mesh)
            except MeshShapeError as err:
                facts["unpadded_vocab_refused"] = str(err)
            else:
                raise Fail(f"vocab {cfg.vocab_size} % tp={n_dev} != 0 was "
                           f"not refused")
            cfg = dataclasses.replace(
                cfg, vocab_size=-(-cfg.vocab_size // 128) * 128)
        facts["vocab"] = cfg.vocab_size
        tok = WordTokenizer(cfg.vocab_size, SEED)
        params = D.init_params(jax.random.PRNGKey(SEED), cfg)
        prompts, ids, mask = logit_prompts(
            sizes, tok, np.random.default_rng(SEED))
        ref = first_token_logits(params, ids, mask, cfg, reference=True)

        def arm(mesh):
            chat = TPUDecoderChat(
                params=params, cfg=cfg, tokenizer=tok,
                max_new_tokens=sizes.new_tokens, temperature=0.0,
                max_prompt_tokens=sizes.max_prompt_tokens, continuous=True,
                n_slots=sizes.n_slots, mesh=mesh,
            )
            try:
                srv = chat._server
                logits = first_token_logits(srv.params, ids, mask, cfg)
                texts = chat.__wrapped__(prompts)
                p_dev = D.params_device_bytes(srv.params)
                kv_dev: dict = {}
                for comp in D.pool_component_device_bytes(srv.pool).values():
                    for d, b in comp.items():
                        kv_dev[d] = kv_dev.get(d, 0) + b
                return logits, texts, p_dev, kv_dev
            finally:
                chat.close()

        m_logits, m_texts, m_params, m_kv = arm(mesh)
        in_use = {str(d.id): (d.memory_stats() or {}).get("peak_bytes_in_use")
                  for d in jax.devices()}
        s_logits, s_texts, s_params, s_kv = arm(None)

        for who, logits, texts in (("mesh", m_logits, m_texts),
                                   ("one-device", s_logits, s_texts)):
            facts[f"{who}_logit_max_abs_err"] = round(
                check_decoder(who, logits, texts, ref, sizes), 4)
        err = float(np.abs(m_logits - s_logits).max())
        facts["mesh_vs_one_device_logit_max_abs_err"] = round(err, 4)
        check(err <= LOGIT_TOL, f"mesh vs one device off by {err}")

        one_p, one_kv = sum(s_params.values()), sum(s_kv.values())
        check(len(m_params) == n_dev and len(m_kv) == n_dev,
              f"mesh arm placed params on {sorted(m_params)} and KV on "
              f"{sorted(m_kv)}, not on all {n_dev} devices")
        # KV splits by heads, exactly; parameters come to a quarter plus
        # what tp leaves whole (positions, norms, biases: 0.3 % of
        # GPT2_MEDIUM, a seventh of the rehearsal's toy)
        for what, per_dev, one, most in (("parameter", m_params, one_p, 0.40),
                                         ("KV", m_kv, one_kv, 0.26)):
            check(len(set(per_dev.values())) == 1,
                  f"{what} bytes differ between devices: {per_dev}")
            for d, b in per_dev.items():
                check(b <= most * one,
                      f"device {d} holds {b} {what} bytes, over {most} of "
                      f"the one-device {one}")
        facts.update(
            devices=n_dev, mesh={"tp": n_dev}, requests=len(prompts) * 2,
            tokens=len(prompts) * 2 * sizes.new_tokens,
            param_bytes_one_device=one_p, param_bytes_per_device=m_params,
            kv_bytes_one_device=one_kv, kv_bytes_per_device=m_kv,
            mesh_arm_peak_bytes_in_use_per_device=in_use,
        )


# ----------------------------------------------------------------- main


def require_tpu(chips: int) -> dict:
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke.py needs a TPU; JAX found {devs[0].platform!r} "
                 f"({devs[0].device_kind})")
    if len(devs) < chips:
        sys.exit(f"chip_smoke.py --chips {chips}: JAX found {len(devs)} "
                 f"device(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def setup_facts(device: dict) -> None:
    """What the run stands on: the native runtime must be the C++ one (so
    the chip never measures the Python tokenizer by accident)."""
    import jax

    import pathway_tpu
    from pathway_tpu import native

    if not native.AVAILABLE:
        sys.exit("pathway_tpu.native did not build here: the C++ host "
                 "runtime is part of what this run must exercise")
    emit(
        phase="setup", device=device, native=native.AVAILABLE,
        cache_dir=jax.config.jax_compilation_cache_dir,
        cache_dir_from_env="JAX_COMPILATION_CACHE_DIR" in os.environ,
        jax=jax.__version__, pathway_tpu=pathway_tpu.__version__,
    )


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = only the paths that exist across chips")
    args = ap.parse_args()
    device = require_tpu(args.chips)
    # a phase that hangs must not hold the chip: all stacks, then exit 1,
    # inside the 1200 s the run is allowed
    faulthandler.dump_traceback_later(1150, exit=True)
    t0 = time.perf_counter()
    clock = Clock()
    setup_facts(device)
    sizes = Sizes()
    if args.chips == 4:
        run_sharded_index(sizes, device, clock)
        run_mesh_decoder(sizes, device, clock)
    else:
        run_ingest_and_serve(sizes, device, clock)
        run_wordcount(sizes, device, clock)
        run_join(sizes, device, clock)
    emit(phase="total", device=device,
         wall_s=round(time.perf_counter() - t0, 3),
         compile_s=round(clock.seconds, 3), compiles=clock.compiles)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
