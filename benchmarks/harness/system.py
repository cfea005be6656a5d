"""Builders of the system under test: the program's own classes, wired the
way a user wires them, from a configuration's file. The pieces are copies of
``chip_smoke.py``'s (proven on the chip in PR 22) with the seed as an
argument: ``warm_factory``, ``RerankingStore``, ``make_window_feeder``,
``Clock``, ``device_barrier``, ``require_tpu``.

A builder is named in the configuration's file (``"builder"``) and found by
``manifest.resolve``: one of :data:`BUILDERS`, or ``build`` of a
``<path>/builders/<name>.py``. What a model's entry in that file means (the
program's config, the weight tree) is its layout's to say
(:mod:`harness.layouts`, ``config["layouts"]``).
"""

from __future__ import annotations

import queue
import sys
import threading
import time

from . import weights as W
from .corpus import Corpus, WordTokenizer


class Clock:
    """Seconds JAX spent tracing, lowering and compiling, and how many
    backend compiles ran (``chip_smoke.py:Clock``)."""

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


def require_tpu(chips: int) -> dict:
    """No fallback and no platform set: where JAX finds no TPU, or fewer
    chips than the cell asks for, exit non-zero and print no result."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"the benchmark needs a TPU; JAX found "
                 f"{devs[0].platform!r} ({devs[0].device_kind})")
    if len(devs) < chips:
        sys.exit(f"the cell asks for {chips} chip(s); JAX found {len(devs)}")
    return device_stamp()


def device_stamp() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_bytes() -> int | None:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def warm_factory(seed: int, dep: dict, embedder, loaded: list):
    """``BruteForceKnnFactory`` whose per-worker index instance starts from
    the deployment's warm state, loaded through the index's OWN add path
    (``add_device``). Warm rows have negative keys: no document row backs
    them, so one reaching a reply would surface as an error."""
    from pathway_tpu.engine.operators.external_index import (
        ExternalIndexFactory,
    )
    from pathway_tpu.stdlib.indexing import (
        BruteForceKnn,
        BruteForceKnnFactory,
    )

    dim, rows = dep["index_dimensions"], dep["index_warm_rows"]

    class WarmInstances(ExternalIndexFactory):
        def __init__(self, inner):
            self.inner = inner

        def make_instance(self):
            index = self.inner.make_instance()
            for start, chunk in W.warm_chunks(seed, rows, dim):
                keys = list(range(-start - 1, -start - 1 - len(chunk), -1))
                index.add_device(keys, chunk)
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

    return WarmKnnFactory(dimensions=dim, embedder=embedder,
                          reserved_space=dep["index_capacity"])


class RerankingStore:
    """The indexer a user writes to put a cross-encoder between retrieval
    and reply (``chip_smoke.py:RerankingStore``): ask the document store for
    ``candidates`` rows, score every (query, doc) pair with the reranker
    UDF, keep the best ``k``. Passes through what ``DocumentStoreServer``
    and ``QARestServer`` read of a store."""

    def __init__(self, store, reranker, candidates: int):
        self.store = store
        self.reranker = reranker
        self.candidates = candidates
        self.statistics_query = store.statistics_query
        self.inputs_query = store.inputs_query
        self.RetrieveQuerySchema = store.RetrieveQuerySchema
        self.StatisticsQuerySchema = store.StatisticsQuerySchema
        self.InputsQuerySchema = store.InputsQuerySchema

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


def make_commit_feeder():
    """Python connector subject: one engine commit per
    ``commits.put([(doc_id, text), ...])``; ``None`` ends the stream."""
    import pathway_tpu as pw

    class CommitFeeder(pw.io.python.ConnectorSubject):
        def __init__(self):
            super().__init__()
            self.commits: queue.Queue = queue.Queue()

        def run(self) -> None:
            while (commit := self.commits.get()) is not None:
                for doc_id, text in commit:
                    self.next(data=text, _metadata={"doc_id": int(doc_id)})
                self.commit()

    return CommitFeeder()


class System:
    """The running system under test and what the benchmark holds of it."""

    def __init__(self, config: dict, seed: int):
        self.config = config
        self.seed = seed
        self.dep = config["deployment"]
        self.corpus = Corpus(seed, self.dep["doc_words"])
        self.clock = Clock()
        self.instances: list = []       # every index instance built
        self.doc_of_key: dict = {}      # engine row key -> doc_id
        self.rows_seen = 0
        self.closed = 0                 # rows seen when the last commit closed
        self.commits_closed = 0
        self.landed = threading.Condition()
        self.committed_docs = 0
        self.params: dict = {}          # the benchmark's own weight trees
        self.chat = None
        self.server = None
        self.url = ""
        self.spans: dict[str, list[float]] = {}
        self.setup_steps: dict[str, float] = {}   # seconds of each step
        self._t_step = time.perf_counter()

    def step_done(self, name: str) -> None:
        now = time.perf_counter()
        self.setup_steps[name] = round(now - self._t_step, 3)
        self._t_step = now

    # -- construction ------------------------------------------------------

    def build_encoders(self) -> None:
        import jax
        import jax.numpy as jnp

        from pathway_tpu.models.cross_encoder import CrossEncoderModel
        from pathway_tpu.models.embedder import SentenceEmbedderModel
        from pathway_tpu.xpacks.llm.embedders import (
            SentenceTransformerEmbedder,
        )
        from pathway_tpu.xpacks.llm.rerankers import CrossEncoderReranker

        models, layouts = self.config["models"], self.config["layouts"]
        emb, rer = models["embedder"], models["reranker"]
        emb_layout, rer_layout = layouts["embedder"], layouts["reranker"]
        self.params["embedder"] = W.make_params(
            self.seed, W.STREAM_EMBEDDER,
            emb_layout.weight_spec(emb, "embedder"))
        self.params["reranker"] = W.make_params(
            self.seed, W.STREAM_RERANKER,
            rer_layout.weight_spec(rer, "reranker"))
        self.embedder = SentenceTransformerEmbedder(
            model=SentenceEmbedderModel(
                cfg=emb_layout.program_config(emb),
                params=self.params["embedder"],
                max_length=self.dep["embed_max_length"]),
            max_batch_size=self.dep["embed_max_batch"])
        # the cross-encoder keeps float32 leaves (it casts at each use);
        # these hold the same bfloat16 values
        f32 = jax.tree.map(lambda a: a.astype(jnp.float32),
                           self.params["reranker"])
        head = f32.pop("head")
        self.reranker = CrossEncoderReranker(CrossEncoderModel(
            cfg=rer_layout.program_config(rer), params=f32, head=head,
            max_length=self.dep["rerank_max_length"]))

    def build_decoder(self) -> None:
        from pathway_tpu.xpacks.llm.llms import TPUDecoderChat

        model = self.config["models"]["decoder"]
        layout = self.config["layouts"]["decoder"]
        srv = self.dep["decoder_server"]
        self.params["decoder"] = W.make_params(
            self.seed, W.STREAM_DECODER, layout.weight_spec(model, "decoder"))
        cfg = layout.program_config(model)
        self.tokenizer = WordTokenizer(cfg.vocab_size, self.seed)
        self.chat = TPUDecoderChat(
            params=self.params["decoder"], cfg=cfg,
            tokenizer=self.tokenizer, max_new_tokens=srv["max_new_tokens"],
            temperature=srv["temperature"],
            max_prompt_tokens=srv["max_prompt_tokens"],
            continuous=True, deferred=True, n_slots=srv["n_slots"],
        )

    def build_store(self):
        import pathway_tpu as pw
        from pathway_tpu.internals.errors import get_global_error_log
        from pathway_tpu.xpacks.llm.document_store import DocumentStore

        class DocSchema(pw.Schema):
            data: str
            _metadata: pw.Json

        pw.clear_graph()
        get_global_error_log().clear()
        self.feeder = make_commit_feeder()
        docs = pw.io.python.read(self.feeder, schema=DocSchema,
                                 autocommit_duration_ms=None)
        store = DocumentStore(docs, retriever_factory=warm_factory(
            self.seed, self.dep, self.embedder, self.instances))

        def on_change(key, row, time, is_addition):
            self.rows_seen += 1 if is_addition else -1
            meta = row["metadata"]
            self.doc_of_key[getattr(key, "value", key)] = int(
                getattr(meta, "value", meta)["doc_id"])

        def on_time_end(time):
            with self.landed:
                self.closed = self.rows_seen
                self.commits_closed += 1
                self.landed.notify_all()

        pw.io.subscribe(store.chunked_docs, on_change=on_change,
                        on_time_end=on_time_end)
        return RerankingStore(store, self.reranker,
                              self.dep["rerank_candidates"])

    def start(self, server) -> None:
        self.server = server
        server.run(threaded=True)
        if not server.webserver._started.wait(timeout=300):
            raise RuntimeError("the REST server did not start")
        self.url = f"http://127.0.0.1:{server.webserver.port}"

    # -- ingest ------------------------------------------------------------

    def make_commits(self, n_commits: int, commit_docs: int) -> list:
        first = self.corpus.n_documents
        texts = self.corpus.documents(n_commits * commit_docs)
        return [
            [(first + i, texts[i]) for i in range(c * commit_docs,
                                                  (c + 1) * commit_docs)]
            for c in range(n_commits)
        ]

    def put(self, commit: list) -> None:
        self.committed_docs += len(commit)
        self.feeder.commits.put(commit)

    def wait_closed(self, rows: int, timeout: float = 600.0) -> None:
        with self.landed:
            if not self.landed.wait_for(lambda: self.closed >= rows,
                                        timeout=timeout):
                raise RuntimeError(
                    f"commits did not land: {self.closed} of {rows} rows")

    def ingest(self, commits: list) -> None:
        """Commit by commit, each landed before the next (set-up only)."""
        for commit in commits:
            self.put(commit)
            self.wait_closed(self.committed_docs)
        device_barrier()

    # -- end ---------------------------------------------------------------

    def counters(self) -> dict:
        out = {"embed_dedup_" + k: v
               for k, v in self.embedder.dedup_stats.items()}
        if self.chat is not None:
            stats = self.chat._server.stats
            for k in ("admitted", "chunks", "steps", "slot_steps_total",
                      "spec_emitted", "spec_verify_steps", "spec_dispatches",
                      "prefill_chunks"):
                out["decoder_" + k] = stats[k]
        from pathway_tpu.engine import probes

        for kind, n in probes.dispatch_counts().items():
            out["dispatch_" + kind] = n
        out["compiles"] = self.clock.compiles
        return out

    def close(self) -> None:
        """Stop every thread the system started and drop what it holds on
        the device, so that the reference runs beside nothing."""
        import gc

        import pathway_tpu as pw

        self.feeder.commits.put(None)
        for c in pw.G.connectors:
            c._stop.set()
            c.close()
        if self.server is not None and self.server._thread is not None:
            self.server._thread.join(timeout=120)
        if self.chat is not None:
            self.chat.close()
        self.embedder.model.close()
        pw.clear_graph()
        self.instances.clear()
        self.chat = self.server = self.embedder = self.reranker = None
        gc.collect()


def post(url: str, payload: dict, timeout: float):
    """POST JSON, return the decoded reply (the benchmark's own client)."""
    import json
    import urllib.request

    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    with urllib.request.urlopen(req, timeout=timeout) as resp:  # noqa: S310
        return json.loads(resp.read().decode())


def _floor_probe(system: System, n: int) -> None:
    """``serve.request_floor_ms``: ``n`` one-at-a-time
    ``POST /v1/statistics``, which touches no model (ROADMAP S7's dispatch
    floor). The mix's file gives ``floor_probes``; a mix without it pays
    nothing."""
    if not n:
        return
    out = []
    for _ in range(n):
        t0 = time.perf_counter()
        post(f"{system.url}/v1/statistics", {}, timeout=60)
        out.append((time.perf_counter() - t0) * 1e3)
    system.spans["request_floor_ms"] = out


def _warm_encoders(system: System, traffic: dict) -> None:
    """Every (rows, sequence) bucket the cell's traffic can reach, through
    the components' own entries, so that nothing compiles in the window."""
    dep = system.dep
    clients = traffic.get("clients", 0)
    doc = system.corpus.text_of(0)
    query = " ".join(doc.split()[: traffic.get("query_words", 100)])
    if clients:
        rows = 8
        while rows <= max(8, clients):
            system.embedder.model.embed_batch([query] * rows)
            rows *= 2
        pairs, most = dep["rerank_candidates"], min(
            clients * dep["rerank_candidates"], 512)
        while pairs <= most:
            system.reranker.model.score_batch([(query, doc)] * pairs)
            pairs *= 2
        # the search at the k the GRAPH asks for (the index node fetches
        # more than ``rerank_candidates`` beside a filter column): one
        # request through the served route; the index dispatches every
        # query bucket with its first search at a k
        post(f"{system.url}/v1/retrieve", {"query": query, "k": 1},
             timeout=600)
    device_barrier()


def documentstore_server(config: dict, traffic: dict, seed: int) -> System:
    """``DocumentStoreServer`` (ONE index instance) over the reranking
    store: cells that ingest or retrieve, and build no decoder."""
    from pathway_tpu.xpacks.llm.servers import DocumentStoreServer

    system = System(config, seed)
    system.step_done("corpus")
    system.build_encoders()
    system.step_done("encoders")
    store = system.build_store()
    system.start(DocumentStoreServer("127.0.0.1", 0, store))
    system.step_done("graph_and_server")
    _finish_setup(system, traffic)
    return system


def qa_rest_server(config: dict, traffic: dict, seed: int) -> System:
    """``BaseRAGQuestionAnswerer`` behind ``QARestServer`` (one index
    instance per retrieving route: four). The decoder is built first,
    before the indexes take their share of memory."""
    from pathway_tpu.xpacks.llm.question_answering import (
        BaseRAGQuestionAnswerer,
    )
    from pathway_tpu.xpacks.llm.servers import QARestServer

    system = System(config, seed)
    system.step_done("corpus")
    system.build_decoder()
    system.step_done("decoder")
    system.build_encoders()
    system.step_done("encoders")
    store = system.build_store()
    qa = BaseRAGQuestionAnswerer(
        llm=system.chat, search_topk=system.dep["search_topk"], indexer=store)
    system.start(QARestServer("127.0.0.1", 0, qa))
    system.step_done("graph_and_server")
    _finish_setup(system, traffic)
    return system


def _finish_setup(system: System, traffic: dict) -> None:
    dep = system.dep
    n = dep["setup_commits"]
    system.ingest(system.make_commits(n, dep["commit_docs"]))
    system.step_done("warm_rows_and_setup_commits")
    if not system.instances:
        raise RuntimeError("no index instance was built")
    want = dep["index_warm_rows"] + n * dep["commit_docs"]
    for ix in system.instances:
        if len(ix) != want or ix.capacity != dep["index_capacity"]:
            raise RuntimeError(
                f"index holds {len(ix)} of {want} rows at capacity "
                f"{ix.capacity}, stated {dep['index_capacity']}")
    _warm_encoders(system, traffic)
    system.step_done("warm_shapes")
    _floor_probe(system, traffic.get("floor_probes", 0))
    system.step_done("floor_probe")


BUILDERS = {
    "documentstore_server": documentstore_server,
    "qa_rest_server": qa_rest_server,
}
