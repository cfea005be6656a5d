"""Fused RAG query pipeline — ONE dispatch from query text to results.

The reference answers a query in stages (embed the query, search the
index, gather documents, rerank — ``xpacks/llm/vector_store.py:440``,
``question_answering.py``), each a separate host round trip with its own dispatch, so the stages
dominate end-to-end latency. TPU-first redesign: keep everything the query touches
RESIDENT in HBM — the embedding corpus (the brute-force index matrix) AND
the documents' token ids — and compile the whole pipeline into a single
executable:

    tokenize (host, C++)  →  [ encode+pool+normalize  →  gemm + top-k  →
    gather doc tokens  →  assemble [CLS] q [SEP] d [SEP] pairs  →
    cross-encoder  ]  →  one fetch

The bracketed section is one jit; a query costs exactly one round trip
whether it retrieves or retrieves-and-reranks.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from pathway_tpu.engine.probes import record_cascade, record_device_dispatch
from pathway_tpu.internals.config import pathway_config
from pathway_tpu.models.embedder import embed_fn, mean_pool
from pathway_tpu.models.tokenizer import PAD_ID, SEP_ID
from pathway_tpu.models.transformer import TransformerConfig, encode
from pathway_tpu.ops import next_pow2
from pathway_tpu.ops.knn import BruteForceKnnIndex, knn_scores, topk_scores
from pathway_tpu.ops.late_bank import (
    doc_token_states,
    late_projection,
    maxsim_flops,
    maxsim_scores,
    projection_flops,
    query_token_states,
)

_NEG_INF = -1e30


def _encoder_flops(cfg: TransformerConfig, seq: int, n_layers: int,
                   pairs: int) -> float:
    """Model FLOPs of ``pairs`` sequences of length ``seq`` through
    ``n_layers`` encoder layers (qkv+attn-out+mlp gemms + 2 S^2 attention
    gemms)."""
    h, i = cfg.hidden, cfg.intermediate
    per_layer = 2 * seq * h * (3 * h + h + 2 * i) + 4 * seq * seq * h
    return float(pairs) * n_layers * per_layer


@functools.partial(
    jax.jit, static_argnames=("cfg", "k", "metric", "f32_scores")
)
def _fused_retrieve(params, q_ids, q_mask, corpus, valid,
                    cfg: TransformerConfig, k: int, metric: str,
                    f32_scores: bool = False):
    """Query encode + pool + normalise + corpus gemm + top-k, one dispatch.
    q_ids/q_mask: (Qb, S). Returns (scores (Qb, k), idx (Qb, k))."""
    emb = embed_fn(params, q_ids, q_mask, cfg)  # (Qb, H) unit vectors
    return topk_scores(
        knn_scores(corpus, valid, emb, metric, f32_scores=f32_scores), k
    )


def _assemble_pairs(q_ids_row, q_len, doc_tokens, doc_lens, pair_seq: int):
    """Build (k, pair_seq) cross-encoder inputs on device:
    ``[CLS] q [SEP] d [SEP]`` with masks and BERT segment ids. ``q_ids_row``
    is already ``[CLS] q [SEP]`` of true length ``q_len``; ``doc_tokens``
    (k, dseq) carry bare doc tokens of ``doc_lens`` each."""
    k, dseq = doc_tokens.shape
    j = jnp.arange(pair_seq)[None, :]                      # (1, P)
    q_pad = jnp.pad(q_ids_row, (0, max(pair_seq - q_ids_row.shape[0], 0)))
    q_part = q_pad[:pair_seq][None, :]                     # (1, P)
    dpos = jnp.clip(j - q_len, 0, dseq - 1)                # (1, P)
    d_vals = jnp.take_along_axis(
        doc_tokens, jnp.broadcast_to(dpos, (k, pair_seq)), axis=1
    )                                                      # (k, P)
    end = q_len + doc_lens[:, None]                        # (k, 1) SEP slot
    pair = jnp.where(
        j < q_len,
        jnp.broadcast_to(q_part, (k, pair_seq)),
        jnp.where(
            j < end, d_vals, jnp.where(j == end, SEP_ID, PAD_ID)
        ),
    )
    mask = (j <= end).astype(jnp.int32)
    ttype = ((j >= q_len) & (j <= end)).astype(jnp.int32)
    return pair.astype(jnp.int32), mask, ttype


@functools.partial(
    jax.jit,
    static_argnames=("e_cfg", "r_cfg", "k", "metric", "pair_seq"),
)
def _fused_retrieve_rerank(e_params, q_ids, q_mask, corpus, valid,
                           doc_tokens, doc_lens, r_params, r_head,
                           e_cfg: TransformerConfig,
                           r_cfg: TransformerConfig,
                           k: int, metric: str, pair_seq: int):
    """One dispatch: embed query -> top-k over the corpus -> gather the
    hit documents' token ids -> cross-encode (query, doc) pairs -> rerank.
    Single query (q_ids (1, S)). Returns (knn_scores (k,), idx (k,),
    rerank_scores (k,), order (k,))."""
    emb = embed_fn(e_params, q_ids, q_mask, e_cfg)           # (1, H)
    scores, idx = topk_scores(
        knn_scores(corpus, valid, emb, metric), k
    )                                                        # (1, k)
    idx0 = idx[0]
    d_tok = jnp.take(doc_tokens, idx0, axis=0)               # (k, dseq)
    d_len = jnp.take(doc_lens, idx0)                         # (k,)
    q_len = jnp.sum(q_mask[0]).astype(jnp.int32)
    pair, mask, ttype = _assemble_pairs(
        q_ids[0], q_len, d_tok, d_len, pair_seq
    )
    hidden = encode(r_params, pair, mask, r_cfg, ttype)
    cls = hidden[:, 0, :]
    pooled = jnp.tanh(
        cls @ r_params["pooler"]["w"].astype(jnp.float32)
        + r_params["pooler"]["b"].astype(jnp.float32)
    )
    r_scores = (pooled @ r_head["w"] + r_head["b"])[:, 0]    # (k,)
    # hits beyond the live corpus (padded capacity) must sort last
    r_scores = jnp.where(scores[0] <= _NEG_INF / 2, _NEG_INF, r_scores)
    order = jnp.argsort(-r_scores)
    return scores[0], idx0, r_scores, order


def _pair_scores(r_params, r_head, pair, mask, ttype,
                 r_cfg: TransformerConfig, n_layers: int | None = None):
    """Cross-encoder scores for a flat (B, P) pair batch: encode (optionally
    truncated to ``n_layers``) -> tanh pooler on [CLS] -> scalar head."""
    hidden = encode(r_params, pair, mask, r_cfg, ttype, n_layers=n_layers)
    cls = hidden[:, 0, :]
    pooled = jnp.tanh(
        cls @ r_params["pooler"]["w"].astype(jnp.float32)
        + r_params["pooler"]["b"].astype(jnp.float32)
    )
    return (pooled @ r_head["w"] + r_head["b"])[:, 0]


def _retrieve_and_assemble(e_params, q_ids, q_mask, corpus, valid,
                           doc_tokens, doc_lens,
                           e_cfg: TransformerConfig, k: int, metric: str,
                           pair_seq: int):
    """Shared front half of the batched rerank kernels: embed queries,
    top-k the corpus, gather hit docs, assemble (Qb, k, P) pair inputs."""
    emb = embed_fn(e_params, q_ids, q_mask, e_cfg)            # (Qb, H)
    scores, idx = topk_scores(
        knn_scores(corpus, valid, emb, metric), k
    )                                                         # (Qb, k)
    d_tok = jnp.take(doc_tokens, idx, axis=0)                 # (Qb, k, dseq)
    d_len = jnp.take(doc_lens, idx)                           # (Qb, k)
    q_len = jnp.sum(q_mask, axis=1).astype(jnp.int32)         # (Qb,)
    pair, mask, ttype = jax.vmap(
        functools.partial(_assemble_pairs, pair_seq=pair_seq)
    )(q_ids, q_len, d_tok, d_len)                             # (Qb, k, P)
    return scores, idx, pair, mask, ttype


@functools.partial(
    jax.jit,
    static_argnames=("e_cfg", "r_cfg", "k", "metric", "pair_seq"),
)
def _fused_retrieve_rerank_batch(e_params, q_ids, q_mask, corpus, valid,
                                 doc_tokens, doc_lens, r_params, r_head,
                                 e_cfg: TransformerConfig,
                                 r_cfg: TransformerConfig,
                                 k: int, metric: str, pair_seq: int):
    """Multi-query generalisation of :func:`_fused_retrieve_rerank` — the
    whole (Qb, k) candidate matrix cross-encodes as ONE flat batch, so a
    micro-batching tick of Qb queries still costs one dispatch. Returns
    (knn_scores (Qb, k), idx (Qb, k), rerank_scores (Qb, k), order (Qb, k))."""
    scores, idx, pair, mask, ttype = _retrieve_and_assemble(
        e_params, q_ids, q_mask, corpus, valid, doc_tokens, doc_lens,
        e_cfg, k, metric, pair_seq,
    )
    qb = q_ids.shape[0]
    flat = lambda a: a.reshape(qb * k, pair_seq)  # noqa: E731
    r_scores = _pair_scores(
        r_params, r_head, flat(pair), flat(mask), flat(ttype), r_cfg
    ).reshape(qb, k)
    # hits beyond the live corpus (padded capacity) must sort last
    r_scores = jnp.where(scores <= _NEG_INF / 2, _NEG_INF, r_scores)
    order = jnp.argsort(-r_scores, axis=1)
    return scores, idx, r_scores, order


@functools.partial(
    jax.jit,
    static_argnames=(
        "e_cfg", "r_cfg", "k", "metric", "pair_seq",
        "depth", "keep", "seed_weight",
    ),
)
def _fused_retrieve_rerank_cascade(e_params, q_ids, q_mask, corpus, valid,
                                   doc_tokens, doc_lens, r_params, r_head,
                                   e_cfg: TransformerConfig,
                                   r_cfg: TransformerConfig,
                                   k: int, metric: str, pair_seq: int,
                                   depth: int, keep: int,
                                   seed_weight: float):
    """Cascaded early-exit rerank, still ONE dispatch: a truncated-depth
    cheap pass (first ``depth`` layers + the score head, seeded with the
    retrieval score) ranks all k candidates; only the top ``keep``
    survivors pay the full cross-encoder. Survivor selection happens on
    device (``lax.top_k`` + gather), so the cheap and full stages share a
    single executable and a single round trip.

    Returns (knn_scores (Qb, k), idx (Qb, k), rerank_scores (Qb, k),
    order (Qb, k)). ``order`` lists survivors first (by full-depth score)
    then the rest (by cheap score); ``rerank_scores`` holds full-depth
    scores at survivor positions and cheap scores elsewhere — the two
    ranges are internally ordered but not mutually calibrated."""
    scores, idx, pair, mask, ttype = _retrieve_and_assemble(
        e_params, q_ids, q_mask, corpus, valid, doc_tokens, doc_lens,
        e_cfg, k, metric, pair_seq,
    )
    qb = q_ids.shape[0]
    flat = lambda a, n: a.reshape(qb * n, pair_seq)  # noqa: E731
    cheap = _pair_scores(
        r_params, r_head, flat(pair, k), flat(mask, k), flat(ttype, k),
        r_cfg, n_layers=depth,
    ).reshape(qb, k)
    # seed with the ranking signal retrieval already paid for
    cheap = cheap + jnp.float32(seed_weight) * scores.astype(jnp.float32)
    cheap = jnp.where(scores <= _NEG_INF / 2, _NEG_INF, cheap)
    _, surv = jax.lax.top_k(cheap, keep)                      # (Qb, keep)
    gather = lambda a: jnp.take_along_axis(  # noqa: E731
        a, surv[:, :, None], axis=1
    )
    full = _pair_scores(
        r_params, r_head,
        flat(gather(pair), keep), flat(gather(mask), keep),
        flat(gather(ttype), keep), r_cfg,
    ).reshape(qb, keep)
    surv_knn = jnp.take_along_axis(scores, surv, axis=1)
    full = jnp.where(surv_knn <= _NEG_INF / 2, _NEG_INF, full)
    rows = jnp.arange(qb)[:, None]
    r_scores = cheap.at[rows, surv].set(full)
    # survivors first, ranked by full-depth score; the cascaded-out rest
    # follow in cheap-score order
    surv_sorted = jnp.take_along_axis(surv, jnp.argsort(-full, axis=1), axis=1)
    # survivor slots drop to -inf, STRICTLY below the _NEG_INF of padded
    # candidates — otherwise (live docs < keep) they tie and the argsort
    # re-includes survivor indices, so ``order`` stops being a permutation
    rest = cheap.at[rows, surv].set(-jnp.inf)
    rest_order = jnp.argsort(-rest, axis=1)                   # survivors last
    order = jnp.concatenate([surv_sorted, rest_order[:, : k - keep]], axis=1)
    return scores, idx, r_scores, order


@functools.partial(
    jax.jit,
    static_argnames=(
        "e_cfg", "r_cfg", "k", "metric", "pair_seq", "keep", "seed_weight",
    ),
)
def _fused_retrieve_maxsim_cascade(e_params, q_ids, q_mask, corpus, valid,
                                   doc_tokens, doc_lens, bank_q, bank_scale,
                                   late_proj, r_params, r_head,
                                   e_cfg: TransformerConfig,
                                   r_cfg: TransformerConfig,
                                   k: int, metric: str, pair_seq: int,
                                   keep: int, seed_weight: float):
    """Late-interaction cascade, still ONE dispatch: the cheap stage is
    MaxSim over the candidates' ingest-time token banks instead of a
    truncated encoder pass, so it pays one (S, dc) x (dc, T) gemm per
    candidate — no query-time encoder FLOPs at all for the cascaded-out
    rest. The query encodes ONCE: the same token states feed the pooled
    retrieval embedding and the projected query tokens MaxSim dots
    against. Survivor selection, the full-depth pass and the order
    construction are IDENTICAL to :func:`_fused_retrieve_rerank_cascade`
    (the two kernels differ only in where ``cheap`` comes from).

    Returns (knn_scores (Qb, k), idx (Qb, k), rerank_scores (Qb, k),
    order (Qb, k)) with the same survivors-first contract."""
    hidden = encode(e_params, q_ids, q_mask, e_cfg)           # (Qb, S, H)
    pooled = mean_pool(hidden, q_mask)
    emb = pooled / jnp.clip(
        jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-9, None
    )
    scores, idx = topk_scores(
        knn_scores(corpus, valid, emb, metric), k
    )                                                         # (Qb, k)
    d_tok = jnp.take(doc_tokens, idx, axis=0)                 # (Qb, k, dseq)
    d_len = jnp.take(doc_lens, idx)                           # (Qb, k)
    q_len = jnp.sum(q_mask, axis=1).astype(jnp.int32)
    pair, mask, ttype = jax.vmap(
        functools.partial(_assemble_pairs, pair_seq=pair_seq)
    )(q_ids, q_len, d_tok, d_len)
    q_tok = query_token_states(hidden, q_mask, late_proj)     # (Qb, S, dc)
    b_q = jnp.take(bank_q, idx, axis=0)                       # (Qb, k, T, dc)
    b_s = jnp.take(bank_scale, idx, axis=0)                   # (Qb, k, T, 1)
    cheap = maxsim_scores(q_tok, q_mask, b_q, b_s, d_len)     # (Qb, k)
    # seed with the ranking signal retrieval already paid for
    cheap = cheap + jnp.float32(seed_weight) * scores.astype(jnp.float32)
    cheap = jnp.where(scores <= _NEG_INF / 2, _NEG_INF, cheap)
    qb = q_ids.shape[0]
    flat = lambda a, n: a.reshape(qb * n, pair_seq)  # noqa: E731
    _, surv = jax.lax.top_k(cheap, keep)                      # (Qb, keep)
    gather = lambda a: jnp.take_along_axis(  # noqa: E731
        a, surv[:, :, None], axis=1
    )
    full = _pair_scores(
        r_params, r_head,
        flat(gather(pair), keep), flat(gather(mask), keep),
        flat(gather(ttype), keep), r_cfg,
    ).reshape(qb, keep)
    surv_knn = jnp.take_along_axis(scores, surv, axis=1)
    full = jnp.where(surv_knn <= _NEG_INF / 2, _NEG_INF, full)
    rows = jnp.arange(qb)[:, None]
    r_scores = cheap.at[rows, surv].set(full)
    surv_sorted = jnp.take_along_axis(surv, jnp.argsort(-full, axis=1), axis=1)
    # survivor slots drop to -inf, STRICTLY below the _NEG_INF of padded
    # candidates (same permutation guarantee as the encoder cascade)
    rest = cheap.at[rows, surv].set(-jnp.inf)
    rest_order = jnp.argsort(-rest, axis=1)
    order = jnp.concatenate([surv_sorted, rest_order[:, : k - keep]], axis=1)
    return scores, idx, r_scores, order


class FusedRAGPipeline:
    """HBM-resident retrieval (+ optional rerank) with one-dispatch queries.

    ``add(keys, texts)`` embeds documents into the brute-force corpus AND
    stores their token ids on device; ``retrieve``/``retrieve_rerank`` then
    cost exactly one round trip. ``*_device`` variants return handles so a
    stream of queries can pipeline dispatches and drain once."""

    def __init__(self, embedder, reranker=None, *,
                 llm_reranker=None,
                 reserved_space: int = 1024, metric: str = "cos",
                 doc_seq: int = 96, pair_seq: int = 160):
        self.embedder = embedder          # SentenceEmbedderModel
        self.reranker = reranker          # CrossEncoderModel | None
        # optional listwise LLM final stage (PATHWAY_TPU_LLM_RERANK):
        # reorders cascade survivors host-side after the fused dispatch
        # resolves; doc texts are kept host-side for its prompts
        self.llm_reranker = llm_reranker  # ListwiseLLMReranker | None
        self._text_by_key: dict = {}
        self.metric = metric
        self.doc_seq = doc_seq
        self.pair_seq = pair_seq
        # the rerank pair is [CLS] q [SEP] d [SEP]: a query longer than
        # pair_seq - doc_seq - 1 would silently crowd the document out of
        # the cross-encoder input, so rerank queries truncate to this
        # budget (and it must leave room for a real query)
        self._rerank_q_budget = pair_seq - doc_seq - 1
        if self._rerank_q_budget < 8:
            raise ValueError(
                f"pair_seq={pair_seq} leaves only {self._rerank_q_budget} "
                f"query tokens next to doc_seq={doc_seq}; raise pair_seq "
                "or lower doc_seq"
            )
        self.index = BruteForceKnnIndex(
            dimensions=embedder.cfg.hidden,
            reserved_space=reserved_space, metric=metric,
        )
        # mesh-resident retrieval (PATHWAY_TPU_MESH): mirror the corpus
        # into a sharded IVF (one shard per device, ICI top-k merge) and
        # answer plain ``retrieve`` from it, so QueryServer queries scan
        # 1/dp of the corpus per chip. Exhaustive probing (nprobe ==
        # n_cells) keeps recall at 1.0 — the win here is the shard split,
        # not IVF pruning. Rerank keeps the fused dense path (its doc
        # gather + cross-encode is one dispatch against the dense slots).
        self.sharded_index = None
        from pathway_tpu.stdlib.indexing.nearest_neighbors import (
            mesh_retrieval_active,
        )

        if mesh_retrieval_active():
            import jax as _jax

            from pathway_tpu.parallel.mesh import make_mesh
            from pathway_tpu.parallel.sharded_ivf import ShardedIvfIndex

            devices = _jax.devices()
            self.sharded_index = ShardedIvfIndex(
                make_mesh(devices, dp=len(devices), tp=1),
                dimensions=embedder.cfg.hidden,
                n_cells=16, nprobe=16,
                metric="l2" if metric in ("l2", "l2sq") else "cos",
            )
        cap = self.index.capacity
        self._doc_tokens = jnp.zeros((cap, doc_seq), dtype=jnp.int32)
        self._doc_lens = jnp.zeros((cap,), dtype=jnp.int32)
        # longest stored doc-token row, tracked on host so the pair-packing
        # bucket is computable without a device round trip; monotone (not
        # lowered on remove) so it stays a safe upper bound
        self._max_doc_len = 0
        # late-interaction doc-token bank (PATHWAY_TPU_LATE_INTERACTION):
        # int8 per-token states + f32 scales, device-resident next to the
        # corpus. Allocated lazily at the first add/query with the flag
        # on — flag-off pipelines pay zero HBM — and dc freezes at that
        # first allocation. `_bank_valid` (host) tracks which slots hold
        # a current bank row, so rows ingested with the flag off backfill
        # lazily at query time instead of silently scoring garbage.
        self._bank_q = None       # (cap, doc_seq, dc) int8
        self._bank_scale = None   # (cap, doc_seq, 1) f32
        self._bank_valid = None   # (cap,) bool, host
        self._late_proj = None    # (H, dc) f32, shared ingest/query
        self._late_dim = 0

    # ------------------------------------------------------------- ingest
    def _doc_token_rows(self, texts: list[str]):
        tok = self.embedder.tokenizer
        ids = np.zeros((len(texts), self.doc_seq), dtype=np.int32)
        lens = np.zeros((len(texts),), dtype=np.int32)
        for i, t in enumerate(texts):
            seq = tok.tokenize_ids(t, self.doc_seq + 2)[1:-1]  # strip specials
            seq = seq[: self.doc_seq]
            ids[i, : len(seq)] = seq
            lens[i] = len(seq)
        return ids, lens

    def add(self, keys: list, texts: list[str]) -> None:
        if not keys:
            return
        start = self.index.n
        # fused embed+append: one dispatch from token ids to corpus rows
        # (the vectors never leave HBM; no transport cast, no separate
        # append enqueue)
        from pathway_tpu.models.embedder import embed_fn
        from pathway_tpu.models.tokenizer import pad_to_buckets

        m = self.embedder
        ids, mask = m.tokenizer(list(texts), max_length=m.max_length)
        ids, mask = pad_to_buckets(ids, mask)
        self.index.add_embed(
            keys, m.params, jnp.asarray(ids), jnp.asarray(mask), m.cfg,
            embed_fn,
        )
        if self.index.capacity != self._doc_tokens.shape[0]:
            grow = self.index.capacity - self._doc_tokens.shape[0]
            self._doc_tokens = jnp.pad(self._doc_tokens, ((0, grow), (0, 0)))
            self._doc_lens = jnp.pad(self._doc_lens, (0, grow))
        ids, lens = self._doc_token_rows(list(texts))
        if self.llm_reranker is not None:
            self._text_by_key.update(zip(keys, texts))
        if lens.size:
            self._max_doc_len = max(self._max_doc_len, int(lens.max()))
        self._doc_tokens = jax.lax.dynamic_update_slice(
            self._doc_tokens, jnp.asarray(ids), (start, 0)
        )
        self._doc_lens = jax.lax.dynamic_update_slice(
            self._doc_lens, jnp.asarray(lens), (start,)
        )
        if pathway_config.late_interaction or self._bank_q is not None:
            self._late_alloc()
            if pathway_config.late_interaction:
                # ingest-time bank build: ONE fused full-depth encode per
                # batch; queries will only ever gather + dequantize
                bq, bs = self._late_bank_rows(ids, lens)
                self._bank_q = jax.lax.dynamic_update_slice(
                    self._bank_q, bq, (start, 0, 0)
                )
                self._bank_scale = jax.lax.dynamic_update_slice(
                    self._bank_scale, bs, (start, 0, 0)
                )
                self._bank_valid[start:start + len(lens)] = True
            else:
                # flag flipped off mid-stream: new rows backfill on the
                # next late-interaction query
                self._bank_valid[start:start + len(lens)] = False
            self._record_late_bank()
        if self.sharded_index is not None:
            # mirror the just-embedded rows into the sharded IVF (slot
            # map, not [start:start+n] — upserts may have moved rows)
            slots = [self.index._slot_of[key] for key in keys]
            vecs = np.asarray(
                jnp.take(self.index._corpus, jnp.asarray(slots), axis=0),
                np.float32,
            )
            self.sharded_index.add(list(keys), vecs)

    # ------------------------------------------------------------ queries
    def _tokenize_queries(self, texts: list[str], max_length: int | None = None):
        """Tokenize + bucket-pad queries. Returns device arrays plus the
        true max query length (a host int, read from the numpy mask BEFORE
        transfer so pair-bucket selection costs no device round trip)."""
        m = self.embedder
        ids, mask = m.tokenizer(texts, max_length=max_length or m.max_length)
        from pathway_tpu.models.tokenizer import pad_to_buckets

        q_max = int(mask.sum(axis=1).max()) if mask.size else 2
        ids, mask = pad_to_buckets(ids, mask, row_lo=1)
        return jnp.asarray(ids), jnp.asarray(mask), q_max

    def _pair_bucket(self, q_max: int) -> int:
        """Static pair width for this query batch: the pow2 bucket of the
        true worst-case pair length ``q_len + max_doc_len + 1`` (capped at
        the configured ``pair_seq``, which also stays the kill-switch
        width when ``PATHWAY_TPU_PAIR_BUCKETS=0``). Executables cache per
        bucket, so short corpora stop paying ``pair_seq``-wide attention."""
        if not pathway_config.pair_buckets:
            return self.pair_seq
        need = q_max + min(self._max_doc_len, self.doc_seq) + 1
        return min(self.pair_seq, next_pow2(need, 16))

    def _cascade_plan(self, k: int):
        """(depth, survivors, seed_weight) for a cascade over k candidates,
        env-overridable with auto defaults: half the encoder depth for the
        cheap pass, half the candidates surviving (floor 8)."""
        c = pathway_config
        layers = self.reranker.cfg.layers
        depth = c.rerank_cascade_depth or max(1, layers // 2)
        depth = max(1, min(depth, layers))
        keep = c.rerank_cascade_survivors or max(8, k // 2)
        keep = max(1, min(keep, k))
        return depth, keep, c.rerank_seed_weight

    def _record_cascade(self, qb: int, k: int, keep: int, depth: int,
                        pair_seq: int) -> None:
        r_cfg = self.reranker.cfg
        record_cascade(
            "cheap", qb * k, _encoder_flops(r_cfg, pair_seq, depth, qb * k)
        )
        record_cascade(
            "full", qb * keep,
            _encoder_flops(r_cfg, pair_seq, r_cfg.layers, qb * keep),
        )

    # ------------------------------------------- late-interaction bank
    def _late_alloc(self) -> None:
        """Allocate the bank (first use) or grow it alongside the index's
        capacity doublings, keeping slot alignment with ``_doc_tokens``."""
        if self._bank_q is None:
            self._late_dim = int(pathway_config.late_dim)
            self._late_proj = late_projection(
                self.embedder.cfg.hidden, self._late_dim
            )
            cap = self.index.capacity
            self._bank_q = jnp.zeros(
                (cap, self.doc_seq, self._late_dim), dtype=jnp.int8
            )
            self._bank_scale = jnp.zeros(
                (cap, self.doc_seq, 1), dtype=jnp.float32
            )
            self._bank_valid = np.zeros((cap,), dtype=bool)
            return
        if self.index.capacity != self._bank_q.shape[0]:
            grow = self.index.capacity - self._bank_q.shape[0]
            self._bank_q = jnp.pad(self._bank_q, ((0, grow), (0, 0), (0, 0)))
            self._bank_scale = jnp.pad(
                self._bank_scale, ((0, grow), (0, 0), (0, 0))
            )
            self._bank_valid = np.pad(self._bank_valid, (0, grow))

    def _late_bank_rows(self, ids: np.ndarray, lens: np.ndarray):
        """Bank rows for a batch of already-tokenized docs: ONE fused
        encode->project->quant dispatch. Rows pad to the pow2 bucket so
        ingest batch sizes reuse executables; the doc-token width stays
        exactly ``doc_seq`` (the bank's storage width)."""
        rows = ids.shape[0]
        rb = next_pow2(max(rows, 1), 1)
        ids_p = np.zeros((rb, self.doc_seq), dtype=np.int32)
        ids_p[:rows] = ids
        # empty docs keep one live (PAD) position: an all-masked row
        # would NaN the encoder softmax; d_len=0 hides it from MaxSim
        live = np.maximum(lens, 1)
        mask_p = np.zeros((rb, self.doc_seq), dtype=np.int32)
        mask_p[:rows] = (
            np.arange(self.doc_seq)[None, :] < live[:, None]
        ).astype(np.int32)
        record_device_dispatch("late_bank_build")
        bq, bs = doc_token_states(
            self.embedder.params, jnp.asarray(ids_p), jnp.asarray(mask_p),
            self._late_proj, self.embedder.cfg,
        )
        return bq[:rows], bs[:rows]

    def _ensure_late_bank(self) -> None:
        """Backfill bank rows for live slots ingested while the flag was
        off (or before this pipeline ran late-interaction at all), in
        bounded batches — each one fused dispatch. After this every live
        slot's bank row is current."""
        self._late_alloc()
        n = self.index.n
        missing = np.flatnonzero(~self._bank_valid[:n])
        if not missing.size:
            return
        for i in range(0, missing.size, 256):
            sl = missing[i:i + 256]
            dev_sl = jnp.asarray(sl)
            ids = np.asarray(jnp.take(self._doc_tokens, dev_sl, axis=0))
            lens = np.asarray(jnp.take(self._doc_lens, dev_sl))
            bq, bs = self._late_bank_rows(ids, lens)
            self._bank_q = self._bank_q.at[dev_sl].set(bq)
            self._bank_scale = self._bank_scale.at[dev_sl].set(bs)
            self._bank_valid[sl] = True
        self._record_late_bank()

    def _record_late_bank(self) -> None:
        """Record the bank's LIVE footprint on the HBM ledger, per device
        (``late_bank`` component). Live rows, not allocated capacity, so
        retraction visibly lowers the gauge — the same observable the
        retraction/compaction tests pin."""
        from pathway_tpu.engine.probes import record_hbm
        from pathway_tpu.models.decoder import _device_bytes

        cap = self._bank_q.shape[0]
        live = int(self._bank_valid.sum())
        per_dev: dict[str, int] = {}
        for arr in (self._bank_q, self._bank_scale):
            for dev, nb in _device_bytes(arr).items():
                per_dev[dev] = per_dev.get(dev, 0) + nb
        frac = (live / cap) if cap else 0.0
        for dev, nb in per_dev.items():
            record_hbm("late_bank", int(nb * frac), device=dev)

    def _maxsim_args(self, arrays):
        """Interleave the bank arrays into the shared ``_rerank_args``
        bundle, backfilling any stale slots first."""
        self._ensure_late_bank()
        return arrays[:7] + (
            self._bank_q, self._bank_scale, self._late_proj,
        ) + arrays[7:]

    def _record_maxsim(self, qb: int, k: int, keep: int,
                       pair_seq: int) -> None:
        """Cascade-ledger attribution for the MaxSim stage: the per-pair
        similarity gemm plus the per-query projection, and the full-depth
        pass over survivors — so ``cascade_stats()`` can report the
        pair-FLOPs collapse vs the encoder cheap stage."""
        r_cfg = self.reranker.cfg
        q_seq = min(self.embedder.max_length, self._rerank_q_budget)
        record_cascade(
            "maxsim", qb * k,
            maxsim_flops(q_seq, self.doc_seq, self._late_dim, qb * k)
            + projection_flops(
                q_seq, self.embedder.cfg.hidden, self._late_dim, qb
            ),
        )
        record_cascade(
            "full", qb * keep,
            _encoder_flops(r_cfg, pair_seq, r_cfg.layers, qb * keep),
        )

    def remove(self, keys: list) -> None:
        """Remove documents, keeping the token store aligned with the
        index's swap-with-last slot moves. Use THIS, not ``index.remove``,
        for pipelines with a reranker — the raw index call would leave
        another document's tokens in the vacated slot."""
        for key in keys:
            slot = self.index._slot_of.get(key)
            if slot is None:
                continue
            last = self.index.n - 1
            if slot != last:
                self._doc_tokens = self._doc_tokens.at[slot].set(
                    self._doc_tokens[last]
                )
                self._doc_lens = self._doc_lens.at[slot].set(
                    self._doc_lens[last]
                )
            self._doc_lens = self._doc_lens.at[last].set(0)
            if self._bank_q is not None:
                # bank rows compact with the same swap-with-last move;
                # the vacated tail slot loses validity (and its bytes
                # leave the late_bank gauge below)
                if slot != last:
                    self._bank_q = self._bank_q.at[slot].set(
                        self._bank_q[last]
                    )
                    self._bank_scale = self._bank_scale.at[slot].set(
                        self._bank_scale[last]
                    )
                    self._bank_valid[slot] = self._bank_valid[last]
                self._bank_valid[last] = False
            self.index.remove([key])
            self._text_by_key.pop(key, None)
        if self._bank_q is not None:
            self._record_late_bank()
        if self.sharded_index is not None:
            self.sharded_index.remove(list(keys))

    def retrieve_device(self, texts: list[str], k: int):
        ids, mask, _ = self._tokenize_queries(texts)
        k_eff = min(k, self.index.capacity)
        record_device_dispatch("fused_retrieve")
        return _fused_retrieve(
            self.embedder.params, ids, mask, self.index._corpus,
            self.index._valid, self.embedder.cfg, k_eff, self.metric,
            f32_scores=self.index.f32_scores,
        )

    def retrieve(self, texts: list[str], k: int):
        """[(key, score)] per query — ONE dispatch round trip (under a
        serving mesh: one sharded-IVF dispatch, every chip scanning its
        shard, plus the query-embed dispatch)."""
        if self.sharded_index is not None:
            ids, mask, _ = self._tokenize_queries(texts)
            record_device_dispatch("sharded_ivf_search")
            emb = np.asarray(
                embed_fn(self.embedder.params, ids, mask, self.embedder.cfg),
                np.float32,
            )[: len(texts)]
            return self.sharded_index.search(emb, k)
        from pathway_tpu.engine.probes import record_retrieval_backend

        scores, idx = jax.device_get(self.retrieve_device(texts, k))
        record_retrieval_backend("dense", len(texts))
        return self.index.resolve(scores, idx, len(texts), k)

    def _rerank_args(self, texts: list[str], k: int):
        """Tokenize rerank queries and bundle the (device args, statics)
        shared by the single/batch/cascade rerank kernels."""
        if self.reranker is None:
            raise ValueError("construct FusedRAGPipeline with a reranker")
        ids, mask, q_max = self._tokenize_queries(
            texts,
            max_length=min(self.embedder.max_length, self._rerank_q_budget),
        )
        k_eff = min(k, self.index.capacity)
        pair_seq = self._pair_bucket(q_max)
        arrays = (
            self.embedder.params, ids, mask, self.index._corpus,
            self.index._valid, self._doc_tokens, self._doc_lens,
            self.reranker.params, self.reranker.head,
        )
        return arrays, k_eff, pair_seq

    def retrieve_rerank_device(self, text: str, k: int):
        arrays, k_eff, pair_seq = self._rerank_args([text], k)
        if pathway_config.rerank_cascade:
            depth, keep, seed_w = self._cascade_plan(k_eff)
            if pathway_config.late_interaction:
                record_device_dispatch("fused_rerank_maxsim")
                args = self._maxsim_args(arrays)
                self._record_maxsim(1, k_eff, keep, pair_seq)
                scores, idx, r_scores, order = _fused_retrieve_maxsim_cascade(
                    *args, self.embedder.cfg, self.reranker.cfg,
                    k_eff, self.metric, pair_seq, keep, seed_w,
                )
                return scores[0], idx[0], r_scores[0], order[0]
            record_device_dispatch("fused_rerank_cascade")
            self._record_cascade(1, k_eff, keep, depth, pair_seq)
            scores, idx, r_scores, order = _fused_retrieve_rerank_cascade(
                *arrays, self.embedder.cfg, self.reranker.cfg,
                k_eff, self.metric, pair_seq, depth, keep, seed_w,
            )
            return scores[0], idx[0], r_scores[0], order[0]
        record_device_dispatch("fused_retrieve_rerank")
        return _fused_retrieve_rerank(
            *arrays, self.embedder.cfg, self.reranker.cfg,
            k_eff, self.metric, pair_seq,
        )

    def retrieve_rerank(self, text: str, k: int):
        """[(key, rerank_score)] best-first — ONE dispatch round trip for
        embed + search + gather + cross-encode (cascaded or not)."""
        scores, idx, r_scores, order = jax.device_get(
            self.retrieve_rerank_device(text, k)
        )
        row = self._resolve_rerank_row(scores, idx, r_scores, order)
        return self._llm_rerank_rows([text], [row])[0]

    def _llm_rerank_rows(self, texts: list[str], rows: list[list]):
        """Optional listwise LLM final stage over resolved rerank rows.

        Each row is ``[(key, score)]`` best-first from the cross-encoder;
        the LLM permutes the ORDER while each doc keeps its cross-encoder
        score (RankLLM semantics — the listwise pass ranks, it does not
        re-score). No-op unless a reranker is attached AND the flag is on.
        """
        if self.llm_reranker is None or not pathway_config.llm_rerank:
            return rows
        docs_lists = [
            [self._text_by_key.get(key, "") for key, _ in row] for row in rows
        ]
        record_cascade("llm_rerank", sum(len(r) for r in rows))
        perms = self.llm_reranker.rerank_batch(list(texts), docs_lists)
        return [[row[j] for j in perm] for row, perm in zip(rows, perms)]

    def _resolve_rerank_row(self, scores, idx, r_scores, order):
        out = []
        for j in order:
            if scores[j] <= _NEG_INF / 2:
                continue
            slot = int(idx[j])
            if slot < len(self.index._keys):
                out.append((self.index._keys[slot], float(r_scores[j])))
        return out

    def retrieve_rerank_batch_device(self, texts: list[str], k: int):
        """Batched fused retrieve+rerank: the whole query batch costs ONE
        dispatch (the micro-batching server's tick primitive). Returns
        (knn_scores, idx, rerank_scores, order), each (Qb', k) with Qb'
        the pow2 row bucket — callers slice ``[:len(texts)]``."""
        arrays, k_eff, pair_seq = self._rerank_args(texts, k)
        if pathway_config.rerank_cascade:
            depth, keep, seed_w = self._cascade_plan(k_eff)
            if pathway_config.late_interaction:
                record_device_dispatch("fused_rerank_maxsim")
                args = self._maxsim_args(arrays)
                self._record_maxsim(len(texts), k_eff, keep, pair_seq)
                return _fused_retrieve_maxsim_cascade(
                    *args, self.embedder.cfg, self.reranker.cfg,
                    k_eff, self.metric, pair_seq, keep, seed_w,
                )
            record_device_dispatch("fused_rerank_cascade")
            self._record_cascade(len(texts), k_eff, keep, depth, pair_seq)
            return _fused_retrieve_rerank_cascade(
                *arrays, self.embedder.cfg, self.reranker.cfg,
                k_eff, self.metric, pair_seq, depth, keep, seed_w,
            )
        record_device_dispatch("fused_retrieve_rerank")
        return _fused_retrieve_rerank_batch(
            *arrays, self.embedder.cfg, self.reranker.cfg,
            k_eff, self.metric, pair_seq,
        )

    def retrieve_rerank_batch(self, texts: list[str], k: int):
        """Per-query [(key, rerank_score)] best-first lists for a batch of
        queries — still one dispatch round trip for the whole batch."""
        scores, idx, r_scores, order = jax.device_get(
            self.retrieve_rerank_batch_device(texts, k)
        )
        rows = [
            self._resolve_rerank_row(scores[i], idx[i], r_scores[i], order[i])
            for i in range(len(texts))
        ]
        return self._llm_rerank_rows(texts, rows)
