"""Ingest-time compressed late-interaction doc-token bank.

The rerank cascade's cheap stage (PR 3) re-encodes every (query, doc)
pair through the first N transformer layers at QUERY time — O(query +
doc) encoder FLOPs per candidate, paid again on every query. The
KaLM-Reranker observation: that cost belongs at INGEST. Each document is
encoded once through the full encoder when it enters the index; its
per-token states are projected to a small ``dc``-dim space
(``PATHWAY_TPU_LATE_DIM``), L2-normalized and stored int8-quantized
(per-token symmetric scales, the PR-6 KV-quant idiom) in a
device-resident bank alongside the IVF vectors. The query-time cheap
stage becomes late-interaction MaxSim over the gathered bank rows:

    maxsim(q, d) = sum_s  max_t  <q_s, d_t>          (unit vectors)

one (S, dc) x (dc, T) gemm per candidate — O(query tokens) per doc,
independent of encoder depth. At ``dc``=32 a bank token costs
``dc + 4`` bytes; the ``late_bank`` HBM component tracks the footprint.

This module holds the pure/jitted pieces — projection, quantized
token-state encoding, dequant + MaxSim — shared by the fused query
kernel (``ops/fused_query.py``) and the embedder token-level submit path
(``models/embedder.py``). Bank LIFECYCLE (append /
retraction / compaction mirroring the IVF row lifecycle) lives with the
row owners: :class:`~pathway_tpu.ops.fused_query.FusedRAGPipeline`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from pathway_tpu.models.transformer import TransformerConfig, encode

# symmetric int8 quantization constants — same contract as the KV-quant
# path (models/decoder.py): |x| / scale <= 127 by construction, all-zero
# rows (padding) quantize to exact zeros via the scale floor
_LATE_QMAX = 127.0
_LATE_SCALE_FLOOR = 1e-8


def late_projection(hidden: int, dc: int, seed: int = 0) -> jax.Array:
    """Deterministic ``(hidden, dc)`` down-projection for token states.

    A fixed random projection (seeded, 1/sqrt(hidden) scale) — the same
    matrix at ingest and query time by construction, with no checkpoint
    to version. Random projections approximately preserve inner products
    (Johnson–Lindenstrauss), which is all MaxSim consumes."""
    w = jax.random.normal(jax.random.PRNGKey(seed), (hidden, dc), jnp.float32)
    return w / jnp.sqrt(jnp.float32(hidden))


def _project_tokens(hidden, mask, proj):
    """(B, S, H) token states -> (B, S, dc) unit vectors, padding zeroed."""
    t = hidden.astype(jnp.float32) @ proj.astype(jnp.float32)
    t = t / jnp.clip(jnp.linalg.norm(t, axis=-1, keepdims=True), 1e-9, None)
    return t * mask.astype(jnp.float32)[:, :, None]


def _quant_tokens(t):
    """Per-token symmetric int8 quant over the dc axis: ``(payload int8,
    scale f32 (..., 1))`` with ``t ~= payload * scale``."""
    amax = jnp.max(jnp.abs(t), axis=-1, keepdims=True)
    scale = jnp.maximum(amax / _LATE_QMAX, _LATE_SCALE_FLOOR)
    return jnp.round(t / scale).astype(jnp.int8), scale


@functools.partial(jax.jit, static_argnames=("cfg", "flash"))
def doc_token_states(params, input_ids, attention_mask, proj,
                     cfg: TransformerConfig, flash: bool = False):
    """One fused executable: full-depth encode -> project -> normalize ->
    int8 quant. Returns ``(payload int8 (B, S, dc), scale f32 (B, S, 1))``
    — the bank rows for a batch of documents. Runs ONCE per document at
    ingest; queries only ever dequantize."""
    hidden = encode(params, input_ids, attention_mask, cfg, flash=flash)
    return _quant_tokens(_project_tokens(hidden, attention_mask, proj))


def query_token_states(hidden, q_mask, proj):
    """Query-side (B, S, dc) unit token states from ALREADY-computed
    encoder states — the fused kernel encodes the query once and feeds
    both the pooled retrieval embedding and this projection, so MaxSim
    adds zero encoder passes."""
    return _project_tokens(hidden, q_mask, proj)


def maxsim_scores(q_tok, q_mask, bank_q, bank_scale, d_lens):
    """Late-interaction MaxSim: ``sum_s max_t <q_s, d_t>``.

    q_tok (Qb, S, dc) unit query tokens (padding rows already zero),
    q_mask (Qb, S), bank_q int8 (Qb, k, T, dc) + bank_scale (Qb, k, T, 1)
    the gathered candidate rows, d_lens (Qb, k) live doc-token counts.
    Returns (Qb, k) f32. Doc positions >= d_lens are masked out of the
    max with a large-negative fill (not -inf: a zero-length doc must
    yield a finite very-bad score, and the caller's padded-candidate
    masking uses finite ``_NEG_INF`` sentinels downstream)."""
    d = bank_q.astype(jnp.float32) * bank_scale          # (Qb, k, T, dc)
    sim = jnp.einsum("qsd,qktd->qkst", q_tok.astype(jnp.float32), d)
    t_live = (
        jnp.arange(d.shape[2])[None, None, :] < d_lens[:, :, None]
    )                                                    # (Qb, k, T)
    sim = jnp.where(t_live[:, :, None, :], sim, -1e9)
    best = jnp.max(sim, axis=3)                          # (Qb, k, S)
    q_live = q_mask.astype(jnp.float32)[:, None, :]      # (Qb, 1, S)
    return jnp.sum(jnp.where(q_live > 0, best, 0.0), axis=2)


def maxsim_flops(q_seq: int, doc_seq: int, dc: int, pairs: int) -> float:
    """Model FLOPs of the MaxSim stage over ``pairs`` candidates: the
    (S, dc) x (dc, T) similarity gemm per pair. The per-query projection
    (S x H x dc, amortized over k candidates) is charged by the caller."""
    return float(pairs) * 2.0 * q_seq * doc_seq * dc


def projection_flops(q_seq: int, hidden: int, dc: int, queries: int) -> float:
    """FLOPs of projecting ``queries`` queries' token states to dc."""
    return float(queries) * 2.0 * q_seq * hidden * dc


def bank_row_bytes(doc_seq: int, dc: int) -> int:
    """Bank bytes per document row: int8 payload + f32 per-token scale."""
    return doc_seq * dc + doc_seq * 4
