"""Cross-encoder reranker: (query, doc) pair -> relevance score.

TPU-native equivalent of sentence-transformers CrossEncoder as used by the
reference's CrossEncoderReranker
(/root/reference/python/pathway/xpacks/llm/rerankers.py:186-249). The pair is
encoded jointly ([CLS] q [SEP] d [SEP]); the [CLS] hidden state goes through a
tanh pooler and a scalar head. One jitted call scores a whole padded batch of
pairs — the rerank stage of the RAG pipeline is a single MXU-bound kernel.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from pathway_tpu.engine.tracing import region
from pathway_tpu.models.tokenizer import (
    HashTokenizer,
    bucket_pow2,
    pad_to_buckets,
)
from pathway_tpu.models.transformer import (
    TransformerConfig,
    MINILM_L6,
    encode,
    init_params,
    _dense_init,
)


# The dense attention of one dispatch keeps rows x heads x S x S float32
# scores a layer: a batch that would pass this many bytes goes as several
# dispatches (512 pairs of 256 tokens at 12 heads are 1.6 GB and go as one;
# 384 pairs of 512 tokens as three of 128).
_MAX_SCORE_BYTES = 2 << 30


@functools.partial(jax.jit, static_argnames=("cfg", "flash"))
def score_fn(params, head, input_ids, attention_mask, cfg: TransformerConfig,
             token_type_ids=None, flash: bool = False):
    hidden = encode(params, input_ids, attention_mask, cfg, token_type_ids,
                    flash=flash)
    cls = hidden[:, 0, :]
    pooled = jnp.tanh(cls @ params["pooler"]["w"].astype(jnp.float32)
                      + params["pooler"]["b"].astype(jnp.float32))
    return (pooled @ head["w"] + head["b"])[:, 0]


class CrossEncoderModel:
    """Host-facing reranker: [(query, doc)] -> np.ndarray scores."""

    def __init__(
        self,
        cfg: TransformerConfig = MINILM_L6,
        params=None,
        head=None,
        tokenizer=None,
        max_length: int = 256,
        seed: int = 1,
    ):
        self.cfg = cfg
        self.tokenizer = tokenizer or HashTokenizer(max_length=max_length)
        self.max_length = max_length
        # Construction-time flag read (reload="construction"): the rerank
        # cascade gets the same O(S) flash encoder as the embedder.
        from pathway_tpu.internals.config import pathway_config

        self.flash_prefill = bool(pathway_config.flash_prefill)
        if self.flash_prefill:
            from pathway_tpu.models import flash_attention as _fa

            _fa.configure_blocks(pathway_config.flash_block_q,
                                 pathway_config.flash_block_k)
        key = jax.random.PRNGKey(seed)
        if params is None:
            params = init_params(key, cfg)
        # weight-only int8 (PATHWAY_TPU_WEIGHT_QUANT, construction-time
        # read): the rerank encoder's word table and layer weights store
        # int8 + f32 scales, dequantized inside the einsum read; the
        # pooler/head stay f32 (they feed the score in f32 already)
        self.weight_quant = str(pathway_config.weight_quant or "")
        if self.weight_quant:
            from pathway_tpu.models.transformer import quantize_encoder_params

            params = quantize_encoder_params(params)
        self.params = params
        # HBM ledger: the reranker's physical param footprint at
        # construction (host-held arrays charge device "0")
        from pathway_tpu.engine.probes import record_hbm
        from pathway_tpu.models.decoder import params_device_bytes

        for dev, nbytes in params_device_bytes(self.params).items():
            record_hbm("weights.reranker", nbytes, device=dev)
        if head is None:
            head = {
                "w": _dense_init(jax.random.fold_in(key, 7),
                                 (cfg.hidden, 1), jnp.float32),
                "b": jnp.zeros((1,), jnp.float32),
            }
        self.head = head

    @classmethod
    def from_pretrained(cls, path: str, max_length: int = 256, **kw):
        """Load a local HF cross-encoder checkpoint (e.g.
        ms-marco-MiniLM-L-6-v2: BertForSequenceClassification with a 1-label
        classifier head) plus its tokenizer."""
        from pathway_tpu.models.checkpoint import load_encoder_checkpoint
        from pathway_tpu.models.tokenizer import load_tokenizer

        params, cfg, head = load_encoder_checkpoint(path)
        if head is None:
            raise ValueError(f"{path!r} has no classifier head — not a cross-encoder")
        import jax.numpy as _jnp

        head = {"w": _jnp.asarray(head["w"]), "b": _jnp.asarray(head["b"])}
        init = dict(
            cfg=cfg,
            params=params,
            head=head,
            tokenizer=load_tokenizer(path, max_length=max_length),
            max_length=max_length,
        )
        init.update(kw)  # explicit caller overrides win
        return cls(**init)

    def score_batch(self, pairs: list[tuple[str, str]]) -> np.ndarray:
        if not pairs:
            return np.zeros((0,), dtype=np.float32)
        return self.score_resolve([self.score_submit(pairs)])[0]

    # -- two-phase path: dispatch many pair-batches, drain once ------------
    def score_submit(self, pairs: list[tuple[str, str]]):
        """Tokenize + dispatch WITHOUT waiting; resolve the returned handle
        via :meth:`score_resolve` (same pipelining contract as
        ``SentenceEmbedderModel.embed_submit``)."""
        with region("pw.rerank.score", pairs=len(pairs)):
            ids, mask, types = self.tokenizer.encode_pairs(
                pairs, max_length=self.max_length, return_types=True
            )
            outs = []
            for rows in self._dispatch_rows(*ids.shape):
                i, m, t = pad_to_buckets(ids[rows], mask[rows], types[rows])
                outs.append(score_fn(
                    self.params, self.head, jnp.asarray(i), jnp.asarray(m),
                    self.cfg, jnp.asarray(t), flash=self.flash_prefill))
        return (outs[0] if len(outs) == 1 else tuple(outs), len(pairs))

    def _dispatch_rows(self, n: int, seq: int) -> list[np.ndarray]:
        """The rows of a batch of ``n`` pairs of ``seq`` tokens by dispatch:
        evenly, as few dispatches as keep each one's dense scores under
        ``_MAX_SCORE_BYTES`` (the tiled read keeps none: one dispatch)."""
        per_row = self.cfg.heads * bucket_pow2(seq, 16) ** 2 * 4
        # rows are padded to a power of two: the largest that fits
        cap = max(8, 1 << (_MAX_SCORE_BYTES // per_row).bit_length() - 1)
        parts = 1 if self.flash_prefill else -(-n // cap)
        return np.array_split(np.arange(n), parts)

    def score_resolve(self, handles) -> list[np.ndarray]:
        with region("pw.rerank.score", pairs=sum(n for _, n in handles)):
            fetched = jax.device_get([h for h, _ in handles])
        out = []
        for o, (_, n) in zip(fetched, handles):
            if isinstance(o, tuple):    # several dispatches, rows in order
                rows = np.array_split(np.arange(n), len(o))
                o = np.concatenate(
                    [np.asarray(c)[:len(r)] for c, r in zip(o, rows)])
            out.append(np.asarray(o)[:n])
        return out

    def __call__(self, pairs: list[tuple[str, str]]) -> np.ndarray:
        return self.score_batch(pairs)
